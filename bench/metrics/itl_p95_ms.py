"""95th percentile of every gap between consecutive generated tokens of a
request, over the gaps that end in the window."""
from bench.driver import in_window, percentile


def read(run):
    gaps = [b - a for s in run.served for a, b in zip(s.times, s.times[1:], strict=False)
            if in_window(b, run.t0, run.t1)]
    return percentile(gaps, 95) * 1e3 if gaps else None
