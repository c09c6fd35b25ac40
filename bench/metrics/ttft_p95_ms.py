"""95th percentile, over requests whose first token lands in the window, of
the time from send to the first generated token on the host."""
from bench.driver import in_window, percentile


def read(run):
    waits = [s.first - s.send for s in run.served if in_window(s.first, run.t0, run.t1)]
    return percentile(waits, 95) * 1e3 if waits else None
