"""Generated tokens delivered in the window over the window's seconds.

Absorbed prompt tokens do not count; a token counts when its host
timestamp falls in the window.
"""
from bench.driver import in_window


def read(run):
    n = sum(1 for s in run.served for t in s.times if in_window(t, run.t0, run.t1))
    return n / (run.t1 - run.t0)
