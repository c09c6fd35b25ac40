"""Device time of one decode step: the runs of the engine's ``serve_decode``
program in the traced window (``XLA Modules`` events), over the window's
steps.  Returns nothing when the trace holds another number of such runs
than the window made steps (``bench.program_trace.decode_steps``)."""
from bench import program_trace as pt


def read(run):
    got = pt.decode_steps(run)
    if got is None:
        return None
    trace, n = got
    return sum(b - a for a, b in trace.module_runs(pt.DECODE)) * 1e-9 / n
