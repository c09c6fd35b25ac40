"""Device self time per decode step of the weight refold: ops of the
``serve_decode`` program whose scope path holds the program's ``refold``
scope (the per-step gathers of each paired weight's live segments,
``kernels/ops.py``).  Returns nothing without such ops, or when the trace
holds another number of ``serve_decode`` runs than the window made steps."""
from bench import program_trace as pt

SCOPE = "refold"


def read(run):
    got = pt.decode_steps(run)
    if got is None:
        return None
    trace, n = got
    s = trace.self_seconds(lambda o: o.program == pt.DECODE and SCOPE in o.path.split("/"))
    return s * 1e3 / n if s > 0 else None
