"""Device idle time per decode step inside the engine's
``engine.step.logits_to_host`` span (the host copy of the step's logits).
Returns nothing unless the trace holds one such span and one ``serve_decode``
run for each of the window's steps."""
from bench import program_trace as pt

SPAN = "engine.step.logits_to_host"


def read(run):
    got = pt.decode_steps(run)
    if got is None:
        return None
    trace, n = got
    spans = trace.named(SPAN)
    return trace.idle_seconds(spans) * 1e3 / n if len(spans) == n else None
