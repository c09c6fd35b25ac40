"""Device idle time per forced prompt token: idle inside the engine's
``engine.force`` spans (one per ``force_token`` call) over their number.
Returns nothing without such spans, or when the trace holds another number
of ``serve_decode`` runs than the window made steps."""
from bench import program_trace as pt

SPAN = "engine.force"


def read(run):
    got = pt.decode_steps(run)
    if got is None:
        return None
    trace, _ = got
    spans = trace.named(SPAN)
    return trace.idle_seconds(spans) * 1e3 / len(spans) if spans else None
