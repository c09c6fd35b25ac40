"""Bytes a decode step copies to the host: the ``d2h_bytes`` arg of the
engine's ``engine.step`` spans (the logits, the tokens and the positions of
the capacity check, counted from the arrays' sizes), per step.  A count, the
same in every run of a cell.  Returns nothing unless the trace holds one
such span and one ``serve_decode`` run for each of the window's steps."""
from bench import program_trace as pt

SPAN = "engine.step"


def read(run):
    got = pt.decode_steps(run)
    if got is None:
        return None
    trace, n = got
    spans = trace.named(SPAN)
    if len(spans) != n or any("d2h_bytes" not in s.args for s in spans):
        return None
    return sum(s.args["d2h_bytes"] for s in spans) / n
