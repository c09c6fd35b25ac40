"""Mean host time of one admission (``add_request``: prefill, cache splice,
first-token read; and ``force_token`` of the prompt's tail) in the window."""


def read(run):
    if not run.admits:
        return None
    return sum(a.end - a.start for a in run.admits) / len(run.admits) * 1e3
