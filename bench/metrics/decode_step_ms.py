"""Mean host time of one ``ServeEngine.step`` call (ends in the host copy
of the logits) in the window."""


def read(run):
    if not run.steps:
        return None
    return sum(s.end - s.start for s in run.steps) / len(run.steps) * 1e3
