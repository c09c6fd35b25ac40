"""Set-up: process start until the window opens (weights, pairing, compile
or cache loads, and the warm phase)."""


def read(run):
    return run.setup_s
