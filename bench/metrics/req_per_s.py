"""Invocations completed in the window per second of the window (host
clock): from the first submit to the last answer on the host."""


def read(run):
    return run.requests / run.window_s
