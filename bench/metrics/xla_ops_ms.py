"""Device time per request in every op that is not a Pallas kernel (the
im2col patches, pads, slices, pooling, residual adds, head and argmax):
summed event durations over the requests of the traced window."""


def read(run):
    if run.trace is None:
        return None
    s = run.trace.xla_s()
    return s / run.requests * 1e3 if s > 0 else None
