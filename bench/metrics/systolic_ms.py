"""Device time per request in the f2 systolic kernel: summed durations of
its events in the trace, over the requests of the traced window."""

KERNEL = "systolic"


def read(run):
    if run.trace is None:
        return None
    s = run.trace.kernel_s(KERNEL)
    return s / run.requests * 1e3 if s > 0 else None
