"""Share of the traced window in which no op ran on the device: 1 minus
the union of the device's busy intervals over the window (mean over the
chips)."""


def read(run):
    if run.trace is None:
        return None
    busy = run.trace.busy_s()
    if busy <= 0 or run.trace.window_s <= 0:
        return None          # no op ran on the device: nothing to read
    return 100.0 * (1.0 - busy / run.trace.window_s)
