"""Host time per request from the call into the served function to its
return, before the wait for the device: the client's pick plus the host
work of the invocation (host clock, mean over the traced window)."""
import numpy as np


def read(run):
    return float(np.mean(run.invoked - run.submit)) * 1e3
