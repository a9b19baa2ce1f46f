"""95th percentile, over every request of the window, of the time from
submit until its answer is on the host (host clock)."""
import numpy as np


def read(run):
    return float(np.percentile(run.done - run.submit, 95)) * 1e3
