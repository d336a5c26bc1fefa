"""The 95th percentile of the scheduler's own queue wait (its arrival
stamp to its dispatch stamp, ``ScheduledRequest.queue_wait``), in ms, over
the window's requests."""
import numpy as np


def read(run):
    wait = run.window.queue_wait_s
    if wait is None or not np.isfinite(wait).any():
        return None
    return float(np.nanpercentile(wait, 95)) * 1e3
