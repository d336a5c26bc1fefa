"""The 50th percentile of request latency, in ms: every request sent in
the window, timed from its scheduled send time to the scheduler's
completion stamp (requests still in flight at the close are waited for)."""
import numpy as np


def read(run):
    lat = run.window.latency_s
    if lat is None or not np.isfinite(lat).any():
        return None
    return float(np.nanpercentile(lat, 50)) * 1e3
