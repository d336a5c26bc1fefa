"""The share of the traced window in which no operation ran on the device:
1 - (union of the device's op intervals / window), averaged over the
devices the cell drives."""


def read(run):
    if run.trace is None:
        return None
    return 100.0 * run.trace.idle_share
