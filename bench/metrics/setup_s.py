"""Process start to the window's first request: JAX start-up, weights,
compilation or cache load, warm-up of every batch shape."""


def read(run):
    return run.setup_s
