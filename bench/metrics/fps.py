"""Images completed inside the window per second of the window."""


def read(run):
    return run.window.completed / run.window.seconds
