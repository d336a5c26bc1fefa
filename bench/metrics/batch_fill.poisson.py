"""Requests per dispatched batch, as a share of the largest bucket: the
requests the scheduler dispatched inside the window over (its dispatches
there x the largest bucket), from the scheduler's own dispatch stamps
(``ScheduledRequest.dispatch_t``, one per batch and replica)."""


def read(run):
    w = run.window
    if not w.calls:
        return None
    largest = max(run.cell.mix["server"]["batch_sizes"])
    return 100.0 * w.dispatched / (w.calls * largest)
