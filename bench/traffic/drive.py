"""The load loops that own the measured window: a closed loop (one client
keeps the engine's queue full) and an open loop (requests sent at their
scheduled times, whether or not earlier ones have finished).

Each records a span around each of its calls into the program
(``bench.submit``, ``bench.tick``, ``bench.sleep``) and one around the
whole window (``bench.window``), on the host's realtime clock, the profiler
trace's own time base; so the trace reduction can say what the host was
doing while the device sat idle.  The profiler's host tracer stays off: on
this program it records over a million runtime events a second and slows
the host path several times over.
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import time
from typing import Callable, List, Optional, Tuple

import numpy as np


class Spans:
    """Host spans ``(name, start_ns, end_ns)`` on ``time.time_ns``."""

    def __init__(self):
        self.spans: List[Tuple[str, int, int]] = []

    @contextlib.contextmanager
    def __call__(self, name: str):
        t0 = time.time_ns()
        try:
            yield
        finally:
            self.spans.append((name, t0, time.time_ns()))


@dataclasses.dataclass
class Window:
    """What one measured window did.  Per request (in send order) only
    numbers and logit arrays are kept, never the request objects, so the
    harness adds no garbage-collected objects that grow with the window."""

    opened: float                  # time.monotonic() at the window's open
    seconds: float                 # host clock, window open to close
    attempted: int                 # requests sent
    completed: int                 # images served inside the window
    calls: int                     # forward calls inside the window
    answers: list                  # logits of each request, None if unserved
    latency_s: Optional[np.ndarray] = None     # open loop: send -> complete
    queue_wait_s: Optional[np.ndarray] = None  # open loop: the scheduler's
    lateness_s: Optional[np.ndarray] = None    # open loop: submit - send
    dispatched: int = 0            # open loop: requests dispatched inside


def closed_loop(engine, make_request: Callable[[int], object],
                batch: int, queued_batches: int, seconds: float,
                span: Spans) -> Window:
    """Keep at least ``queued_batches`` full batches queued in a
    ``ResNetEngine`` and tick it until ``seconds`` have passed; then drain
    what is still queued (outside the window)."""
    answers: list = []
    pending: collections.deque = collections.deque()

    def collect():
        while pending and pending[0].done:      # the engine serves FIFO
            r = pending.popleft()
            answers[r.rid] = r.logits

    served0 = engine.served
    target = queued_batches * batch
    calls = 0
    with span("bench.window"):
        opened = time.monotonic()
        t0 = time.perf_counter()
        end = t0 + seconds
        while True:
            with span("bench.submit"):
                while len(engine.queue) < target:
                    r = make_request(len(answers))
                    engine.submit(r)
                    answers.append(None)
                    pending.append(r)
            if time.perf_counter() >= end:
                break
            with span("bench.tick"):
                engine.tick()
            calls += 1
            collect()
        t1 = time.perf_counter()
    completed = engine.served - served0
    engine.run()
    collect()
    return Window(opened=opened, seconds=t1 - t0, attempted=len(answers),
                  completed=completed, calls=calls, answers=answers)


def open_loop(engine, make_request: Callable[[int], object],
              send_s: np.ndarray, seconds: float, span: Spans) -> Window:
    """Send request ``i`` at ``send_s[i]`` seconds after the window opens
    through a ``ShardedResNetEngine`` and tick it in between; after the
    window, drain every request sent and time each from its send time to
    the scheduler's completion stamp."""
    clock = engine.clock
    n = len(send_s)
    answers: list = [None] * n
    late, complete, wait = (np.full(n, np.nan) for _ in range(3))
    dispatch, replica = np.full(n, np.nan), np.full(n, -1)
    pending: collections.deque = collections.deque()

    def collect(everything=False):
        # a request completes once its batch is harvested; replicas finish
        # out of order, so a finished one may wait behind the head a while
        while pending and (everything or
                           pending[0][1].complete_t is not None):
            i, r = pending.popleft()
            if r.complete_t is not None:
                complete[i], answers[i] = r.complete_t, r.payload.logits
            if r.dispatch_t is not None:
                dispatch[i], replica[i] = r.dispatch_t, r.replica
                wait[i] = r.queue_wait

    i = 0
    served0 = engine.served
    with span("bench.window"):
        opened = time.monotonic()
        t0 = clock.now()
        due = t0 + send_s
        while True:
            now = clock.now()
            with span("bench.submit"):
                while i < n and due[i] <= now:
                    pending.append((i, engine.submit(make_request(i))))
                    late[i] = now - due[i]
                    i += 1
            if now >= t0 + seconds and i == n:
                break
            with span("bench.tick"):
                progressed = engine.tick()
            collect()
            if not progressed:
                wake = due[i] if i < n else t0 + seconds
                nxt = engine.sched.next_due_at()
                if nxt is not None:
                    wake = min(wake, nxt)
                with span("bench.sleep"):
                    clock.sleep(wake - clock.now())
        t1 = clock.now()
        completed = engine.served - served0
    engine.run()                      # drain: late answers are late, not lost
    collect(everything=True)
    inside = (dispatch >= t0) & (dispatch <= t1)
    # one dispatch stamps its whole batch with one time on one replica
    calls = len(set(zip(replica[inside].tolist(), dispatch[inside].tolist())))
    return Window(opened=opened, seconds=t1 - t0, attempted=n,
                  completed=completed,
                  calls=calls, answers=answers, latency_s=complete - due,
                  queue_wait_s=wait, lateness_s=late,
                  dispatched=int(inside.sum()))
