"""The program's own host spans in a traced run: a ``repro.obs`` session
around the window, idle time charged to the innermost span, and the
engines' host milliseconds a batch.

A live ``repro.obs`` session of a program that offers ``TIME_BASE ==
"time_ns"`` exports its host spans (``engine.tick``, ``model.put``, ``gc``,
…) on ``time.time_ns``, the clock of the profiler trace and of the
harness's own spans (``traffic.drive.Spans``), so both line up with the
device's idle gaps.  A program without it gets no session, and a run reads
as it would without this module.

Spans nest (``bench.tick`` ⊃ ``engine.tick`` ⊃ ``model.put``) and a ``gc``
span may straddle any of them.  :func:`innermost` cuts them into sibling
pieces, each named for the innermost span that covers it, so
``trace.reduce`` charges every idle nanosecond to one span, once.  Given
sibling spans only, it returns them unchanged.
"""
from __future__ import annotations

import heapq
import statistics
from typing import Iterable, List, Optional, Sequence, Tuple

Span = Tuple[str, int, int]          # (name, start_ns, end_ns)


def open_session():
    """Install a live ``repro.obs`` session, if the program exports on the
    profiler's time base; else install nothing and return None."""
    from repro.obs import runtime, trace
    if getattr(trace, "TIME_BASE", None) != "time_ns":
        return None
    return runtime.instrument()


def close_session(ob) -> List[Tuple[str, int, int, int]]:
    """Remove the session ``open_session`` installed; its host spans
    ``(name, start_ns, end_ns, arg)`` ([] for None)."""
    if ob is None:
        return []
    from repro.obs import runtime
    if runtime.active() is ob:
        runtime.disable()
    return ob.trace.host_spans()


def innermost(spans: Iterable[Span]) -> List[Span]:
    """Cut ``spans`` into non-overlapping pieces, each named for the
    covering span that started last (of two that start together, the one
    that ends first); time no span covers is left out.  Sorted by start."""
    spans = sorted((s, e, name) for name, s, e in spans if e > s)
    bounds = sorted({t for s, e, _ in spans for t in (s, e)})
    out: List[list] = []
    live: list = []                   # heap of (-start, end, index)
    j = 0
    for lo, hi in zip(bounds, bounds[1:]):
        while j < len(spans) and spans[j][0] <= lo:
            heapq.heappush(live, (-spans[j][0], spans[j][1], j))
            j += 1
        while live and live[0][1] <= lo:
            heapq.heappop(live)       # ended; a later start hid it till now
        if not live:
            continue
        k = live[0][2]
        if out and out[-1][3] == k and out[-1][2] == lo:
            out[-1][2] = hi
        else:
            out.append([spans[k][2], lo, hi, k])
    return [(name, s, e) for name, s, e, _ in out]


def nested(harness: Sequence[Span], program: Sequence[tuple],
           window: str = "bench.window") -> List[Span]:
    """The window span and the innermost pieces of every other harness and
    program span: what ``trace.reduce`` splits the idle gaps by."""
    inner = [x for x in harness if x[0] != window] + \
        [(name, s, e) for name, s, e, *_ in program]
    return [x for x in harness if x[0] == window] + innermost(inner)


def in_window(harness: Sequence[Span], program: Sequence[tuple],
              window: str = "bench.window") -> List[tuple]:
    """The program spans that lie inside the window."""
    lo, hi = next((s, e) for name, s, e in harness if name == window)
    return [x for x in program if x[1] >= lo and x[2] <= hi]


def _by_batch(program, names) -> dict:
    batches: dict = {}
    for name, s, e, batch in program:
        if name in names:
            row = batches.setdefault(batch, dict.fromkeys(names, 0))
            row[name] += e - s
    return batches


def host_ms_per_batch_offline(program) -> Optional[float]:
    """Median over the ``engine.tick`` spans of (``engine.tick`` − its
    ``engine.wait``), in ms; None without ticks."""
    rows = [r for r in _by_batch(program, ("engine.tick",
                                           "engine.wait")).values()
            if r["engine.tick"]]
    if not rows:
        return None
    return statistics.median(r["engine.tick"] - r["engine.wait"]
                             for r in rows) * 1e-6


def host_ms_per_batch_poisson(program) -> Optional[float]:
    """Median over the batches of ``engine.dispatch`` + ``engine.harvest``
    − ``engine.wait``, in ms, over batches both dispatched and harvested;
    None without any."""
    rows = [r for r in _by_batch(program, ("engine.dispatch",
                                           "engine.harvest",
                                           "engine.wait")).values()
            if r["engine.dispatch"] and r["engine.harvest"]]
    if not rows:
        return None
    return statistics.median(r["engine.dispatch"] + r["engine.harvest"]
                             - r["engine.wait"] for r in rows) * 1e-6


def median_ms(spans, name: str) -> Optional[float]:
    """Median duration of the spans named ``name``, in ms; None without
    any."""
    ms = [(e - s) * 1e-6 for n, s, e, *_ in spans if n == name]
    return statistics.median(ms) if ms else None


def total_s(spans, names) -> float:
    """Summed seconds of the spans named ``names``."""
    return sum(x[2] - x[1] for x in spans if x[0] in names) * 1e-9


def stalls(pieces: Sequence[Span], window_start: int,
           at_least_ms: float = 50.0) -> List[Tuple[str, float, float]]:
    """Innermost pieces of at least ``at_least_ms``: ``(span, seconds into
    the window, ms)``, longest first."""
    out = [(name, (s - window_start) * 1e-9, (e - s) * 1e-6)
           for name, s, e in pieces if (e - s) * 1e-6 >= at_least_ms]
    return sorted(out, key=lambda x: -x[2])
