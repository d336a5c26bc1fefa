"""Span tracing with Chrome ``trace_event`` and JSONL export.

Spans are recorded *after the fact* — every call site in the stack already
knows both endpoints of the interval it measured (``arrival``/``dispatch_t``/
``complete_t`` stamps in ``serve.sched``, wall timers around kernels), so
there is no begin/end token API to keep balanced, just:

    trace.span("batch", cat="sched", track="replica0", t0=a, t1=b, n=32)
    trace.instant("retrace", cat="compile", track="compile", bucket=8)

Time base.  Under an injected clock (``FakeClock`` in simulations) every
timestamp is that clock's seconds, so a seeded simulation's whole event log
is deterministic.  A live session (no clock) exports every event on the
profiler's base, ``time.time_ns``: the call sites stamp ``time.monotonic``
(the scheduler's ``MonotonicClock``; :func:`host_ns` for host spans) and
the trace keeps those stamps as recorded.  One ``(monotonic, realtime)``
anchor, taken when the trace is created, converts them on export
(:meth:`Trace.export_t`, :meth:`Trace.exported`): the Chrome and JSONL
exports, :meth:`Trace.host_spans`, the flight recorder's ring and metric
deltas, and a debug bundle's manifest and alerts all come out on one base,
so program spans line up with a JAX profiler trace and with each other.

Host spans.  The serving engines record one nest of host spans per batch
(``engine.tick`` ⊃ ``engine.gather``, ``model.put``, …) and the runtime a
``gc`` span per collection, thousands a second.  :meth:`Trace.host_span`
packs each into one row of an ``array('q')`` (name code, two
:func:`host_ns` stamps, one integer arg), so recording allocates no
garbage-collected object; they become :class:`TraceEvent` s only on export.

Export formats:

* ``chrome()`` — a Chrome ``trace_event`` JSON object (Perfetto /
  chrome://tracing loadable): ``ph:"X"`` complete events with µs ``ts``/
  ``dur``, ``ph:"i"`` instants, plus ``ph:"M"`` metadata naming each track.
  Tracks map to ``pid=1`` and a ``tid`` assigned by sorted track name at
  export time, so the mapping never depends on recording order.
* ``jsonl()`` — one JSON object per event: the events in recording order,
  then the host spans in recording order.

Volatility: a simulation driven by a ``FakeClock`` is deterministic, but
kernel-profile *durations* and host spans are wall-clock measurements and
some span args (``wall_us``, ``gbps``, ``vs_roofline``…) derive from them.
Those fields are enumerated here (``VOLATILE_ARGS`` / ``VOLATILE_CATS``)
and stripped by ``strip_volatile=True`` exports, which is what the
byte-identical trace determinism tests compare.  docs/observability.md
documents the contract.
"""
from __future__ import annotations

import dataclasses
import json
import struct
import time
from array import array
from typing import Any, Dict, List, Optional, Tuple

__all__ = [
    "TraceEvent", "Trace", "VOLATILE_ARGS", "VOLATILE_CATS",
    "strip_volatile_events", "chrome_json", "host_ns", "TIME_BASE",
    "HOST_SPANS",
]

# What a live session's exported timestamps are: ``time.time_ns``, the JAX
# profiler's base.  Readers check for it before lining spans up with a
# profiler trace.
TIME_BASE = "time_ns"

# The clock host spans are stamped with (monotonic ns).
host_ns = time.monotonic_ns

# Host span name -> (cat, track, the name of its one integer arg).  The
# engines' spans of one batch share its id in ``batch``.
HOST_SPANS: Dict[str, Tuple[str, str, str]] = {
    name: ("host", "host", "batch") for name in (
        "engine.tick", "engine.dispatch", "engine.harvest",
        "engine.gather", "engine.wait", "engine.fetch", "engine.fanout",
        "model.put", "model.launch", "sched.poll", "sched.complete")}
HOST_SPANS["gc"] = ("gc", "gc", "generation")
_HOST_NAMES = tuple(HOST_SPANS)
_HOST_CODES = {name: i for i, name in enumerate(_HOST_NAMES)}
_ROW = struct.Struct("=4q")      # name code, t0 ns, t1 ns, arg

# Args whose values are wall-clock-derived even in virtual-time runs.
VOLATILE_ARGS = frozenset({
    "wall_us", "wall_ms", "wall_s", "gbps", "vs_roofline", "us_per_call",
})

# Event categories whose ts/dur are wall measurements rather than values in
# the injected clock domain (kernel profiling times real executions; host
# and gc spans time the host path).
VOLATILE_CATS = frozenset({"kernel", "host", "gc"})


@dataclasses.dataclass
class TraceEvent:
    """One event: ``ph`` is the Chrome phase ("X" complete span, "i"
    instant).  ``ts``/``dur`` are seconds in the trace's clock domain."""

    ph: str
    name: str
    cat: str
    track: str
    ts: float
    dur: float = 0.0
    args: Optional[Dict[str, Any]] = None

    def to_dict(self) -> dict:
        d = dict(ph=self.ph, name=self.name, cat=self.cat, track=self.track,
                 ts=self.ts)
        if self.ph == "X":
            d["dur"] = self.dur
        if self.args:
            d["args"] = self.args
        return d


def _strip_args(args: Optional[Dict[str, Any]]) -> Optional[Dict[str, Any]]:
    if not args:
        return args
    kept = {k: v for k, v in args.items() if k not in VOLATILE_ARGS}
    return kept or None


def strip_volatile_events(events: List[TraceEvent]) -> List[TraceEvent]:
    """Copy of ``events`` with the documented volatile content removed:
    volatile args dropped everywhere; ``ts``/``dur`` zeroed for events in
    ``VOLATILE_CATS``.  What remains must be byte-identical across seeded
    ``FakeClock`` runs."""
    out = []
    for e in events:
        wall = e.cat in VOLATILE_CATS
        out.append(TraceEvent(ph=e.ph, name=e.name, cat=e.cat, track=e.track,
                              ts=0.0 if wall else e.ts,
                              dur=0.0 if wall else e.dur,
                              args=_strip_args(e.args)))
    return out


def _anchor_ns() -> int:
    """realtime ns minus monotonic ns, read once: the monotonic stamps are
    taken either side of the realtime one and averaged."""
    m0 = time.monotonic_ns()
    wall = time.time_ns()
    return wall - (m0 + time.monotonic_ns()) // 2


def chrome_json(events: List[TraceEvent]) -> dict:
    """Chrome ``trace_event`` JSON object of exported ``events`` (µs
    timestamps).  Tracks get a ``tid`` by sorted name, independent of
    recording order."""
    tids = {t: i + 1 for i, t in enumerate(sorted({e.track for e in events}))}
    out: List[dict] = [
        {"ph": "M", "pid": 1, "tid": tid, "name": "thread_name",
         "args": {"name": track}}
        for track, tid in sorted(tids.items(), key=lambda kv: kv[1])
    ]
    for e in events:
        d: Dict[str, Any] = {
            "ph": e.ph, "name": e.name, "cat": e.cat or "default",
            "pid": 1, "tid": tids[e.track],
            "ts": round(e.ts * 1e6, 3),
        }
        if e.ph == "X":
            d["dur"] = round(e.dur * 1e6, 3)
        elif e.ph == "i":
            d["s"] = "t"                      # thread-scoped instant
        if e.args:
            d["args"] = e.args
        out.append(d)
    return {"traceEvents": out, "displayTimeUnit": "ms"}


class Trace:
    """An append-only event log bound to an injectable clock."""

    def __init__(self, clock=None):
        self.clock = clock
        self.events: List[TraceEvent] = []
        # event subscribers (the flight recorder's ring): called with each
        # TraceEvent as it is recorded.  Empty list = one falsy check.
        self.listeners: List = []
        self._anchor = _anchor_ns()
        self._host = array("q")          # host spans, 4 columns a row

    def now(self) -> float:
        return self.clock.now() if self.clock is not None \
            else time.monotonic()

    @property
    def offset_ns(self) -> int:
        """What turns a stamp of this trace's clock domain into an exported
        one: the monotonic-to-realtime anchor when live, else 0."""
        return 0 if self.clock is not None else self._anchor

    def export_t(self, t: float) -> float:
        """A time of this trace's clock domain (seconds) on the exported
        base: every export of the session goes through here."""
        off = self.offset_ns
        return float(t) + off * 1e-9 if off else t

    def exported(self, events: List[TraceEvent]) -> List[TraceEvent]:
        """Copies of recorded ``events`` on the exported base."""
        if not self.offset_ns:
            return list(events)
        return [dataclasses.replace(e, ts=self.export_t(e.ts))
                for e in events]

    def __len__(self) -> int:
        return len(self.events) + len(self._host) // 4

    # -- recording ----------------------------------------------------------

    def span(self, name: str, cat: str = "", track: str = "main",
             t0: Optional[float] = None, t1: Optional[float] = None,
             **args) -> TraceEvent:
        """Record a complete span [t0, t1] (defaults: both = now)."""
        if t1 is None:
            t1 = self.now()
        if t0 is None:
            t0 = t1
        e = TraceEvent(ph="X", name=name, cat=cat, track=track,
                       ts=float(t0), dur=max(float(t1) - float(t0), 0.0),
                       args=dict(args) if args else None)
        self.events.append(e)
        if self.listeners:
            for fn in self.listeners:
                fn(e)
        return e

    def instant(self, name: str, cat: str = "", track: str = "main",
                t: Optional[float] = None, **args) -> TraceEvent:
        e = TraceEvent(ph="i", name=name, cat=cat, track=track,
                       ts=float(t) if t is not None else self.now(),
                       args=dict(args) if args else None)
        self.events.append(e)
        if self.listeners:
            for fn in self.listeners:
                fn(e)
        return e

    def host_span(self, name: str, t0: int, t1: int, arg: int = -1) -> None:
        """Record one host span of ``HOST_SPANS`` between two
        :func:`host_ns` stamps.  One packed row, written by one C call, so
        a collection or another thread cannot split it; listeners are not
        called (host spans reach them only through the exports)."""
        self._host.frombytes(_ROW.pack(_HOST_CODES[name], t0, t1, arg))

    def host_mark(self) -> int:
        """A mark for :meth:`host_claim`: the host spans recorded so far."""
        return len(self._host)

    def host_claim(self, mark: int, arg: int) -> None:
        """Give ``arg`` to the host spans recorded since ``mark`` without
        one (-1): an engine claims the spans the model recorded for its
        batch, as the model does not know the batch's id."""
        h = self._host
        for i in range(mark + 3, len(h), 4):
            if h[i] == -1:
                h[i] = arg

    # -- export -------------------------------------------------------------

    def host_spans(self) -> List[Tuple[str, int, int, int]]:
        """The host spans, in recording order, as ``(name, start_ns,
        end_ns, arg)`` on the exported time base (``time.time_ns`` when
        live)."""
        off, h = self.offset_ns, self._host
        return [(_HOST_NAMES[h[i]], h[i + 1] + off, h[i + 2] + off, h[i + 3])
                for i in range(0, len(h), 4)]

    def all_events(self) -> List[TraceEvent]:
        """``events`` followed by the host spans as :class:`TraceEvent` s,
        on the exported time base."""
        out = self.exported(self.events)
        for name, t0, t1, arg in self.host_spans():
            cat, track, key = HOST_SPANS[name]
            out.append(TraceEvent(ph="X", name=name, cat=cat, track=track,
                                  ts=t0 * 1e-9, dur=max(t1 - t0, 0) * 1e-9,
                                  args={key: arg}))
        return out

    def _exported(self, strip_volatile: bool) -> List[TraceEvent]:
        events = self.all_events()
        return strip_volatile_events(events) if strip_volatile else events

    def chrome(self, strip_volatile: bool = False) -> dict:
        """Chrome ``trace_event`` JSON object (µs timestamps)."""
        return chrome_json(self._exported(strip_volatile))

    def jsonl(self, strip_volatile: bool = False) -> str:
        """One JSON object per line, recording order (events, then host
        spans), seconds timestamps."""
        return "".join(json.dumps(e.to_dict(), sort_keys=True) + "\n"
                       for e in self._exported(strip_volatile))

    def write_chrome(self, path: str, strip_volatile: bool = False) -> None:
        with open(path, "w") as f:
            json.dump(self.chrome(strip_volatile=strip_volatile), f)
            f.write("\n")

    def write_jsonl(self, path: str, strip_volatile: bool = False) -> None:
        with open(path, "w") as f:
            f.write(self.jsonl(strip_volatile=strip_volatile))

    # -- summary ------------------------------------------------------------

    def summary(self) -> dict:
        events = self.all_events()
        spans = [e for e in events if e.ph == "X"]
        by_track: Dict[str, dict] = {}
        for e in spans:
            row = by_track.setdefault(e.track, dict(spans=0, total_s=0.0))
            row["spans"] += 1
            row["total_s"] += e.dur
        return dict(events=len(events), spans=len(spans),
                    instants=sum(1 for e in events if e.ph == "i"),
                    tracks={t: by_track[t] for t in sorted(by_track)})
