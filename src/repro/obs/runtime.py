"""The on/off switch every instrumented call site checks.

The whole zero-overhead-when-disabled contract lives here: instrumented
code does

    ob = runtime.active()
    if ob is not None:
        ob.metrics.counter(...).inc(...)
        ob.trace.span(...)

so the disabled cost is one module-global read returning ``None`` — no
allocation, no method call, no event object.  tests/test_obs.py enforces
this by installing an :class:`Observability` whose trace/metrics raise on
any use and running the serving path with obs *disabled*.

``instrument()`` installs a session (optionally bound to a ``FakeClock``
so a virtual-time simulation yields a deterministic event log);
``disable()`` removes it; ``instrumented()`` is the context-manager form.
Only one session is active at a time — the last ``instrument()`` wins,
which is the right semantics for a CLI process.

While a live session (no injected clock) is installed, a ``gc.callbacks``
hook records every garbage collection as a ``gc`` host span with its
generation, so a stall in the trace can be put down to a collection.  The
hook is registered only then: with no session, or a ``FakeClock`` one,
collections cost nothing extra and simulations stay deterministic.
"""
from __future__ import annotations

import contextlib
import gc
import time
from typing import List, Optional

from repro.obs.metrics import MetricsRegistry
from repro.obs.trace import Trace, host_ns

__all__ = [
    "Observability", "active", "instrument", "install", "disable",
    "instrumented", "export",
]


class Observability:
    """One instrumentation session: a metrics registry + a trace + any
    kernel profiles attached along the way, sharing one clock domain."""

    def __init__(self, clock=None):
        self.clock = clock
        self.metrics = MetricsRegistry()
        self.trace = Trace(clock=clock)
        self.profiles: List = []          # TaskProfile rows (obs.profile)
        self.health = None                # HealthMonitor, when alerting is on

    def now(self) -> float:
        return self.clock.now() if self.clock is not None \
            else time.monotonic()

    def set_clock(self, clock) -> None:
        """Re-bind the clock domain (a runner that builds its ``FakeClock``
        after instrumentation was requested calls this before recording)."""
        self.clock = clock
        self.trace.clock = clock


_ACTIVE: Optional[Observability] = None
_GC_START = [0]                   # host_ns at the running collection's start


def active() -> Optional[Observability]:
    """The installed session, or None — THE hot-path check."""
    return _ACTIVE


def _on_gc(phase: str, info: dict) -> None:
    if phase == "start":
        _GC_START[0] = host_ns()
        return
    ob = _ACTIVE
    # a session re-bound to a FakeClock after install (set_clock) stays
    # deterministic: no wall-timed collections in its log
    if ob is not None and ob.clock is None:
        ob.trace.host_span("gc", _GC_START[0], host_ns(), info["generation"])


def install(ob: Optional[Observability]) -> Optional[Observability]:
    """(Re)install a specific session (or ``None`` to uninstall) — how the
    ``overhead_obs`` benchmark toggles one accumulating session on and off
    around interleave-timed calls, and how callers restore whatever was
    active before they borrowed the switch."""
    global _ACTIVE
    _ACTIVE = ob
    hooked = _on_gc in gc.callbacks
    live = ob is not None and ob.clock is None
    if live and not hooked:
        gc.callbacks.append(_on_gc)
    elif hooked and not live:
        gc.callbacks.remove(_on_gc)
    return ob


def instrument(clock=None) -> Observability:
    """Install (and return) a fresh observability session."""
    return install(Observability(clock=clock))


def disable() -> Optional[Observability]:
    """Uninstall the session; returns it so callers can still export."""
    ob = _ACTIVE
    install(None)
    return ob


@contextlib.contextmanager
def instrumented(clock=None):
    """``with obs.instrumented() as ob: ...`` — always uninstalls."""
    ob = instrument(clock=clock)
    try:
        yield ob
    finally:
        disable()


def export(ob: Observability, trace_out: Optional[str] = None,
           metrics_out: Optional[str] = None,
           jsonl_out: Optional[str] = None,
           strip_volatile: bool = False) -> dict:
    """Write the session's artifacts; returns {kind: path} for what was
    written.  ``trace_out`` gets Chrome ``trace_event`` JSON (Perfetto),
    ``jsonl_out`` the line-per-event log, ``metrics_out`` Prometheus text."""
    written = {}
    if trace_out:
        ob.trace.write_chrome(trace_out, strip_volatile=strip_volatile)
        written["trace"] = trace_out
    if jsonl_out:
        ob.trace.write_jsonl(jsonl_out, strip_volatile=strip_volatile)
        written["jsonl"] = jsonl_out
    if metrics_out:
        with open(metrics_out, "w") as f:
            f.write(ob.metrics.render_text())
        written["metrics"] = metrics_out
    return written
