"""Reduce a profiler trace (``.xplane.pb``) to device busy time, idle
share, the top device operations and the idle gaps by host activity.

Read with ``jax.profiler.ProfileData`` alone.  A device is a plane named
``/device:TPU:<n>``; its operations are the events of its ``XLA Ops`` line,
timed from the trace's ``profile_start_time``.  Busy time is the union of
those intervals inside the traced window, so overlapping operations count
once.  The window is the harness's host span named ``window_span``; its
other host spans (``traffic.drive.Spans``, on ``time.time_ns``, the clock
of ``profile_start_time``) say what the host was doing in each idle gap: a
gap's time goes to the spans that overlap it.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re
from typing import Dict, List, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"


@dataclasses.dataclass
class DeviceTrace:
    name: str
    busy_s: float


@dataclasses.dataclass
class TraceSummary:
    window_s: float
    devices: List[DeviceTrace]
    # (host activity, idle s) and (op name, busy s), each per device
    gaps: List[Tuple[str, float]]
    top_ops: List[Tuple[str, float]]

    @property
    def busy_s(self) -> float:
        """Busy seconds, averaged over the devices."""
        return sum(d.busy_s for d in self.devices) / len(self.devices)

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def op_name(text: str) -> str:
    """``%resblock_fused_op.10 = u8[...] custom-call(...)`` -> the HLO
    instruction's name, ``resblock_fused_op.10``."""
    return text.split(" = ", 1)[0].lstrip("%")


def merge(intervals: Sequence[Tuple[int, int]]) -> List[Tuple[int, int]]:
    """Union of half-open intervals, sorted."""
    out: List[Tuple[int, int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def reduce(profile, host_spans, n_devices: int,
           window_span: str = "bench.window", top: int = 10) -> TraceSummary:
    """Reduce a ``jax.profiler.ProfileData`` and the harness's host spans
    ``(name, start_ns, end_ns)`` to a :class:`TraceSummary` of the devices
    ``/device:TPU:0`` to ``n_devices - 1`` (a device that ran nothing in the
    window counts as idle throughout)."""
    t0 = None
    dev_events: Dict[str, List[Tuple[int, int, str]]] = {}
    for plane in profile.planes:
        for k, v in plane.stats:
            if k == "profile_start_time":
                t0 = int(v)
        if DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    dev_events.setdefault(plane.name, []).extend(
                        (int(e.start_ns), int(e.start_ns + e.duration_ns),
                         op_name(e.name)) for e in line.events)
    if t0 is None:
        raise ValueError("the trace has no profile_start_time")
    spans = [(s - t0, e - t0, name) for name, s, e in host_spans]
    window = next(((s, e) for s, e, name in spans if name == window_span),
                  None)
    spans = [x for x in spans if x[2] != window_span]
    if window is None:
        raise ValueError(f"no host span {window_span!r}")
    lo, hi = window
    spans.sort()
    starts = [s for s, _, _ in spans]
    devices = []
    op_seconds: Dict[str, float] = {}
    gap_seconds: Dict[str, float] = {}
    for k in range(n_devices):
        name = f"/device:TPU:{k}"
        evs = [(max(s, lo), min(e, hi), n)
               for s, e, n in dev_events.get(name, ()) if e > lo and s < hi]
        for s, e, n in evs:
            op_seconds[n] = op_seconds.get(n, 0.0) + (e - s) * 1e-9
        busy = merge([(s, e) for s, e, _ in evs])
        devices.append(DeviceTrace(
            name=name, busy_s=sum(e - s for s, e in busy) * 1e-9))
        prev = lo
        for s, e in busy + [(hi, hi)]:
            if s > prev:
                for who, secs in _host_activity(spans, starts, prev, s):
                    gap_seconds[who] = gap_seconds.get(who, 0.0) + secs
            prev = max(prev, e)
    gaps = sorted(((k, v / n_devices) for k, v in gap_seconds.items()),
                  key=lambda kv: -kv[1])[:top]
    top_ops = sorted(((k, v / n_devices) for k, v in op_seconds.items()),
                     key=lambda kv: -kv[1])[:top]
    return TraceSummary(window_s=(hi - lo) * 1e-9, devices=devices,
                        gaps=gaps, top_ops=top_ops)


def _host_activity(spans, starts, lo: int, hi: int):
    """Split the idle gap [lo, hi) by the harness spans (siblings, not
    nested) that overlap it: ``(span name, seconds)``, the rest as
    ``outside harness spans``."""
    out = []
    covered = 0
    i = max(bisect.bisect_right(starts, lo) - 1, 0)
    while i < len(spans) and spans[i][0] < hi:
        s, e, name = spans[i]
        overlap = min(e, hi) - max(s, lo)
        if overlap > 0:
            out.append((name, overlap * 1e-9))
            covered += overlap
        i += 1
    if hi - lo > covered:
        out.append(("outside harness spans", (hi - lo - covered) * 1e-9))
    return out


def reduce_file(path: str, host_spans, n_devices: int) -> TraceSummary:
    from jax.profiler import ProfileData
    return reduce(ProfileData.from_file(path), host_spans, n_devices)
