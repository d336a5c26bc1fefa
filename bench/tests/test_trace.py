"""The trace reduction: busy time as a union, idle share, top device ops
and idle gaps split by the harness's host spans; on a synthetic profile
with known answers, and on a trace recorded on the chip."""
import json
import os
import types

import pytest

from bench import trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
T0 = 1_700_000_000_000_000_000        # profile_start_time, realtime ns
MS = 1_000_000


def ev(name, start_ms, dur_ms):
    return types.SimpleNamespace(name=name, start_ns=start_ms * MS,
                                 duration_ns=dur_ms * MS)


def plane(name, lines=(), stats=()):
    return types.SimpleNamespace(
        name=name, stats=list(stats),
        lines=[types.SimpleNamespace(name=n, events=e) for n, e in lines])


def profile(ops_by_device):
    planes = [plane("Task Environment",
                    stats=[("profile_start_time", str(T0))])]
    for k, ops in ops_by_device.items():
        planes.append(plane(f"/device:TPU:{k}", [
            ("XLA Modules", [ev("jit__staged(1)", 0, 100)]),
            ("XLA Ops", ops)]))
    return types.SimpleNamespace(planes=planes)


def span(name, a_ms, b_ms):
    return (name, T0 + a_ms * MS, T0 + b_ms * MS)


SPANS = [span("bench.window", 10, 110), span("bench.submit", 10, 30),
         span("bench.tick", 30, 90), span("bench.sleep", 90, 110)]


def test_busy_is_a_union_clipped_to_the_window():
    ops = [ev("%a.1 = u8[1] custom-call()", 0, 20),     # 10 ms in window
           ev("%b.2 = f32[1] copy()", 40, 20),           # 40-60
           ev("%c.3 = f32[1] fusion()", 50, 20),         # overlaps: 60-70
           ev("%a.1 = u8[1] custom-call()", 100, 30)]    # 100-110 inside
    s = trace.reduce(profile({0: ops}), SPANS, n_devices=1)
    assert s.window_s == pytest.approx(0.1)
    assert s.busy_s == pytest.approx(0.050)
    assert s.idle_share == pytest.approx(0.5)
    assert s.top_ops[0] == ("a.1", pytest.approx(0.020))
    assert dict(s.top_ops) == pytest.approx(
        {"a.1": 0.020, "b.2": 0.020, "c.3": 0.020})
    # idle 20-40 in submit (10) and tick (10), 70-90 in tick, 90-100 sleep
    assert dict(s.gaps) == pytest.approx(
        {"bench.submit": 0.010, "bench.tick": 0.030, "bench.sleep": 0.010})


def test_device_that_ran_nothing_is_idle():
    s = trace.reduce(profile({0: [ev("%x = f32[] add()", 10, 100)]}),
                     SPANS, n_devices=2)
    assert s.busy_s == pytest.approx(0.05)      # the mean of 0.1 and 0
    assert s.idle_share == pytest.approx(0.5)


def test_gap_outside_spans():
    spans = [span("bench.window", 0, 100), span("bench.tick", 0, 40)]
    s = trace.reduce(profile({0: [ev("%x = f32[] add()", 80, 20)]}),
                     spans, n_devices=1)
    assert dict(s.gaps) == pytest.approx(
        {"bench.tick": 0.04, "outside harness spans": 0.04})


def test_missing_window_is_an_error():
    with pytest.raises(ValueError):
        trace.reduce(profile({0: []}), SPANS[1:], n_devices=1)


def test_merge():
    assert trace.merge([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]


def test_recorded_chip_trace():
    # 0.3 s of resnet8-offline traced on one TPU v5 lite; the run itself
    # reported the busy and window seconds kept in expected.json
    with open(os.path.join(DATA, "spans.json")) as f:
        spans = json.load(f)
    with open(os.path.join(DATA, "expected.json")) as f:
        expected = json.load(f)
    s = trace.reduce_file(os.path.join(DATA, "run.xplane.pb"), spans,
                          n_devices=1)
    assert 0 < s.busy_s < s.window_s
    assert s.window_s == pytest.approx(expected["window_s"])
    assert s.busy_s == pytest.approx(expected["busy_s"])
    assert s.busy_s == pytest.approx(expected["reported_busy_s"])
    assert s.window_s == pytest.approx(expected["reported_window_s"])
    names = [n for n, _ in s.top_ops]
    assert any(n.startswith("resblock_fused") for n in names)
    assert sum(v for _, v in s.gaps) == pytest.approx(
        s.window_s - s.busy_s)
