"""The program's spans in a traced run: innermost attribution of idle time
(each idle nanosecond charged once, sibling spans left as they are), the
two host-milliseconds-a-batch readings, no session for a program without
the profiler's time base, and one whole traced run on the CPU."""
import json
import os
import time

import jax
import pytest

from bench import host_split, spans, trace
from bench.tests.test_faults import small_cell
from bench.tests.test_trace import DATA, MS, SPANS, T0, ev, profile, span


def test_innermost_leaves_sibling_spans_as_they_are():
    sib = [x for x in SPANS if x[0] != "bench.window"]
    assert spans.innermost(sib) == sorted(sib, key=lambda x: x[1])
    assert spans.nested(SPANS, []) == [SPANS[0]] + sib


def test_innermost_names_each_piece_for_the_innermost_span():
    pieces = spans.innermost([
        span("bench.tick", 0, 100), span("engine.tick", 10, 90),
        span("model.put", 20, 30), span("engine.wait", 40, 80),
        span("gc", 25, 45),      # overlaps: the later start wins at 40
        span("bench.sleep", 100, 110)])
    assert [(n, (s - T0) // MS, (e - T0) // MS) for n, s, e in pieces] == [
        ("bench.tick", 0, 10), ("engine.tick", 10, 20),
        ("model.put", 20, 25), ("gc", 25, 40), ("engine.wait", 40, 80),
        ("engine.tick", 80, 90), ("bench.tick", 90, 100),
        ("bench.sleep", 100, 110)]


def test_idle_time_is_charged_once_to_the_innermost_span():
    harness = [span("bench.window", 0, 100), span("bench.tick", 0, 100)]
    program = [("engine.tick", T0 + 10 * MS, T0 + 90 * MS, 0),
               ("model.put", T0 + 20 * MS, T0 + 30 * MS, 0),
               ("engine.wait", T0 + 30 * MS, T0 + 80 * MS, 0)]
    ops = [ev("%s1b0.1 = u8[1] custom-call()", 30, 40)]      # 30-70 busy
    s = trace.reduce(profile({0: ops}), spans.nested(harness, program),
                     n_devices=1)
    assert dict(s.gaps) == pytest.approx({
        "bench.tick": 0.020, "engine.tick": 0.020, "model.put": 0.010,
        "engine.wait": 0.010})
    assert sum(v for _, v in s.gaps) == pytest.approx(s.window_s - s.busy_s)
    assert s.top_ops == [("s1b0.1", pytest.approx(0.040))]


def test_recorded_chip_trace_reads_the_same_through_nested():
    with open(os.path.join(DATA, "spans.json")) as f:
        harness = [tuple(x) for x in json.load(f)]
    path = os.path.join(DATA, "run.xplane.pb")
    a = trace.reduce_file(path, harness, n_devices=1)
    b = trace.reduce_file(path, spans.nested(harness, []), n_devices=1)
    assert (a.window_s, a.busy_s, a.top_ops) == \
        (b.window_s, b.busy_s, b.top_ops)
    assert dict(a.gaps) == pytest.approx(dict(b.gaps), abs=1e-12)


def _batch(name_ms, bid):
    return [(n, T0 + a * MS, T0 + b * MS, bid) for n, a, b in name_ms]


def test_host_ms_per_batch_offline():
    program = (_batch([("engine.tick", 0, 20), ("engine.wait", 5, 15)], 0)
               + _batch([("engine.tick", 20, 50), ("engine.wait", 25, 35)], 1)
               + _batch([("engine.tick", 50, 60), ("engine.wait", 52, 58)], 2)
               + [("gc", T0, T0 + MS, 2)])
    assert spans.host_ms_per_batch_offline(program) == pytest.approx(10.0)
    assert spans.median_ms(program, "engine.wait") == pytest.approx(10.0)
    assert spans.host_ms_per_batch_offline([]) is None
    assert spans.median_ms([], "engine.wait") is None
    assert spans.host_ms_per_batch_poisson(program) is None


def test_host_ms_per_batch_poisson():
    program = (_batch([("engine.dispatch", 0, 2), ("engine.harvest", 5, 9),
                       ("engine.wait", 5, 8)], 7)
               + _batch([("engine.dispatch", 2, 5), ("engine.harvest", 9, 12),
                         ("engine.wait", 9, 10)], 8)
               + _batch([("engine.dispatch", 12, 13)], 9))   # not harvested
    assert spans.host_ms_per_batch_poisson(program) == pytest.approx(4.0)
    assert spans.host_ms_per_batch_poisson([]) is None
    assert spans.host_ms_per_batch_offline(program) is None


def test_stalls_and_window():
    harness = [span("bench.window", 10, 200), span("bench.tick", 10, 190)]
    program = [("gc", T0 + 20 * MS, T0 + 90 * MS, 2),
               ("engine.tick", T0 + 195 * MS, T0 + 210 * MS, 0)]
    inside = spans.in_window(harness, program)
    assert inside == program[:1]
    pieces = spans.innermost(harness[1:] + [x[:3] for x in inside])
    (name, at_s, ms), (name2, _, ms2) = spans.stalls(pieces, T0 + 10 * MS)
    assert (name, at_s, ms) == ("bench.tick", pytest.approx(0.08),
                                pytest.approx(100.0))
    assert (name2, ms2) == ("gc", pytest.approx(70.0))


def test_a_program_without_the_time_base_gets_no_session(monkeypatch):
    from repro.obs import runtime
    from repro.obs import trace as program_trace
    monkeypatch.delattr(program_trace, "TIME_BASE")
    assert spans.open_session() is None
    assert runtime.active() is None
    assert spans.close_session(None) == []


def test_a_session_is_removed_with_its_spans():
    from repro.obs import runtime
    ob = spans.open_session()
    assert runtime.active() is ob
    ob.trace.host_span("engine.tick", 1, 2, 0)
    assert ("engine.tick", 1 + ob.trace.offset_ns, 2 + ob.trace.offset_ns,
            0) in spans.close_session(ob)
    assert runtime.active() is None


@pytest.mark.parametrize("cell", ["resnet8-offline", "resnet20-poisson"])
def test_traced_run_on_the_cpu(cell):
    """The whole traced run at a small size: the CPU has no TPU plane, so
    every nanosecond of the window is idle, and all of it is charged.
    (The CPU has no peaks, so only the idle share is read.)"""
    c = small_cell(cell)
    c.per_layer = [m for m in c.per_layer if m["name"].startswith("device")]
    out = host_split.traced_run(c, 2 ** 31 + 5, 0.5, jax.devices(),
                                t_start=time.monotonic())
    assert out["metrics"]
    assert out["correct"]
    hs = out["host_split"]
    assert hs["session"]
    gaps = dict(out["breakdown"]["idle_gaps"])
    assert sum(gaps.values()) == pytest.approx(out["device"]["window_s"])
    if cell == "resnet8-offline":
        assert hs["host_ms_per_batch.offline"] > 0
        assert {"engine.gather", "model.put", "engine.fanout"} <= set(gaps)
        assert hs["engine_tick_s"] <= hs["bench_tick_s"]
    else:
        assert hs["host_ms_per_batch.poisson"] > 0
        assert {"sched.poll", "engine.gather", "sched.complete"} <= set(gaps)
        assert hs["engine_dispatch_harvest_s"] <= hs["bench_tick_s"]
    assert hs["idle_on_bench_tick"] == pytest.approx(
        gaps.get("bench.tick", 0.0) / out["device"]["window_s"])
    from repro.obs import runtime
    assert runtime.active() is None
