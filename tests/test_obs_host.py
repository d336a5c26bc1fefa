"""repro.obs host spans: the serving engines' per-batch host path, the
``gc`` span, the profiler's time base, and what recording costs.

* Under a live session each engine records one nest of named host spans a
  batch, each child inside its parent and carrying its batch's id;
  ``ResNetEngine``'s tick also holds the launch of the batch after its own.
* A live session exports on ``time.time_ns`` (the JAX profiler's base),
  its debug bundles too; a ``FakeClock`` session keeps its virtual
  seconds and records no wall-timed collections.
* Recording a host span allocates no garbage-collected object.
"""
import gc
import json
import time

import jax
import numpy as np
import pytest

from repro.models import resnet as R
from repro.obs import runtime as obsrt
from repro.obs import trace as T
from repro.obs.bundle import read_bundle
from repro.obs.health import BitExactSentinel, HealthMonitor
from repro.obs.recorder import FlightRecorder
from repro.serve.engine import (ImageRequest, ResNetEngine,
                                ShardedResNetEngine)
from repro.serve.sched import FakeClock

LAUNCH = ("engine.gather", "model.put", "model.launch")
COMPLETE = ("engine.wait", "engine.fetch", "engine.fanout")
DISPATCH = ("sched.poll", "engine.gather", "model.put", "model.launch")
HARVEST = ("engine.wait", "engine.fetch", "sched.complete", "engine.fanout")


@pytest.fixture(autouse=True)
def _no_session_leaks():
    prior = obsrt.disable()
    yield
    obsrt.install(prior)


@pytest.fixture(scope="module")
def qp8():
    params = R.init_params(R.RESNET8, jax.random.PRNGKey(7))
    return R.quantize_params(R.fold_params(params), R.RESNET8)


def _requests(n):
    imgs = np.asarray(jax.random.uniform(
        jax.random.PRNGKey(1), (n, 32, 32, 3), minval=0.0, maxval=0.999))
    return [ImageRequest(rid=i, image=im) for i, im in enumerate(imgs)]


def _by_batch(spans, names):
    """{batch id: {span name: (t0, t1)}} over the named spans, asserting
    each name comes once a batch."""
    out = {}
    for name, t0, t1, arg in spans:
        if name in names:
            assert name not in out.setdefault(arg, {}), (name, arg)
            out[arg][name] = (t0, t1)
    return out


def _assert_in_order(parent, children, where):
    prev, end = parent
    for c0, c1 in children:                # in order, inside the parent
        assert prev <= c0 <= c1 <= end, (where, parent, children)
        prev = c1


def _assert_nest(batches, parent, children):
    for bid, sp in batches.items():
        assert set(sp) == {parent, *children}, (bid, sorted(sp))
        _assert_in_order(sp[parent], [sp[c] for c in children], bid)


def test_resnet_engine_records_one_nest_per_batch(qp8):
    eng = ResNetEngine(R.RESNET8, qp8, batch=2, backend="lax-int")
    ob = obsrt.instrument()
    for r in _requests(5):
        eng.submit(r)
    assert eng.run() == 3
    obsrt.disable()
    batches = _by_batch(ob.trace.host_spans(),
                        ("engine.tick",) + LAUNCH + COMPLETE)
    assert sorted(batches) == [0, 1, 2]
    for bid, sp in batches.items():
        assert set(sp) == {"engine.tick", *LAUNCH, *COMPLETE}, bid
    # tick k launches batch k + 1 (tick 0 launches batch 0 first), then
    # waits on, fetches and fans out batch k, all inside its engine.tick
    for k, sp in batches.items():
        launched = [0] * (k == 0) + [k + 1] * (k + 1 in batches)
        children = [batches[b][n] for b in launched for n in LAUNCH] + \
            [sp[n] for n in COMPLETE]
        _assert_in_order(sp["engine.tick"], children, k)
    assert ob.metrics.total("engine_overlapped_launches_total") == 2


def test_resnet_engine_refills_the_same_staging_buffers(qp8):
    eng = ResNetEngine(R.RESNET8, qp8, batch=2, backend="lax-int")
    ob = obsrt.instrument()
    for r in _requests(11):                  # 6 launches, the last ragged
        eng.submit(r)
    held = []                        # every buffer, free or in flight
    while eng.tick():
        held.append(sorted(id(b) for b in [*eng._path._free,
                                           *(b.buf for b in eng._in_flight)]))
    obsrt.disable()
    assert eng.ticks == 6
    assert len(held[0]) == 2                 # two batches in flight at most
    assert held == [held[0]] * 6             # allocated once, then reused
    assert ob.metrics.total("engine_staging_reuses_total") == 6 - 2
    assert ob.metrics.total("engine_overlapped_launches_total") == 5


def test_sharded_engine_refills_the_same_staging_buffers(qp8):
    """Dispatches one at a time refill one staging buffer, and the logits
    stay bitwise those of ResNetEngine."""
    reqs, alone = _requests(6), _requests(6)
    plain = ResNetEngine(R.RESNET8, qp8, batch=2, backend="lax-int")
    for r in alone:
        plain.submit(r)
    plain.run()
    eng = ShardedResNetEngine(R.RESNET8, qp8, batch=2, backend="lax-int",
                              replicas=1, slack_ms=1.0, clock=FakeClock())
    ob = obsrt.instrument()
    for k in range(0, 6, 2):                 # a full bucket is due at once
        eng.submit(reqs[k])
        eng.submit(reqs[k + 1])
        eng.run()
        assert not eng._in_flight
    obsrt.disable()
    assert all(r.done for r in reqs)
    assert len(eng._paths[0]._free) == 1
    assert ob.metrics.total("engine_staging_reuses_total") == 2
    np.testing.assert_array_equal(np.stack([r.logits for r in reqs]),
                                  np.stack([r.logits for r in alone]))


def test_sharded_engine_records_dispatch_and_harvest_nests(qp8):
    eng = ShardedResNetEngine(R.RESNET8, qp8, batch=2, backend="lax-int",
                              replicas=1, batch_sizes=(1, 2), slack_ms=0.0)
    ob = obsrt.instrument()
    reqs = _requests(5)
    for r in reqs:
        eng.submit(r)
    eng.run()
    obsrt.disable()
    assert all(r.done for r in reqs)
    spans = ob.trace.host_spans()
    dispatched = _by_batch(spans, ("engine.dispatch",) + DISPATCH)
    harvested = _by_batch(spans, ("engine.harvest",) + HARVEST)
    assert sorted(dispatched) == sorted(harvested) == [0, 1, 2]
    _assert_nest(dispatched, "engine.dispatch", DISPATCH)
    _assert_nest(harvested, "engine.harvest", HARVEST)
    for bid in dispatched:                 # harvested after dispatching
        assert dispatched[bid]["engine.dispatch"][1] <= \
            harvested[bid]["engine.harvest"][0]
    # the scheduler's per-batch span says how many it served
    batch = [e for e in ob.trace.events if e.name == "batch"]
    assert sorted(e.args["n"] for e in batch) == [1, 2, 2]


def test_live_spans_are_on_the_profilers_clock():
    ob = obsrt.instrument()
    t0 = T.host_ns()
    before = time.time_ns()
    ob.trace.host_span("engine.tick", t0, T.host_ns(), 0)
    ob.trace.span("batch", cat="sched", t0=ob.now(), t1=ob.now())
    after = time.time_ns()
    (name, s, e, _), = ob.trace.host_spans()
    assert name == "engine.tick"
    assert before - 1_000_000 <= s <= e <= after + 1_000_000
    recorded, = ob.trace.events                 # kept in the clock domain
    assert abs(recorded.ts - time.monotonic()) < 1.0
    batch = [e for e in ob.trace.all_events() if e.name == "batch"][0]
    assert abs(batch.ts * 1e9 - before) < 1_000_000
    exported = [d for d in ob.trace.chrome()["traceEvents"]
                if d["ph"] == "X"]
    assert all(abs(d["ts"] * 1e3 - before) < 1_000_000 for d in exported)


def test_live_bundle_puts_alerts_and_spans_on_one_base(tmp_path):
    ob = obsrt.instrument()
    rec = FlightRecorder()
    rec.attach(ob.trace)
    hm = HealthMonitor(ob, rules=[BitExactSentinel()], recorder=rec,
                       bundle_dir=str(tmp_path))
    before = time.time()
    ob.trace.span("batch", cat="sched", track="replica0",
                  t0=ob.now() - 0.001, t1=ob.now())
    ob.metrics.counter("ab_mismatch_total", "m").inc()
    fired = hm.tick(ob.now())
    after = time.time()
    assert fired and len(hm.bundles) == 1
    b = read_bundle(hm.bundles[0])
    alert_t = b["alerts"][0]["t"]
    assert before - 0.001 <= alert_t <= after + 0.001
    assert b["manifest"]["t"] == alert_t
    assert [d["t"] for d in b["deltas"]] == [alert_t]
    instant, = [e for e in b["trace_events"] if e["name"] == "alert"]
    assert instant["ts"] == pytest.approx(alert_t * 1e6, abs=1.0)   # µs
    batch, = [e for e in b["trace_events"] if e["name"] == "batch"]
    assert before * 1e6 - 2e3 <= batch["ts"] <= instant["ts"]
    assert json.loads(hm.alert_log_jsonl())["t"] == alert_t


def test_model_spans_outside_an_engine_carry_no_batch_id(qp8):
    eng = ResNetEngine(R.RESNET8, qp8, batch=2, backend="lax-int")
    ob = obsrt.instrument()
    eng.model(np.zeros((2, 32, 32, 3), np.float32))
    for r in _requests(2):
        eng.submit(r)
    eng.tick()
    obsrt.disable()
    model = [(n, arg) for n, _, _, arg in ob.trace.host_spans()
             if n.startswith("model.")]
    assert model == [("model.put", -1), ("model.launch", -1),
                     ("model.put", 0), ("model.launch", 0)]


def test_fake_clock_sessions_keep_virtual_time_and_record_no_gc():
    clock = FakeClock(5.0)
    ob = obsrt.instrument(clock=clock)
    ob.trace.span("batch", cat="sched", t0=5.0, t1=5.5)
    gc.collect()
    assert ob.trace.events[0].ts == 5.0
    assert not ob.trace.host_spans()
    assert obsrt._on_gc not in gc.callbacks


def test_gc_spans_only_while_a_live_session_is_installed():
    ob = obsrt.instrument()
    assert obsrt._on_gc in gc.callbacks
    gc.collect()
    obsrt.disable()
    assert obsrt._on_gc not in gc.callbacks
    n = len(ob.trace.host_spans())
    gc.collect()
    spans = ob.trace.host_spans()
    assert len(spans) == n
    assert {s[0] for s in spans} == {"gc"}
    assert 2 in [s[3] for s in spans]              # gc.collect(): gen 2
    assert all(s[1] <= s[2] for s in spans)
    event = [e for e in ob.trace.all_events() if e.name == "gc"][-1]
    assert event.cat == "gc" and event.track == "gc"
    assert set(event.args) == {"generation"}


def test_recording_host_spans_allocates_no_tracked_objects():
    ob = obsrt.instrument()
    trace, ns = ob.trace, T.host_ns
    trace.host_span("engine.tick", ns(), ns(), 0)   # warm the code paths
    gc.collect()
    gc.disable()
    try:
        before = len(gc.get_objects())
        for i in range(10_000):
            trace.host_span("engine.tick", ns(), ns(), i)
        after = len(gc.get_objects())
    finally:
        gc.enable()
    assert after - before <= 0
    ticks = [sp for sp in trace.host_spans() if sp[0] == "engine.tick"]
    assert [sp[3] for sp in ticks] == [0] + list(range(10_000))


def test_host_spans_join_the_exports_and_strip_as_volatile():
    ob = obsrt.instrument(clock=FakeClock())    # no gc spans
    ob.trace.host_span("model.put", 10, 30, 4)
    put, = [d for d in ob.trace.chrome()["traceEvents"] if d["ph"] == "X"]
    assert put["name"] == "model.put" and put["args"] == {"batch": 4}
    assert put["ts"] == pytest.approx(0.01) and put["dur"] == \
        pytest.approx(0.02)                       # µs
    assert ob.trace.jsonl(strip_volatile=True) == (
        '{"args": {"batch": 4}, "cat": "host", "dur": 0.0, '
        '"name": "model.put", "ph": "X", "track": "host", "ts": 0.0}\n')
    assert ob.trace.summary()["tracks"]["host"]["spans"] == 1
    with pytest.raises(KeyError):
        ob.trace.host_span("not.a.span", 0, 1)
