"""repro.obs — the observability contracts.

Four layers of guarantee, strictest first:

1. **Zero overhead disabled** — with no session installed the serving path
   must never touch an observer (the poisoned-session test), and served
   logits are bit-identical with obs on vs off: instrumentation reads the
   system, it never steers it.
2. **Determinism** — under a seeded ``FakeClock`` simulation the exported
   metrics text is byte-identical across runs, and the trace (Chrome and
   JSONL) is byte-identical after the documented volatile-field strip
   (``VOLATILE_ARGS`` / ``VOLATILE_CATS``).
3. **Correctness of the recorded story** — span endpoints equal the
   scheduler's own virtual-time stamps, counter totals agree with
   ``Scheduler.summary()`` / ``Autoscaler.decisions``, compile counters
   agree with ``CompiledModel.trace_counts``.
4. **Artifacts parse** — the ``python -m repro.obs`` report CLI accepts
   what ``--trace-out`` / ``--metrics-out`` write and rejects garbage.
"""
import copy
import json

import numpy as np
import pytest

from repro.obs import metrics as M
from repro.obs import runtime as obsrt
from repro.obs import trace as T
from repro.serve.sched import FakeClock, Scheduler


@pytest.fixture(autouse=True)
def _no_session_leaks():
    """Obs state is a module global: every test starts and ends clean."""
    prior = obsrt.disable()
    yield
    obsrt.install(prior)


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------


def test_counter_labels_value_total():
    c = M.Counter("served_total")
    c.inc(replica="0")
    c.inc(3, replica="1")
    c.inc(replica="0")
    assert c.value(replica="0") == 2
    assert c.value(replica="1") == 3
    assert c.value(replica="9") == 0
    assert c.total() == 5
    with pytest.raises(ValueError):
        c.inc(-1)


def test_gauge_set_add():
    g = M.Gauge("active")
    g.set(4)
    g.add(-1)
    assert g.value() == 3
    g.set(2.5, pool="a")
    assert g.value(pool="a") == 2.5


def test_histogram_cumulative_buckets():
    h = M.Histogram("wait_ms", buckets=(1.0, 10.0, 100.0))
    for v in (0.5, 5.0, 50.0, 500.0):
        h.observe(v)
    snap = h.snapshot()["series"][""]
    # Prometheus cumulative semantics: each bucket counts everything <= le
    assert snap["buckets"] == {"1": 1, "10": 2, "100": 3}
    assert snap["count"] == 4
    assert snap["sum"] == pytest.approx(555.5)
    assert h.count() == 4 and h.sum() == pytest.approx(555.5)


def test_registry_create_or_get_and_kind_conflict():
    r = M.MetricsRegistry()
    assert r.counter("a") is r.counter("a")
    with pytest.raises(TypeError):
        r.gauge("a")
    assert r.total("a") == 0
    r.counter("a").inc(5, k="x")
    assert r.total("a") == 5
    assert r.get("nope") is None


def test_render_text_is_insertion_order_independent():
    def build(order):
        r = M.MetricsRegistry()
        for name in order:
            r.counter(name, f"help for {name}")
        r.counter("aa").inc(2, b="2", a="1")
        r.counter("aa").inc(1)
        r.counter("zz").inc(7)
        r.histogram("h_ms", buckets=(1.0, 5.0)).observe(0.3, cls="x")
        return r.render_text()

    assert build(["zz", "aa"]) == build(["aa", "zz"])


def test_render_text_round_trips_through_parse_text():
    r = M.MetricsRegistry()
    r.counter("runs_total", "runs").inc(3, bucket="8")
    r.gauge("frac").set(0.125)
    r.histogram("lat_ms", buckets=(1.0,)).observe(0.5)
    parsed = M.parse_text(r.render_text())
    assert parsed["runs_total"]['{bucket="8"}'] == 3
    assert parsed["frac"][""] == 0.125
    assert parsed["lat_ms_bucket"]['{le="1"}'] == 1
    assert parsed["lat_ms_bucket"]['{le="+Inf"}'] == 1
    assert parsed["lat_ms_count"][""] == 1


def test_parse_text_rejects_malformed():
    with pytest.raises(ValueError):
        M.parse_text("dangling_name\n")
    with pytest.raises(ValueError):
        M.parse_text("name{unbalanced 3\n")
    with pytest.raises(ValueError):
        M.parse_text("name not_a_number\n")
    assert M.parse_text("# comment only\n\n") == {}


# ---------------------------------------------------------------------------
# trace recording + export
# ---------------------------------------------------------------------------


def _sample_trace(order=("b_track", "a_track")):
    tr = T.Trace(clock=FakeClock())
    tr.span("work", cat="sched", track=order[0], t0=0.001, t1=0.003, seq=1)
    tr.instant("mark", cat="control", track=order[1], t=0.002, reason="x")
    tr.span("slow", cat="kernel", track="kernels", t0=0.0, t1=0.5,
            wall_us=500000.0, hbm_modeled_bytes=1024)
    return tr


def test_chrome_structure_and_track_tids():
    ch = _sample_trace().chrome()
    assert set(ch) == {"traceEvents", "displayTimeUnit"}
    meta = [e for e in ch["traceEvents"] if e["ph"] == "M"]
    # tids assigned by sorted track name, independent of recording order
    assert [m["args"]["name"] for m in meta] == \
        ["a_track", "b_track", "kernels"]
    assert [m["tid"] for m in meta] == [1, 2, 3]
    span = next(e for e in ch["traceEvents"]
                if e["ph"] == "X" and e["name"] == "work")
    assert span["ts"] == 1000.0 and span["dur"] == 2000.0      # µs
    assert span["tid"] == 2 and span["pid"] == 1
    inst = next(e for e in ch["traceEvents"] if e["ph"] == "i")
    assert inst["s"] == "t" and inst["args"]["reason"] == "x"
    # recording the same story in a different track order -> same export
    assert ch == _sample_trace(order=("b_track", "a_track")).chrome()


def test_jsonl_lines_parse_with_sorted_keys():
    lines = _sample_trace().jsonl().splitlines()
    assert len(lines) == 3
    for line in lines:
        d = json.loads(line)
        assert list(d) == sorted(d)
        assert d["ph"] in ("X", "i")


def test_strip_volatile_drops_wall_fields_and_kernel_times():
    tr = _sample_trace()
    stripped = T.strip_volatile_events(tr.events)
    kernel = next(e for e in stripped if e.cat == "kernel")
    assert kernel.ts == 0.0 and kernel.dur == 0.0
    assert "wall_us" not in (kernel.args or {})
    assert kernel.args["hbm_modeled_bytes"] == 1024    # modeled bytes stay
    sched = next(e for e in stripped if e.cat == "sched")
    assert sched.ts == 0.001 and sched.dur == pytest.approx(0.002)
    # originals untouched
    assert tr.events[2].args["wall_us"] == 500000.0


def test_trace_summary_counts():
    s = _sample_trace().summary()
    assert s["events"] == 3 and s["spans"] == 2 and s["instants"] == 1
    assert s["tracks"]["kernels"]["total_s"] == pytest.approx(0.5)


# ---------------------------------------------------------------------------
# runtime switch: zero overhead when disabled
# ---------------------------------------------------------------------------


class _Poison:
    def __getattr__(self, name):
        raise AssertionError(
            f"obs used while disabled (attribute {name!r})")

    def __setattr__(self, name, value):
        raise AssertionError(
            f"obs used while disabled (set attribute {name!r})")


def _drive_serving_path(clock):
    sched = Scheduler(2, max_batch=4, slack_s=0.002, clock=clock,
                      max_pending=64)
    for i in range(8):
        sched.submit(i, deadline_in=0.05, priority=i % 2)
    clock.advance(0.01)
    while True:
        d = sched.poll()
        if d is None:
            break
        clock.advance(0.001)
        sched.complete(d)
    sched.set_active(1, reason="test")
    sched.drain(lambda d: sched.complete(d))
    return sched


def test_disabled_serving_path_never_touches_the_session():
    """The zero-overhead contract: after disable(), a session captured
    earlier must be unreachable from the serving path — call sites must go
    through ``runtime.active()`` every time, never cache the observer."""
    import jax
    from repro.models import resnet as R
    from repro.serve.engine import (
        ImageRequest, ResNetEngine, ShardedResNetEngine)

    clock = FakeClock()
    ob = obsrt.instrument(clock=clock)
    sched = Scheduler(2, max_batch=4, clock=clock)   # built while enabled
    # the engines' host spans and the model's put/launch spans too
    qp = R.quantize_params(R.fold_params(
        R.init_params(R.RESNET8, jax.random.PRNGKey(0))), R.RESNET8)
    engines = [ResNetEngine(R.RESNET8, qp, batch=2, backend="lax-int"),
               ShardedResNetEngine(R.RESNET8, qp, batch=2,
                                   backend="lax-int", replicas=1,
                                   slack_ms=0.0)]
    obsrt.disable()
    assert obsrt.active() is None
    ob.metrics = ob.trace = _Poison()                # detonate any later use
    for i in range(4):
        sched.submit(i)
    clock.advance(1.0)
    d = sched.poll()
    sched.complete(d)
    sched.set_active(1)
    _drive_serving_path(clock)                       # fresh sched, still off
    assert sched.summary()["count"] == 4
    image = np.zeros((32, 32, 3), np.float32)
    for eng in engines:
        reqs = [ImageRequest(rid=i, image=image) for i in range(3)]
        for r in reqs:
            eng.submit(r)
        eng.run()
        assert all(r.done for r in reqs)


def test_instrumented_context_manager_always_uninstalls():
    with obsrt.instrumented() as ob:
        assert obsrt.active() is ob
        with pytest.raises(RuntimeError):
            raise RuntimeError("boom")


def test_install_restores_a_specific_session():
    a = obsrt.instrument()
    b = obsrt.Observability()
    assert obsrt.install(b) is b and obsrt.active() is b
    obsrt.install(a)
    assert obsrt.active() is a
    obsrt.install(None)
    assert obsrt.active() is None


# ---------------------------------------------------------------------------
# scheduler instrumentation: spans/metrics tell the scheduler's own story
# ---------------------------------------------------------------------------


def test_scheduler_spans_match_virtual_timestamps():
    clock = FakeClock()
    ob = obsrt.instrument(clock=clock)
    sched = _drive_serving_path(clock)

    s = sched.summary()
    assert ob.metrics.total("sched_submitted_total") == 8
    assert ob.metrics.total("sched_served_total") == s["count"] == 8
    batches = [e for e in ob.trace.events if e.name == "batch"]
    assert len(batches) == ob.metrics.total("sched_dispatches_total") == 2
    assert all(e.cat == "sched" and e.track.startswith("replica")
               for e in batches)
    assert sum(e.args["n"] for e in batches) == 8
    # no per-request spans: the histograms keep the per-request waits
    assert not {"queue_wait", "compute"} & {e.name for e in ob.trace.events}
    # span endpoints are the scheduler's own stamps, in FakeClock seconds:
    # all 8 admitted at t=0, first batch dispatched at t=0.01, the second
    # one complete-cycle (0.001s) later; each completes 0.001s after
    assert [round(e.ts, 6) for e in batches] == [0.01, 0.011]
    assert all(round(e.dur, 6) == 0.001 for e in batches)
    assert [round(e.args["oldest_wait_ms"], 6) for e in batches] == \
        [10.0, 11.0]
    holds = [e for e in ob.trace.events if e.name == "coalesce_hold"]
    assert len(holds) == ob.metrics.total("sched_dispatches_total")
    h = ob.metrics.get("sched_queue_wait_ms")
    assert h.count(priority="0") + h.count(priority="1") == 8
    # every request carried a deadline -> counted by outcome
    assert ob.metrics.total("sched_deadline_total") == 8
    # set_active change -> instant + counter + summary surfacing
    scales = [e for e in ob.trace.events if e.name == "scale"]
    assert len(scales) == s["scale_events"] == 1
    assert scales[0].args["reason"] == "test"
    assert s["last_scale_reason"] == "test"
    assert ob.metrics.total("sched_scale_events_total") == 1
    assert ob.metrics.get("sched_active_replicas").value() == 1
    drains = [e for e in ob.trace.events if e.name == "drain"]
    assert len(drains) == 1


def test_backpressure_counter():
    clock = FakeClock()
    ob = obsrt.instrument(clock=clock)
    sched = Scheduler(1, max_batch=2, clock=clock, max_pending=2)
    sched.submit(0)
    sched.submit(1)
    from repro.serve.sched import Backpressure
    with pytest.raises(Backpressure):
        sched.submit(2)
    assert ob.metrics.total("sched_backpressure_total") == 1


def test_metrics_text_deterministic_across_identical_sim_runs():
    """The byte-stability half of the determinism contract, without the
    CLI: two identical seeded virtual-time runs -> identical exports."""
    def run():
        clock = FakeClock()
        ob = obsrt.instrument(clock=clock)
        _drive_serving_path(clock)
        obsrt.disable()
        return (ob.metrics.render_text(), ob.trace.jsonl(),
                json.dumps(ob.trace.chrome(), sort_keys=True))

    assert run() == run()


# ---------------------------------------------------------------------------
# autoscaler + tune-cache instrumentation
# ---------------------------------------------------------------------------


def test_autoscaler_decisions_counted_and_reason_surfaced():
    from repro.traffic import AutoscaleConfig, Autoscaler
    clock = FakeClock()
    ob = obsrt.instrument(clock=clock)
    auto = Autoscaler(AutoscaleConfig(max_replicas=4, cooldown_s=0.0),
                      clock=clock)
    assert auto.last_reason is None
    auto.observe(busy=1, queue_depth=50, slots_per_replica=1)   # queue spike
    auto.observe(busy=2, queue_depth=50, slots_per_replica=1)
    assert auto.active == 3 and auto.last_reason == "queue"
    assert ob.metrics.total("autoscale_decisions_total") == \
        len(auto.decisions) == 2
    assert auto.summary()["last_reason"] == "queue"
    instants = [e for e in ob.trace.events if e.name == "autoscale"]
    assert [e.args["reason"] for e in instants] == ["queue", "queue"]


def test_tune_cache_hit_miss_counters(tmp_path):
    from repro.tune import KernelConfig
    from repro.tune.cache import TuneCache
    ob = obsrt.instrument()
    cache = TuneCache(path=str(tmp_path / "cache.json"))
    assert cache.get("k1") is None
    cache.put("k1", {"stem": KernelConfig()})
    assert cache.get("k1") is not None
    assert ob.metrics.get("tune_cache_total").value(result="miss") == 1
    assert ob.metrics.get("tune_cache_total").value(result="hit") == 1


# ---------------------------------------------------------------------------
# compiler instrumentation
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_compile_counters_and_retrace_detector():
    import jax
    import jax.numpy as jnp
    from repro.compile import compile_model
    from repro.models import resnet as R

    cfg = R.RESNET8
    params = R.init_params(cfg, jax.random.PRNGKey(0))
    qp = R.quantize_params(R.fold_params(params), cfg)
    ob = obsrt.instrument()
    cm = compile_model(cfg, qp, backend="lax-int", batch_sizes=(4,))
    imgs = jnp.zeros((4, 32, 32, 3), jnp.float32)
    cm(imgs)
    assert ob.metrics.total("compile_traces_total") == 1
    assert ob.metrics.get("compile_executables_total").value(
        kind="default", bucket="4", backend="lax-int") == 1
    assert ob.metrics.total("model_runs_total") == 1
    assert ob.metrics.total("compile_retraces_total") == 0
    # padded dispatch: 2 rows rounded up to the 4-bucket
    cm(imgs[:2])
    assert ob.metrics.get("model_pad_rows_total").value(
        bucket="4", backend="lax-int") == 2
    # force a second trace of the same bucket: the retrace detector fires
    # in lockstep with the committed trace_counts discipline
    cm._staged(imgs)
    assert cm.trace_counts[4] == 2
    assert ob.metrics.total("compile_retraces_total") == 1
    assert any(e.name == "retrace" for e in ob.trace.events)


# ---------------------------------------------------------------------------
# kernel profiling
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_profile_tasks_pairs_walltime_with_modeled_bytes():
    import jax
    from repro.models import resnet as R
    from repro.obs.profile import profile_tasks

    cfg = R.RESNET8
    params = R.init_params(cfg, jax.random.PRNGKey(0))
    qp = R.quantize_params(R.fold_params(params), cfg)
    ob = obsrt.instrument()
    rows = profile_tasks(cfg, qp, backend="pallas", batch=2, reps=1, ob=ob)
    # per-block pipeline: the stem plus one row per residual block
    assert [r.kind for r in rows] == ["stem", "block", "block", "block"]
    for r in rows:
        assert r.wall_us > 0 and r.hbm_bytes > 0 and r.vmem_bytes > 0
        assert r.vs_roofline > 0 and r.gbps > 0
        d = r.to_dict()
        assert d["hbm_bytes"] == r.hbm_bytes
    # attached to the session: kernel spans + deterministic byte gauges,
    # and NO wall-derived values in the metrics registry
    assert ob.metrics.total("kernel_profiles_total") == len(rows)
    kernel_spans = [e for e in ob.trace.events if e.cat == "kernel"]
    assert len(kernel_spans) == len(rows)
    text = ob.metrics.render_text()
    assert "kernel_hbm_modeled_bytes" in text
    assert "wall" not in text and "gbps" not in text
    with pytest.raises(ValueError):
        profile_tasks(cfg, qp, backend="lax-int")


# ---------------------------------------------------------------------------
# report CLI
# ---------------------------------------------------------------------------


def test_obs_report_cli_parses_exports(tmp_path, capsys):
    from repro.obs.__main__ import main as obs_main
    clock = FakeClock()
    ob = obsrt.instrument(clock=clock)
    _drive_serving_path(clock)
    obsrt.disable()
    trace = tmp_path / "trace.json"
    mtx = tmp_path / "metrics.txt"
    obsrt.export(ob, trace_out=str(trace), metrics_out=str(mtx))
    out_json = tmp_path / "summary.json"
    assert obs_main(["--trace", str(trace), "--metrics", str(mtx),
                     "--top", "3", "--json", str(out_json)]) == 0
    out = capsys.readouterr().out
    assert "spans" in out and "metrics:" in out
    summary = json.loads(out_json.read_text())
    assert summary["trace_events"] > 0 and summary["metrics"] > 0


def test_obs_report_cli_rejects_garbage(tmp_path, capsys):
    from repro.obs.__main__ import main as obs_main
    bad = tmp_path / "bad.json"
    bad.write_text('{"noTraceEvents": []}')
    assert obs_main(["--trace", str(bad)]) == 1
    badm = tmp_path / "bad.txt"
    badm.write_text("dangling_name\n")
    assert obs_main(["--metrics", str(badm)]) == 1
    capsys.readouterr()


# ---------------------------------------------------------------------------
# end-to-end through the traffic CLI (the PR's acceptance path)
# ---------------------------------------------------------------------------

_TRAFFIC_ARGV = [
    "sim", "--arch", "resnet8", "--degrade-arch", "", "--pattern", "bursty",
    "--rate", "600", "--duration", "0.1", "--fps-primary", "3200",
    "--replicas", "2", "--eval-n", "8", "--batch", "4", "--seed", "0",
]


def _run_traffic(tmp_path, tag, profile=True):
    from repro.traffic.__main__ import main as traffic_main
    d = tmp_path / tag
    d.mkdir()
    argv = _TRAFFIC_ARGV + [
        "--trace-out", str(d / "trace.json"),
        "--jsonl-out", str(d / "trace.jsonl"),
        "--metrics-out", str(d / "metrics.txt"),
    ] + ([] if profile else ["--no-profile"])
    report = traffic_main(argv)
    return d, report


def _stripped_jsonl(path):
    """Apply the documented volatile-field contract to an exported JSONL
    file — what remains must be identical across seeded runs."""
    out = []
    for line in path.read_text().splitlines():
        d = json.loads(line)
        if d.get("cat") in T.VOLATILE_CATS:
            d["ts"] = d["dur"] = 0.0
        args = {k: v for k, v in d.get("args", {}).items()
                if k not in T.VOLATILE_ARGS}
        d.pop("args", None)
        if args:
            d["args"] = args
        out.append(json.dumps(d, sort_keys=True))
    return "\n".join(out)


def _stripped_chrome(path):
    events = copy.deepcopy(json.loads(path.read_text())["traceEvents"])
    for e in events:
        if e.get("cat") in T.VOLATILE_CATS:
            e["ts"] = 0.0
            e.pop("dur", None)
        if "args" in e and e["ph"] != "M":
            e["args"] = {k: v for k, v in e["args"].items()
                         if k not in T.VOLATILE_ARGS}
    return json.dumps(events, sort_keys=True)


@pytest.mark.slow
def test_traffic_cli_exports_trace_with_kernel_profiles(tmp_path):
    """The acceptance pin: a seeded sim run with --trace-out produces a
    Perfetto-loadable Chrome trace carrying per-batch scheduler spans AND
    per-task kernel profiles with measured-vs-modeled HBM ratios."""
    from repro.obs.__main__ import load_chrome_trace
    d, report = _run_traffic(tmp_path, "a")
    events = load_chrome_trace(str(d / "trace.json"))    # validates shape
    names = {e.get("name") for e in events}
    assert {"batch", "coalesce_hold"} <= names
    assert not {"queue_wait", "compute"} & names
    for e in events:
        if e.get("name") == "batch":
            assert e["args"]["n"] >= 1 and e["args"]["oldest_wait_ms"] >= 0
    kernels = [e for e in events if e.get("cat") == "kernel"]
    assert kernels, "no kernel-profile spans in the trace"
    for e in kernels:
        assert e["args"]["hbm_modeled_bytes"] > 0
        assert e["args"]["vs_roofline"] > 0
    assert report["obs"]["profiles"]
    assert {p["kind"] for p in report["obs"]["profiles"]} == \
        {"stem", "block"}
    # the session was torn down after export
    assert obsrt.active() is None
    # metrics artifact parses and carries the serving counters
    parsed = M.parse_text((d / "metrics.txt").read_text())
    assert "sched_served_total" in parsed
    assert "kernel_hbm_modeled_bytes" in parsed


@pytest.mark.slow
def test_traffic_cli_trace_determinism_across_runs(tmp_path):
    """Same seed + FakeClock => byte-identical metrics, and byte-identical
    JSONL/Chrome traces modulo the documented volatile fields."""
    d1, _ = _run_traffic(tmp_path, "r1")
    d2, _ = _run_traffic(tmp_path, "r2")
    assert (d1 / "metrics.txt").read_bytes() == \
        (d2 / "metrics.txt").read_bytes()
    assert _stripped_jsonl(d1 / "trace.jsonl") == \
        _stripped_jsonl(d2 / "trace.jsonl")
    assert _stripped_chrome(d1 / "trace.json") == \
        _stripped_chrome(d2 / "trace.json")


@pytest.mark.slow
def test_obs_off_serving_is_bit_identical():
    """Instrumentation must not perturb the arithmetic: the same seeded
    sim serving a real compiled model yields bit-identical logits with an
    obs session installed vs none."""
    import jax
    from repro.compile import compile_model
    from repro.models import resnet as R
    from repro.traffic import (
        OverloadRouter, PoissonProcess, ServiceModel, SimServer, SLOClass,
        TrafficSim)

    cfg = R.RESNET8
    params = R.init_params(cfg, jax.random.PRNGKey(0))
    qp = R.quantize_params(R.fold_params(params), cfg)
    cm = compile_model(cfg, qp, backend="lax-int", batch_sizes=(4,))
    rng = np.random.default_rng(0)
    images = rng.random((12, cfg.img, cfg.img, 3)).astype(np.float32)
    classes = [SLOClass("standard", deadline_ms=1000.0, priority=1,
                        policy="degrade")]
    arrivals = PoissonProcess(200.0, seed=1,
                              class_mix={"standard": 1.0}).generate(n=12)

    def serve(instrumented):
        clock = FakeClock()
        if instrumented:
            obsrt.instrument(clock=clock)
        try:
            server = SimServer("resnet8", ServiceModel.from_fps(3200.0),
                               clock, replicas=1, max_batch=4, model=cm)
            sim = TrafficSim({"resnet8": server}, classes,
                             OverloadRouter(classes, primary="resnet8"),
                             clock)
            sim.run(arrivals, images=images)
            return np.stack([r.logits for r in sim.requests])
        finally:
            if instrumented:
                obsrt.disable()

    off, on = serve(False), serve(True)
    assert np.array_equal(off, on)


# ---------------------------------------------------------------------------
# the overhead acceptance: <3% instrumented, bit-identical logits
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_overhead_obs_bench_under_three_percent():
    """PR acceptance: the overhead_obs benchmark measures <3% enabled
    overhead on the e2e_pallas workload (the median of interleaved on/off pairs;
    retried to ride out host noise — the enabled path only adds counter
    increments, so a persistent >=3% reading is a real regression)."""
    from benchmarks import run as bench

    last = None
    for _ in range(3):
        n0 = len(bench.ROWS)
        bench.overhead_obs()
        row = bench.ROWS[-1]
        del bench.ROWS[n0:]
        d = row["derived"]
        assert d["bit_identical"], "obs toggled the served logits"
        assert d["runs_counted"] == 1 + d["reps"]   # on-warmup + on-reps
        last = d["obs_overhead_frac"]
        if last < 0.03:
            return
    pytest.fail(f"instrumented overhead {last:+.2%} >= 3% on 3 attempts")
