"""Benchmark harness — one function per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run
    PYTHONPATH=src python -m benchmarks.run --json results/bench.json --seed 0

Each benchmark prints ``name,us_per_call,derived`` CSV rows and records the
same row with *unformatted* values; ``--json`` dumps the full run as

    {"rows": [...], "seed": ..., "digest": ...}

so the perf trajectory is machine-trackable across PRs.  Every random input
is drawn from ``--seed`` (the benches call :func:`key`/:func:`nprng`), and
``digest`` is a sha256 over the *deterministic* row content (name + derived,
minus the wall-time-derived :data:`VOLATILE` keys) — two runs at the same
seed on the same code produce the same digest, so an unexplained digest
change means the benchmark's inputs or modeled outputs moved, not the
machine (tests/test_bench_repro.py pins this).  Benchmarks:
  * table3_fps      — ILP throughput model vs paper Table 3 (4 platform x
                      model cells: FPS, Gops/s, DSPs)
  * table4_buffers  — skip-connection buffering, eq. 21/22/23 (R_sc = 0.5)
  * fig13_addfold   — fused residual kernel vs unfused oracle: bit-exactness
                      + HBM traffic model ratio
  * e2e_pallas      — whole-network inference through ``repro.compile``:
                      compiled pallas vs compiled lax-int executables (FPS,
                      bit-exactness, modeled per-block HBM-traffic saving)
  * e2e_stream      — the block-chain streaming megakernel
                      (``pallas-stream``) vs the per-block pipeline:
                      interleave-timed FPS both ways, the chain partition,
                      modeled HBM bytes saved — the row the CI perf gate
                      (``benchmarks/compare.py`` vs ``BENCH_0006.json``)
                      tracks across PRs
  * e2e_tuned       — the autotuned pipeline (``repro.tune`` two-stage
                      search) vs the default config: FPS + speedup, the
                      chosen KernelConfig per task, cache hit/miss counts
  * e2e_sharded     — scale-out serving (``serve.ShardedResNetEngine``):
                      FPS vs replica count + queue-wait/compute latency
                      percentiles through the deadline coalescer
  * e2e_slo         — trace-driven SLO serving (``repro.traffic``): a seeded
                      bursty trace simulated in virtual time against 1 vs N
                      replicas with degradation A/B'd on/off — per-class
                      deadline-hit-rate + effective accuracy under load —
                      plus the obs-driven control loop (``repro.obs.health``)
                      A/B'd against the queue-signal baseline on an
                      EWMA-adversarial trickle/burst trace
                      (deterministic; only real wall time is VOLATILE)
  * overhead_obs    — the cost of observability: the same compiled ResNet8
                      executable interleave-timed with the ``repro.obs``
                      session installed vs removed (volatile overhead frac;
                      deterministic bit-identical logits + counter totals)
  * accuracy        — the paper's accuracy story in miniature
                      (``repro.quantize``): float-train ResNet8 briefly on
                      the synthetic task, PTQ-calibrate, export, top-1 of
                      float vs int8 through the serving engine
  * kernels_micro   — per-kernel wall time (interpret mode on CPU; TPU is
                      the target, numbers are correctness-path timings)
  * roofline        — reads results/dryrun/*.json (launch.dryrun) and prints
                      the three-term table per (arch x shape)
"""
from __future__ import annotations

import argparse
import hashlib
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.compile.cache import enable_compile_cache  # noqa: E402
from repro.core import dataflow, graph, ilp  # noqa: E402

ROWS = []
SEED = 0

# derived keys that are functions of wall time, never of the inputs — they
# are excluded from the run digest (reproducibility covers the *science*,
# not the machine's scheduling noise).  "config"/"source" are e2e_tuned's
# device-timed search outcome: the winner is an argmin over measured wall
# clock, so near-tied tilings can flip between runs on noise; "space_size"
# is 0 on a REPRO_TUNE_CACHE hit (cache state, not seed).
VOLATILE = frozenset({
    "fps", "int_graph_fps", "default_fps", "speedup", "search_us",
    "cache_hits", "cache_misses", "p50_wait_ms", "p99_wait_ms",
    "p50_compute_ms", "p99_compute_ms", "ticks", "config", "source",
    "space_size", "wall_s",
})


def is_volatile(key: str) -> bool:
    """True when a derived key is a function of wall time, not of the
    inputs.  Beyond the legacy :data:`VOLATILE` names, the observability
    rows follow a naming contract instead of growing the set one key at a
    time: any ``obs_*`` measurement and any ``*_wall_s``/``*_wall_us``/
    ``*_wall_ms`` suffix is machine noise.  Both the run digest and
    ``benchmarks/compare.py``'s strict-derived gate key off this predicate,
    so a timing key that skips the pattern WILL fail CI on the next
    machine — name it accordingly."""
    return (key in VOLATILE or key.startswith("obs_")
            or key.endswith(("_wall_s", "_wall_us", "_wall_ms")))


def key(i: int):
    """Per-bench jax PRNG key derived from the run seed."""
    return jax.random.fold_in(jax.random.PRNGKey(SEED), i)


def nprng():
    """Numpy generator derived from the run seed."""
    return np.random.default_rng(SEED)


def input_digest(*arrays) -> str:
    """Short content hash of drawn input tensors — two runs at the same seed
    must produce the same value (the seed-threading regression check)."""
    h = hashlib.sha256()
    for a in arrays:
        a = np.asarray(a)
        h.update(str(a.dtype).encode() + str(a.shape).encode())
        h.update(np.ascontiguousarray(a).tobytes())
    return h.hexdigest()[:12]


def run_digest(rows) -> str:
    """sha256 over the deterministic row content: names + derived values
    minus :func:`is_volatile` keys and us_per_call."""
    stable = [(r["name"], {k: v for k, v in sorted(r["derived"].items())
                           if not is_volatile(k)})
              for r in sorted(rows, key=lambda r: r["name"])]
    return hashlib.sha256(
        json.dumps(stable, sort_keys=True, default=str).encode()).hexdigest()


def emit(name, us, **derived):
    """Print one CSV row and record it for the ``--json`` dump."""
    def fmt(v):
        if isinstance(v, float):
            return f"{v:.4g}"
        return str(v)

    print(f"{name},{us:.0f}," + ";".join(f"{k}={fmt(v)}"
                                         for k, v in derived.items()))
    ROWS.append(dict(name=name, us_per_call=round(us, 1), derived=derived))


def _time(fn, *args, n=3):
    fn(*args)
    t0 = time.perf_counter()
    for _ in range(n):
        r = fn(*args)
    jax.block_until_ready(r)
    return (time.perf_counter() - t0) / n * 1e6


def table3_fps():
    print("\n## table3_fps — ILP throughput model vs paper Table 3")
    print("name,us_per_call,derived")
    paper = {("ultra96", "resnet8"): (12971, 317),
             ("ultra96", "resnet20"): (3254, 264),
             ("kv260", "resnet8"): (30153, 773),
             ("kv260", "resnet20"): (7601, 616)}
    for plat in ("ultra96", "kv260"):
        for name, layers in (("resnet8", dataflow.resnet8_layers()),
                             ("resnet20", dataflow.resnet20_layers())):
            t0 = time.perf_counter()
            sol = ilp.predict_fps(layers, plat)
            us = (time.perf_counter() - t0) * 1e6
            pf, pg = paper[(plat, name)]
            emit(f"table3/{plat}/{name}", us,
                 fps=round(sol.fps), paper_fps=pf,
                 err=round(sol.fps / pf - 1, 4), gops=round(sol.gops),
                 dsp=sol.dsp_used)


def table4_buffers():
    print("\n## table4_buffers — skip buffering (eq. 21/22/23)")
    print("name,us_per_call,derived")
    t0 = time.perf_counter()
    g0 = graph.resnet20_graph()
    g1 = graph.optimize(graph.resnet20_graph())
    rep = graph.skip_buffer_report(g0, g1)
    us = (time.perf_counter() - t0) * 1e6
    mean_ratio = float(np.mean([r["ratio"] for r in rep]))
    emit("table4/resnet20", us, blocks=len(rep),
         mean_R_sc=round(mean_ratio, 3), paper_R_sc=0.5)
    adds = sum(1 for n in g1.nodes if n.op == "add")
    emit("table4/addfold", us, residual_adds_after_opt=adds)


def fig13_addfold():
    print("\n## fig13_addfold — fused residual block kernel")
    print("name,us_per_call,derived")
    from repro.kernels.resblock_fused.ops import resblock_fused_op
    from repro.kernels.resblock_fused.ref import resblock_ref
    k = key(13)
    N, H, C = 2, 16, 16
    x = jax.random.randint(k, (N, H, H, C), 0, 256, jnp.int32).astype(jnp.uint8)
    w0 = jax.random.randint(jax.random.fold_in(k, 1), (3, 3, C, C), -128,
                            128, jnp.int32).astype(jnp.int8)
    w1 = jax.random.randint(jax.random.fold_in(k, 2), (3, 3, C, C), -128,
                            128, jnp.int32).astype(jnp.int8)
    b = jnp.zeros((C,), jnp.int32)
    us = _time(lambda: resblock_fused_op(x, w0, b, w1, b, shift0=8, shift1=8,
                                         skip_shift=3))
    ref = resblock_ref(x, w0, b, w1, b, shift0=8, shift1=8, skip_shift=3)
    got = resblock_fused_op(x, w0, b, w1, b, shift0=8, shift1=8, skip_shift=3)
    exact = bool((np.asarray(got) == np.asarray(ref)).all())
    hbm_f = dataflow.residual_block_hbm_bytes(32, 32, 16, 16, fused=True)
    hbm_u = dataflow.residual_block_hbm_bytes(32, 32, 16, 16, fused=False)
    emit("fig13/resblock_fused", us, bit_exact=exact,
         hbm_traffic_ratio_saved=round(hbm_u / hbm_f, 2),
         inputs=input_digest(x, w0, w1))


def e2e_pallas():
    """Whole-network inference through ``repro.compile``: the optimized graph
    lowered once per backend into a fixed-shape executable, timed executable
    vs executable (pallas vs lax-int), plus the modeled per-block HBM ratio."""
    print("\n## e2e_pallas — compiled full-network inference "
          "(interpret-mode timings off-TPU)")
    print("name,us_per_call,derived")
    from repro.compile import compile_model
    from repro.models import resnet as R
    batch = 4
    imgs = jax.random.uniform(key(20), (batch, 32, 32, 3),
                              minval=0.0, maxval=0.999)
    for cfg, layers in ((R.RESNET8, dataflow.resnet8_layers()),
                        (R.RESNET20, dataflow.resnet20_layers())):
        params = R.init_params(cfg, key(21))
        qp = R.quantize_params(R.fold_params(params), cfg)
        cm_p = compile_model(cfg, qp, backend="pallas", batch_sizes=(batch,))
        cm_i = compile_model(cfg, qp, backend="lax-int", batch_sizes=(batch,))
        exact = bool(np.array_equal(np.asarray(cm_p(imgs)),
                                    np.asarray(cm_i(imgs))))
        us_p = _time(lambda: cm_p(imgs), n=1)
        us_i = _time(lambda: cm_i(imgs), n=1)
        ratios = []
        for i, (l, stride) in enumerate(
                [(l, l.stride) for l in layers if l.name.endswith("_0")]):
            ds = any(x.name == f"ds{i}" for x in layers)
            f = dataflow.residual_block_hbm_bytes(
                l.ih, l.iw, l.ich, l.och, fused=True, downsample=ds,
                stride=stride)
            u = dataflow.residual_block_hbm_bytes(
                l.ih, l.iw, l.ich, l.och, fused=False, downsample=ds,
                stride=stride)
            ratios.append(u / f)
            emit(f"e2e_pallas/{cfg.name}/block{i}", 0,
                 hbm_fused_B=f, hbm_unfused_B=u, ratio=round(u / f, 2))
        emit(f"e2e_pallas/{cfg.name}", us_p,
             fps=round(batch / (us_p / 1e6), 1),
             int_graph_fps=round(batch / (us_i / 1e6), 1),
             bit_exact=exact,
             mean_block_hbm_saving=round(float(np.mean(ratios)), 2),
             retraces=max(cm_p.trace_counts.values()),
             inputs=input_digest(imgs))


def e2e_stream():
    """The block-chain streaming megakernel (``pallas-stream``) vs the
    per-block fused pipeline (``pallas``), interleave-timed so host drift
    cancels: FPS both ways, the planned chain partition, the modeled HBM
    bytes the chain fusion saves (``core.dataflow.chain_saved_hbm_bytes``),
    and bit-exactness vs the lax integer reference.  The per-row FPS pair is
    the measurement half of ROADMAP item 3 — ``benchmarks/compare.py`` gates
    CI on it against the committed ``BENCH_0006.json``."""
    print("\n## e2e_stream — block-chain streaming megakernel vs per-block "
          "kernels")
    print("name,us_per_call,derived")
    from repro.compile import compile_model, lowering
    from repro.core import dataflow
    from repro.models import resnet as R
    from repro.tune import interleaved_time
    batch = 4
    imgs = jax.random.uniform(key(25), (batch, 32, 32, 3),
                              minval=0.0, maxval=0.999)
    for cfg in (R.RESNET8, R.RESNET20):
        params = R.init_params(cfg, key(26))
        qp = R.quantize_params(R.fold_params(params), cfg)
        cm_s = compile_model(cfg, qp, backend="pallas-stream",
                             batch_sizes=(batch,))
        cm_p = compile_model(cfg, qp, backend="pallas", batch_sizes=(batch,))
        cm_i = compile_model(cfg, qp, backend="lax-int", batch_sizes=(batch,))
        exact = bool(np.array_equal(np.asarray(cm_s(imgs)),
                                    np.asarray(cm_i(imgs))))
        us_s, us_p = interleaved_time(cm_s, cm_p, imgs, reps=5)
        plan = lowering.plan_model(lowering.optimized_graph(cfg))
        chains = lowering.plan_chains(plan, cfg)
        shapes = dataflow.resnet_block_shapes(cfg.blocks_per_stage,
                                              cfg.base_width, cfg.img)
        saved = sum(
            dataflow.chain_saved_hbm_bytes(
                [shapes[t.index] for t in c.blocks], batch)
            + (2 * batch * shapes[0].in_bytes() if c.stem is not None else 0)
            for c in chains)
        per_block = sum(
            dataflow.resblock_task_hbm_bytes(
                s.h, s.w, s.ich, s.och, batch, 1,
                downsample=s.downsample, stride=s.stride) for s in shapes)
        kernels_stream = len(chains) + (1 if chains[0].stem is None else 0)
        emit(f"e2e_stream/{cfg.name}", us_s,
             fps=round(batch / (us_s / 1e6), 1),
             default_fps=round(batch / (us_p / 1e6), 1),
             speedup=round(us_p / us_s, 3),
             bit_exact=exact,
             chains="|".join(c.describe() for c in chains),
             kernel_calls=kernels_stream,
             per_block_kernel_calls=1 + len(shapes),
             hbm_saved_B=saved,
             hbm_saved_frac=round(saved / per_block, 3),
             inputs=input_digest(imgs))


def e2e_tuned():
    """The tuned pipeline vs the default config: ``repro.tune.search`` (two
    stages — analytic ranking, then timing the top-K real executables, the
    default always among them) picks a per-task ``KernelConfig``; the row
    reports tuned FPS, default FPS, the speedup, the chosen config per task,
    and the config-cache hit/miss counts so a perf change is attributable to
    a config change."""
    print("\n## e2e_tuned — autotuned compiled inference vs default config")
    print("name,us_per_call,derived")
    from repro import tune as T
    from repro.compile import compile_model
    from repro.models import resnet as R
    batch = 4
    imgs = jax.random.uniform(key(30), (batch, 32, 32, 3),
                              minval=0.0, maxval=0.999)
    cache = T.TuneCache()          # honors REPRO_TUNE_CACHE
    for cfg in (R.RESNET8, R.RESNET20):
        params = R.init_params(cfg, key(31))
        qp = R.quantize_params(R.fold_params(params), cfg)
        t0 = time.perf_counter()
        res = T.search(cfg, qp, backend="pallas", batch=batch, top_k=2,
                       device=True, reps=3, cache=cache)
        search_us = (time.perf_counter() - t0) * 1e6
        cm_t = compile_model(cfg, qp, backend="pallas", batch_sizes=(batch,),
                             tune=res.tuning)
        cm_d = compile_model(cfg, qp, backend="pallas", batch_sizes=(batch,))
        cm_i = compile_model(cfg, qp, backend="lax-int", batch_sizes=(batch,))
        exact = bool(np.array_equal(np.asarray(cm_t(imgs)),
                                    np.asarray(cm_i(imgs))))
        if all(not c.to_dict() for c in res.tuning.values()):
            # the search kept the default config: tuned and default are the
            # same executable — re-timing them separately would only report
            # host noise as a "speedup"
            us_t = us_d = _time(lambda: cm_t(imgs), n=3)
        else:
            us_t, us_d = T.interleaved_time(cm_t, cm_d, imgs, reps=5)
        emit(f"e2e_tuned/{cfg.name}", us_t,
             fps=round(batch / (us_t / 1e6), 1),
             default_fps=round(batch / (us_d / 1e6), 1),
             speedup=round(us_d / us_t, 3),
             bit_exact=exact,
             source=res.source,
             config={t: c.to_dict() for t, c in sorted(res.tuning.items())},
             space_size=res.space_size,
             search_us=round(search_us),
             cache_hits=cache.hits, cache_misses=cache.misses)


def e2e_sharded():
    """Scale-out serving through ``serve.ShardedResNetEngine``: the compiled
    model instantiated once per device (replica pool), requests flowing
    through the deadline-based batch coalescer to the least-loaded replica.
    One row per (arch x replica count up to the local device count): FPS,
    queue-wait and compute latency percentiles, per-replica served counts,
    and bit-exactness vs the single-device compiled path.  On a 1-device
    host this emits the replicas=1 row only; on real multi-device hosts FPS
    should scale with the replica count (tests/test_serve_sharded.py checks
    monotonicity when devices are available)."""
    print("\n## e2e_sharded — replica-pool serving (FPS vs replica count)")
    print("name,us_per_call,derived")
    from repro.models import resnet as R
    from repro.serve.engine import ImageRequest, ShardedResNetEngine
    batch, requests = 8, 32
    rng = nprng()
    n_dev = jax.local_device_count()
    counts = [c for c in (1, 2, 4, 8) if c <= n_dev]
    for cfg in (R.RESNET8, R.RESNET20):
        params = R.init_params(cfg, key(41))
        qp = R.quantize_params(R.fold_params(params), cfg)
        imgs = rng.random((requests, cfg.img, cfg.img, 3)).astype(np.float32)
        ref = None
        for n_rep in counts:
            eng = ShardedResNetEngine(cfg, qp, batch=batch, backend="pallas",
                                      replicas=n_rep, slack_ms=2.0)
            eng.pool.warmup()
            if ref is None:
                # scheduling must not alter the arithmetic: the reference is
                # the same compiled model invoked directly, once per arch
                ref = np.asarray(eng.model(imgs[:batch]))
            reqs = [ImageRequest(rid=i, image=imgs[i])
                    for i in range(requests)]
            t0 = time.perf_counter()
            for r in reqs:
                eng.submit(r)
            ticks = eng.run()
            dt = time.perf_counter() - t0
            st = eng.latency_stats()
            exact = bool(np.array_equal(
                np.stack([r.logits for r in reqs[:batch]]), ref))
            emit(f"e2e_sharded/{cfg.name}/r{n_rep}",
                 dt / max(ticks, 1) * 1e6,
                 replicas=n_rep,
                 fps=round(eng.served / dt, 1),
                 ticks=ticks,
                 served=eng.served,
                 bit_exact=exact,
                 p50_wait_ms=round(st["queue_wait_ms"]["p50"], 3),
                 p99_wait_ms=round(st["queue_wait_ms"]["p99"], 3),
                 p50_compute_ms=round(st["compute_ms"]["p50"], 3),
                 p99_compute_ms=round(st["compute_ms"]["p99"], 3),
                 inputs=input_digest(imgs))


def e2e_slo():
    """Trace-driven SLO serving in virtual time (``repro.traffic``): a
    seeded bursty arrival trace simulated against 1 vs N replicas, with the
    overload router's accuracy-aware degradation A/B'd on/off.  The service
    envelope keeps the paper's KV260 ResNet8:ResNet20 FPS ratio (~4x) scaled
    down so the burst peak overloads ResNet20 capacity but not ResNet8's.
    Per row: per-class deadline-hit-rate, degraded/dropped counts, and the
    effective accuracy under load (per-variant top-1 through
    ``repro.quantize.evaluate``'s serving harness; a dropped request scores
    zero).  Queueing runs entirely on FakeClock, so every number except the
    real wall clock (``wall_s``, VOLATILE) is deterministic per (code, seed)
    and sits in the run digest."""
    print("\n## e2e_slo — SLO classes + degradation under a bursty trace "
          "(virtual time)")
    print("name,us_per_call,derived")
    from repro.models import resnet as R
    from repro.quantize import synthetic_eval_set
    from repro.serve.sched import FakeClock
    from repro.traffic import (
        DEFAULT_CLASSES, OverloadRouter, ServiceModel, SimServer, TrafficSim,
        make_process, variant_accuracies)

    rate, duration, eval_n = 2400.0, 0.4, 128
    mix = {"interactive": 0.25, "standard": 0.5, "bulk": 0.25}
    arrivals = make_process("bursty", rate, seed=SEED, class_mix=mix,
                            burst_on_s=0.05, burst_off_s=0.05
                            ).generate(horizon_s=duration)
    variants = {}
    for cfg in (R.RESNET20, R.RESNET8):
        params = R.init_params(cfg, key(70))
        variants[cfg.name] = (cfg,
                              R.quantize_params(R.fold_params(params), cfg))
    images, labels = synthetic_eval_set(eval_n, seed=SEED)
    t0 = time.perf_counter()
    acc = variant_accuracies(variants, images, labels, backend="lax-int")
    eval_s = time.perf_counter() - t0
    emit("e2e_slo/variants", eval_s * 1e6,
         **{f"top1_{v}": round(a, 4) for v, a in sorted(acc.items())},
         eval_n=eval_n, arrivals=len(arrivals), wall_s=round(eval_s, 3))
    svc = {"resnet20": ServiceModel.from_fps(800.0),
           "resnet8": ServiceModel.from_fps(3200.0)}
    for n_rep in (1, 4):
        for degrade in (False, True):
            clock = FakeClock()
            servers = {
                "resnet20": SimServer("resnet20", svc["resnet20"], clock,
                                      replicas=n_rep, max_batch=8),
                "resnet8": SimServer("resnet8", svc["resnet8"], clock,
                                     replicas=1, max_batch=8)}
            router = OverloadRouter(DEFAULT_CLASSES, primary="resnet20",
                                    degraded="resnet8", enabled=degrade)
            sim = TrafficSim(servers, DEFAULT_CLASSES, router, clock)
            t0 = time.perf_counter()
            rep = sim.run(arrivals, accuracy_by_variant=acc)
            wall = time.perf_counter() - t0
            tot, cls = rep["totals"], rep["classes"]
            emit(f"e2e_slo/r{n_rep}/degrade_{'on' if degrade else 'off'}",
                 wall * 1e6,
                 replicas=n_rep, degrade=degrade,
                 sim_s=rep["duration_s"],
                 hit_rate=tot["deadline_hit_rate"],
                 **{f"hit_{name}": c["deadline_hit_rate"]
                    for name, c in sorted(cls.items())},
                 served=tot["served"], dropped=tot["dropped"],
                 degraded=tot["degraded"],
                 effective_top1=rep["accuracy"]["effective_top1"],
                 accuracy_cost=rep["accuracy"]["accuracy_cost"],
                 wall_s=round(wall, 3))

    # autoscale arm: the controller steering the primary fleet under the
    # same trace, run with an obs session bound to the FakeClock so the row
    # reads the scale-event counts back out of the metrics registry — the
    # registry totals must agree with Scheduler.summary() and the
    # autoscaler's own decision log, and everything except the real wall
    # clock is deterministic (virtual time) and digest-pinned.
    from repro.obs import runtime as obsrt
    from repro.traffic import AutoscaleConfig, Autoscaler
    clock = FakeClock()
    prior = obsrt.disable()
    ob = obsrt.instrument(clock=clock)
    try:
        servers = {
            "resnet20": SimServer("resnet20", svc["resnet20"], clock,
                                  replicas=4, max_batch=8, active=1),
            "resnet8": SimServer("resnet8", svc["resnet8"], clock,
                                 replicas=1, max_batch=8)}
        router = OverloadRouter(DEFAULT_CLASSES, primary="resnet20",
                                degraded="resnet8", enabled=True)
        auto = Autoscaler(AutoscaleConfig(min_replicas=1, max_replicas=4,
                                          cooldown_s=0.05), clock=clock)
        sim = TrafficSim(servers, DEFAULT_CLASSES, router, clock,
                         autoscaler=auto)
        t0 = time.perf_counter()
        rep = sim.run(arrivals, accuracy_by_variant=acc)
        wall = time.perf_counter() - t0
        prim = rep["servers"]["resnet20"]
        emit("e2e_slo/autoscale", wall * 1e6,
             replicas_max=4,
             scale_events=prim["scale_events"],
             last_scale_reason=prim["last_scale_reason"],
             autoscaler_events=rep["autoscaler"]["scale_events"],
             metrics_scale_events=int(
                 ob.metrics.total("sched_scale_events_total")),
             metrics_autoscale_decisions=int(
                 ob.metrics.total("autoscale_decisions_total")),
             final_active=rep["autoscaler"]["active"],
             hit_rate=rep["totals"]["deadline_hit_rate"],
             served=rep["totals"]["served"],
             wall_s=round(wall, 3))
    finally:
        obsrt.install(prior)

    # health arm: the obs-driven control loop vs the queue-signal baseline.
    # A trickle/burst trace is adversarial for the predictive router: each
    # trickle phase trains the scheduler's EWMA service estimate on cheap
    # singleton batches, so at the next burst front the primary is
    # under-priced and degrade-class requests are admitted primary just
    # before the backlog lands.  The SLO burn-rate alert's fast window (1 s)
    # is longer than the 0.23 s cycle, so it stays active across bursts and
    # the actuated arm degrades those requests pre-emptively.  Three runs
    # over the identical trace and identical compiled models: queue-signal
    # baseline, observe-only (alerts recorded, routing untouched — served
    # logits must be bit-identical with the baseline), and alert-actuated
    # (strictly higher standard-class hit rate, the control-loop
    # acceptance).  Alert logs are FakeClock-timestamped JSONL, so their
    # hashes sit in the digest.
    from repro.compile import compile_model
    from repro.obs import HealthMonitor, default_rules
    from repro.traffic import parse_classes
    from repro.traffic.loadgen import Arrival

    h_classes = parse_classes("standard:25:1:degrade")
    hrng = nprng()
    h_arrivals, tc = [], 0.0
    for _ in range(6):
        t = tc
        while t < tc + 0.15:            # trickle: the EWMA decays
            h_arrivals.append(Arrival(t=t, slo="standard"))
            t += hrng.exponential(1.0 / 60.0)
        t = tc + 0.15
        while t < tc + 0.23:            # burst: ~6x primary capacity
            h_arrivals.append(Arrival(t=t, slo="standard"))
            t += hrng.exponential(1.0 / 2500.0)
        tc += 0.23
    h_svc = {"resnet20": ServiceModel.from_fps(400.0),
             "resnet8": ServiceModel.from_fps(30000.0)}
    models = {name: compile_model(cfg, qp, backend="lax-int",
                                  batch_sizes=(8,))
              for name, (cfg, qp) in variants.items()}

    def health_arm(mode):
        clock = FakeClock()
        prior = obsrt.disable()
        try:
            health = None
            if mode != "base":
                ob = obsrt.instrument(clock=clock)
                health = HealthMonitor(
                    ob, rules=default_rules(["standard"], objective=0.99),
                    interval_s=0.01)
                ob.health = health
            servers = {
                name: SimServer(name, h_svc[name], clock, replicas=1,
                                max_batch=8, model=models[name])
                for name in ("resnet20", "resnet8")}
            router = OverloadRouter(
                h_classes, primary="resnet20", degraded="resnet8",
                health=health if mode == "act" else None)
            sim = TrafficSim(servers, h_classes, router, clock,
                             health=health)
            t0 = time.perf_counter()
            rep = sim.run(h_arrivals, images=images)
            wall = time.perf_counter() - t0
            logits = np.stack([r.logits for r in sim.requests
                               if r.logits is not None])
            log = health.alert_log_jsonl() if health else ""
            summ = health.summary() if health else {}
            return rep, logits, log, summ, wall
        finally:
            obsrt.install(prior)

    base_rep, base_logits, _, _, base_wall = health_arm("base")
    obs_rep, obs_logits, obs_log, obs_summ, obs_wall = health_arm("obs")
    act_rep, act_logits, act_log, act_summ, act_wall = health_arm("act")
    hit_base = base_rep["classes"]["standard"]["deadline_hit_rate"]
    hit_obs = obs_rep["classes"]["standard"]["deadline_hit_rate"]
    hit_act = act_rep["classes"]["standard"]["deadline_hit_rate"]
    wall = base_wall + obs_wall + act_wall
    emit("e2e_slo/health", wall * 1e6,
         arrivals=len(h_arrivals),
         hit_standard_base=hit_base,
         hit_standard_obs=hit_obs,
         hit_standard_health=hit_act,
         health_gain=round(hit_act - hit_base, 6),
         bit_identical=bool(np.array_equal(base_logits, obs_logits)),
         degraded_base=base_rep["classes"]["standard"]["degraded"],
         degraded_health=act_rep["classes"]["standard"]["degraded"],
         alerts_obs=obs_summ.get("alerts", 0),
         alerts_health=act_summ.get("alerts", 0),
         burn_alerts_health=act_summ.get("by_rule", {}).get(
             "burn_rate:standard", 0),
         alert_log_sha=hashlib.sha256(obs_log.encode()).hexdigest()[:12],
         alert_log_sha_act=hashlib.sha256(act_log.encode()).hexdigest()[:12],
         wall_s=round(wall, 3))


def overhead_obs():
    """The observability tax on the e2e_pallas workload: one compiled
    ResNet8 executable interleave-timed (host drift cancels) with a
    ``repro.obs`` session installed vs removed around each call.  On the
    direct compiled path the enabled cost is the counter increments in
    ``CompiledModel._run_batched``; the acceptance (<3% enabled overhead,
    slow-marked in tests/test_obs.py; exactly zero calls when disabled,
    enforced by the poisoned-observer test) keeps instrumentation honest.
    The overhead fraction is wall-derived and so ``obs_``-volatile; the
    bit-identical flag and the counter totals are deterministic and sit in
    the digest."""
    print("\n## overhead_obs — instrumented vs uninstrumented compiled "
          "inference")
    print("name,us_per_call,derived")
    from repro.compile import compile_model
    from repro.models import resnet as R
    from repro.obs import runtime as obsrt
    batch, reps = 4, 8
    imgs = jax.random.uniform(key(80), (batch, 32, 32, 3),
                              minval=0.0, maxval=0.999)
    cfg = R.RESNET8
    params = R.init_params(cfg, key(81))
    qp = R.quantize_params(R.fold_params(params), cfg)
    cm = compile_model(cfg, qp, backend="pallas", batch_sizes=(batch,))
    prior = obsrt.disable()         # never time someone else's session
    ob = obsrt.Observability()
    try:
        out_off = np.asarray(cm(imgs))            # off-mode warmup + trace
        obsrt.install(ob)
        out_on = np.asarray(cm(imgs))             # on-mode warmup
        obsrt.install(None)
        t_on, t_off = [], []
        for i in range(reps):
            # alternate which mode runs first: the second call of a pair
            # reads a few percent slower on the CPU, whichever mode it is
            for on in ((False, True) if i % 2 == 0 else (True, False)):
                obsrt.install(ob if on else None)
                t0 = time.perf_counter()
                jax.block_until_ready(cm(imgs))
                (t_on if on else t_off).append(time.perf_counter() - t0)
            obsrt.install(None)
    finally:
        obsrt.install(prior)
    # the work is identical modulo two counter incs.  Every number of the
    # row comes from the median pair (upper median) by on/off time ratio:
    # the two calls of a pair share the host's load, and the median drops
    # the pairs a scheduler spike split (best-of per mode, which pairs the
    # fastest call of each mode from different moments, read up to +17% on
    # a loaded 8-core CPU host)
    ratio = np.array(t_on) / np.array(t_off)
    mid = int(np.argsort(ratio)[reps // 2])
    emit(f"overhead_obs/{cfg.name}", t_off[mid] * 1e6,
         fps=round(batch / t_off[mid], 1),
         obs_fps=round(batch / t_on[mid], 1),
         obs_overhead_frac=round(float(ratio[mid]) - 1.0, 4),
         bit_identical=bool(np.array_equal(out_on, out_off)),
         runs_counted=int(ob.metrics.total("model_runs_total")),
         reps=reps,
         inputs=input_digest(imgs))


def accuracy():
    """The accuracy half of the reproduction (``repro.quantize``): a short
    seeded float train of ResNet8 on the synthetic task, PTQ calibration to
    per-tensor pow2 grids, export to typed integer params (gated bit-exact
    pallas vs lax-int), then top-1 of the float reference vs the served int8
    model on the held-out synthetic eval set.  The top-1 values are
    deterministic per (code, seed) and so part of the run digest; only the
    wall-clock-derived fps is volatile."""
    print("\n## accuracy — float vs PTQ-int8 top-1 through the serving "
          "engine")
    print("name,us_per_call,derived")
    import dataclasses as dc

    from repro.data.synthetic import SyntheticCifar
    from repro.models import resnet as R
    from repro.quantize import (
        calibration_batches, evaluate_compiled, evaluate_float, ptq_quantize,
        synthetic_eval_set, validate_export)
    from repro.train import optimizer as opt_lib

    cfg = dc.replace(R.RESNET8, quant="none")
    steps, batch, eval_n = 40, 64, 256
    params = R.init_params(cfg, key(60))
    opt = opt_lib.sgdm(lr=0.1, total_steps=steps, warmup=4)
    opt_state = opt.init(params)
    pipe = SyntheticCifar(batch, seed=SEED)

    @jax.jit
    def step(p, s, i, b):
        (_, m), g = jax.value_and_grad(
            lambda pp: R.loss_fn(pp, cfg, b), has_aux=True)(p)
        return (*opt.update(g, s, p, i), m)

    t0 = time.perf_counter()
    for i in range(steps):
        params, opt_state, _ = step(params, opt_state, i, pipe.next())
    jax.block_until_ready(params)

    calib_batches = calibration_batches(2, batch, SEED)
    params, _, qp = ptq_quantize(cfg, params, calib_batches)
    check = validate_export(cfg, qp, calib_batches[0]["images"][:2])

    images, labels = synthetic_eval_set(eval_n, seed=SEED)
    fl = evaluate_float(cfg, params, images, labels)
    res = evaluate_compiled(cfg, qp, images, labels, backend="lax-int",
                            batch=64)
    us = (time.perf_counter() - t0) * 1e6
    emit(f"accuracy/{cfg.name}", us,
         float_top1=round(fl["top1"], 4),
         int8_top1=round(res["top1"], 4),
         top1_gap=round(fl["top1"] - res["top1"], 4),
         bit_exact=check["bit_exact"],
         retraces=res["retraces"],
         train_steps=steps, eval_n=eval_n,
         fps=round(res["fps"], 1),
         inputs=input_digest(images))


def e2e_transformer():
    """The generic graph->task compiler's LM rows: the reduced decoder-only
    transformer (gemma-2b smoke) and Mamba1 stack (falcon-mamba-7b smoke)
    lowered through the SAME serving compiler as the ResNet pipeline and
    timed executable vs executable (pallas task kernels vs the lax-int
    mirror).  Deterministic content: bit-exactness (the acceptance gate for
    the int8 LM arithmetic), the lowered task census, and the seeded token
    digest; FPS keys are wall-derived and volatile."""
    print("\n## e2e_transformer — compiled LM inference through the generic "
          "compiler (interpret-mode timings off-TPU)")
    print("name,us_per_call,derived")
    from repro.compile import compile_model, init_lm_params, lm_config
    from repro.compile import lowering
    from repro.configs.base import get_smoke_config
    batch, seq_len = 4, 16
    rng = nprng()
    for label, name in (("transformer", "gemma-2b"),
                        ("ssm", "falcon-mamba-7b")):
        cfg = lm_config(get_smoke_config(name), seq_len=seq_len)
        params = init_lm_params(cfg, seed=SEED)
        toks = rng.integers(0, cfg.vocab_size,
                            (batch, seq_len)).astype(np.int32)
        cm_p = compile_model(cfg, params, backend="pallas",
                             batch_sizes=(batch,))
        cm_i = compile_model(cfg, params, backend="lax-int",
                             batch_sizes=(batch,))
        exact = bool(np.array_equal(np.asarray(cm_p(toks)),
                                    np.asarray(cm_i(toks))))
        us_p = _time(lambda: cm_p(toks), n=1)
        us_i = _time(lambda: cm_i(toks), n=1)
        plan = lowering.plan_lm(lowering.optimized_graph(cfg), params)
        kinds = {}
        for t in plan.tasks:
            kinds[t.kind] = kinds.get(t.kind, 0) + 1
        folds = sum(1 for t in plan.tasks
                    if getattr(t, "skip", None) is not None)
        emit(f"e2e_transformer/{label}", us_p,
             fps=round(batch / (us_p / 1e6), 2),
             int_graph_fps=round(batch / (us_i / 1e6), 2),
             bit_exact=exact,
             layers=cfg.num_layers, seq_len=seq_len,
             vocab=cfg.vocab_size,
             tasks="|".join(f"{k}:{v}" for k, v in sorted(kinds.items())),
             residual_folds=folds,
             retraces=max(cm_p.trace_counts.values()),
             inputs=input_digest(toks))


def kernels_micro():
    print("\n## kernels_micro — interpret-mode timings (TPU is the target)")
    print("name,us_per_call,derived")
    from repro.kernels.matmul_int8.ops import matmul_int8_op
    k = key(50)
    a = jax.random.randint(k, (128, 128), -128, 128, jnp.int32).astype(jnp.int8)
    b = jax.random.randint(jax.random.fold_in(k, 1), (128, 128), -128, 128,
                           jnp.int32).astype(jnp.int8)
    us = _time(matmul_int8_op, a, b)
    emit("kernel/matmul_int8_128", us, note="int8->int32_MXU_tiles",
         inputs=input_digest(a, b))
    from repro.kernels.flash_attention.ops import flash_attention_op
    q = jax.random.normal(jax.random.fold_in(k, 3), (1, 128, 4, 32))
    us = _time(lambda: flash_attention_op(q, q[:, :, :4], q[:, :, :4],
                                          bq=64, bk=64))
    emit("kernel/flash_attention_128", us, note="online_softmax")
    from repro.kernels.selective_scan.ops import selective_scan_op
    u = jax.random.normal(jax.random.fold_in(k, 4), (2, 64, 32))
    dt = jax.nn.softplus(u)
    A = -jnp.ones((32, 8))
    Bc = jax.random.normal(jax.random.fold_in(k, 5), (2, 64, 8))
    h0 = jnp.zeros((2, 32, 8))
    us = _time(lambda: selective_scan_op(u, dt, A, Bc, Bc, h0, bd=16))
    emit("kernel/selective_scan_64", us, note="mamba1_recurrence")
    from repro.kernels.conv2d_int8.ops import conv2d_int8_op
    x = jax.random.randint(k, (2, 16, 16, 16), -128, 128, jnp.int32).astype(jnp.int8)
    w = jax.random.randint(jax.random.fold_in(k, 2), (3, 3, 16, 16), -128,
                           128, jnp.int32).astype(jnp.int8)
    us = _time(lambda: conv2d_int8_op(x, w, jnp.zeros((16,), jnp.int32)))
    emit("kernel/conv2d_int8_16", us, note="nhwc_vmem_tiles")


def roofline():
    print("\n## roofline — from the compiled dry-run (results/dryrun)")
    print("name,us_per_call,derived")
    d = os.path.join(os.path.dirname(__file__), "..", "results", "dryrun")
    if not os.path.isdir(d):
        emit("roofline/missing", 0, note="run launch.dryrun_all first")
        return
    import glob
    for f in sorted(glob.glob(os.path.join(d, "*__single.json"))):
        r = json.load(open(f))
        tag = f"{r['arch']}/{r['shape']}"
        if r.get("skipped"):
            emit(f"roofline/{tag}", 0, note="SKIP_full_attention")
            continue
        emit(f"roofline/{tag}", 0,
             compute_s=r["an_compute_s"], memory_s=r["an_memory_s"],
             collective_s=r["an_collective_s"],
             bottleneck=r["an_bottleneck"], mfu_bound=r["an_mfu"])


def main(argv=None) -> None:
    global SEED
    ap = argparse.ArgumentParser()
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also write all rows as machine-readable JSON")
    ap.add_argument("--only", action="append", default=None, metavar="NAME",
                    help="benchmark name(s) to run instead of the full "
                         "suite; repeatable and/or comma-separated "
                         "(--only e2e_pallas --only e2e_stream)")
    ap.add_argument("--seed", type=int, default=0,
                    help="RNG seed for every drawn benchmark input; the "
                         "JSON digest is reproducible per (code, seed)")
    args = ap.parse_args(argv)
    enable_compile_cache()
    SEED = args.seed
    ROWS.clear()              # main() is callable in-process; never let a
    # prior run's rows leak into this run's JSON/digest
    benches = dict(table3_fps=table3_fps, table4_buffers=table4_buffers,
                   fig13_addfold=fig13_addfold, e2e_pallas=e2e_pallas,
                   e2e_stream=e2e_stream, e2e_transformer=e2e_transformer,
                   e2e_tuned=e2e_tuned,
                   e2e_sharded=e2e_sharded, e2e_slo=e2e_slo,
                   overhead_obs=overhead_obs, accuracy=accuracy,
                   kernels_micro=kernels_micro, roofline=roofline)
    names = [n for arg in args.only for n in arg.split(",") if n] \
        if args.only else list(benches)
    unknown = [n for n in names if n not in benches]
    if unknown:
        ap.error(f"unknown benchmark(s) {unknown}; "
                 f"choose from {list(benches)}")
    for name in names:
        benches[name]()
    if args.json:
        os.makedirs(os.path.dirname(args.json) or ".", exist_ok=True)
        digest = run_digest(ROWS)
        with open(args.json, "w") as f:
            json.dump(dict(rows=ROWS, seed=SEED, digest=digest),
                      f, indent=1, default=str)
        print(f"\nwrote {len(ROWS)} rows to {args.json} "
              f"(seed={SEED}, digest={digest[:12]})")


if __name__ == "__main__":
    main()
