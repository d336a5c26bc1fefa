"""Serving launcher: LM continuous batching or compiled ResNet image serving.

LM workload (continuous-batching Engine):

    PYTHONPATH=src python -m repro.launch.serve --arch llama3.2-3b --smoke \
        --requests 8 --max-new 16

Image-classification workload (the paper's networks through repro.compile —
the optimized graph lowered once per batch bucket, served by ResNetEngine):

    PYTHONPATH=src python -m repro.launch.serve --arch resnet8 \
        --backend pallas --requests 64 --batch 8 --buckets 1,8

Scale-out serving (replica pool + deadline-based batch coalescing; one
model replica per device, least-loaded dispatch, p50/p99 latency split):

    PYTHONPATH=src python -m repro.launch.serve --arch resnet8 \
        --replicas 2 --slack-ms 5 --deadline-ms 50 --requests 64 --batch 8

Trace-driven SLO serving (repro.traffic: arrivals from a JSON trace or a
seeded Poisson process, per-class deadlines/priorities/policies, optional
autoscaling and accuracy-aware degradation to a cheaper variant):

    PYTHONPATH=src python -m repro.launch.serve --arch resnet20 \
        --trace results/trace.json --slo-classes \
        "interactive:25:0:strict,standard:50:1:degrade" \
        --autoscale --replicas 2 --degrade-arch resnet8
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.compile.cache import enable_compile_cache

RESNET_ARCHS = ("resnet8", "resnet20")


def serve_lm(args):
    from repro.configs import base as cbase
    from repro.models import model as M
    from repro.serve.engine import Engine, Request

    cfg = (cbase.get_smoke_config(args.arch) if args.smoke
           else cbase.get_config(args.arch))
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    eng = Engine(cfg, params, slots=args.slots, max_len=args.max_len)
    reqs = [Request(rid=i, prompt=[1 + i % 7, 2, 3 + i % 5],
                    max_new=args.max_new) for i in range(args.requests)]
    for r in reqs:
        eng.submit(r)
    t0 = time.time()
    ticks = eng.run()
    dt = time.time() - t0
    tokens = sum(len(r.out) for r in reqs)
    print(f"served {len(reqs)} requests, {tokens} tokens in {ticks} ticks, "
          f"{dt:.2f}s ({tokens/dt:.1f} tok/s)")
    for r in reqs[:3]:
        print(f"  req {r.rid}: {r.out[:10]}")


def serve_resnet_sharded(args, cfg, qp, buckets):
    """Replica-pool serving: one compiled replica per device, deadline-based
    coalescing, least-loaded dispatch."""
    from repro.serve.engine import ImageRequest, ShardedResNetEngine

    if args.ab:
        raise SystemExit(
            "--ab shadow backends are not supported with --replicas yet; "
            "run the A/B probe on the single-device engine (drop --replicas)")
    eng = ShardedResNetEngine(
        cfg, qp, batch=args.batch, backend=args.backend,
        replicas=args.replicas, batch_sizes=buckets,
        slack_ms=args.slack_ms, tune=args.tune or None)
    if eng.tuning:
        print(f"  tuned: {({t: c.to_dict() for t, c in eng.tuning.items()})}")
    eng.pool.warmup()                 # serve-only timings below
    rng = np.random.default_rng(args.seed)
    t0 = time.time()
    for i in range(args.requests):
        eng.submit(ImageRequest(
            rid=i, image=rng.random((cfg.img, cfg.img, 3), np.float32)),
            deadline_ms=args.deadline_ms or None)
    ticks = eng.run()
    dt = time.time() - t0
    st = eng.latency_stats()
    print(f"served {eng.served} images in {ticks} ticks, {dt:.2f}s "
          f"({eng.served/dt:.1f} img/s) via backend={args.backend!r} "
          f"x{len(eng.pool)} replicas")
    print(f"  queue wait ms p50/p99: {st['queue_wait_ms']['p50']:.2f}/"
          f"{st['queue_wait_ms']['p99']:.2f}   compute ms p50/p99: "
          f"{st['compute_ms']['p50']:.2f}/{st['compute_ms']['p99']:.2f}")
    print(f"  deadlines: {st['deadline_total'] - st['deadline_misses']}/"
          f"{st['deadline_total']} met; per-replica served: "
          f"{[r['served'] for r in st['replicas']]}")


def _make_launch_health(args, classes=None):
    """HealthMonitor on the active obs session when --alerts/--bundle-dir/
    --health-actuate is set (observe-only unless --health-actuate)."""
    if not (args.alerts or args.bundle_dir or args.health_actuate):
        return None
    from repro.obs import runtime as _obsrt
    from repro.obs import FlightRecorder, HealthMonitor, default_rules
    ob = _obsrt.active()
    if ob is None:
        return None
    rec = FlightRecorder()
    rec.attach(ob.trace)
    names = [c.name for c in classes] if classes else None
    health = HealthMonitor(ob, rules=default_rules(names),
                           recorder=rec, bundle_dir=args.bundle_dir or None)
    health.census_extra.update(arch=args.arch, backend=args.backend,
                               batch=args.batch, seed=args.seed)
    ob.health = health
    return health


def serve_resnet_traffic(args, cfg, qp, buckets):
    """Trace-driven SLO serving via ``repro.traffic``: the live runner over
    ``ShardedResNetEngine`` replicas, with per-class deadline accounting,
    optional autoscaling, and overload degradation to ``--degrade-arch``."""
    from repro.models import resnet as R
    from repro.serve.engine import ShardedResNetEngine
    from repro.traffic import (
        Autoscaler, AutoscaleConfig, LiveTrafficRunner, OverloadRouter,
        PoissonProcess, TraceReplay, parse_classes)
    from repro.traffic.__main__ import print_report

    classes = parse_classes(args.slo_classes)
    if args.trace:
        arrivals = TraceReplay.from_file(args.trace).generate(
            n=args.requests or None)
    else:
        arrivals = PoissonProcess(
            args.arrival_rate, seed=args.seed,
            class_mix={c.name: 1.0 for c in classes}).generate(
                n=args.requests)
    # more replicas than devices raises in ReplicaPool, never clamps
    replicas = max(args.replicas, 1)
    variants = {args.arch: ShardedResNetEngine(
        cfg, qp, batch=args.batch, backend=args.backend, replicas=replicas,
        batch_sizes=buckets, slack_ms=args.slack_ms, tune=args.tune or None)}
    if args.degrade_arch:
        dcfg = {"resnet8": R.RESNET8, "resnet20": R.RESNET20}[
            args.degrade_arch]
        dparams = R.init_params(dcfg, jax.random.PRNGKey(args.seed + 1))
        dqp = R.quantize_params(R.fold_params(dparams), dcfg)
        variants[args.degrade_arch] = ShardedResNetEngine(
            dcfg, dqp, batch=args.batch, backend=args.backend, replicas=1,
            slack_ms=args.slack_ms)
    for eng in variants.values():
        eng.pool.warmup()
    health = _make_launch_health(args, classes)
    actuating = health if args.health_actuate else None
    autoscaler = None
    if args.autoscale:
        autoscaler = Autoscaler(
            AutoscaleConfig(min_replicas=1, max_replicas=replicas),
            clock=variants[args.arch].clock, health=actuating)
        variants[args.arch].set_active_replicas(autoscaler.active)
    router = OverloadRouter(classes, primary=args.arch,
                            degraded=args.degrade_arch or None,
                            health=actuating)
    rng = np.random.default_rng(args.seed)
    images = rng.random((64, cfg.img, cfg.img, 3)).astype(np.float32)
    runner = LiveTrafficRunner(variants, classes, router,
                               autoscaler=autoscaler, health=health)
    report = runner.run(arrivals, images)
    print(f"served trace of {len(arrivals)} arrivals through "
          f"{list(variants)} (replicas={replicas}, "
          f"autoscale={'on' if autoscaler else 'off'})")
    print_report(report)
    return report


def serve_resnet(args):
    from repro.models import resnet as R

    cfg = {"resnet8": R.RESNET8, "resnet20": R.RESNET20}[args.arch]
    params = R.init_params(cfg, jax.random.PRNGKey(0))
    qp = R.quantize_params(R.fold_params(params), cfg)
    buckets = tuple(int(b) for b in args.buckets.split(",")) if args.buckets \
        else (args.batch,)
    ob = None
    if args.trace_out or args.metrics_out or args.alerts \
            or args.bundle_dir or args.health_actuate:
        from repro import obs as _o
        ob = _o.instrument()     # engines run on the same monotonic domain
    try:
        if args.trace or args.slo_classes or args.autoscale:
            return serve_resnet_traffic(args, cfg, qp, buckets)
        # single/sharded paths have no event loop: the monitor (if asked
        # for) is ticked once after the run, in the finally block below
        _make_launch_health(args)
        if args.replicas:
            return serve_resnet_sharded(args, cfg, qp, buckets)
        return _serve_resnet_single(args, cfg, qp, buckets)
    finally:
        if ob is not None:
            from repro import obs as _o
            if ob.health is not None and ob.health.ticks == 0:
                # non-traffic paths have no event loop: one final tick
                # evaluates the rules (the A/B bit-exactness sentinel in
                # particular) over the finished run
                ob.health.tick(ob.now())
            written = _o.export(ob, trace_out=args.trace_out or None,
                                metrics_out=args.metrics_out or None)
            _o.disable()
            if ob.health is not None:
                import os as _os
                from repro.obs import alert_log_path
                if args.bundle_dir:
                    _os.makedirs(args.bundle_dir, exist_ok=True)
                    log = _os.path.join(args.bundle_dir, "alerts.jsonl")
                    ob.health.write_alert_log(log)
                    written["alerts"] = log
                if args.metrics_out:
                    log = alert_log_path(args.metrics_out)
                    ob.health.write_alert_log(log)
                    written["alerts"] = log
                h = ob.health.summary()
                print(f"health: {h['alerts']} alerts {h['by_rule']}, "
                      f"{len(h['bundles'])} bundles")
            for kind, path in sorted(written.items()):
                print(f"wrote {kind} to {path}")


def _serve_resnet_single(args, cfg, qp, buckets):
    from repro.serve.engine import ImageRequest, ResNetEngine

    eng = ResNetEngine(cfg, qp, batch=args.batch, backend=args.backend,
                       batch_sizes=buckets,
                       ab_backends=tuple(
                           b for b in args.ab.split(",") if b) if args.ab
                       else (),
                       tune=args.tune or None)
    if eng.tuning:
        print(f"  tuned: {({t: c.to_dict() for t, c in eng.tuning.items()})}")
    # warm every bucket of the primary and the A/B shadows so the timing
    # below is serve-only
    eng.model.warmup()
    for shadow in eng.shadows.values():
        shadow.warmup()
    rng = np.random.default_rng(args.seed)
    for i in range(args.requests):
        eng.submit(ImageRequest(
            rid=i, image=rng.random((cfg.img, cfg.img, 3), np.float32)))
    t0 = time.time()
    ticks = eng.run()
    dt = time.time() - t0
    print(f"served {eng.served} images in {ticks} ticks, {dt:.2f}s "
          f"({eng.served/dt:.1f} img/s) via backend={args.backend!r}")
    print(f"  compiled: {eng.model.stats()}")
    for name, devs in eng.ab_stats.items():
        print(f"  A/B vs {name}: max|Δlogit| = {max(devs):.3g} "
              f"over {len(devs)} ticks")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--batch", type=int, default=8,
                    help="resnet: max images per tick")
    ap.add_argument("--buckets", default="",
                    help="resnet: comma-separated compiled batch buckets "
                         "(default: just --batch)")
    ap.add_argument("--backend", default="pallas",
                    help="resnet: a repro.compile registered backend")
    ap.add_argument("--ab", default="",
                    help="resnet: comma-separated shadow backends to A/B")
    ap.add_argument("--replicas", type=int, default=0,
                    help="resnet: serve through a replica pool of this many "
                         "devices (0 = single-device ResNetEngine)")
    ap.add_argument("--slack-ms", type=float, default=5.0,
                    help="resnet: batch-coalescing window — how long a "
                         "micro-batch may be held open waiting to fill "
                         "(larger = better throughput, worse p99 wait)")
    ap.add_argument("--deadline-ms", type=float, default=0.0,
                    help="resnet: per-request completion deadline (0 = "
                         "best-effort under --slack-ms only)")
    ap.add_argument("--seed", type=int, default=0,
                    help="resnet: RNG seed for the synthetic request images")
    ap.add_argument("--trace", default="",
                    help="resnet: serve a repro.traffic JSON trace file "
                         "(engages the SLO-class serving path)")
    ap.add_argument("--slo-classes", default="",
                    help="resnet: SLO class spec "
                         "name:deadline_ms:priority[:policy],... or a JSON "
                         "file (engages the SLO-class serving path)")
    ap.add_argument("--autoscale", action="store_true",
                    help="resnet: autoscale the active replica set from "
                         "queue depth + utilization (ceiling = --replicas)")
    ap.add_argument("--degrade-arch", default="",
                    help="resnet: cheaper variant that degrade-policy SLO "
                         "classes fall back to under overload")
    ap.add_argument("--arrival-rate", type=float, default=200.0,
                    help="resnet: Poisson arrival rate (req/s) when serving "
                         "SLO classes without a --trace file")
    ap.add_argument("--trace-out", default="",
                    help="resnet: write a Chrome trace_event JSON of the "
                         "serving run (repro.obs; load in Perfetto)")
    ap.add_argument("--metrics-out", default="",
                    help="resnet: write Prometheus-style metrics text "
                         "(repro.obs); the alert log lands next to it "
                         "when alerting is on")
    ap.add_argument("--alerts", action="store_true",
                    help="resnet: run the repro.obs.health alert engine "
                         "(passive; see docs/observability.md)")
    ap.add_argument("--bundle-dir", default="",
                    help="resnet: dump debug bundles here on alert or "
                         "missed-deadline drain (implies --alerts)")
    ap.add_argument("--health-actuate", action="store_true",
                    help="resnet: let active alerts drive the autoscaler "
                         "and overload router (implies --alerts)")
    ap.add_argument("--tune", default="",
                    choices=("", "auto", "analytic", "device"),
                    help="resnet: kernel autotuning — 'auto' serves from the "
                         "REPRO_TUNE_CACHE config cache (searching on miss), "
                         "'analytic' is cost-model-only, 'device' forces a "
                         "fresh two-stage search")
    args = ap.parse_args()
    enable_compile_cache()
    if args.arch in RESNET_ARCHS:
        serve_resnet(args)
    else:
        serve_lm(args)


if __name__ == "__main__":
    main()
