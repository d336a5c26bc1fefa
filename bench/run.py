#!/usr/bin/env python3
"""Run one benchmark cell once, on the chips of this machine.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> \\
        --trace <0|1>

The cell is an entry of ``workloads`` in ``BENCHMARK.json``; it names a
configuration (``bench/configs/<config>.json``) and a traffic mix
(``bench/traffic/<traffic>.json``).  The configuration's ``family`` key
chooses the package ``bench/families/<family>/`` (``families.load``) that
holds every family-specific step: the plain reference (``reference.py``)
and the adapter to the program (``program.py``); this module names no
family.  In one process the run

1. makes the weights on the device from the configuration's
   ``weight_seed`` (the family's ``reference.make_weights``) and the images
   from ``--seed`` (``traffic/generate.py``), and builds the engine the mix
   names (the family's ``program.build_engine``);
2. compiles and warms every batch shape the mix will use (set-up);
3. drives the engine for ``--seconds`` (``traffic/drive.py``), with the
   profiler on when ``--trace 1``;
4. frees the program, then checks a seeded sample of the served logits
   against the family's plain reference (``check.py``);
5. prints, as the last line of stdout, one JSON object: ``correct``,
   ``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1``
   also ``breakdown``, and last ``checks`` (each compared number with its
   limit, also the last lines of stderr).

Each metric is read by ``bench/metrics/<metric>.py``: ``--trace 0`` reports
the cell's end-to-end metrics, ``--trace 1`` its per-layer metrics.  A
reader that finds nothing to read returns None and the metric is left out.

Without a TPU, or with fewer chips than the cell asks for, the run exits
with code 2 and prints no result.  JAX's persistent compilation cache is
``<checkout>/.jax_cache``.
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from typing import Any, Optional  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# The cell, from BENCHMARK.json and the files it names
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Cell:
    workload: dict
    config: dict
    family: Any                   # families.Family, by config["family"]
    mix: dict
    end_to_end: list              # BENCHMARK.json entries this cell reports
    per_layer: list

    @property
    def chips(self) -> int:
        return int(self.workload["chips"])


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: str = ROOT) -> Cell:
    from bench import families
    from bench.traffic.generate import load_mix

    with open(os.path.join(root, "BENCHMARK.json")) as f:
        spec = json.load(f)
    by_name = {w["name"]: w for w in spec["workloads"]}
    if name not in by_name:
        raise SystemExit(f"unknown workload {name!r}; known: "
                         f"{sorted(by_name)}")
    w = by_name[name]
    cfg = next(c for c in spec["configs"] if c["name"] == w["config"])
    with open(os.path.join(root, cfg["file"])) as f:
        config = json.load(f)
    return Cell(workload=w, config=config, family=families.load(config),
                mix=load_mix(w["traffic"]),
                end_to_end=[m for m in spec["end_to_end"]
                            if _applies(m, name)],
                per_layer=[m for m in spec["per_layer"] if _applies(m, name)])


def load_reader(metric: str):
    path = os.path.join(BENCH, "metrics", f"{metric}.py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + metric.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# ---------------------------------------------------------------------------
# One run
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Run:
    """What a metric reader may read."""

    cell: Cell
    window: Any                   # traffic.drive.Window
    setup_s: float
    work: Any                     # work.Work
    device_kind: str
    n_devices: int                # devices the cell drives
    trace: Any = None             # trace.TraceSummary (--trace 1)

    @property
    def peaks(self) -> dict:
        from bench import work
        return work.peaks(self.device_kind)


def compile_cache() -> None:
    import jax
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def accelerators(chips: int):
    """The first ``chips`` TPU devices, or None (with the reason logged)."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        log(f"no TPU: JAX found {devices[0].platform} devices")
        return None
    if len(devices) < chips:
        log(f"the cell needs {chips} chips, JAX found {len(devices)}")
        return None
    return devices


class CompileCount:
    """Backend compiles and persistent-cache hits in this process so far.
    JAX's event listeners are process-wide and cannot be removed, so each
    is registered once."""

    n = 0
    hits = 0
    misses = 0
    _listening = False

    @classmethod
    def start(cls) -> None:
        if not cls._listening:
            import jax
            jax.monitoring.register_event_duration_secs_listener(cls._event)
            jax.monitoring.register_event_listener(cls._count)
            cls._listening = True

    @classmethod
    def _event(cls, name, secs, **kw):
        if name.endswith("backend_compile_duration"):
            cls.n += 1

    @classmethod
    def _count(cls, name, **kw):
        if name == "/jax/compilation_cache/cache_hits":
            cls.hits += 1
        elif name == "/jax/compilation_cache/cache_misses":
            cls.misses += 1


def warm(engine, mix: dict, image) -> None:
    """Compile and run every batch shape the mix will send."""
    import jax
    import numpy as np
    server = mix["server"]
    x = np.broadcast_to(image, (server["batch"],) + image.shape)
    if server["engine"] == "ResNetEngine":
        engine.model.warmup()
        for _ in range(2):
            np.asarray(engine.model(np.array(x)))
        return
    engine.pool.warmup()
    for i in range(len(engine.pool)):
        for n in range(1, server["batch"] + 1):   # every ragged batch size
            jax.block_until_ready(engine.pool.run(i, np.array(x[:n])))


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, devices,
             t_start: float = T_START, trace_dir: Optional[str] = None,
             break_program=None, keep_window: Optional[dict] = None) -> dict:
    """One run of ``cell``; returns the result line's object.
    ``break_program(engine)``, for the harness's own tests, breaks the
    timed path after set-up; ``keep_window["window"]`` receives the
    window's record, for the sweep."""
    import jax
    import numpy as np

    from bench import check, work
    from bench import trace as tr
    from bench.traffic import drive, generate

    cfg, mix = cell.config, cell.mix
    reference, program = cell.family.reference, cell.family.program
    used = devices[:cell.chips]
    CompileCount.start()
    n_hits, n_misses = CompileCount.hits, CompileCount.misses
    net = reference.build_net(cfg)
    # The program closes its weights over as executable constants, so
    # weights drawn from --seed would compile every run cold.  They come
    # from the configuration's fixed weight_seed; the images, arrivals and
    # the checked sample come from --seed.
    weights = reference.make_weights(net, cfg["weight_seed"])
    engine = program.build_engine(cfg, net, weights, mix["server"], used)
    pool = generate.image_pool(seed, mix["pool_images"], cfg["img"],
                               cfg["in_channels"])
    warm(engine, mix, pool[0])
    log(f"set-up: {CompileCount.hits - n_hits} programs loaded from the "
        f"persistent cache, {CompileCount.misses - n_misses} compiled")
    if break_program is not None:
        break_program(engine)
    n_compiles = CompileCount.n
    pauses, gc_t = [], []

    def on_gc(phase, info):
        if phase == "start":
            gc_t.append(time.perf_counter())
        elif gc_t:
            pauses.append((info["generation"],
                           time.perf_counter() - gc_t.pop()))

    def make_request(i):
        return program.Request(rid=i, image=pool[i % len(pool)])

    span = drive.Spans()
    log_dir = None
    if trace:
        log_dir = tempfile.mkdtemp(prefix="bench-trace-")
        opts = jax.profiler.ProfileOptions()
        # device events only: the harness keeps its own host spans
        opts.host_tracer_level = 0
        opts.python_tracer_level = 0
        jax.profiler.start_trace(log_dir, profiler_options=opts)
    gc.callbacks.append(on_gc)
    if mix["loop"] == "closed":
        win = drive.closed_loop(engine, make_request, mix["server"]["batch"],
                                mix["queued_batches"], seconds, span)
    else:
        send = generate.arrivals(mix, seed, seconds)
        win = drive.open_loop(engine, make_request, send, seconds, span)
        late = win.lateness_s * 1e3
        log(f"generator lateness ms: p50 {np.percentile(late, 50):.4f} "
            f"p99 {np.percentile(late, 99):.4f} max {late.max():.4f} "
            f"at {send[late.argmax()]:.3f} s into the window, over "
            f"{len(late)} requests")
    gc.callbacks.remove(on_gc)
    n_compiles = CompileCount.n - n_compiles
    summary = None
    if trace:
        jax.profiler.stop_trace()
        xplane = tr.find_xplane(log_dir)
        summary = tr.reduce_file(xplane, span.spans, len(used))
        if trace_dir:
            shutil.copy(xplane, os.path.join(trace_dir, "run.xplane.pb"))
            with open(os.path.join(trace_dir, "spans.json"), "w") as f:
                json.dump(span.spans, f)
        shutil.rmtree(log_dir, ignore_errors=True)
    setup_s = win.opened - t_start
    log(f"window {win.seconds:.3f} s: {win.completed} images in "
        f"{win.calls} forward calls, {win.attempted} requests sent; "
        f"compiles in the window and drain: {n_compiles}")
    full = [t for g, t in pauses if g == 2]
    log(f"garbage collections in the window and drain: {len(pauses)}, "
        f"{sum(t for _, t in pauses) * 1e3:.3f} ms in all; {len(full)} full, "
        f"longest {max(full, default=0.0) * 1e3:.3f} ms")

    kind = used[0].device_kind
    device = dict(platform=used[0].platform, kind=kind,
                  count=len(jax.devices()),
                  memory_peak_bytes=max(
                      (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                      for d in used))
    if summary is not None:
        device.update(busy_s=summary.busy_s, window_s=summary.window_s)
    run = Run(cell=cell, window=win, setup_s=setup_s,
              work=work.count(net), device_kind=kind,
              n_devices=len(used), trace=summary)
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        value = load_reader(m["name"])(run)
        if value is not None:
            metrics[m["name"]] = dict(value=float(value), unit=m["unit"])

    # the program's state goes before the reference runs on the chip
    served = win.answers
    if keep_window is not None:
        keep_window["window"] = win
    del engine, run, win
    gc.collect()
    t0 = time.monotonic()
    checks = check.check(reference, net, weights, pool, served, seed,
                         mix["sample"], cfg["limits"])
    log(f"reference check of {min(mix['sample'], len(served))} requests: "
        f"{time.monotonic() - t0:.3f} s")
    out = dict(correct=all(v["value"] <= v["limit"]
                           for v in checks.values()),
               attempted=len(served),
               failed=int(checks["unserved"]["value"]),
               metrics=metrics, device=device)
    if summary is not None:
        out["breakdown"] = dict(device_ops=[list(kv) for kv in
                                            summary.top_ops],
                                idle_gaps=[list(kv) for kv in summary.gaps])
    out["checks"] = checks
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--trace-dir", default=None,
                    help="also keep the trace's .xplane.pb here")
    args = ap.parse_args(argv)

    cell = load_cell(args.workload)
    compile_cache()
    devices = accelerators(cell.chips)
    if devices is None:
        return 2
    out = run_cell(cell, args.seed, args.seconds, bool(args.trace), devices,
                   trace_dir=args.trace_dir)
    for name, v in out["checks"].items():
        print(f"check {name}: {v['value']!r} (limit {v['limit']!r})",
              file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
