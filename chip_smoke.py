#!/usr/bin/env python3
"""Chip smoke test: serve ResNet20 through the fused Pallas path on a TPU.

    python chip_smoke.py [--seed N] [--chips 4]

One chip (default): ResNet20 at its published widths (16/32/64, 32x32
input), random weights from ``--seed``, is compiled per batch bucket
(1, 8, 32) on the ``pallas``, ``lax-int`` and ``pallas-stream`` backends,
64 single-image requests are served through ``ResNetEngine`` on ``pallas``
and on ``pallas-stream``, and both served logit sets must equal the
``lax-int`` logits of the same images on the chip, bit for bit.  So must
every bucket of every backend: a request's logits may not depend on the
bucket its batch ran in.  Every compiled Pallas bucket must contain a
Mosaic kernel (``tpu_custom_call`` in its HLO).

``--chips 4``: only the scale-out phase — a 4-replica
``ShardedResNetEngine`` over the first four devices (every replica must
serve a batch) and ``CompiledModel.run_sharded`` over a 4-device ``data``
mesh, both bit-equal to the one-device bucket-32 executable.

Exits non-zero, printing no result, when JAX finds no TPU.  The last line of
stdout is ``{"ok": true, "device": {...}}``.  Everything runs in this one
process, which holds the chip.  JAX's persistent compile cache is kept where
``JAX_COMPILATION_CACHE_DIR`` says, else in ``<checkout>/.jax_cache``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

BUCKETS = (1, 8, 32)
REQUESTS = 64               # two full buckets; eight batches of 8 over 4 chips


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def fail(msg: str) -> None:
    print(f"[chip_smoke] FAIL: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


def check(cond, msg: str) -> None:
    if not cond:
        fail(msg)


def compile_buckets(cm, kernels: bool) -> None:
    """Compile every bucket of ``cm`` (what ``compile_model(...,
    eager=True)`` does, one bucket at a time so each is timed); with
    ``kernels``, assert each executable holds a Mosaic kernel."""
    for b in cm.batch_sizes:
        t0 = time.perf_counter()
        exe = cm.executable(b)
        log(f"compile {cm.backend.name} bucket={b}: "
            f"{time.perf_counter() - t0:.3f} s")
        if kernels:
            check("tpu_custom_call" in exe.as_text(),
                  f"{cm.backend.name} bucket {b} has no tpu_custom_call: "
                  f"its Pallas kernels did not compile for the chip")


def serve(eng, images):
    """Serve ``images`` as single-image requests through ``eng`` twice;
    returns the served logits in request order and the warm images/s of
    the second pass (information only)."""
    import numpy as np
    from repro.serve import ImageRequest

    out = None
    for _ in range(2):
        reqs = [ImageRequest(rid=i, image=im) for i, im in enumerate(images)]
        t0 = time.perf_counter()
        for r in reqs:
            eng.submit(r)
        eng.run()
        dt = time.perf_counter() - t0
        check(all(r.done for r in reqs), f"{eng.backend}: requests unserved")
        got = np.stack([r.logits for r in reqs])
        if out is None:
            out = got
        check(np.array_equal(got, out), f"{eng.backend}: second pass differs")
    return out, len(images) / dt


def model_and_images(seed: int):
    import jax
    import numpy as np
    from repro.models import resnet as R

    cfg = R.RESNET20
    params = R.init_params(cfg, jax.random.PRNGKey(seed))
    qp = R.quantize_params(R.fold_params(params), cfg)
    rng = np.random.default_rng(seed)
    images = rng.random((REQUESTS, cfg.img, cfg.img, 3), dtype=np.float32)
    return cfg, qp, images


def one_chip(args) -> None:
    import numpy as np
    from repro.compile import compile_model
    from repro.serve import ResNetEngine

    cfg, qp, images = model_and_images(args.seed)
    log(f"{cfg.name}: widths {cfg.base_width}/{2 * cfg.base_width}/"
        f"{4 * cfg.base_width}, {cfg.img}x{cfg.img} input, "
        f"{REQUESTS} requests, buckets {BUCKETS}")
    ref_cm = compile_model(cfg, qp, backend="lax-int", batch_sizes=BUCKETS)
    compile_buckets(ref_cm, kernels=False)
    ref = np.asarray(ref_cm(images))      # two full batches of bucket 32
    check(ref.shape == (len(images), cfg.num_classes),
          f"lax-int logits shape {ref.shape}")
    check(np.isfinite(ref).all(), "lax-int logits are not finite")
    check(ref.std() > 0, "lax-int logits are constant: the comparison "
          "would prove nothing")
    # a request's logits must not depend on the bucket its batch ran in
    for b in BUCKETS:
        d = np.abs(np.asarray(ref_cm(images[:b])) - ref[:b]).max()
        log(f"lax-int bucket {b} vs bucket {BUCKETS[-1]}: max|d|={d:.3g}")
        check(d == 0, f"lax-int bucket {b} differs from bucket {BUCKETS[-1]}")
    for backend in ("pallas", "pallas-stream"):
        eng = ResNetEngine(cfg, qp, batch=BUCKETS[-1], backend=backend,
                           batch_sizes=BUCKETS)
        compile_buckets(eng.model, kernels=True)
        got, ips = serve(eng, images)
        same = np.array_equal(got, ref)
        log(f"serve {backend}: {len(images)} requests, bitwise == lax-int: "
            f"{same}, max|d|={np.abs(got - ref).max():.3g}, warm "
            f"{ips:.1f} images/s (information only)")
        check(same, f"{backend} served logits differ from lax-int on chip")
        for b in BUCKETS:                 # the buckets serving did not use
            check(np.array_equal(np.asarray(eng.model(images[:b])), ref[:b]),
                  f"{backend} bucket {b} differs from lax-int on chip")
        check(eng.model.stats()["trace_counts"] == {b: 1 for b in BUCKETS},
              f"{backend}: a bucket retraced")


def four_chips(args) -> None:
    import jax
    import numpy as np
    from jax.sharding import Mesh
    from repro.compile import compile_model
    from repro.serve import ImageRequest, ShardedResNetEngine

    devs = jax.devices()[:4]
    check(len(devs) == 4, f"--chips 4 needs 4 devices, found {len(devs)}")
    cfg, qp, images = model_and_images(args.seed)
    ref = np.asarray(compile_model(cfg, qp, backend="pallas",
                                   batch_sizes=(32,))(images))
    check(np.isfinite(ref).all(), "one-device logits are not finite")

    eng = ShardedResNetEngine(cfg, qp, batch=8, backend="pallas",
                              devices=devs, replicas=4, slack_ms=1.0)
    t0 = time.perf_counter()
    eng.pool.warmup()
    log(f"replica pool: compiled 4 replicas in "
        f"{time.perf_counter() - t0:.3f} s")
    reqs = [ImageRequest(rid=i, image=im) for i, im in enumerate(images)]
    for r in reqs:
        eng.submit(r)
    eng.run()
    check(all(r.done for r in reqs), "replica pool left requests unserved")
    got = np.stack([r.logits for r in reqs])
    served = [r.served for r in eng.sched.replicas]
    same = np.array_equal(got, ref)
    log(f"replica pool over {[str(d) for d in devs]}: served per replica "
        f"{served}, bitwise == one device: {same}")
    check(same, "replica pool logits differ from the one-device executable")
    check(all(s > 0 for s in served), f"a replica served nothing: {served}")

    mesh = Mesh(np.array(devs), ("data",))
    t0 = time.perf_counter()
    cm = compile_model(cfg, qp, backend="pallas", batch_sizes=(32,))
    got = np.asarray(cm.run_sharded(images, mesh))
    same = np.array_equal(got, ref)
    log(f"run_sharded over a 4-device data mesh "
        f"({time.perf_counter() - t0:.3f} s incl. compile): "
        f"bitwise == one device: {same}")
    check(same, "SPMD logits differ from the one-device executable")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights and request images")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the replica-pool and SPMD phase")
    args = ap.parse_args()

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        fail(f"no TPU: JAX found {devices[0].platform} devices")
    from repro.compile.cache import enable_compile_cache
    log(f"compile cache: {enable_compile_cache()}")
    kind = devices[0].device_kind
    log(f"devices: {len(devices)} x {kind}")

    (four_chips if args.chips == 4 else one_chip)(args)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": kind,
        "count": len(devices)}}), flush=True)


if __name__ == "__main__":
    main()
