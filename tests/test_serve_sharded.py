"""Scale-out serving: ShardedResNetEngine (replica pool + deadline-based
coalescing) and the CompiledModel placement APIs.

Single-device cases run inline (the pool degenerates to one replica and
must be bit-exact with the plain engine).  Multi-device cases follow the
test_parallel.py convention: a subprocess with
``--xla_force_host_platform_device_count`` so the main process keeps its
single default device.  The FPS-scaling check only makes sense on real
parallel hardware, so it is skipped at ``jax.device_count() == 1``.
"""
import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest

from repro.models import resnet as R
from repro.serve import (Backpressure, FakeClock, ImageRequest, ResNetEngine,
                         ShardedResNetEngine)

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def run_sub(code: str, devices: int = 4) -> str:
    prog = ("import os\n"
            f"os.environ['XLA_FLAGS'] = "
            f"'--xla_force_host_platform_device_count={devices}'\n"
            + textwrap.dedent(code))
    env = dict(os.environ, PYTHONPATH=SRC)
    r = subprocess.run([sys.executable, "-c", prog], capture_output=True,
                       text=True, env=env, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    return r.stdout


def _qparams(cfg, seed):
    params = R.init_params(cfg, jax.random.PRNGKey(seed))
    return R.quantize_params(R.fold_params(params), cfg)


@pytest.fixture(scope="module")
def qp8():
    return _qparams(R.RESNET8, seed=7)


@pytest.fixture(scope="module")
def images():
    return np.asarray(jax.random.uniform(
        jax.random.PRNGKey(1), (6, 32, 32, 3), minval=0.0, maxval=0.999))


# ---------------------------------------------------------------------------
# bit-exactness on a single-device pool
# ---------------------------------------------------------------------------


def test_sharded_engine_bit_exact_with_single_device_pallas(qp8, images):
    """Acceptance: the sharded engine on a 1-device mesh produces exactly
    the single-device fused-pallas logits — scheduling and placement never
    touch the arithmetic."""
    cfg = R.RESNET8
    ref = np.asarray(R.pallas_forward(qp8, cfg, images))
    eng = ShardedResNetEngine(cfg, qp8, batch=4, backend="pallas",
                              replicas=1, batch_sizes=(2, 4), slack_ms=1.0)
    reqs = [ImageRequest(rid=i, image=img) for i, img in enumerate(images)]
    for r in reqs:
        eng.submit(r, deadline_ms=500.0)
    eng.run()
    assert all(r.done for r in reqs)
    np.testing.assert_array_equal(np.stack([r.logits for r in reqs]), ref)


def test_sharded_engine_matches_plain_engine(qp8, images):
    """Same requests through ResNetEngine and ShardedResNetEngine (lax-int
    for speed): identical logits and identical served counts."""
    cfg = R.RESNET8
    plain = ResNetEngine(cfg, qp8, batch=3, backend="lax-int")
    preqs = [ImageRequest(rid=i, image=img) for i, img in enumerate(images)]
    for r in preqs:
        plain.submit(r)
    plain.run()

    shard = ShardedResNetEngine(cfg, qp8, batch=3, backend="lax-int",
                                replicas=1, slack_ms=0.5)
    sreqs = [ImageRequest(rid=i, image=img) for i, img in enumerate(images)]
    for r in sreqs:
        shard.submit(r)
    shard.run()
    assert shard.served == plain.served == len(images)
    np.testing.assert_array_equal(np.stack([r.logits for r in sreqs]),
                                  np.stack([r.logits for r in preqs]))


def test_sharded_engine_no_per_tick_retracing(qp8, images):
    """Per-device executables are compiled once and reused: serving many
    waves never grows the trace/compile counts."""
    cfg = R.RESNET8
    eng = ShardedResNetEngine(cfg, qp8, batch=2, backend="lax-int",
                              replicas=1, slack_ms=0.2)
    eng.pool.warmup()
    counts_after_warmup = (dict(eng.model.trace_counts),
                           eng.model.compile_count)
    for wave in range(3):
        reqs = [ImageRequest(rid=i, image=img)
                for i, img in enumerate(images[:4])]
        for r in reqs:
            eng.submit(r)
        eng.run()
    assert eng.served == 12
    assert (dict(eng.model.trace_counts),
            eng.model.compile_count) == counts_after_warmup


def test_sharded_engine_validates_shape_and_buckets(qp8):
    eng = ShardedResNetEngine(R.RESNET8, qp8, batch=2, backend="lax-int",
                              replicas=1)
    with pytest.raises(ValueError, match="shape"):
        eng.submit(ImageRequest(rid=0, image=np.zeros((16, 16, 3),
                                                      np.float32)))
    with pytest.raises(ValueError, match="batch_sizes"):
        ShardedResNetEngine(R.RESNET8, qp8, batch=8, backend="lax-int",
                            replicas=1, batch_sizes=(2, 4))
    with pytest.raises(ValueError, match="devices"):
        ShardedResNetEngine(R.RESNET8, qp8, batch=2, backend="lax-int",
                            replicas=jax.local_device_count() + 7)


def test_launch_serve_rejects_more_replicas_than_devices(qp8):
    """``launch.serve --replicas`` above the device count is an error, never
    a silent clamp to the devices there are."""
    import argparse
    from repro.launch.serve import serve_resnet_traffic

    args = argparse.Namespace(
        arch="resnet8", slo_classes="gold:50:0", trace="", requests=4,
        arrival_rate=100.0, seed=0, replicas=jax.local_device_count() + 1,
        batch=2, backend="lax-int", slack_ms=1.0, tune="", degrade_arch="")
    with pytest.raises(ValueError, match="devices are available"):
        serve_resnet_traffic(args, R.RESNET8, qp8, (2,))


def test_fake_clock_engine_is_deterministic(qp8, images):
    """With an injected FakeClock, the engine's scheduling timeline is fully
    simulated: queue waits come out as exact simulated values."""
    cfg = R.RESNET8
    eng = ShardedResNetEngine(cfg, qp8, batch=4, backend="lax-int",
                              replicas=1, slack_ms=2.0, clock=FakeClock())
    for i in range(3):                    # partial batch: held for slack
        eng.submit(ImageRequest(rid=i, image=images[i]))
    eng.run()
    assert eng.served == 3
    st = eng.latency_stats()
    # dispatched exactly when the 2ms window closed, never before
    assert st["queue_wait_ms"]["max"] == pytest.approx(2.0, abs=0.2)


def test_latency_stats_split_queue_wait_vs_compute(qp8, images):
    cfg = R.RESNET8
    eng = ShardedResNetEngine(cfg, qp8, batch=3, backend="lax-int",
                              replicas=1, slack_ms=0.5)
    eng.pool.warmup()
    for i, img in enumerate(images):
        eng.submit(ImageRequest(rid=i, image=img))
    eng.run()
    st = eng.latency_stats()
    assert st["count"] == 6
    assert st["compute_ms"]["p50"] > 0
    assert st["queue_wait_ms"]["p50"] >= 0
    assert [r["served"] for r in st["replicas"]] == [6]
    full = eng.stats()                    # regression: key collision crash
    assert full["served"] == 6 and full["pool_size"] == 1
    assert full["model"]["backend"] == "lax-int"


def test_failed_dispatch_releases_accounting(qp8, images, monkeypatch):
    """A dispatch whose device execution errors is evicted — in-flight
    accounting releases, its requests stay done=False, and the engine can
    keep serving afterwards (no head-of-line jam)."""
    import repro.serve.engine as E

    cfg = R.RESNET8
    eng = ShardedResNetEngine(cfg, qp8, batch=2, backend="lax-int",
                              replicas=1, slack_ms=0.2)
    bad = [ImageRequest(rid=i, image=images[i]) for i in range(2)]
    for r in bad:
        eng.submit(r)
    with monkeypatch.context() as m:
        m.setattr(E.jax, "block_until_ready",
                  lambda x: (_ for _ in ()).throw(RuntimeError("device died")))
        with pytest.raises(RuntimeError, match="device died"):
            eng.run()
    assert not eng._in_flight
    assert eng.sched.in_flight == 0
    assert all(not r.done for r in bad)
    st = eng.latency_stats()
    # failed requests are counted as failures, never as successes
    assert st["failed"] == 2 and st["count"] == 0
    assert st["replicas"][0]["served"] == 0
    assert st["replicas"][0]["failed"] == 2
    good = [ImageRequest(rid=10 + i, image=images[i]) for i in range(2)]
    for r in good:                        # the engine is not poisoned
        eng.submit(r)
    eng.run()
    assert all(r.done for r in good)
    ref = np.asarray(R.int_forward(qp8, cfg, images[:2]))
    np.testing.assert_array_equal(np.stack([r.logits for r in good]), ref)


# ---------------------------------------------------------------------------
# async dispatch loop + backpressure
# ---------------------------------------------------------------------------


def test_run_async_with_backpressure_serves_everything(qp8, images):
    import asyncio

    cfg = R.RESNET8
    eng = ShardedResNetEngine(cfg, qp8, batch=2, backend="lax-int",
                              replicas=1, slack_ms=0.5, max_pending=3)
    reqs = [ImageRequest(rid=i, image=images[i % 6]) for i in range(10)]

    async def produce():
        for r in reqs:
            await eng.submit_async(r)     # awaits instead of raising
        eng.shutdown()

    async def main():
        await asyncio.gather(eng.run_async(), produce())

    asyncio.run(main())
    assert eng.served == 10
    assert all(r.done for r in reqs)
    ref = np.asarray(R.int_forward(qp8, cfg, images[:2]))
    np.testing.assert_array_equal(np.stack([reqs[0].logits, reqs[1].logits]),
                                  ref)


def test_submit_raises_backpressure_when_pending_full(qp8, images):
    eng = ShardedResNetEngine(R.RESNET8, qp8, batch=4, backend="lax-int",
                              replicas=1, slack_ms=1000.0, max_pending=2,
                              clock=FakeClock())
    eng.submit(ImageRequest(rid=0, image=images[0]))
    eng.submit(ImageRequest(rid=1, image=images[1]))
    with pytest.raises(Backpressure):
        eng.submit(ImageRequest(rid=2, image=images[2]))
    eng.shutdown()                        # graceful drain flushes the two
    eng.run()
    assert eng.served == 2


# ---------------------------------------------------------------------------
# multi-device: replica pool + SPMD shard_map path (subprocess)
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_replica_pool_spreads_load_across_devices_subprocess():
    """4 forced host devices, 2 replicas: both replicas serve, results stay
    bit-exact with the unsharded path, per-device executables live on their
    own devices.  (slow: subprocess run per the marker definition)"""
    out = run_sub("""
        import jax, numpy as np
        from repro.models import resnet as R
        from repro.serve import ImageRequest, ShardedResNetEngine

        cfg = R.RESNET8
        p = R.init_params(cfg, jax.random.PRNGKey(7))
        qp = R.quantize_params(R.fold_params(p), cfg)
        imgs = np.asarray(jax.random.uniform(
            jax.random.PRNGKey(1), (8, 32, 32, 3), maxval=0.999))
        ref = np.asarray(R.int_forward(qp, cfg, imgs))

        eng = ShardedResNetEngine(cfg, qp, batch=2, backend="lax-int",
                                  replicas=2, slack_ms=0.5)
        eng.pool.warmup()
        reqs = [ImageRequest(rid=i, image=img) for i, img in enumerate(imgs)]
        for r in reqs:
            eng.submit(r)
        eng.run()
        got = np.stack([r.logits for r in reqs])
        assert np.array_equal(got, ref), "sharded != single-device"
        served = [r.served for r in eng.sched.replicas]
        assert sum(served) == 8
        devs = {str(d) for d in eng.pool.devices}
        assert len(devs) == 2
        print("POOL_OK", served)
    """)
    assert "POOL_OK" in out
    served = eval(out.split("POOL_OK")[1].strip())
    assert all(s > 0 for s in served)      # both replicas actually served


@pytest.mark.slow
def test_shard_executable_spmd_matches_single_device_subprocess():
    """CompiledModel.shard_executable: batch sharded over a 4-device 'data'
    mesh via shard_map with replicated weights — bit-exact with the
    unsharded executable for pallas AND lax-int.  (slow: whole-network
    pallas compile inside a fresh subprocess)"""
    out = run_sub("""
        import jax, numpy as np
        from repro.models import resnet as R
        from repro.compile import compile_model

        cfg = R.RESNET8
        p = R.init_params(cfg, jax.random.PRNGKey(7))
        qp = R.quantize_params(R.fold_params(p), cfg)
        imgs = np.asarray(jax.random.uniform(
            jax.random.PRNGKey(1), (8, 32, 32, 3), maxval=0.999))
        mesh = jax.make_mesh((4,), ("data",))
        for backend in ("lax-int", "pallas"):
            cm = compile_model(cfg, qp, backend=backend, batch_sizes=(8,))
            ref = np.asarray(cm(imgs))
            got = np.asarray(cm.run_sharded(imgs, mesh))
            assert np.array_equal(got, ref), backend
            # ragged batch: zero-padded onto the compiled bucket (same
            # bucket discipline as __call__ — no per-shape recompiles)
            got5 = np.asarray(cm.run_sharded(imgs[:5], mesh))
            assert np.array_equal(got5, ref[:5]), backend + "/pad"
            assert len(cm._shard_execs) == 1, backend + "/bucket"
        print("SPMD_OK")
    """)
    assert "SPMD_OK" in out


@pytest.mark.slow
def test_run_placed_pins_output_to_device_subprocess():
    out = run_sub("""
        import jax, numpy as np
        from repro.models import resnet as R
        from repro.compile import compile_model

        cfg = R.RESNET8
        p = R.init_params(cfg, jax.random.PRNGKey(7))
        qp = R.quantize_params(R.fold_params(p), cfg)
        imgs = np.asarray(jax.random.uniform(
            jax.random.PRNGKey(1), (3, 32, 32, 3), maxval=0.999))
        cm = compile_model(cfg, qp, backend="lax-int", batch_sizes=(4,))
        ref = np.asarray(cm(imgs))
        for d in jax.local_devices()[:2]:
            out = cm.run_placed(imgs, d)
            assert list(out.devices()) == [d], (d, out.devices())
            assert np.array_equal(np.asarray(out), ref)
        print("PLACED_OK")
    """)
    assert "PLACED_OK" in out


@pytest.mark.skipif(jax.device_count() == 1,
                    reason="needs real parallel devices for FPS scaling")
def test_e2e_sharded_fps_increases_with_replicas(qp8, images):
    """On genuinely parallel hardware, throughput must grow monotonically
    with the replica count (the paper's replicated-pipeline scaling law)."""
    import time

    cfg = R.RESNET8
    counts = [c for c in (1, 2, 4) if c <= jax.device_count()]
    fps = []
    for n_rep in counts:
        eng = ShardedResNetEngine(cfg, qp8, batch=4, backend="pallas",
                                  replicas=n_rep, slack_ms=1.0)
        eng.pool.warmup()
        reqs = [ImageRequest(rid=i, image=images[i % 6]) for i in range(64)]
        t0 = time.perf_counter()
        for r in reqs:
            eng.submit(r)
        eng.run()
        fps.append(eng.served / (time.perf_counter() - t0))
    assert fps == sorted(fps), f"FPS not monotonic vs replicas: {fps}"
