"""Serving layer: ResNetEngine on CompiledModel (backend parity, zero-pad
short batches, bucket selection, no per-tick retracing, A/B hooks, the
one-batch-ahead pipeline) and the LM Engine admission regressions."""
import jax
import numpy as np
import pytest

from repro.models import resnet as R
from repro.obs import runtime as obsrt
from repro.serve.engine import Engine, ImageRequest, Request, ResNetEngine


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


def _serve(eng, imgs):
    reqs = [ImageRequest(rid=i, image=img) for i, img in enumerate(imgs)]
    for r in reqs:
        eng.submit(r)
    eng.run()
    assert all(r.done for r in reqs)
    return reqs


# ---------------------------------------------------------------------------
# backend parity through the engine
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_backend_parity_pallas_vs_lax_int_through_engine(qp8, images):
    """The pallas and lax-int backends must produce bit-equal logits when
    serving the same requests through the engine."""
    cfg = R.RESNET8
    results = {}
    for backend in ("pallas", "lax-int"):
        eng = ResNetEngine(cfg, qp8, batch=3, backend=backend)
        reqs = _serve(eng, images)
        results[backend] = np.stack([r.logits for r in reqs])
    np.testing.assert_array_equal(results["pallas"], results["lax-int"])


def test_legacy_int_backend_name_still_works(qp8, images):
    eng = ResNetEngine(R.RESNET8, qp8, batch=2, backend="int")
    reqs = _serve(eng, images[:2])
    ref = np.asarray(R.int_forward(qp8, R.RESNET8, images[:2]))
    np.testing.assert_array_equal(np.stack([r.logits for r in reqs]), ref)


def test_ab_shadow_backend_records_exact_parity(qp8, images):
    eng = ResNetEngine(R.RESNET8, qp8, batch=2, backend="lax-int",
                       ab_backends=("float",))
    _serve(eng, images[:4])
    assert len(eng.ab_stats["float"]) == 2          # one entry per tick
    assert max(eng.ab_stats["float"]) < 1e-3        # float emulation tracks


# ---------------------------------------------------------------------------
# short batches, buckets, retracing
# ---------------------------------------------------------------------------


def test_short_batch_zero_padding_matches_direct_forward(qp8, images):
    """2 requests into a batch-4 engine: the padded tick must return exactly
    the logits of an unpadded direct forward on those 2 images."""
    cfg = R.RESNET8
    eng = ResNetEngine(cfg, qp8, batch=4, backend="lax-int")
    reqs = _serve(eng, images[:2])
    ref = np.asarray(R.int_forward(qp8, cfg, images[:2]))
    np.testing.assert_array_equal(np.stack([r.logits for r in reqs]), ref)
    assert eng.served == 2
    assert sorted(eng.model._execs) == [4]          # padded onto the bucket


def test_bucket_selection_short_ticks_use_small_bucket(qp8, images):
    cfg = R.RESNET8
    eng = ResNetEngine(cfg, qp8, batch=4, backend="lax-int",
                       batch_sizes=(2, 4))
    _serve(eng, images[:2])                          # one tick of 2
    assert sorted(eng.model._execs) == [2]           # small bucket compiled
    _serve(eng, images)                              # ticks of 4 and 2
    assert sorted(eng.model._execs) == [2, 4]
    assert eng.served == 8


def test_no_per_tick_retracing(qp8, images):
    """Acceptance: the engine reuses one compiled executable across ticks —
    trace/compile counts stay at 1 per bucket no matter how many ticks run."""
    cfg = R.RESNET8
    eng = ResNetEngine(cfg, qp8, batch=2, backend="lax-int")
    for wave in range(3):
        _serve(eng, images[:4])                      # 2 ticks per wave
    assert eng.served == 12
    assert eng.model.trace_counts == {2: 1}
    assert eng.model.compile_count == 1


def test_engine_rejects_batch_outside_buckets(qp8):
    with pytest.raises(ValueError, match="batch_sizes"):
        ResNetEngine(R.RESNET8, qp8, batch=8, backend="lax-int",
                     batch_sizes=(2, 4))


# ---------------------------------------------------------------------------
# the one-batch-ahead pipeline: a tick launches the next batch before it
# blocks on the oldest one in flight
# ---------------------------------------------------------------------------


def _requests(imgs):
    return [ImageRequest(rid=i, image=img) for i, img in enumerate(imgs)]


@pytest.mark.parametrize("backend", ["lax-int", "pallas"])
def test_pipelined_serving_matches_one_request_at_a_time(qp8, images,
                                                         backend):
    one = ResNetEngine(R.RESNET8, qp8, batch=2, backend=backend)
    alone = []
    for r in _requests(images):              # nothing queued behind it
        one.submit(r)
        assert one.run() == 1
        alone.append(r.logits)
    eng = ResNetEngine(R.RESNET8, qp8, batch=2, backend=backend)
    piped = _serve(eng, images)
    np.testing.assert_array_equal(np.stack([r.logits for r in piped]),
                                  np.stack(alone))


def test_pipelined_ticks_complete_one_batch_each_in_fifo_order(qp8, images):
    eng = ResNetEngine(R.RESNET8, qp8, batch=2, backend="lax-int")
    reqs = _requests(images[:5])             # batches of 2, 2 and 1
    for r in reqs:
        eng.submit(r)
    for t, served in enumerate((2, 4, 5)):
        assert eng.tick()
        assert [r.done for r in reqs] == [i < served for i in range(5)]
        assert eng.served == served and eng.ticks == t + 1
    assert not eng.tick()


def test_tick_is_false_only_once_queue_and_in_flight_are_empty(qp8, images):
    eng = ResNetEngine(R.RESNET8, qp8, batch=2, backend="lax-int")
    reqs = _requests(images[:4])
    for r in reqs:
        eng.submit(r)
    assert eng.tick()                        # launched both, completed one
    assert not eng.queue and not reqs[2].done
    assert eng.tick()                        # the batch still in flight
    assert all(r.done for r in reqs)
    assert not eng.tick() and eng.ticks == 2


def test_run_returns_the_batches_and_drains_the_in_flight_one(qp8, images):
    eng = ResNetEngine(R.RESNET8, qp8, batch=2, backend="lax-int")
    reqs = _requests(images[:5])
    for r in reqs:
        eng.submit(r)
    assert eng.run() == 3 and all(r.done for r in reqs)
    reqs = _requests(images[:4])
    for r in reqs:
        eng.submit(r)
    eng.tick()                               # leaves a batch in flight
    assert not eng.queue
    assert eng.run() == 1 and all(r.done for r in reqs)
    assert eng.served == 9 and eng.ticks == 5


def test_ab_shadow_replays_the_batch_it_completes(qp8, images):
    """Every batch holds other images, so a shadow that replayed the batch
    just launched, not the one completed, would read a deviation."""
    eng = ResNetEngine(R.RESNET8, qp8, batch=2, backend="lax-int",
                       ab_backends=("lax-int",))
    _serve(eng, images)
    assert eng.ab_stats["lax-int"] == [0.0, 0.0, 0.0]


def test_overlapped_launches_count_all_but_the_first_of_a_drained_run(
        qp8, images):
    eng = ResNetEngine(R.RESNET8, qp8, batch=2, backend="lax-int")
    ob = obsrt.instrument()
    try:
        _serve(eng, images[:5])              # 3 launches
        first = ob.metrics.total("engine_overlapped_launches_total")
        _serve(eng, images)                  # 3 more, after a drain
    finally:
        obsrt.disable()
    assert first == 2
    assert ob.metrics.total("engine_overlapped_launches_total") == 4


def test_staging_buffers_hold_each_batch_until_it_completes(qp8):
    """Nine images in batches of 2: the two staging buffers are refilled
    three times between them and the last batch is ragged.  A buffer
    refilled while its batch is in flight, or before the shadow replays
    it, would give a request another image's logits or the shadow a
    deviation."""
    imgs = np.asarray(jax.random.uniform(
        jax.random.PRNGKey(3), (9, 32, 32, 3), minval=0.0, maxval=0.999))
    eng = ResNetEngine(R.RESNET8, qp8, batch=2, backend="int",
                       ab_backends=("int",))
    reqs = _serve(eng, imgs)
    for r, img in zip(reqs, imgs):
        np.testing.assert_array_equal(
            r.logits, np.asarray(eng.model(img[None]))[0])
    assert eng.ab_stats["int"] == [0.0] * 5


def test_a_launch_with_every_staging_buffer_in_flight_raises(qp8, images):
    eng = ResNetEngine(R.RESNET8, qp8, batch=2, backend="lax-int")
    for r in _requests(images):
        eng.submit(r)
    eng._launch(None)
    eng._launch(None)                        # two batches in flight
    with pytest.raises(RuntimeError, match="in flight"):
        eng._launch(None)
    assert len(eng.queue) == 2               # the queue is left as it was
    for k, (_, staged, _) in enumerate(eng._in_flight):
        np.testing.assert_array_equal(staged, images[2 * k:2 * k + 2])


# ---------------------------------------------------------------------------
# submit-time validation (regression: mixed image shapes crashed tick)
# ---------------------------------------------------------------------------


def test_submit_rejects_mismatched_image_shape(qp8, images):
    eng = ResNetEngine(R.RESNET8, qp8, batch=2, backend="lax-int")
    eng.submit(ImageRequest(rid=0, image=images[0]))
    with pytest.raises(ValueError, match="shape"):
        eng.submit(ImageRequest(rid=1, image=np.zeros((16, 16, 3),
                                                      np.float32)))
    with pytest.raises(ValueError, match="shape"):
        eng.submit(ImageRequest(rid=2, image=np.zeros((32, 32), np.float32)))
    # the bad submits left the queue consistent: only the good request runs
    eng.run()
    assert eng.served == 1


# ---------------------------------------------------------------------------
# LM Engine admission (regression: empty prompt hit UnboundLocalError)
# ---------------------------------------------------------------------------


def test_engine_admits_empty_prompt_without_crash():
    from repro.configs import base as cbase
    from repro.models import model as M

    cfg = cbase.get_smoke_config("llama3.2-3b")
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    eng = Engine(cfg, params, slots=2, max_len=32)
    empty = Request(rid=0, prompt=[], max_new=3)
    normal = Request(rid=1, prompt=[4, 8], max_new=3)
    eng.submit(empty)
    eng.submit(normal)
    eng.run()
    assert empty.done and normal.done
    assert len(empty.out) >= 1          # decoded from the BOS-like seed
    assert len(normal.out) >= 3
