"""repro.compile: typed parameter containers, graph-driven lowering, backend
registry, and the compiled-executable contract (bit-exactness, buckets,
padding, zero retracing)."""
import dataclasses
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import compile as C
from repro.core import graph as G
from repro.models import resnet as R


def _qparams(cfg, seed):
    params = R.init_params(cfg, jax.random.PRNGKey(seed))
    return R.quantize_params(R.fold_params(params), cfg)


@pytest.fixture(scope="module")
def images():
    return jax.random.uniform(jax.random.PRNGKey(0), (4, 32, 32, 3),
                              minval=0.0, maxval=0.999)


@pytest.fixture(scope="module")
def qp8():
    return _qparams(R.RESNET8, seed=2)


# ---------------------------------------------------------------------------
# typed parameter containers
# ---------------------------------------------------------------------------


def test_from_dict_to_dict_roundtrip_is_bit_identical(qp8):
    tp = C.QResNetParams.from_dict(qp8)
    rt = tp.to_dict()
    flat_a = jax.tree_util.tree_leaves(qp8)
    flat_b = jax.tree_util.tree_leaves(rt)
    assert len(flat_a) == len(flat_b)
    for a, b in zip(flat_a, flat_b):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # structure round-trips too: ds present exactly where the dict had it
    assert [b.has_ds for b in tp.blocks] == \
        ["ds" in b for b in qp8["blocks"]]


def test_typed_params_are_a_pytree_with_static_specs(qp8):
    tp = C.QResNetParams.from_dict(qp8)
    leaves = jax.tree_util.tree_leaves(tp)
    # every leaf is an array — QSpecs ride as aux data, not leaves
    assert all(hasattr(l, "dtype") for l in leaves)
    doubled = jax.tree_util.tree_map(lambda x: x, tp)
    assert isinstance(doubled, C.QResNetParams)
    assert doubled.stem.w_spec == tp.stem.w_spec      # aux survives the map
    assert doubled.blocks[0].conv0.x_spec == tp.blocks[0].conv0.x_spec


def test_block_shifts_match_models_resnet(qp8):
    tp = C.QResNetParams.from_dict(qp8)
    for qb, blk in zip(qp8["blocks"], tp.blocks):
        assert blk.shifts(R.A_SPEC.exp) == R.block_shifts(qb)


def test_ensure_typed_accepts_both_and_rejects_junk(qp8):
    tp = C.ensure_typed(qp8)
    assert isinstance(tp, C.QResNetParams)
    assert C.ensure_typed(tp) is tp
    with pytest.raises(TypeError):
        C.ensure_typed([1, 2, 3])


# ---------------------------------------------------------------------------
# lowering: optimized IR -> plan
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("cfg,n_blocks", [(R.RESNET8, 3), (R.RESNET20, 9)])
def test_plan_model_walks_the_optimized_graph(cfg, n_blocks):
    plan = C.plan_model(C.optimized_graph(cfg))
    assert len(plan.blocks) == n_blocks
    assert plan.stem.och == cfg.base_width
    assert plan.head.num_classes == cfg.num_classes
    # stage-entry blocks (after stage 0) are the strided/downsample ones
    strides = [t.stride for t in plan.blocks]
    has_ds = [t.has_ds for t in plan.blocks]
    assert strides == R.block_strides(cfg)
    assert has_ds == [s == 2 for s in strides]
    # tasks arrive in graph (execution) order
    assert [t.index for t in plan.blocks] == list(range(n_blocks))


def test_plan_model_rejects_unoptimized_graph():
    with pytest.raises(C.LoweringError, match="optimize"):
        C.plan_model(C.model_graph(R.RESNET8))


def test_plan_model_rejects_partially_optimized_graph():
    g = C.model_graph(R.RESNET8)
    g = G.merge_relu(G.fold_bn(g))   # bn/relu folded but residuals untouched
    with pytest.raises(C.LoweringError):
        C.plan_model(g)


def test_plan_model_cross_checks_params(qp8):
    tp = C.QResNetParams.from_dict(qp8)
    bad = dataclasses.replace(tp, blocks=tp.blocks[:-1])
    with pytest.raises(C.LoweringError, match="blocks"):
        C.plan_model(C.optimized_graph(R.RESNET8), bad)


# ---------------------------------------------------------------------------
# backend registry
# ---------------------------------------------------------------------------


def test_builtin_backends_registered():
    assert {"pallas", "lax-int", "float"} <= set(C.list_backends())
    assert C.get_backend("pallas").name == "pallas"
    assert C.get_backend("int").name == "lax-int"     # legacy engine alias


def test_register_backend_decorator():
    @C.register_backend("test-null")
    class NullBackend:
        def lower(self, g, cfg, params):
            return lambda images: jnp.zeros((images.shape[0],
                                             cfg.num_classes))

    try:
        assert "test-null" in C.list_backends()
        cm = C.compile_model(R.RESNET8, _qparams(R.RESNET8, 0),
                             backend="test-null", batch_sizes=(2,))
        out = cm(jnp.ones((2, 32, 32, 3)))
        assert out.shape == (2, 10) and not np.any(np.asarray(out))
    finally:
        from repro.compile import backends as B
        B._REGISTRY.pop("test-null", None)


def test_unknown_backend_raises():
    with pytest.raises(ValueError, match="registered"):
        C.get_backend("hexagon")


# ---------------------------------------------------------------------------
# compile_model: the executable contract
# ---------------------------------------------------------------------------


# NOTE: compiled pallas-vs-int_forward bit-exactness moved to the
# cross-backend conformance matrix (tests/test_conformance.py), which covers
# both archs at every bucket/pad/chunk path and two kernel tilings.


@pytest.mark.parametrize("cfg", [R.RESNET8, R.RESNET20],
                         ids=lambda c: c.name)
def test_compiled_lax_int_matches_int_forward(cfg, images):
    """The bucketed AOT plumbing (pad/jit/slice) is identity w.r.t. the
    un-bucketed wrapper on both archs (int_forward IS the lax-int backend,
    so this pins the compile_model wrapper, not the arithmetic — the
    cross-backend arithmetic matrix lives in tests/test_conformance.py)."""
    qp = _qparams(cfg, seed=2)
    ref = R.int_forward(qp, cfg, images)
    cm = C.compile_model(cfg, qp, backend="lax-int", batch_sizes=(4,))
    np.testing.assert_array_equal(np.asarray(cm(images)), np.asarray(ref))


def test_float_backend_tracks_integer_backend(qp8, images):
    cfg = R.RESNET8
    ref = np.asarray(R.int_forward(qp8, cfg, images))
    cm = C.compile_model(cfg, qp8, backend="float", batch_sizes=(4,))
    np.testing.assert_allclose(np.asarray(cm(images)), ref, rtol=1e-4,
                               atol=1e-4)


@pytest.mark.parametrize("batch", [1, 5])
def test_int_head_is_exact_per_row(qp8, batch):
    """The pool + classifier tail is integer up to one float scale and the
    float bias, so each logit equals an int64 host computation bit for bit,
    whatever the batch shape (a float dot on TPU is not shape-invariant)."""
    from repro.compile.backends import _int_head
    fc = C.QResNetParams.from_dict(qp8).fc
    h = np.random.default_rng(batch).integers(0, 256, (batch, 8, 8, 64),
                                              dtype=np.uint8)
    got = np.asarray(jax.jit(_int_head, static_argnums=2)(h, fc, R.A_SPEC))
    acc = h.astype(np.int64).sum(axis=(1, 2)) @ np.asarray(fc.wq, np.int64)
    scale = np.float32(R.A_SPEC.scale * fc.w_spec.scale / 64)
    want = acc.astype(np.float32) * scale + np.asarray(fc.b, np.float32)
    np.testing.assert_array_equal(got, want)


def test_bucket_selection_padding_and_chunking(qp8, images):
    cfg = R.RESNET8
    cm = C.compile_model(cfg, qp8, backend="lax-int", batch_sizes=(2, 4))
    assert cm.bucket_for(1) == 2 and cm.bucket_for(2) == 2
    assert cm.bucket_for(3) == 4 and cm.bucket_for(9) == 4
    ref = np.asarray(R.int_forward(qp8, cfg, images))
    # short batch: padded to bucket 2, padding rows discarded
    np.testing.assert_array_equal(np.asarray(cm(images[:1])), ref[:1])
    assert sorted(cm._execs) == [2]
    # 3 rows selects bucket 4
    np.testing.assert_array_equal(np.asarray(cm(images[:3])), ref[:3])
    assert sorted(cm._execs) == [2, 4]
    # oversized batch is chunked through the largest bucket
    big = jnp.concatenate([images, images[:1]], axis=0)   # 5 rows
    out = np.asarray(cm(big))
    np.testing.assert_array_equal(out[:4], ref)
    np.testing.assert_array_equal(out[4:], ref[:1])


def test_no_retracing_across_repeated_calls(qp8, images):
    cfg = R.RESNET8
    cm = C.compile_model(cfg, qp8, backend="lax-int", batch_sizes=(4,))
    for _ in range(5):
        cm(images)
    assert cm.trace_counts == {4: 1}
    assert cm.compile_count == 1
    assert cm.executable(4) is cm.executable(4)   # one executable, reused


def test_eager_warmup_compiles_every_bucket(qp8):
    cfg = R.RESNET8
    cm = C.compile_model(cfg, qp8, backend="lax-int", batch_sizes=(1, 2),
                         eager=True)
    assert cm.compile_count == 2 and sorted(cm._execs) == [1, 2]


def test_compile_model_rejects_bad_buckets(qp8):
    with pytest.raises(ValueError):
        C.compile_model(R.RESNET8, qp8, backend="lax-int", batch_sizes=())
    with pytest.raises(ValueError):
        C.compile_model(R.RESNET8, qp8, backend="lax-int", batch_sizes=(0,))
    cm = C.compile_model(R.RESNET8, qp8, backend="lax-int", batch_sizes=(2,))
    with pytest.raises(ValueError, match="bucket"):
        cm.executable(3)
    with pytest.raises(ValueError, match="empty"):
        cm(jnp.zeros((0, 32, 32, 3)))


_CACHE_PROBE = """
import jax, jax.numpy as jnp
from repro.compile.cache import enable_compile_cache
print(enable_compile_cache())
print(jax.config.jax_compilation_cache_dir)
if {compile}:
    jax.jit(lambda x: x * 3 + 1).lower(jnp.ones(8)).compile()
"""


def _cache_probe(env_dir, compile_):
    """Run ``enable_compile_cache`` in a fresh process (it changes global
    JAX config); returns its two printed directories."""
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = env_dir
    r = subprocess.run(
        [sys.executable, "-c", _CACHE_PROBE.format(compile=compile_)],
        capture_output=True, text=True, env=env, timeout=300)
    assert r.returncode == 0, r.stderr[-3000:]
    return r.stdout.split()


def test_compile_cache_uses_env_dir_when_set(tmp_path):
    d = str(tmp_path / "cache")
    assert _cache_probe(d, compile_=True) == [d, d]
    assert os.listdir(d), "nothing was cached in $JAX_COMPILATION_CACHE_DIR"


def test_compile_cache_defaults_to_the_checkout():
    from repro.compile.cache import CHECKOUT_CACHE_DIR

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert CHECKOUT_CACHE_DIR == os.path.join(root, ".jax_cache")
    assert _cache_probe(None, compile_=False) == [CHECKOUT_CACHE_DIR] * 2


# ---------------------------------------------------------------------------
# stable device-op names
# ---------------------------------------------------------------------------

R20_BLOCKS = ["s1b0", "s1b1", "s1b2", "s2b0_ds", "s2b1", "s2b2",
              "s3b0_ds", "s3b1", "s3b2"]


@pytest.mark.parametrize("backend,kernels", [
    ("pallas", ["stem"] + R20_BLOCKS),
    (C.backends.PallasStreamBackend(cuts=[[0, 1, 2], [3], [4, 5, 6, 7, 8]]),
     ["stem_s1b0_to_s1b2", "s2b0_ds", "s2b1_to_s3b2"]),
    (C.backends.PallasStreamBackend(cuts=[[i] for i in range(9)],
                                    fuse_stem=False),
     ["stem"] + R20_BLOCKS),
], ids=["pallas", "pallas-stream", "pallas-stream-unfused"])
def test_kernels_carry_their_place_in_the_network(backend, kernels):
    """Every Pallas kernel of the ResNet path is named for its place in the
    network (the profiler's device-op name), and the input quantize runs
    in its own named scope, in the lowered HLO."""
    import re
    cfg = R.RESNET20
    qp = _qparams(cfg, seed=3)
    cm = C.compile_model(cfg, qp, backend=backend, batch_sizes=(2,))
    hlo = jax.jit(cm._forward).lower(cm.input_spec(2)).as_text(
        debug_info=True)
    found = re.findall(r'"(\w+)/pallas_call"', hlo)
    assert sorted(set(found)) == sorted(kernels)
    assert "/input_quantize/" in hlo
