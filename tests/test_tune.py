"""repro.tune: the design-space exploration subsystem — config round-trips,
legality (every enumerated config bit-exact vs the kernel refs in interpret
mode), cost-model ranking sanity, the persistent config cache, and the tuned
compile integration.  Plus the roofline _key regression (unknown archs sort
last instead of crashing)."""
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import tune as T
from repro.core import dataflow, ilp
from repro.models import resnet as R
from repro.tune import cost as tcost
from repro.tune import space as tspace
from repro.tune.config import KernelConfig, largest_divisor_leq


def _qparams(cfg, seed):
    params = R.init_params(cfg, jax.random.PRNGKey(seed))
    return R.quantize_params(R.fold_params(params), cfg)


# ---------------------------------------------------------------------------
# KernelConfig
# ---------------------------------------------------------------------------


def test_kernel_config_dict_roundtrip_and_hashability():
    c = KernelConfig(batch_tile=4, cout_block=8)
    assert KernelConfig.from_dict(c.to_dict()) == c
    assert KernelConfig.from_dict({}) == KernelConfig()
    assert c.to_dict() == dict(batch_tile=4, cout_block=8)  # defaults dropped
    hash(c)                                   # usable as a jit static arg
    assert KernelConfig().describe() == "default"


def test_kernel_config_normalize_snaps_to_divisors():
    assert largest_divisor_leq(12, 8) == 6
    c = KernelConfig(batch_tile=8, cout_block=24).normalize(n=6, cout=16)
    assert c.batch_tile == 6 and c.cout_block == 16
    # 0 means maximal
    c = KernelConfig(batch_tile=0, cout_block=0).normalize(n=5, cout=32)
    assert c.batch_tile == 5 and c.cout_block == 32


# ---------------------------------------------------------------------------
# config cache (REPRO_TUNE_CACHE; corrupt -> empty)
# ---------------------------------------------------------------------------


def test_cache_roundtrip(tmp_path, monkeypatch):
    path = tmp_path / "tune.json"
    monkeypatch.setenv("REPRO_TUNE_CACHE", str(path))
    assert T.cache_path() == str(path)
    c = T.TuneCache()
    key = T.cache_key("model:resnet8", ((4, 32, 32, 3),), "float32",
                      "pallas", "cpu:interpret")
    assert c.get(key) is None and c.misses == 1
    tuning = {"stem": KernelConfig(batch_tile=4, cout_block=16),
              "block0": KernelConfig(batch_tile=2)}
    c.put(key, tuning)
    c.save()
    # a fresh cache object reads the same assignment back, bit for bit
    c2 = T.TuneCache()
    got = c2.get(key)
    assert got == tuning and c2.hits == 1
    # the on-disk format is plain JSON with compact config dicts
    raw = json.loads(path.read_text())
    assert raw[key]["stem"] == {"batch_tile": 4, "cout_block": 16}


def test_cache_corrupt_file_treated_as_empty(tmp_path, monkeypatch):
    path = tmp_path / "tune.json"
    path.write_text("{ this is not json")
    monkeypatch.setenv("REPRO_TUNE_CACHE", str(path))
    c = T.TuneCache()
    assert len(c) == 0
    assert c.get("anything") is None
    c.put("k", {"stem": KernelConfig()})
    c.save()                                   # save over the corrupt file
    assert T.TuneCache().get("k") == {"stem": KernelConfig()}
    # non-dict JSON is also "empty", not an error
    path.write_text("[1, 2, 3]")
    assert len(T.TuneCache()) == 0


def test_cache_default_path_used_without_env(monkeypatch):
    monkeypatch.delenv("REPRO_TUNE_CACHE", raising=False)
    monkeypatch.delenv("XDG_CACHE_HOME", raising=False)
    assert T.cache_path() == os.path.expanduser("~/.cache/repro/tune.json")


def test_cache_path_honors_xdg_cache_home(tmp_path, monkeypatch):
    monkeypatch.delenv("REPRO_TUNE_CACHE", raising=False)
    monkeypatch.setenv("XDG_CACHE_HOME", str(tmp_path / "xdg"))
    assert T.cache_path() == str(tmp_path / "xdg" / "repro" / "tune.json")
    # REPRO_TUNE_CACHE still wins over XDG
    monkeypatch.setenv("REPRO_TUNE_CACHE", str(tmp_path / "explicit.json"))
    assert T.cache_path() == str(tmp_path / "explicit.json")
    # save() creates the missing XDG parent directories
    monkeypatch.delenv("REPRO_TUNE_CACHE", raising=False)
    c = T.TuneCache()
    c.put("k", {"stem": KernelConfig()})
    c.save()
    assert os.path.isfile(tmp_path / "xdg" / "repro" / "tune.json")


# ---------------------------------------------------------------------------
# space: legality of every enumerated config
# ---------------------------------------------------------------------------


def test_model_space_structure_and_balance_pruning():
    spaces = tspace.model_space(R.RESNET8, batch=4)
    assert set(spaces) == {"stem", "block0", "block1", "block2"}
    layers = dataflow.resnet8_layers()
    floor = dict(zip((l.name for l in layers),
                     ilp.balanced_och_par(layers, pow2=True)))["stem"]
    assert floor > 1                     # the balance floor actually prunes
    for c in spaces["stem"]:
        assert c.cout_block >= floor     # eq. 12-14 pruning
        assert 16 % c.cout_block == 0 and 4 % c.batch_tile == 0
        # Mosaic takes a lane block of the full width or of 128s only
        assert c.cout_block in (0, 16) or c.cout_block % 128 == 0
    for k in ("block0", "block1", "block2"):
        for c in spaces[k]:
            assert c.cout_block == 0     # fusion-illegal knob never enumerated
            assert 4 % c.batch_tile == 0
    assert tspace.space_size(spaces) == \
        np.prod([len(v) for v in spaces.values()])


def test_every_enumerated_stem_config_bitexact_vs_ref():
    """Legality contract: any config the space emits must change only the
    schedule, never a bit (ResNet8 stem shapes, interpret mode)."""
    from repro.kernels.conv_stem.ops import conv_stem_op
    from repro.kernels.conv_stem.ref import conv_stem_ref
    key = jax.random.PRNGKey(0)
    batch = 2
    x = jax.random.randint(key, (batch, 32, 32, 3), 0, 256,
                           jnp.int32).astype(jnp.uint8)
    w = jax.random.randint(jax.random.fold_in(key, 1), (3, 3, 3, 16),
                           -128, 128, jnp.int32).astype(jnp.int8)
    b = jax.random.randint(jax.random.fold_in(key, 2), (16,), -100, 100,
                           jnp.int32)
    ref = np.asarray(conv_stem_ref(x, w, b, shift=7))
    spaces = tspace.model_space(R.RESNET8, batch=batch)
    assert spaces["stem"]
    for c in spaces["stem"]:
        got = np.asarray(conv_stem_op(x, w, b, shift=7, config=c))
        np.testing.assert_array_equal(got, ref, err_msg=c.describe())


def test_every_enumerated_block_config_bitexact_vs_ref():
    """Same contract for the fused residual block, covering the identity
    (block0) and downsample (block1) shapes of the small ResNet8 graph."""
    from repro.kernels.resblock_fused.ops import resblock_fused_op
    from repro.kernels.resblock_fused.ref import resblock_ref
    key = jax.random.PRNGKey(3)
    batch = 2
    spaces = tspace.model_space(R.RESNET8, batch=batch)
    layers = {l.name: l for l in dataflow.resnet8_layers()}
    for i in (0, 1):                      # identity block, downsample block
        l0 = layers[f"c{i}_0"]
        ds = f"ds{i}" in layers
        x = jax.random.randint(jax.random.fold_in(key, i),
                               (batch, l0.ih, l0.iw, l0.ich), 0, 256,
                               jnp.int32).astype(jnp.uint8)
        w0 = jax.random.randint(jax.random.fold_in(key, 10 + i),
                                (3, 3, l0.ich, l0.och), -128, 128,
                                jnp.int32).astype(jnp.int8)
        w1 = jax.random.randint(jax.random.fold_in(key, 20 + i),
                                (3, 3, l0.och, l0.och), -128, 128,
                                jnp.int32).astype(jnp.int8)
        bz = jnp.zeros((l0.och,), jnp.int32)
        wd = bd = None
        if ds:
            wd = jax.random.randint(jax.random.fold_in(key, 30 + i),
                                    (1, 1, l0.ich, l0.och), -128, 128,
                                    jnp.int32).astype(jnp.int8)
            bd = bz
        kw = dict(stride=l0.stride, shift0=8, shift1=8,
                  skip_shift=-2 if ds else 3)
        ref = np.asarray(resblock_ref(x, w0, bz, w1, bz, wd, bd, **kw))
        assert spaces[f"block{i}"]
        for c in spaces[f"block{i}"]:
            got = np.asarray(
                resblock_fused_op(x, w0, bz, w1, bz, wd, bd, config=c, **kw))
            np.testing.assert_array_equal(got, ref, err_msg=c.describe())


# ---------------------------------------------------------------------------
# cost model
# ---------------------------------------------------------------------------


def test_cost_model_ranks_fused_block_cheaper_than_unfused():
    """The sanity pin of the whole analytic stage: in modeled HBM traffic
    (and modeled time) the fused residual kernel must beat the unfused
    dataflow at every ResNet8/20 block shape."""
    for layers in (dataflow.resnet8_layers(), dataflow.resnet20_layers()):
        by = {l.name: l for l in layers}
        i = 0
        while f"c{i}_0" in by:
            l0, ds = by[f"c{i}_0"], f"ds{i}" in by
            c = KernelConfig(batch_tile=1)
            fused = tcost.block_cost(l0, 8, c, downsample=ds, fused=True)
            unfused = tcost.block_cost(l0, 8, c, downsample=ds, fused=False)
            assert fused.hbm_bytes < unfused.hbm_bytes, l0.name
            assert fused.modeled_s < unfused.modeled_s, l0.name
            assert fused.arithmetic_intensity > unfused.arithmetic_intensity
            i += 1


def test_cost_model_rewards_batch_tiling():
    """Weight re-fetch traffic shrinks as batch_tile grows; the activation
    term is tiling-invariant."""
    layer = dataflow.resnet8_layers()[0]
    costs = [tcost.stem_cost(layer, 8, KernelConfig(batch_tile=bt))
             for bt in (1, 2, 4, 8)]
    hbm = [c.hbm_bytes for c in costs]
    assert hbm == sorted(hbm, reverse=True) and hbm[0] > hbm[-1]
    assert costs[0].grid_steps > costs[-1].grid_steps


def test_joint_candidates_dedup_and_always_include_default():
    spaces = tspace.model_space(R.RESNET8, batch=4)
    ranked = T.rank_spaces(R.RESNET8, 4, spaces)
    cands = T.joint_candidates(ranked, top_k=3)
    default = {t: KernelConfig() for t in ranked}
    assert default in cands
    assert len({json.dumps({t: c.to_dict() for t, c in sorted(x.items())})
                for x in cands}) == len(cands)
    # analytic best comes first and is the per-task argmin of modeled cost
    best = cands[0]
    for task, lst in ranked.items():
        assert best[task] == lst[0]


# ---------------------------------------------------------------------------
# search + compile integration
# ---------------------------------------------------------------------------


def test_annotate_tuning_flows_into_the_plan():
    from repro import compile as C
    g = C.optimized_graph(R.RESNET8)
    tuning = {"stem": KernelConfig(batch_tile=2, cout_block=8),
              "block1": {"batch_tile": 4}}          # dict form (cache load)
    C.annotate_tuning(g, tuning)
    plan = C.plan_model(g)
    assert plan.stem.config == KernelConfig(batch_tile=2, cout_block=8)
    assert plan.blocks[0].config is None            # untouched task
    assert plan.blocks[1].config == KernelConfig(batch_tile=4)


def test_compile_model_normalizes_cache_style_dict_tuning():
    """The documented raw-dict tune form ({'task': {'knob': v}}) must land in
    CompiledModel.tuning as KernelConfig — stats() renders it."""
    from repro.compile import compile_model
    qp = _qparams(R.RESNET8, seed=0)
    cm = compile_model(R.RESNET8, qp, backend="lax-int", batch_sizes=(2,),
                       tune={"stem": {"batch_tile": 2},
                             "block0": KernelConfig(batch_tile=2)})
    assert cm.tuning["stem"] == KernelConfig(batch_tile=2)
    assert cm.stats()["tuning"]["stem"] == {"batch_tile": 2}


def test_search_analytic_only_skips_device_timing(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_TUNE_CACHE", str(tmp_path / "t.json"))
    qp = _qparams(R.RESNET8, seed=0)
    res = T.search(R.RESNET8, qp, batch=4, device=False, validate=False)
    assert res.source == "analytic" and res.timings_us == {}
    assert set(res.tuning) == {"stem", "block0", "block1", "block2"}
    assert res.space_size > 1 and res.candidates >= 2
    assert set(res.modeled) == set(res.tuning)
    # second search is a cache hit with the identical assignment
    res2 = T.search(R.RESNET8, qp, batch=4, device=False, validate=False)
    assert res2.source == "cache" and res2.tuning == res.tuning
    # a different batch bucket is a different tuning problem
    assert T.model_key(R.RESNET8, 4, "pallas") != \
        T.model_key(R.RESNET8, 8, "pallas")


@pytest.mark.slow
def test_tuned_compile_bitexact_on_all_backends(tmp_path, monkeypatch):
    """Acceptance: the searched config is bit-exact with the default path on
    every integer backend (pallas tuned == pallas default == lax-int)."""
    from repro.compile import compile_model
    monkeypatch.setenv("REPRO_TUNE_CACHE", str(tmp_path / "t.json"))
    cfg = R.RESNET8
    qp = _qparams(cfg, seed=2)
    imgs = jax.random.uniform(jax.random.PRNGKey(0), (4, 32, 32, 3),
                              minval=0.0, maxval=0.999)
    res = T.search(cfg, qp, batch=4, device=False, validate=False)
    cm_t = compile_model(cfg, qp, backend="pallas", batch_sizes=(4,),
                         tune=res)                   # TuneResult form
    cm_d = compile_model(cfg, qp, backend="pallas", batch_sizes=(4,))
    cm_i = compile_model(cfg, qp, backend="lax-int", batch_sizes=(4,),
                         tune=res.tuning)            # dict form: no-op knobs
    out_t = np.asarray(cm_t(imgs))
    np.testing.assert_array_equal(out_t, np.asarray(cm_d(imgs)))
    np.testing.assert_array_equal(out_t, np.asarray(cm_i(imgs)))
    assert cm_t.stats()["tuning"] is not None
    assert cm_d.stats()["tuning"] is None


def test_compile_model_rejects_bad_tune_argument():
    from repro.compile import compile_model
    qp = _qparams(R.RESNET8, seed=0)
    with pytest.raises(ValueError, match="tune"):
        compile_model(R.RESNET8, qp, backend="lax-int", batch_sizes=(2,),
                      tune="magic")
    with pytest.raises(TypeError):
        compile_model(R.RESNET8, qp, backend="lax-int", batch_sizes=(2,),
                      tune=42)


# ---------------------------------------------------------------------------
# benchmarks/roofline.py _key regression
# ---------------------------------------------------------------------------


def test_roofline_sorts_unknown_archs_last_instead_of_crashing():
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                    "benchmarks"))
    try:
        import roofline
    finally:
        sys.path.pop(0)
    rows = [
        dict(arch="resnet8", shape="serve_b4", skipped=True),
        dict(arch="gemma-2b", shape="train_4k", skipped=True),
        dict(arch="resnet20", shape="serve_b4", skipped=True),
        dict(arch="zamba2-7b", shape="decode_32k", skipped=True),
    ]
    ordered = sorted(rows, key=roofline._key)        # must not raise
    assert [r["arch"] for r in ordered] == \
        ["gemma-2b", "zamba2-7b", "resnet20", "resnet8"]
    out = roofline.table(rows)                       # renders every row
    assert "resnet8" in out and "resnet20" in out
