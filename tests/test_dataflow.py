"""Dataflow buffer model + analytic roofline sanity (hypothesis sweeps)."""
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.configs import base as cb
from repro.core import dataflow as df
from repro.launch.analytic import Cell, analytic_terms


@given(st.integers(4, 64), st.integers(1, 64), st.sampled_from([1, 3, 5]),
       st.sampled_from([1, 3, 5]))
@settings(max_examples=40, deadline=None)
def test_window_buffer_invariants(iw, ich, fh, fw):
    b1 = df.window_buffer_size(iw, ich, fh, fw, ow_par=1)
    b2 = df.window_buffer_size(iw, ich, fh, fw, ow_par=2)
    assert b2 - b1 == ich                       # eq.17 vs eq.16: +1 column
    assert sum(df.fifo_partition(iw, ich, fh, fw)) == b1


@given(st.integers(8, 64), st.integers(1, 64))
@settings(max_examples=30, deadline=None)
def test_skip_ratio_half_when_iw_ich_conserved(iw, ich):
    """eq. 23: R_sc ~ 0.5 whenever iw*ich is conserved across the block
    (true for every ResNet8/20 block)."""
    r = df.skip_buffer_ratio(iw, ich, 3, 3, iw, ich, 3, 3)
    assert 0.4 < r < 0.6
    r2 = df.skip_buffer_ratio(iw, ich, 3, 3, iw // 2, ich * 2, 3, 3)
    assert 0.4 < r2 < 0.6


def test_hbm_model_monotone_in_fusion():
    for ds in (False, True):
        f = df.residual_block_hbm_bytes(32, 32, 16, 32, fused=True,
                                        downsample=ds, stride=2 if ds else 1)
        u = df.residual_block_hbm_bytes(32, 32, 16, 32, fused=False,
                                        downsample=ds, stride=2 if ds else 1)
        assert u > 2 * f


@pytest.mark.parametrize("arch,shape", [
    ("gemma-2b", "train_4k"), ("mixtral-8x22b", "decode_32k"),
    ("falcon-mamba-7b", "long_500k"), ("deepseek-v3-671b", "prefill_32k"),
    ("zamba2-7b", "train_4k"), ("whisper-large-v3", "prefill_32k"),
])
def test_analytic_terms_positive_and_sane(arch, shape):
    cfg = cb.get_config(arch)
    cell = Cell(cfg=cfg, shape=cb.SHAPES[shape], chips=256, tp=16, fsdp=16,
                grad_accum=8)
    t = analytic_terms(cell)
    assert t["an_compute_s"] > 0 and t["an_bytes_per_device"] > 0
    assert 0 < (t["an_mfu"] or 1) <= 1.0
    # useful-flops ratio is bounded: executed >= 0.1x model, <= ~1.1x
    assert 0.05 < t["an_useful_ratio"] < 1.2


def test_train_flops_scale_with_tokens():
    cfg = cb.get_config("llama3.2-3b")
    t1 = analytic_terms(Cell(cfg=cfg, shape=cb.SHAPES["train_4k"], chips=256,
                             tp=16, fsdp=16))
    big = cb.ShapeSpec("x", 4096, 512, "train")
    t2 = analytic_terms(Cell(cfg=cfg, shape=big, chips=256, tp=16, fsdp=16))
    np.testing.assert_allclose(t2["an_flops_per_device"],
                               2 * t1["an_flops_per_device"], rtol=1e-6)


# ---- chain-level formulas (block-chain streaming megakernel) --------------

def _shapes(arch_blocks):
    return df.resnet_block_shapes(arch_blocks)


@pytest.mark.parametrize("blocks_per_stage", [1, 3])
@pytest.mark.parametrize("batch,batch_tile", [(1, 1), (4, 1), (4, 4), (8, 2)])
def test_chain_hbm_identity(blocks_per_stage, batch, batch_tile):
    """The pinned identity: chain HBM traffic == sum of per-block traffic
    minus the saved interior boundary round trips.  Fusion removes interior
    activation movement and NOTHING else — weight traffic is conserved."""
    shapes = _shapes(blocks_per_stage)
    per_block = sum(df.resblock_task_hbm_bytes(
        s.h, s.w, s.ich, s.och, batch, batch_tile,
        downsample=s.downsample, stride=s.stride) for s in shapes)
    chain = df.chain_task_hbm_bytes(shapes, batch, batch_tile)
    saved = df.chain_saved_hbm_bytes(shapes, batch)
    assert chain == per_block - saved
    assert saved > 0
    assert chain < per_block


@pytest.mark.parametrize("blocks_per_stage", [1, 3])
def test_chain_saved_grows_with_chain_length(blocks_per_stage):
    """Every extra link saves its boundary: savings are strictly monotone in
    chain length, and a singleton chain saves nothing."""
    shapes = _shapes(blocks_per_stage)
    assert df.chain_saved_hbm_bytes(shapes[:1], 4) == 0
    prev = 0
    for k in range(2, len(shapes) + 1):
        cur = df.chain_saved_hbm_bytes(shapes[:k], 4)
        assert cur > prev
        prev = cur


def test_chain_vmem_monotone_in_links_and_tile():
    """Pinning more weights or widening the batch tile can only grow the
    footprint — the planner's greedy extension relies on this."""
    shapes = _shapes(3)
    for k in range(1, len(shapes)):
        assert df.chain_task_vmem_bytes(shapes[:k + 1], 1) > \
            df.chain_task_vmem_bytes(shapes[:k], 1)
    assert df.chain_task_vmem_bytes(shapes, 4) > \
        df.chain_task_vmem_bytes(shapes, 1)
    # fusing the stem trades the 16-channel boundary input tile for the raw
    # 3-channel image — the same lane-padded VMEM tile — and adds the stem
    # filter+bias; the stem working set is dominated by the first block's,
    # so the net delta is exactly the pinned stem filter
    with_stem = df.chain_task_vmem_bytes(shapes, 1, stem_och=16)
    without = df.chain_task_vmem_bytes(shapes, 1)
    assert df.tiled_bytes((34, 34, 3), 1) == df.tiled_bytes((34, 34, 16), 1)
    stem_wts = 2 * (df.tiled_bytes((3, 3, 3, 16), 1)
                    + df.tiled_bytes((16,), 4))
    assert with_stem - without == stem_wts


def test_over_budget_chain_rejected_by_tune_space():
    """tune.space.chain_space returns no legal tiling once the budget is
    below the chain's bt=1 footprint, and chain_cut_points then cuts."""
    from repro.tune import space as tspace
    shapes = _shapes(3)
    need = df.chain_task_vmem_bytes(shapes, 1)
    assert tspace.chain_space(shapes, 4, vmem_budget=need) != []
    assert tspace.chain_space(shapes, 4, vmem_budget=need - 1) == []
    cuts = tspace.chain_cut_points(shapes, 1, vmem_budget=need - 1)
    assert len(cuts) > 1                      # forced to cut somewhere
    assert [i for run in cuts for i in run] == list(range(len(shapes)))
    # tiny budget: every block becomes a singleton fallback chain
    singles = tspace.chain_cut_points(shapes, 1, vmem_budget=1)
    assert singles == [[i] for i in range(len(shapes))]


def test_default_budget_fuses_whole_cifar_models():
    """At the real VMEM budget both CIFAR ResNets chain end to end, stem
    included — the partition the pallas-stream backend ships by default."""
    from repro.tune import space as tspace
    for bps in (1, 3):
        shapes = _shapes(bps)
        cuts = tspace.chain_cut_points(shapes, 1, stem_och=16)
        assert cuts == [list(range(len(shapes)))]
        assert tspace.chain_space(shapes, 1, stem_och=16) != []
