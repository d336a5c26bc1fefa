"""Compile the main-path Pallas kernels for a described TPU v5e chip.

Nothing runs: each kernel is lowered with ``interpret=False`` and compiled
by the TPU compiler for a ``v5e:2x2`` topology that is described, not
attached, so Mosaic's rules (MXU operand dtypes, strided loads, tiling,
scoped VMEM) are checked here at no chip time.  The conv kernels compile at
ResNet20's shapes (widths 16/32/64, 32x32 input, batch bucket 32), each at
the largest batch tile the tuner admits and the chain megakernel at the
tile the ``pallas-stream`` planner picks.  Each of them compiles with its
scoped-VMEM limit set to what ``core.dataflow`` models for that call, which
checks that the model is an upper bound of what the compiler allocates.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and the test workers all import this
file.  The persistent compile cache is off around these compiles (a
compile for a described chip cannot be read back without one).
"""
from functools import partial

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.compile import lowering
from repro.core import dataflow
from repro.models import resnet as R
from repro.tune import space as tspace

N = 32                                    # the largest serving bucket
u8, i8, i32, f32 = jnp.uint8, jnp.int8, jnp.int32, jnp.float32


@pytest.fixture(scope="module")
def one_chip():
    import os

    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:               # no TPU compiler in this install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture
def vmem_limit(monkeypatch):
    """``vmem_limit(nbytes)`` compiles every Pallas kernel of the test with
    a scoped-VMEM limit of ``nbytes`` instead of Mosaic's default."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    pallas_call = pl.pallas_call

    def set_limit(nbytes: int) -> None:
        def limited(*args, **kwargs):
            kwargs["compiler_params"] = pltpu.CompilerParams(
                vmem_limit_bytes=nbytes)
            return pallas_call(*args, **kwargs)
        monkeypatch.setattr(pl, "pallas_call", limited)
    return set_limit


def _compile(fn, sharding, *shapes):
    """Compile ``fn`` for the described chip; shapes are (shape, dtype)
    pairs or tuples of them.  Returns the compiled HLO text."""
    def spec(s):
        shape, dtype = s if len(s) == 2 else (None, None)
        if isinstance(shape, tuple) and all(isinstance(d, int) for d in shape):
            return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
        return tuple(spec(x) for x in s)
    return jax.jit(fn).lower(*(spec(s) for s in shapes)).compile().as_text()


def _block_shapes(b: dataflow.BlockShape):
    ws = [((3, 3, b.ich, b.och), i8), ((b.och,), i32),
          ((3, 3, b.och, b.och), i8), ((b.och,), i32)]
    if b.downsample:
        ws += [((1, 1, b.ich, b.och), i8), ((b.och,), i32)]
    return ws


R20 = dataflow.resnet_block_shapes(R.RESNET20.blocks_per_stage,
                                   R.RESNET20.base_width, R.RESNET20.img)


STEM = dataflow.resnet_layers(R.RESNET20.blocks_per_stage,
                              R.RESNET20.base_width, R.RESNET20.img)[0]


def _stem(**kw):
    from repro.kernels.conv_stem.conv_stem import conv_stem
    return partial(conv_stem, shift=7, interpret=False, **kw)


STEM_SHAPES = (((N, 32, 32, 3), u8), ((3, 3, 3, 16), i8), ((16,), i32))


def test_conv_stem_compiles_for_v5e(one_chip, vmem_limit):
    c = max(tspace.stem_space(STEM, N), key=lambda c: c.batch_tile)
    vmem_limit(dataflow.conv_task_vmem_bytes(STEM, c.batch_tile,
                                             c.cout_block))
    hlo = _compile(_stem(batch_tile=c.batch_tile, cout_block=c.cout_block),
                   one_chip, *STEM_SHAPES)
    assert "tpu_custom_call" in hlo


def test_vmem_limit_is_enforced(one_chip, vmem_limit):
    """The limit the compile tests set binds: 64 KiB cannot hold the stem's
    input tile, so its compile fails."""
    vmem_limit(64 * 2**10)
    with pytest.raises(Exception, match="(?i)vmem|scoped"):
        _compile(_stem(), one_chip, *STEM_SHAPES)


@pytest.mark.parametrize("index", [0, 3, 6], ids=["s1", "s2_ds16", "s2_ds32"])
def test_resblock_fused_compiles_for_v5e(one_chip, vmem_limit, index):
    """Stride-1 identity block and both stride-2 blocks with the fused 1x1
    downsample."""
    from repro.kernels.resblock_fused.resblock_fused import resblock_fused
    b = R20[index]
    layer0 = dataflow.ConvLayer("c0", b.ich, b.h, b.w, b.och, b.oh, b.ow,
                                stride=b.stride)
    bt = max(c.batch_tile for c in tspace.block_space(
        layer0, N, downsample=b.downsample))
    vmem_limit(dataflow.resblock_task_vmem_bytes(
        b.h, b.w, b.ich, b.och, bt, downsample=b.downsample,
        stride=b.stride))
    fn = partial(resblock_fused, stride=b.stride, shift0=7, shift1=7,
                 skip_shift=-1 if b.downsample else 1, batch_tile=bt,
                 interpret=False)
    hlo = _compile(fn, one_chip, ((N, b.h, b.w, b.ich), u8),
                   *_block_shapes(b))
    assert "tpu_custom_call" in hlo


def test_block_chain_compiles_at_planned_tile_for_v5e(one_chip, vmem_limit):
    """The pallas-stream plan for ResNet20 at bucket 32: chain cuts and the
    largest batch tile the VMEM model admits must compile, within the VMEM
    the model counts for it."""
    from repro.kernels.megakernel.megakernel import ChainBlockSpec, block_chain
    cfg = R.RESNET20
    chains = lowering.plan_chains(
        lowering.plan_model(lowering.optimized_graph(cfg)), cfg)
    assert [len(c.blocks) for c in chains] == [len(R20)]   # one whole chain
    chain, = chains
    legal = tspace.chain_space(R20, N, stem_och=cfg.base_width)
    bt = max(c.batch_tile for c in legal)
    assert bt > 1
    vmem_limit(dataflow.chain_task_vmem_bytes(R20, bt,
                                              stem_och=cfg.base_width))
    specs = tuple(ChainBlockSpec(stride=b.stride, has_ds=b.downsample,
                                 shift0=7, shift1=7, skip_shift=0)
                  for b in R20)

    def fn(x, blocks, stem):
        return block_chain(x, blocks, specs=specs, stem=stem, stem_shift=7,
                           batch_tile=bt, interpret=False)

    hlo = _compile(fn, one_chip, ((N, 32, 32, 3), u8),
                   tuple(tuple(_block_shapes(b)) for b in R20),
                   (((3, 3, 3, 16), i8), ((16,), i32)))
    assert chain.stem is not None and "tpu_custom_call" in hlo


def test_matmul_int8_compiles_for_v5e(one_chip):
    from repro.kernels.matmul_int8.matmul_int8 import matmul_int8
    hlo = _compile(partial(matmul_int8, interpret=False), one_chip,
                   ((256, 2048), i8), ((2048, 2048), i8), ((256, 2048), i32))
    assert "tpu_custom_call" in hlo


def test_flash_attention_compiles_for_v5e(one_chip):
    from repro.kernels.flash_attention.flash_attention import flash_attention
    hlo = _compile(partial(flash_attention, causal=True, interpret=False),
                   one_chip, ((16, 256, 256), f32), ((16, 256, 256), f32),
                   ((16, 256, 256), f32))
    assert "tpu_custom_call" in hlo


def test_selective_scan_compiles_for_v5e(one_chip):
    from repro.kernels.selective_scan.selective_scan import selective_scan
    B, S, di, n = 2, 64, 512, 16
    hlo = _compile(partial(selective_scan, interpret=False), one_chip,
                   ((B, S, di), f32), ((B, S, di), f32), ((di, n), f32),
                   ((B, S, n), f32), ((B, S, n), f32), ((B, di, n), f32))
    assert "tpu_custom_call" in hlo
