"""Block-chain streaming megakernel — cross-layer fusion of the paper's
on-chip dataflow (temporal reuse + loop merging, §III-D) pushed past a single
residual block.

``resblock_fused`` keeps one block's intermediates in VMEM but still writes
the block *output* to HBM, where the next kernel re-reads (and re-pads) it.
This kernel fuses a **run of consecutive residual blocks** — optionally with
the stem conv at its head — into ONE ``pallas_call``: the running activation
stays in VMEM from the chain's input to its output, each inter-block boundary
saving the write+read round trip that ``core.dataflow.chain_saved_hbm_bytes``
quantifies.  This is the TPU analogue of the paper's layer-to-layer streaming,
where feature maps flow accelerator-stage to accelerator-stage without ever
visiting DRAM.

Chain legality:

* any run of *consecutive* graph blocks is fusable — stride-2 entries may sit
  anywhere in the chain (the per-block streaming body handles its own stride
  and the inter-block pad is applied in VMEM with the successor's SAME
  convention), so chain cut points are purely a VMEM-budget decision;
* every chain weight (both 3x3 filters + optional 1x1 downsample per block,
  plus the stem filter when fused) is **pinned in VMEM** for the kernel's
  lifetime via constant-index BlockSpecs — Pallas fetches each exactly once
  and keeps it resident across all batch-grid steps.  A chain whose pinned
  weights + working set exceed the VMEM budget is *rejected by the planner*
  (``core.dataflow.chain_task_vmem_bytes`` / ``tune.space.chain_space``) and
  cut shorter — down to single-block chains, which the ``pallas-stream``
  backend lowers through plain ``resblock_fused``;
* the batch-grid input/output tiles keep grid-varying index maps, so Pallas's
  automatic pipelining double-buffers the HBM activation traffic that remains.

Per-block arithmetic is ``kernels.common.block_body`` — the datapath
``resblock_fused`` runs too — so the result is bit-exact with the per-block
pipeline by construction (asserted over every legal partition in the
conformance suite).  Each filter tap is ONE ``(bt*oh*ow, Cin) x (Cin, Cout)``
s8 dot across every image of the tile.  Stride-2 links stage their padded
input in an int32 VMEM scratch (one per distinct shape) for the strided tap
loads.  In interpret mode (CPU emulation) each tap contraction additionally
runs through the exact float32 fast path of :func:`_dot_i32` — bit-identical
below the statically-guarded 2^24 bound, but on XLA:CPU's vectorized GEMM
instead of its scalar integer loops.

Tiling knob (``repro.tune.KernelConfig``): ``batch_tile`` images per grid
step — the ``pallas-stream`` backend defaults it to the *largest* VMEM-legal
tile (``tune.space.chain_space``) since pinned weights make bigger tiles
free.  ``cout_block`` stays structurally illegal for the same reason as
``resblock_fused`` — every block consumes all of its predecessor's channels.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import (
    ACT_OFFSET, block_body, conv_taps, dot_u8s8, offset_bias, pad_same,
    requant)


@dataclasses.dataclass(frozen=True)
class ChainBlockSpec:
    """Static per-block schedule of one chain link (hashable: jit-static).
    Shapes are derived from the weight operands at trace time; only the
    dataflow decisions live here."""
    stride: int
    has_ds: bool
    shift0: int
    shift1: int
    skip_shift: int


# Longest offset-s8 contraction whose dot is exact in float32: every partial
# sum is an integer of magnitude at most rows * 128 * 128, which must not
# pass 2^24 (f32 integer-exactness bound).  1024 — far above the widest
# chain link (Cin = 64).
F32_EXACT_ROWS = (1 << 24) // (ACT_OFFSET * ACT_OFFSET)


def _dot_i32(rows, wm, fast_emul):
    """``(M, K)`` u8-valued rows x ``(K, Cout)`` s8 -> ``(M, Cout)`` int32
    ``(rows - ACT_OFFSET) @ wm``, exact.

    The TPU path is :func:`~repro.kernels.common.dot_u8s8`, one s8 MXU dot.
    Under ``fast_emul`` (interpret mode, i.e. CPU emulation) the SAME
    contraction runs in float32 — XLA:CPU lowers integer GEMMs to scalar
    loops but float GEMMs to the vectorized Eigen path.  Exactness is not
    probabilistic: every partial sum is an integer within 2^24 (guarded by
    :data:`F32_EXACT_ROWS` at trace time), where float32 arithmetic is
    exact, so the rounded-back int32 result is bit-identical."""
    rows = rows.astype(jnp.int32)
    if fast_emul and rows.shape[1] <= F32_EXACT_ROWS:
        return jax.lax.dot((rows - ACT_OFFSET).astype(jnp.float32),
                           wm.astype(jnp.float32)).astype(jnp.int32)
    return dot_u8s8(rows, wm)


def _kernel(*refs, specs: Tuple[ChainBlockSpec, ...], stem_shift, n_in,
            stage_of, fast_emul):
    """refs = (x, [stem_w, stem_b,] per-block weights..., out, stages...).
    The per-block weight refs are (w0, b0, w1, b1[, wd, bd]) — downsample
    operands present only for ``has_ds`` links (the static specs drive the
    unflattening, so identity blocks ship no zero tensors).  ``stage_of[j]``
    indexes link j's int32 staging scratch (stride-2 links only)."""
    x_ref, o_ref, stages = refs[0], refs[n_in], refs[n_in + 1:]
    it = iter(refs[1:n_in])
    dot = functools.partial(_dot_i32, fast_emul=fast_emul)

    h = x_ref[...].astype(jnp.int32)              # (bt,Hp,Wp,C) chain input
    if stem_shift is not None:
        sw, sb = next(it)[...], next(it)[...]
        bt, hp, wp = h.shape[:3]
        acc = jnp.broadcast_to(sb, (bt, hp - 2, wp - 2, sw.shape[-1]))
        # the stem output is re-padded IN VMEM for the first block — the
        # boundary that per-kernel execution pays through HBM
        h = pad_same(requant(conv_taps(h, sw, acc, 1, dot), stem_shift),
                     specs[0].stride)
    for j, s in enumerate(specs):
        ws = [next(it)[...] for _ in range(6 if s.has_ds else 4)]
        if not s.has_ds:
            ws += [None, None]                    # identity skip: no wd/bd
        y = block_body(
            h, *ws, stride=s.stride, shift0=s.shift0, shift1=s.shift1,
            skip_shift=s.skip_shift, dot=dot,
            stage=stages[stage_of[j]] if stage_of[j] is not None else None)
        if j + 1 < len(specs):                    # inter-block VMEM re-pad
            h = pad_same(y, specs[j + 1].stride)
    o_ref[...] = y.astype(jnp.uint8)


def block_chain(x, blocks, *, specs: Tuple[ChainBlockSpec, ...],
                stem=None, stem_shift: Optional[int] = None,
                batch_tile: int = 1, interpret: bool = False):
    """x: (N,H,W,Cin) uint8 unpadded, H and W even — the image batch when
    ``stem`` is fused, else the previous kernel's activation.  ``blocks``:
    one (w0,b0,w1,b1[,wd,bd]) tuple per chain link; ``stem``: optional
    (w, b) fused at the chain head.  Returns the last block's (N,oh,ow,Cout)
    uint8 output; every intermediate activation lives and dies in VMEM."""
    assert len(blocks) == len(specs) and specs, (len(blocks), len(specs))
    N, oh, ow, _ = x.shape
    bt = N if batch_tile == 0 else batch_tile
    assert N % bt == 0, (N, bt)
    first_stride = 1 if stem is not None else specs[0].stride
    # the (0, 1) stride-2 padding matches lax SAME only for even dims
    assert first_stride == 1 or (oh % 2 == 0 and ow % 2 == 0), \
        "stride-2 chain head requires even H/W to match lax SAME padding"
    xp = pad_same(x, first_stride)

    operands = [xp]
    if stem is not None:
        assert stem_shift is not None
        operands += [stem[0], offset_bias(stem[1], stem[0])]
    else:
        assert stem_shift is None
    stage_shapes, stage_of = [], []
    for s, ws in zip(specs, blocks):
        assert len(ws) == (6 if s.has_ds else 4), (s, len(ws))
        w0, b0, w1, b1 = ws[:4]
        operands += [w0, offset_bias(b0, w0), w1, offset_bias(b1, w1)]
        if s.has_ds:
            operands += [ws[4], offset_bias(ws[5], ws[4])]
        stage = None
        if s.stride != 1:
            # one int32 staging scratch per distinct padded input shape
            shape = (bt, oh + 1, ow + 1, w0.shape[2])
            if shape not in stage_shapes:
                stage_shapes.append(shape)
            stage = stage_shapes.index(shape)
        stage_of.append(stage)
        oh, ow = oh // s.stride, ow // s.stride   # SAME conv on even dims
    cout = blocks[-1][2].shape[-1]                # w1: (3,3,Cout,Cout)

    in_specs = [pl.BlockSpec((bt,) + xp.shape[1:], lambda n: (n, 0, 0, 0))]
    # chain weights: constant index maps — fetched once, pinned in VMEM
    # across every batch-grid step (the planner guarantees they fit)
    for op in operands[1:]:
        in_specs.append(pl.BlockSpec(op.shape,
                                     lambda n, d=op.ndim: (0,) * d))
    return pl.pallas_call(
        functools.partial(_kernel, specs=specs, stem_shift=stem_shift,
                          n_in=len(operands), stage_of=tuple(stage_of),
                          fast_emul=interpret),
        grid=(N // bt,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bt, oh, ow, cout), lambda n: (n, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((N, oh, ow, cout), jnp.uint8),
        scratch_shapes=[pltpu.VMEM(s, jnp.int32) for s in stage_shapes],
        interpret=interpret,
    )(*operands)
