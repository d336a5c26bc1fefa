"""Fused residual block Pallas kernel — THE paper's contribution on TPU.

One kernel executes conv0(3x3) -> ReLU/requant -> conv1(3x3) with the skip
stream *initializing conv1's int32 accumulator* (add-fold, Fig. 13) ->
ReLU/requant.  The intermediate activation y0 and the skip tensor never touch
HBM: they live in VMEM for the kernel's lifetime — the TPU analogue of the
paper's 2x skip-buffer reduction (eq. 23).  HBM traffic per block drops from
~8 tensor movements (unfused dataflow) to 2 (read x, write out);
core.dataflow.residual_block_hbm_bytes() quantifies it and
benchmarks/run.py reports the measured ratio.

Covers every block shape of ResNet8/20:

* stride-1 identity block — skip = x, rescaled into conv1's product domain by
  ``skip_shift`` (signed: left shift or rounding right shift).
* stride-2 downsample block — conv0 runs strided and the 1x1 downsample conv
  on the skip path executes *inside the same kernel*: its int32 accumulator is
  shift-aligned from the ds product domain into conv1's product domain and
  folded into conv1's accumulator.  The downsampled skip never exists in HBM.

The arithmetic is ``kernels.common.block_body`` — the datapath shared with
the block-chain megakernel, with its on-chip contract (s8 offset dots, int32
staging for strided taps).  Padding follows ``jax.lax`` SAME: (1, 1) at
stride 1, (0, 1) at stride 2.

Tiling knob (``repro.tune.KernelConfig``): ``batch_tile`` images per grid
step — larger tiles amortize the per-step weight reload and feed each tap
dot ``bt`` images' rows.  Grid: (N/bt,).  Channel blocking is structurally
illegal here: conv1 consumes *all* of conv0's output channels, so splitting
Cout across grid steps would force the intermediate y0 back through HBM —
exactly the traffic the fusion removes.  ``tune.space`` therefore never
enumerates ``cout_block`` for this kernel.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import block_body, offset_bias, pad_same


def _kernel(x_ref, w0_ref, b0_ref, w1_ref, b1_ref, *rest, stride, shift0,
            shift1, skip_shift, has_ds):
    wd_ref, bd_ref = rest[:2] if has_ds else (None, None)
    o_ref, *stage = rest[2:] if has_ds else rest
    y = block_body(
        x_ref[...].astype(jnp.int32), w0_ref[...], b0_ref[...], w1_ref[...],
        b1_ref[...], wd_ref[...] if has_ds else None,
        bd_ref[...] if has_ds else None, stride=stride, shift0=shift0,
        shift1=shift1, skip_shift=skip_shift,
        stage=stage[0] if stage else None)
    o_ref[...] = y.astype(jnp.uint8)


def resblock_fused(x, w0, b0, w1, b1, wd=None, bd=None, *, stride=1,
                   shift0, shift1, skip_shift=0, batch_tile=1,
                   interpret=False):
    """x: (N,H,W,Cin) uint8 unpadded, H and W even; w0: (3,3,Cin,Cout) int8;
    w1: (3,3,Cout,Cout) int8; b0/b1: (Cout,) int; wd: (1,1,Cin,Cout) int8 +
    bd: (Cout,) int for the fused downsample skip (None for identity skip).
    shift0/shift1: pow2 requant shifts (positive = right shift); skip_shift:
    signed product-domain alignment shift.  ``batch_tile`` images per grid
    step (0 = whole batch, must divide N).  Returns (N,H/s,W/s,Cout) uint8."""
    N, H, W, Cin = x.shape
    Cout = w0.shape[-1]
    has_ds = wd is not None
    bt = N if batch_tile == 0 else batch_tile
    assert N % bt == 0, (N, bt)
    # the (0, 1) stride-2 padding matches lax SAME only for even dims
    assert stride == 1 or (H % 2 == 0 and W % 2 == 0), \
        "stride-2 fused block requires even H/W to match lax SAME padding"
    if not has_ds:
        assert stride == 1 and Cin == Cout, "identity skip needs stride 1"
    xp = pad_same(x, stride)
    Hp, Wp = xp.shape[1:3]
    oh, ow = H // stride, W // stride
    operands = [xp, w0, offset_bias(b0, w0), w1, offset_bias(b1, w1)]
    if has_ds:
        operands += [wd, offset_bias(bd, wd)]
    in_specs = [pl.BlockSpec((bt, Hp, Wp, Cin), lambda n: (n, 0, 0, 0))]
    in_specs += [pl.BlockSpec(op.shape, lambda n, d=op.ndim: (0,) * d)
                 for op in operands[1:]]
    stage = [pltpu.VMEM((bt, Hp, Wp, Cin), jnp.int32)] if stride != 1 else []
    return pl.pallas_call(
        functools.partial(_kernel, stride=stride, shift0=shift0,
                          shift1=shift1, skip_shift=skip_shift,
                          has_ds=has_ds),
        grid=(N // bt,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bt, oh, ow, Cout), lambda n: (n, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((N, oh, ow, Cout), jnp.uint8),
        scratch_shapes=stage,
        interpret=interpret,
    )(*operands)
