"""Fused bottleneck block Pallas kernel — the ResNet-50 block on TPU.

One kernel executes conv0 (1x1) -> ReLU/requant -> conv1 (3x3 at the
block's stride, v1.5) -> ReLU/requant -> conv2 (1x1) with the skip stream
*initializing conv2's int32 accumulator* (the paper's add-fold, Fig. 13) ->
ReLU/requant.  Both intermediate maps and the skip live in VMEM for the
kernel's lifetime; HBM sees the block input once and the block output once.

* identity block (stride 1, Cin == Cout): the skip is the block input,
  shifted onto conv2's product grid by ``skip_shift``.
* stage-entry block: the 1x1 projection at the block's stride runs *inside
  the kernel*; its int32 sum (+ bias) is aligned the same way.

The datapath is ``kernels.common``'s, with its on-chip contract: s8 offset
dots, int32 staging for strided taps (in 128-channel chunks,
:func:`~repro.kernels.common.strided_taps`), ``jax.lax`` SAME padding —
(1, 1) at stride 1, (0, 1) at stride 2 on even maps.  The 1x1 convs are one
``(bt*h*w, Cin) x (Cin, Cout)`` dot each.

Tiling: ``batch_tile`` images a grid step (grid ``(N/bt,)``); the filters
are constant-index blocks, fetched once a call.  The scoped-VMEM limit of
each call is set from ``core.dataflow.bottleneck_task_vmem_bytes``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import dataflow
from repro.core.quant import shift_align
from repro.kernels.common import (conv_taps, dot_u8s8, offset_bias, pad_same,
                                  requant, stage_chunks, stage_shape,
                                  strided_taps)


def bottleneck_body(x, w0, b0, w1, b1, w2, b2, wd, bd, *, stride, shift0,
                    shift1, shift2, skip_shift, stage_y=None, stage_x=None,
                    dot=dot_u8s8):
    """One bottleneck block on an int32 (bt, H, W, Cin) u8-valued batch
    tile ``x``; returns the (bt, H/s, W/s, Cout) int32 u8-valued output.
    Filters may be refs (each tap's slice is loaded where it is used);
    biases arrive offset-folded (:func:`offset_bias`).  At stride 2 the
    padded y0 is staged in ``stage_y`` and, with a projection, ``x`` in
    ``stage_x`` (int32 refs of :func:`stage_shape`)."""
    bt, h, w, cin = x.shape
    cm, co = b0.shape[0], b2.shape[0]
    oh, ow = h // stride, w // stride
    rows = x.reshape(bt * h * w, cin)
    # conv0 (1x1) + relu + requant
    y0 = requant(dot(rows, w0[0, 0]) + b0, shift0).reshape(bt, h, w, cm)
    # conv1 (3x3 at the stride) + relu + requant
    yp = pad_same(y0, stride)
    acc1 = jnp.broadcast_to(b1, (bt, oh, ow, cm))
    if stride == 1:
        acc1 = conv_taps(yp, w1, acc1, 1, dot)
    else:
        stage_chunks(stage_y, yp)
        acc1 = strided_taps(stage_y, w1, acc1, stride, dot)
    y1 = requant(acc1, shift1)
    # the skip stream, on conv2's product grid
    if wd is None:
        skip = shift_align(x, skip_shift)
    else:
        if stride == 1:
            accd = dot(rows, wd[0, 0]).reshape(bt, oh, ow, co)
        else:
            stage_chunks(stage_x, x)
            accd = strided_taps(stage_x, wd, jnp.zeros((bt, oh, ow, co),
                                                       jnp.int32), stride, dot)
        skip = shift_align(accd + bd, skip_shift)
    # conv2 (1x1) with add-fold: the skip initializes the accumulator
    acc2 = dot(y1.reshape(bt * oh * ow, cm), w2[0, 0]).reshape(
        bt, oh, ow, co) + b2 + skip
    return requant(acc2, shift2)


def _kernel(x_ref, w0_ref, b0_ref, w1_ref, b1_ref, w2_ref, b2_ref, *rest,
            stride, shift0, shift1, shift2, skip_shift, has_ds):
    wd_ref, bd_ref = rest[:2] if has_ds else (None, None)
    o_ref, *stage = rest[2:] if has_ds else rest
    y = bottleneck_body(
        x_ref[...].astype(jnp.int32), w0_ref, b0_ref[...], w1_ref,
        b1_ref[...], w2_ref, b2_ref[...], wd_ref,
        bd_ref[...] if has_ds else None, stride=stride, shift0=shift0,
        shift1=shift1, shift2=shift2, skip_shift=skip_shift,
        stage_y=stage[0] if stage else None,
        stage_x=stage[1] if len(stage) > 1 else None)
    o_ref[...] = y.astype(jnp.uint8)


def bottleneck_fused(x, w0, b0, w1, b1, w2, b2, wd=None, bd=None, *,
                     stride=1, shift0, shift1, shift2, skip_shift=0,
                     batch_tile=1, interpret=False, name=None):
    """x: (N,H,W,Cin) uint8 unpadded, H and W even at stride 2;
    w0: (1,1,Cin,Cm), w1: (3,3,Cm,Cm), w2: (1,1,Cm,Cout) int8; b0/b1/b2:
    (Cm,)/(Cm,)/(Cout,) int; wd: (1,1,Cin,Cout) int8 + bd: (Cout,) int for
    the fused projection (None for the identity skip).  shift0/1/2: pow2
    requant shifts (positive = right shift); skip_shift: signed alignment
    onto conv2's product grid.  ``batch_tile`` images per grid step (must
    divide N).  ``name`` names the kernel's device op.  Returns
    (N,H/s,W/s,Cout) uint8."""
    N, H, W, Cin = x.shape
    Cm, Cout = w0.shape[-1], w2.shape[-1]
    has_ds = wd is not None
    bt = batch_tile
    assert N % bt == 0, (N, bt)
    assert stride == 1 or (H % 2 == 0 and W % 2 == 0), \
        "stride-2 bottleneck requires even H/W to match lax SAME padding"
    if not has_ds:
        assert stride == 1 and Cin == Cout, "identity skip needs stride 1"
    oh, ow = H // stride, W // stride
    operands = [x, w0, offset_bias(b0, w0), w1, offset_bias(b1, w1), w2,
                offset_bias(b2, w2)]
    if has_ds:
        operands += [wd, offset_bias(bd, wd)]
    in_specs = [pl.BlockSpec((bt, H, W, Cin), lambda n: (n, 0, 0, 0))]
    in_specs += [pl.BlockSpec(op.shape, lambda n, d=op.ndim: (0,) * d)
                 for op in operands[1:]]
    stage = []
    if stride != 1:
        # y0 with its SAME (0, 1) pad, and the projection's input
        stage.append(pltpu.VMEM(stage_shape(bt, H + 1, W + 1, Cm),
                                jnp.int32))
        if has_ds:
            stage.append(pltpu.VMEM(stage_shape(bt, H, W, Cin), jnp.int32))
    vmem = dataflow.bottleneck_task_vmem_bytes(
        dataflow.BottleneckShape(H, W, Cin, Cm, Cout, stride, has_ds), bt)
    return pl.pallas_call(
        functools.partial(_kernel, stride=stride, shift0=shift0,
                          shift1=shift1, shift2=shift2,
                          skip_shift=skip_shift, has_ds=has_ds),
        grid=(N // bt,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bt, oh, ow, Cout), lambda n: (n, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((N, oh, ow, Cout), jnp.uint8),
        scratch_shapes=stage,
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=vmem),
        interpret=interpret,
        name=name,
    )(*operands)
