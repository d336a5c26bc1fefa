"""Stem conv Pallas kernel: 3x3 stride-1 conv + ReLU + pow2 requant.

First layer of the integer ResNet graph: uint8 input pixels (X_SPEC domain,
u8/255-style quantized images) x int8 folded weights -> int32 accumulator
(+ int bias at s_b = s_x + s_w), ReLU, then a rounding shift into the u8
activation domain (A_SPEC).  With resblock_fused covering every residual
block, this kernel completes Pallas coverage of the whole integer graph:
feature maps enter HBM only between kernels, exactly once each.

The input channel count is tiny (3); each grid step owns ``batch_tile``
images in VMEM and issues one ``(bt*H*W, Cin) x (Cin, cb)`` s8 MXU dot per
filter tap (``kernels.common.conv_taps``, with its on-chip contract).

Tiling knobs (``repro.tune.KernelConfig``): ``batch_tile`` images per grid
step and ``cout_block`` output channels per grid step — the software
``och_par`` unroll of the paper's §III-E.  Grid: (N/bt, Cout/cb); the weight
and bias blocks are sliced along the output-channel axis, so a grid step
only holds its own filter slice in VMEM.  Every (bt, cb) point is bit-exact
with the default (asserted per enumerated config in tests/test_tune.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.common import conv_taps, offset_bias, pad_same, requant


def _kernel(x_ref, w_ref, b_ref, o_ref, *, shift):
    bt, oh, ow, cb = o_ref.shape
    acc = jnp.broadcast_to(b_ref[...], (bt, oh, ow, cb))
    acc = conv_taps(x_ref[...].astype(jnp.int32), w_ref[...], acc)
    o_ref[...] = requant(acc, shift).astype(jnp.uint8)


def conv_stem(x, w, b, *, shift, batch_tile=1, cout_block=0, interpret=False):
    """x: (N,H,W,Cin) uint8 unpadded; w: (3,3,Cin,Cout) int8; b: (Cout,) int.
    Returns (N,H,W,Cout) uint8 post-ReLU activations (SAME padding).
    ``batch_tile`` must divide N and ``cout_block`` must divide Cout
    (0 = maximal)."""
    N, H, W, Cin = x.shape
    fh, fw, _, Cout = w.shape
    bt = N if batch_tile == 0 else batch_tile
    cb = Cout if cout_block == 0 else cout_block
    assert N % bt == 0, (N, bt)
    assert Cout % cb == 0, (Cout, cb)
    xp = pad_same(x, 1)
    Hp, Wp = xp.shape[1:3]
    return pl.pallas_call(
        functools.partial(_kernel, shift=shift),
        grid=(N // bt, Cout // cb),
        in_specs=[
            pl.BlockSpec((bt, Hp, Wp, Cin), lambda n, c: (n, 0, 0, 0)),
            pl.BlockSpec((fh, fw, Cin, cb), lambda n, c: (0, 0, 0, c)),
            pl.BlockSpec((cb,), lambda n, c: (c,)),
        ],
        out_specs=pl.BlockSpec((bt, H, W, cb), lambda n, c: (n, 0, 0, c)),
        out_shape=jax.ShapeDtypeStruct((N, H, W, Cout), jnp.uint8),
        interpret=interpret,
    )(xp, w, offset_bias(b, w))
