"""Strided stem + max pool Pallas kernel — ResNet-50's first layer.

A k x k stride-2 conv (7x7 from 3 to 64 channels in ResNet-50) + ReLU +
pow2 requant, then the 3x3 stride-2 max pool, in one kernel: the conv's
map stays in VMEM and only the pooled map reaches HBM.

Three input channels are the worst lane layout on the chip, so the wrapper
lays the input out as patches (im2col): one row of ``k*k*Cin`` (147) u8
values an output pixel, and the conv is one ``(rows, 147) x (147, Cout)``
s8 dot (``kernels.common.dot_u8s8``).  It orders the rows by the output
pixel's phase ``(row % 2, col % 2)``, so the pool needs no strided load:
pool output (i, j) reads conv rows ``2i + dh`` and cols ``2j + dw`` for
dh, dw in 0..2 under ``jax.lax`` SAME padding (0, 1), which are phase
``(dh % 2, dw % 2)`` at ``(i + dh // 2, j + dw // 2)``.  The pad is 0,
which on a post-ReLU u8 map is the same as -inf.

Tiling: ``batch_tile`` images a grid step (grid ``(N/bt,)``).  The scoped
VMEM limit of each call is ``core.dataflow.stem_pool_task_vmem_bytes``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import dataflow
from repro.kernels.common import dot_u8s8, offset_bias, requant


def _kernel(p_ref, w_ref, b_ref, o_ref, *, shift):
    bt, q, _, co = o_ref.shape
    kk = p_ref.shape[-1]
    phases = []
    for ph in range(4):
        rows = p_ref[:, ph].astype(jnp.int32).reshape(bt * q * q, kk)
        y = requant(dot_u8s8(rows, w_ref[...]) + b_ref[...], shift)
        # SAME (0, 1) pad of the pool: one zero row and col at the end
        phases.append(jnp.pad(y.reshape(bt, q, q, co),
                              ((0, 0), (0, 1), (0, 1), (0, 0))))
    out = None
    for dh in range(3):
        for dw in range(3):
            win = phases[2 * (dh % 2) + dw % 2][
                :, dh // 2:dh // 2 + q, dw // 2:dw // 2 + q, :]
            out = win if out is None else jnp.maximum(out, win)
    o_ref[...] = out.astype(jnp.uint8)


def stem_patches(x, k: int):
    """(N, S, S, Cin) uint8 -> (N, 4, (S/4)**2, k*k*Cin) uint8: the k x k
    stride-2 SAME patches of every conv output pixel, features in (kh, kw,
    c) order (a HWIO filter reshaped), rows grouped by the pixel's phase
    (row % 2, col % 2) and row-major within a phase."""
    n, s, _, cin = x.shape
    o = s // 2
    pad = max((o - 1) * 2 + k - s, 0)
    lo = pad // 2
    xp = jnp.pad(x, ((0, 0), (lo, pad - lo), (lo, pad - lo), (0, 0)))
    cols = [xp[:, kh:kh + 2 * o:2, kw:kw + 2 * o:2, :]
            for kh in range(k) for kw in range(k)]
    p = jnp.concatenate(cols, axis=-1)                 # (n, o, o, k*k*cin)
    q = o // 2
    p = p.reshape(n, q, 2, q, 2, k * k * cin).transpose(0, 2, 4, 1, 3, 5)
    return p.reshape(n, 4, q * q, k * k * cin)


def conv_stem_pool(x, w, b, *, shift, batch_tile=1, interpret=False,
                   name=None):
    """x: (N,S,S,Cin) uint8 unpadded, S a multiple of 4; w: (k,k,Cin,Cout)
    int8; b: (Cout,) int.  The k x k stride-2 conv (SAME) + ReLU +
    requant by ``shift``, then the 3x3 stride-2 max pool (SAME).  Returns
    (N,S/4,S/4,Cout) uint8.  ``name`` names the kernel's device op."""
    N, S, _, Cin = x.shape
    k, _, _, Cout = w.shape
    assert S % 4 == 0, f"stem input side {S} is not a multiple of 4"
    bt = batch_tile
    assert N % bt == 0, (N, bt)
    q = S // 4
    kk = k * k * Cin
    patches = stem_patches(x, k)
    wm = w.reshape(kk, Cout)
    vmem = dataflow.stem_pool_task_vmem_bytes(S, Cin, Cout, k, bt)
    return pl.pallas_call(
        functools.partial(_kernel, shift=shift),
        grid=(N // bt,),
        in_specs=[pl.BlockSpec((bt, 4, q * q, kk), lambda n: (n, 0, 0, 0)),
                  pl.BlockSpec((kk, Cout), lambda n: (0, 0)),
                  pl.BlockSpec((Cout,), lambda n: (0,))],
        out_specs=pl.BlockSpec((bt, q, q, Cout), lambda n: (n, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((N, q, q, Cout), jnp.uint8),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=vmem),
        interpret=interpret,
        name=name,
    )(patches, wm, offset_bias(b, w))
