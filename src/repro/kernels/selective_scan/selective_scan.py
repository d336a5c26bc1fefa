"""Mamba1 selective-scan Pallas kernel (falcon-mamba hot loop).

The recurrence h_t = exp(dt_t A) h_{t-1} + dt_t B_t x_t is sequential in t but
parallel over (batch, d_inner).  TPU mapping: grid over (B, d_inner/bd); each
kernel instance keeps its (bd, N) state slice in VMEM/VREGs and walks the
whole sequence with a fori_loop, writing y_t as it goes — the feature map
streams through VMEM exactly once (depth-first execution, the paper's
streaming discipline applied to an SSM).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(u_ref, dt_ref, a_ref, b_ref, c_ref, h0_ref, y_ref, hout_ref, *,
            seq_len):
    A = a_ref[...]                   # (bd, N)
    h = h0_ref[0]                    # (bd, N)

    def step(t, h):
        # t is a traced loop index: a size-1 pl.ds row of each block
        u = u_ref[0, pl.ds(t, 1), :][0]              # (bd,)
        dt = dt_ref[0, pl.ds(t, 1), :][0]            # (bd,)
        Bt = b_ref[0, pl.ds(t, 1), :][0]             # (N,)
        Ct = c_ref[0, pl.ds(t, 1), :][0]             # (N,)
        a = jnp.exp(dt[:, None] * A)
        h = a * h + (dt * u)[:, None] * Bt[None, :]
        y = jnp.sum(h * Ct[None, :], axis=-1)      # (bd,)
        y_ref[0, pl.ds(t, 1), :] = y[None, :]
        return h

    h = jax.lax.fori_loop(0, seq_len, step, h)
    hout_ref[0] = h


def selective_scan(u, dt, A, Bc, Cc, h0, *, bd=128, interpret=False):
    """u, dt: (B,S,di) f32; A: (di,N); Bc, Cc: (B,S,N); h0: (B,di,N).
    Returns (y: (B,S,di), h_last: (B,di,N)).  D-term and gating live outside."""
    from repro.tune.config import largest_divisor_leq

    B, S, di = u.shape
    N = A.shape[1]
    bd = largest_divisor_leq(di, bd)   # any tuned bd stays grid-legal
    grid = (B, di // bd)
    y, h_last = pl.pallas_call(
        functools.partial(_kernel, seq_len=S),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, S, bd), lambda b, d: (b, 0, d)),
            pl.BlockSpec((1, S, bd), lambda b, d: (b, 0, d)),
            pl.BlockSpec((bd, N), lambda b, d: (d, 0)),
            pl.BlockSpec((1, S, N), lambda b, d: (b, 0, 0)),
            pl.BlockSpec((1, S, N), lambda b, d: (b, 0, 0)),
            pl.BlockSpec((1, bd, N), lambda b, d: (b, d, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, S, bd), lambda b, d: (b, 0, d)),
            pl.BlockSpec((1, bd, N), lambda b, d: (b, d, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, S, di), jnp.float32),
            jax.ShapeDtypeStruct((B, di, N), jnp.float32),
        ],
        interpret=interpret,
    )(u, dt, A, Bc, Cc, h0)
    return y, h_last
