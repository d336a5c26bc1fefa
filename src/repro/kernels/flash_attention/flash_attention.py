"""Flash-attention Pallas kernel (online softmax, causal).

Grid: (B*H, Sq/bq).  Each instance owns one (bq, hd) query tile in VMEM and
walks the K/V sequence in (bk, hd) tiles with the usual running-max/denominator
rescaling.  Causal masking skips nothing structurally (the loop bound is
min(kv_len, (q_block+1)*bq) so fully-masked K/V tiles are never read) — this
is the kernel counterpart of the chunked-attention XLA path in models/layers.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl


def _kernel(q_ref, k_ref, v_ref, o_ref, *, bq, bk, hd, causal, kv_len,
            q_offset):
    qi = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32) * (1.0 / np.sqrt(hd))   # (bq, hd)
    m = jnp.full((bq,), -jnp.inf, jnp.float32)
    l = jnp.zeros((bq,), jnp.float32)
    acc = jnp.zeros((bq, hd), jnp.float32)
    # q rows are the LAST Sq positions of the kv sequence (decode-with-cache
    # convention): row r sits at absolute position q_offset + qi*bq + r,
    # where q_offset = Sk - Sq.  With Sq == Sk this is the usual triangle.
    q_pos = q_offset + qi * bq + jnp.arange(bq)

    nk_all = kv_len // bk

    def step(j, carry):
        m, l, acc = carry
        k = k_ref[0, pl.ds(j * bk, bk), :].astype(jnp.float32)  # (bk, hd)
        v = v_ref[0, pl.ds(j * bk, bk), :].astype(jnp.float32)
        s = q @ k.T                                           # (bq, bk)
        if causal:
            k_pos = j * bk + jnp.arange(bk)
            s = jnp.where(q_pos[:, None] >= k_pos[None, :], s, -1e30)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[:, None])
        scale = jnp.exp(m - m_new)
        l = l * scale + jnp.sum(p, axis=-1)
        acc = acc * scale[:, None] + p @ v
        return m_new, l, acc

    if causal:
        # only K/V tiles that intersect the causal triangle of this q tile
        nk = jnp.minimum(
            (q_offset + (qi + 1) * bq + bk - 1) // bk, nk_all)
    else:
        nk = nk_all
    m, l, acc = jax.lax.fori_loop(0, nk, step, (m, l, acc))
    o_ref[0] = (acc / jnp.maximum(l, 1e-30)[:, None]).astype(o_ref.dtype)


def flash_attention(q, k, v, *, causal=True, bq=128, bk=128, interpret=False):
    """q: (BH, Sq, hd), k/v: (BH, Sk, hd).  Flattened batch*heads leading dim
    (GQA head repetition handled by the wrapper).  When ``Sq < Sk`` the
    queries are the suffix of the key sequence (decode with a prefilled
    cache), so causal masking offsets q positions by ``Sk - Sq``."""
    from repro.tune.config import largest_divisor_leq

    BH, Sq, hd = q.shape
    Sk = k.shape[1]
    if causal and Sq > Sk:
        raise ValueError(
            f"causal attention needs Sq <= Sk (q is the kv suffix); "
            f"got Sq={Sq} Sk={Sk}")
    # snap tiles to divisors so any tuned (bq, bk) stays grid-legal
    bq = largest_divisor_leq(Sq, bq)
    bk = largest_divisor_leq(Sk, bk)
    return pl.pallas_call(
        functools.partial(_kernel, bq=bq, bk=bk, hd=hd, causal=causal,
                          kv_len=Sk, q_offset=Sk - Sq),
        grid=(BH, Sq // bq),
        in_specs=[
            pl.BlockSpec((1, bq, hd), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, Sk, hd), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, Sk, hd), lambda b, i: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, hd), lambda b, i: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, Sq, hd), q.dtype),
        interpret=interpret,
    )(q, k, v)
