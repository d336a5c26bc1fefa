"""Pure-jnp oracles for flash attention.

Two references with distinct jobs:

  * :func:`attention_ref` — the naive softmax oracle.  Semantically exact,
    but its normalize-then-matmul order differs from the kernel's online
    softmax, so agreement is to float tolerance, never bitwise.
  * :func:`flash_attention_mirror` — the kernel's tiled arithmetic replayed
    op-for-op in plain lax (same tile walk, same running-max rescaling, same
    final ``acc / max(l, eps)`` divide).  In interpret mode identical op
    sequences produce identical floats, so this is the BIT-EXACT reference
    the ``lax-int`` backend and the conformance matrix pin against.

Both use the decode convention: when ``Sq < Sk`` the q rows are the suffix
of the key sequence (causal masking offsets q positions by ``Sk - Sq``).
"""
import jax
import jax.numpy as jnp
import numpy as np


def attention_ref(q, k, v, *, causal=True):
    BH, Sq, hd = q.shape
    Sk = k.shape[1]
    s = jnp.einsum("bqh,bkh->bqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) / np.sqrt(hd)
    if causal:
        q_pos = (Sk - Sq) + jnp.arange(Sq)
        mask = q_pos[:, None] >= jnp.arange(Sk)[None, :]
        s = jnp.where(mask[None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bqk,bkh->bqh", p, v.astype(jnp.float32)).astype(q.dtype)


def flash_attention_mirror(q, k, v, *, causal=True, bq=128, bk=128):
    """The flash kernel's arithmetic, op-for-op, without pallas: q tiles in
    a python loop (the grid dim), K/V tiles via dynamic_slice (the kernel's
    ``pl.ds`` walk), the identical online-softmax update per step."""
    BH, Sq, hd = q.shape
    Sk = k.shape[1]
    bq = min(bq, Sq)
    bk = min(bk, Sk)
    assert Sq % bq == 0 and Sk % bk == 0
    q_offset = Sk - Sq
    nk_all = Sk // bk
    out = []
    for qi in range(Sq // bq):
        qt = jax.lax.dynamic_slice_in_dim(q, qi * bq, bq, axis=1)
        qt = qt.astype(jnp.float32) * (1.0 / np.sqrt(hd))
        m = jnp.full((BH, bq), -jnp.inf, jnp.float32)
        l = jnp.zeros((BH, bq), jnp.float32)
        acc = jnp.zeros((BH, bq, hd), jnp.float32)
        q_pos = q_offset + qi * bq + jnp.arange(bq)

        def step(j, carry, qt=qt, q_pos=q_pos):
            m, l, acc = carry
            kt = jax.lax.dynamic_slice_in_dim(
                k, j * bk, bk, axis=1).astype(jnp.float32)
            vt = jax.lax.dynamic_slice_in_dim(
                v, j * bk, bk, axis=1).astype(jnp.float32)
            s = jnp.einsum("bqh,bkh->bqk", qt, kt)
            if causal:
                k_pos = j * bk + jnp.arange(bk)
                s = jnp.where(q_pos[None, :, None] >= k_pos[None, None, :],
                              s, -1e30)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            scale = jnp.exp(m - m_new)
            l = l * scale + jnp.sum(p, axis=-1)
            acc = acc * scale[..., None] + jnp.einsum("bqk,bkh->bqh", p, vt)
            return m_new, l, acc

        if causal:
            nk = min((q_offset + (qi + 1) * bq + bk - 1) // bk, nk_all)
        else:
            nk = nk_all
        m, l, acc = jax.lax.fori_loop(0, nk, step, (m, l, acc))
        out.append((acc / jnp.maximum(l, 1e-30)[..., None]).astype(q.dtype))
    return jnp.concatenate(out, axis=1)
