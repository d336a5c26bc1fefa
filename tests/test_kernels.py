"""Per-kernel allclose sweeps vs the pure-jnp oracles (interpret=True on CPU;
the kernels target TPU v5e)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.kernels.conv2d_int8.ops import conv2d_int8_op
from repro.kernels.conv2d_int8.ref import conv2d_int8_ref
from repro.kernels.flash_attention.ops import flash_attention_op
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.matmul_int8.ops import matmul_int8_op
from repro.kernels.matmul_int8.ref import matmul_int8_ref
from repro.kernels.resblock_fused.ops import resblock_fused_op
from repro.kernels.resblock_fused.ref import resblock_ref
from repro.kernels.selective_scan.ops import selective_scan_op
from repro.kernels.selective_scan.ref import selective_scan_ref


def _i8(key, *shape):
    return jax.random.randint(key, shape, -128, 128, jnp.int32).astype(jnp.int8)


# ---------------------------------------------------------------------------
# matmul_int8
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("M,K,N,bm,bk,bn", [
    (128, 128, 128, 128, 128, 128),
    (256, 384, 128, 128, 128, 128),
    (64, 64, 64, 32, 32, 32),
    (128, 256, 256, 64, 128, 128),
])
def test_matmul_int8_shapes(M, K, N, bm, bk, bn):
    key = jax.random.PRNGKey(M + K + N)
    a = _i8(key, M, K)
    b = _i8(jax.random.fold_in(key, 1), K, N)
    out = matmul_int8_op(a, b, bm=bm, bn=bn, bk=bk)
    np.testing.assert_array_equal(np.asarray(out),
                                  np.asarray(matmul_int8_ref(a, b)))


def test_matmul_int8_acc_init_addfold():
    """The accumulator-init operand == the paper's folded residual add."""
    key = jax.random.PRNGKey(7)
    a = _i8(key, 128, 128)
    b = _i8(jax.random.fold_in(key, 1), 128, 128)
    skip = jax.random.randint(jax.random.fold_in(key, 2), (128, 128),
                              -10000, 10000, jnp.int32)
    out = matmul_int8_op(a, b, skip)
    np.testing.assert_array_equal(np.asarray(out),
                                  np.asarray(matmul_int8_ref(a, b, skip)))


@given(st.integers(1, 4), st.integers(1, 4), st.integers(1, 4))
@settings(max_examples=10, deadline=None)
def test_matmul_int8_hypothesis_multiples(mi, ki, ni):
    M, K, N = 32 * mi, 32 * ki, 32 * ni
    key = jax.random.PRNGKey(M * 10000 + K * 100 + N)
    a = _i8(key, M, K)
    b = _i8(jax.random.fold_in(key, 1), K, N)
    out = matmul_int8_op(a, b, bm=32, bn=32, bk=32)
    np.testing.assert_array_equal(np.asarray(out),
                                  np.asarray(matmul_int8_ref(a, b)))


# ---------------------------------------------------------------------------
# conv2d_int8
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("N,H,C,O,stride,relu,shift", [
    (2, 8, 4, 8, 1, False, None),
    (2, 8, 4, 8, 2, False, None),
    (1, 16, 8, 16, 1, True, 7),
    (2, 8, 3, 16, 2, True, 6),
])
def test_conv2d_int8_sweep(N, H, C, O, stride, relu, shift):
    key = jax.random.PRNGKey(N * H + C)
    x = _i8(key, N, H, H, C)
    w = _i8(jax.random.fold_in(key, 1), 3, 3, C, O)
    b = jax.random.randint(jax.random.fold_in(key, 2), (O,), -100, 100,
                           jnp.int32)
    out = conv2d_int8_op(x, w, b, stride=stride, relu=relu, out_shift=shift)
    xp = jnp.pad(x, ((0, 0), (1, 1), (1, 1), (0, 0)))
    ref = conv2d_int8_ref(xp, w, b, stride=stride, relu=relu, out_shift=shift)
    np.testing.assert_array_equal(np.asarray(out, np.int64),
                                  np.asarray(ref, np.int64))


def test_conv2d_int8_skip_acc_init():
    key = jax.random.PRNGKey(11)
    x = _i8(key, 2, 8, 8, 4)
    w = _i8(jax.random.fold_in(key, 1), 3, 3, 4, 4)
    b = jnp.zeros((4,), jnp.int32)
    skip = jax.random.randint(jax.random.fold_in(key, 2), (2, 8, 8, 4),
                              -1000, 1000, jnp.int32)
    out = conv2d_int8_op(x, w, b, skip)
    xp = jnp.pad(x, ((0, 0), (1, 1), (1, 1), (0, 0)))
    ref = conv2d_int8_ref(xp, w, b, skip)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


# ---------------------------------------------------------------------------
# resblock_fused — fused kernel == unfused dataflow oracle, bit exact
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("N,H,C", [(1, 8, 4), (2, 16, 16), (1, 32, 16)])
def test_resblock_fused_bitexact(N, H, C):
    key = jax.random.PRNGKey(H * C)
    x = jax.random.randint(key, (N, H, H, C), 0, 256, jnp.int32).astype(jnp.uint8)
    w0 = _i8(jax.random.fold_in(key, 1), 3, 3, C, C)
    w1 = _i8(jax.random.fold_in(key, 2), 3, 3, C, C)
    b0 = jax.random.randint(jax.random.fold_in(key, 3), (C,), -500, 500, jnp.int32)
    b1 = jax.random.randint(jax.random.fold_in(key, 4), (C,), -500, 500, jnp.int32)
    out = resblock_fused_op(x, w0, b0, w1, b1, shift0=8, shift1=8, skip_shift=3)
    ref = resblock_ref(x, w0, b0, w1, b1, shift0=8, shift1=8, skip_shift=3)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


@pytest.mark.parametrize("N,H,Cin,Cout,skip_shift", [
    (1, 8, 4, 8, 3), (2, 16, 16, 32, 0), (1, 32, 16, 32, -2),
])
def test_resblock_fused_strided_downsample_bitexact(N, H, Cin, Cout,
                                                    skip_shift):
    """The paper's stride-2 block: strided conv0 + the 1x1 downsample conv on
    the skip path fused into the same kernel, signed skip alignment shift."""
    key = jax.random.PRNGKey(H * Cin + Cout)
    x = jax.random.randint(key, (N, H, H, Cin), 0, 256,
                           jnp.int32).astype(jnp.uint8)
    w0 = _i8(jax.random.fold_in(key, 1), 3, 3, Cin, Cout)
    w1 = _i8(jax.random.fold_in(key, 2), 3, 3, Cout, Cout)
    wd = _i8(jax.random.fold_in(key, 3), 1, 1, Cin, Cout)
    b0, b1, bd = (jax.random.randint(jax.random.fold_in(key, 4 + i), (Cout,),
                                     -500, 500, jnp.int32) for i in range(3))
    out = resblock_fused_op(x, w0, b0, w1, b1, wd, bd, stride=2,
                            shift0=8, shift1=8, skip_shift=skip_shift)
    ref = resblock_ref(x, w0, b0, w1, b1, wd, bd, stride=2,
                       shift0=8, shift1=8, skip_shift=skip_shift)
    assert out.shape == (N, H // 2, H // 2, Cout)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_conv_stem_bitexact():
    from repro.kernels.conv_stem.ops import conv_stem_op
    from repro.kernels.conv_stem.ref import conv_stem_ref
    key = jax.random.PRNGKey(5)
    x = jax.random.randint(key, (2, 16, 16, 3), 0, 256,
                           jnp.int32).astype(jnp.uint8)
    w = _i8(jax.random.fold_in(key, 1), 3, 3, 3, 16)
    b = jax.random.randint(jax.random.fold_in(key, 2), (16,), -500, 500,
                           jnp.int32)
    for shift in (9, 0, -1):
        out = conv_stem_op(x, w, b, shift=shift)
        ref = conv_stem_ref(x, w, b, shift=shift)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_resblock_fused_hbm_model():
    """The fused kernel's HBM traffic model: >=3x reduction vs unfused."""
    from repro.core.dataflow import residual_block_hbm_bytes
    fused = residual_block_hbm_bytes(32, 32, 16, 16, fused=True)
    unfused = residual_block_hbm_bytes(32, 32, 16, 16, fused=False)
    assert unfused / fused >= 3.0


# ---------------------------------------------------------------------------
# selective_scan
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("B,S,di,N,bd", [
    (1, 16, 8, 4, 8), (2, 32, 16, 8, 8), (2, 64, 32, 16, 16),
])
def test_selective_scan_sweep(B, S, di, N, bd):
    key = jax.random.PRNGKey(S + di)
    ks = jax.random.split(key, 6)
    u = jax.random.normal(ks[0], (B, S, di))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, di)))
    A = -jnp.exp(jax.random.normal(ks[2], (di, N)) * 0.5)
    Bc = jax.random.normal(ks[3], (B, S, N))
    Cc = jax.random.normal(ks[4], (B, S, N))
    h0 = jax.random.normal(ks[5], (B, di, N))
    y, h = selective_scan_op(u, dt, A, Bc, Cc, h0, bd=bd)
    y_ref, h_ref = selective_scan_ref(u, dt, A, Bc, Cc, h0)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), rtol=1e-5,
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(h), np.asarray(h_ref), rtol=1e-5,
                               atol=1e-5)


# ---------------------------------------------------------------------------
# flash_attention
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("B,S,H,KV,hd,causal", [
    (1, 64, 2, 2, 16, True),
    (2, 128, 4, 2, 32, True),
    (1, 64, 2, 1, 16, False),
])
def test_flash_attention_sweep(B, S, H, KV, hd, causal):
    key = jax.random.PRNGKey(S + H)
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (B, S, H, hd), jnp.float32)
    k = jax.random.normal(ks[1], (B, S, KV, hd), jnp.float32)
    v = jax.random.normal(ks[2], (B, S, KV, hd), jnp.float32)
    out = flash_attention_op(q, k, v, causal=causal, bq=32, bk=32)
    G = H // KV
    kr = jnp.repeat(k, G, 2).transpose(0, 2, 1, 3).reshape(B * H, S, hd)
    vr = jnp.repeat(v, G, 2).transpose(0, 2, 1, 3).reshape(B * H, S, hd)
    qf = q.transpose(0, 2, 1, 3).reshape(B * H, S, hd)
    ref = attention_ref(qf, kr, vr, causal=causal)
    ref = ref.reshape(B, H, S, hd).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5,
                               atol=2e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_dtypes(dtype):
    key = jax.random.PRNGKey(0)
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (1, 64, 2, 16)).astype(dtype)
    k = jax.random.normal(ks[1], (1, 64, 2, 16)).astype(dtype)
    v = jax.random.normal(ks[2], (1, 64, 2, 16)).astype(dtype)
    out = flash_attention_op(q, k, v, bq=32, bk=32)
    qf = q.transpose(0, 2, 1, 3).reshape(2, 64, 16)
    kf = k.transpose(0, 2, 1, 3).reshape(2, 64, 16)
    vf = v.transpose(0, 2, 1, 3).reshape(2, 64, 16)
    ref = attention_ref(qf, kf, vf).reshape(1, 2, 64, 16).transpose(0, 2, 1, 3)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), rtol=tol, atol=tol)


# ---------------------------------------------------------------------------
# megakernel — block-chain streaming kernel == unfused per-block oracle
# ---------------------------------------------------------------------------


def _chain_blocks(key, links):
    """Random weights + specs for a chain described as (Cin, Cout, stride)
    links; returns (blocks, specs) for block_chain_op/block_chain_ref."""
    from repro.kernels.megakernel.megakernel import ChainBlockSpec
    blocks, specs = [], []
    for i, (cin, cout, stride) in enumerate(links):
        k = jax.random.fold_in(key, i)
        has_ds = stride != 1 or cin != cout
        ws = [_i8(jax.random.fold_in(k, 1), 3, 3, cin, cout),
              jax.random.randint(jax.random.fold_in(k, 2), (cout,), -500,
                                 500, jnp.int32),
              _i8(jax.random.fold_in(k, 3), 3, 3, cout, cout),
              jax.random.randint(jax.random.fold_in(k, 4), (cout,), -500,
                                 500, jnp.int32)]
        if has_ds:
            ws += [_i8(jax.random.fold_in(k, 5), 1, 1, cin, cout),
                   jax.random.randint(jax.random.fold_in(k, 6), (cout,),
                                      -500, 500, jnp.int32)]
        blocks.append(tuple(ws))
        specs.append(ChainBlockSpec(stride=stride, has_ds=has_ds, shift0=8,
                                    shift1=8, skip_shift=1 - i % 3))
    return tuple(blocks), tuple(specs)


CHAINS = [
    [(8, 8, 1)],                                   # singleton
    [(8, 8, 1), (8, 8, 1)],                        # identity pair
    [(8, 8, 1), (8, 16, 2), (16, 16, 1)],          # stride-2 mid-chain
    [(4, 8, 2), (8, 16, 2)],                       # stride-2 chain head
]


@pytest.mark.parametrize("links", CHAINS, ids=lambda l: f"{len(l)}links")
@pytest.mark.parametrize("N,bt", [(1, 1), (4, 1), (4, 2), (4, 4)])
def test_block_chain_bitexact(links, N, bt):
    from repro.kernels.megakernel.ops import block_chain_op
    from repro.kernels.megakernel.ref import block_chain_ref
    from repro.tune.config import KernelConfig
    key = jax.random.PRNGKey(len(links) * 7 + N)
    x = jax.random.randint(key, (N, 16, 16, links[0][0]), 0, 256,
                           jnp.int32).astype(jnp.uint8)
    blocks, specs = _chain_blocks(jax.random.fold_in(key, 99), links)
    out = block_chain_op(x, blocks, specs=specs,
                         config=KernelConfig(batch_tile=bt))
    ref = block_chain_ref(x, blocks, specs=specs)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


@pytest.mark.parametrize("N,bt", [(2, 1), (2, 2)])
def test_block_chain_fused_stem_bitexact(N, bt):
    """Stem fused at the chain head: uint8 image -> stem conv -> chain, the
    stem boundary never materialized."""
    from repro.kernels.megakernel.ops import block_chain_op
    from repro.kernels.megakernel.ref import block_chain_ref
    from repro.tune.config import KernelConfig
    key = jax.random.PRNGKey(17)
    x = jax.random.randint(key, (N, 16, 16, 3), 0, 256,
                           jnp.int32).astype(jnp.uint8)
    stem = (_i8(jax.random.fold_in(key, 1), 3, 3, 3, 8),
            jax.random.randint(jax.random.fold_in(key, 2), (8,), -500, 500,
                               jnp.int32))
    blocks, specs = _chain_blocks(jax.random.fold_in(key, 3),
                                  [(8, 8, 1), (8, 16, 2)])
    out = block_chain_op(x, blocks, specs=specs, stem=stem, stem_shift=7,
                         config=KernelConfig(batch_tile=bt))
    ref = block_chain_ref(x, blocks, specs=specs, stem=stem, stem_shift=7)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_block_chain_equals_per_block_kernels():
    """Chain output == running the SAME links through resblock_fused_op one
    by one — the fusion moves boundaries into VMEM without touching a bit."""
    from repro.kernels.megakernel.ops import block_chain_op
    key = jax.random.PRNGKey(23)
    links = [(8, 8, 1), (8, 16, 2), (16, 16, 1)]
    x = jax.random.randint(key, (3, 8, 8, 8), 0, 256,
                           jnp.int32).astype(jnp.uint8)
    blocks, specs = _chain_blocks(jax.random.fold_in(key, 9), links)
    out = block_chain_op(x, blocks, specs=specs)
    h = x
    for s, ws in zip(specs, blocks):
        wd, bd = (ws[4], ws[5]) if s.has_ds else (None, None)
        h = resblock_fused_op(h, ws[0], ws[1], ws[2], ws[3], wd, bd,
                              stride=s.stride, shift0=s.shift0,
                              shift1=s.shift1, skip_shift=s.skip_shift)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(h))


def test_f32_emulation_bound_is_enforced():
    """The interpret-mode fast path runs tap dots in float32 ONLY within the
    2^24 exactness bound; a hypothetical wider-than-1024-channel link must
    fall back to integer dots (checked structurally, not numerically)."""
    from repro.kernels.megakernel.megakernel import F32_EXACT_ROWS, _dot_i32
    # offset-s8 operands: every product is at most 128 * 128 in magnitude
    assert F32_EXACT_ROWS * 128 * 128 <= 2 ** 24
    assert (F32_EXACT_ROWS + 1) * 128 * 128 > 2 ** 24
    wide = jnp.ones((2, F32_EXACT_ROWS + 1), jnp.uint8)
    wm = jnp.ones((F32_EXACT_ROWS + 1, 4), jnp.int8)
    assert _dot_i32(wide, wm, fast_emul=True).dtype == jnp.int32
    # the guarded path stays exact at the widest real chain width
    rows = jax.random.randint(jax.random.PRNGKey(0), (64, 64), 0, 256,
                              jnp.int32).astype(jnp.uint8)
    w = _i8(jax.random.PRNGKey(1), 64, 32).reshape(64, 32)
    np.testing.assert_array_equal(
        np.asarray(_dot_i32(rows, w, fast_emul=True)),
        np.asarray(_dot_i32(rows, w, fast_emul=False)))
