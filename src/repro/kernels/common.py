"""Shared kernel utilities and the integer conv datapath of the conv kernels.

On-chip contract (TPU v5e, Mosaic): the MXU takes s8 x s8 -> s32 dots, not
i32 x i32, and Mosaic has no u8 -> bf16 cast and no strided load of 8-bit
data.  So every conv kernel

* widens its u8 activation block to int32 as soon as it is loaded, and keeps
  every in-kernel activation int32-valued in [0, 255];
* feeds each tap dot the *offset* activation ``x - 128`` narrowed to s8
  (:func:`dot_u8s8`), exact by ``x.w = (x - 128).w + 128 Sum(w)`` — the
  wrapper folds the ``128 Sum(w)`` term into the bias (:func:`offset_bias`),
  which also holds for the zero pad pixels;
* reads stride-2 taps through strided loads of an int32 VMEM staging ref
  (:func:`conv_taps`), never through a strided value slice.

Interpret mode runs the same integer arithmetic, so the CPU conformance
suite proves the chip's datapath bit-exact.
"""
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.quant import shift_align

# activation offset that moves u8 values into the s8 operand range
ACT_OFFSET = 128


def use_interpret() -> bool:
    """Pallas TPU kernels run in interpret mode off-TPU (CPU tests); on a TPU
    they compile through Mosaic."""
    return jax.default_backend() != "tpu"


def requant(acc, shift: int, relu: bool = True):
    """int32 product-domain accumulator -> u8 activation values (kept in
    int32), with a static pow2 shift in core.quant.requantize_shift's
    semantics: positive = rounding (half-away) right shift, negative = left
    shift; then clip to [0, 255].  The epilogue of every integer conv kernel
    and its oracle — one home so bit-exactness can't drift between copies."""
    acc = acc.astype(jnp.int32)
    if relu:
        acc = jnp.maximum(acc, 0)
    if shift > 0:
        acc = (acc + (jnp.int32(1) << (shift - 1))) >> shift
    elif shift < 0:
        acc = acc << (-shift)
    return jnp.clip(acc, 0, 255)


def requant_u8(acc, shift: int, relu: bool = True):
    """:func:`requant` narrowed to the u8 storage dtype."""
    return requant(acc, shift, relu).astype(jnp.uint8)


def offset_bias(b, w):
    """int32 bias of a conv whose tap dots see ``x - ACT_OFFSET``: ``b`` plus
    ``ACT_OFFSET`` times the sum of ``w`` over every axis but the last
    (output channel)."""
    wsum = w.astype(jnp.int32).sum(axis=tuple(range(w.ndim - 1)))
    return b.astype(jnp.int32) + ACT_OFFSET * wsum


def dot_u8s8(rows, wm):
    """``(M, K)`` int32 rows holding u8 values x ``(K, N)`` s8 -> ``(M, N)``
    int32 ``(rows - ACT_OFFSET) @ wm`` as one s8 x s8 MXU dot."""
    xs = (rows - ACT_OFFSET).astype(jnp.int8)
    return jax.lax.dot(xs, wm, preferred_element_type=jnp.int32)


def pad_lo(stride: int) -> int:
    """Low SAME pad of a 3x3 conv on an even map, as jax.lax splits it:
    (1, 1) at stride 1, (0, 1) at stride 2."""
    return 1 if stride == 1 else 0


def pad_same(h, stride: int):
    """SAME-pad a (bt, H, W, C) activation for a 3x3 conv of ``stride``."""
    lo = pad_lo(stride)
    return jnp.pad(h, ((0, 0), (lo, 1), (lo, 1), (0, 0)))


def _window(src, r0, c0, oh, ow, stride):
    """The (bt, oh, ow, C) window of ``src`` starting at (r0, c0) with
    ``stride``; ``src`` is a value (stride 1) or an int32 ref (any stride)."""
    if stride == 1:
        return src[:, r0:r0 + oh, c0:c0 + ow, :]
    return src[:, pl.ds(r0, oh, stride=stride), pl.ds(c0, ow, stride=stride),
               :]


def conv_taps(src, w, acc, stride=1, dot=dot_u8s8):
    """Tap-wise conv over a padded (bt, Hp, Wp, Cin) batch tile: each filter
    tap is ONE ``(bt*oh*ow, Cin) x (Cin, Cout)`` dot accumulated into the
    int32 ``acc`` of shape (bt, oh, ow, Cout).  Stride 2 requires ``src`` to
    be an int32 ref."""
    bt, oh, ow, co = acc.shape
    for kh in range(w.shape[0]):
        for kw in range(w.shape[1]):
            xs = _window(src, kh, kw, oh, ow, stride)
            acc = acc + dot(xs.reshape(bt * oh * ow, -1),
                            w[kh, kw]).reshape(bt, oh, ow, co)
    return acc


def block_body(xp, w0, b0, w1, b1, wd, bd, *, stride, shift0, shift1,
               skip_shift, stage=None, dot=dot_u8s8):
    """One residual block on a padded (bt, Hp, Wp, Cin) int32 batch tile
    ``xp`` (the module's SAME convention): conv0 (strided) -> ReLU/requant ->
    [fused 1x1 downsample] skip align -> conv1 with the skip initializing its
    accumulator (the paper's add-fold) -> ReLU/requant.  Returns the
    (bt, oh, ow, Cout) int32 u8-valued output; nothing leaves VMEM.

    Biases arrive offset-folded (:func:`offset_bias`).  At stride 2 ``xp`` is
    first written to ``stage``, an int32 VMEM ref of its shape, and the
    strided taps load from there.  This is the shared streaming datapath of
    ``resblock_fused`` (one block per call) and the block-chain
    ``megakernel`` (a run of blocks back to back)."""
    lo = pad_lo(stride)
    bt = xp.shape[0]
    oh = (xp.shape[1] - 3) // stride + 1
    ow = (xp.shape[2] - 3) // stride + 1
    co = b0.shape[0]
    src = xp
    if stride != 1:
        stage[...] = xp
        src = stage
    # conv0 (strided) + relu + requant, all in VMEM
    acc0 = jnp.broadcast_to(b0, (bt, oh, ow, co))
    y0 = requant(conv_taps(src, w0, acc0, stride, dot), shift0)
    # skip stream, rescaled into conv1's product domain
    xs = _window(src, lo, lo, oh, ow, stride)
    if wd is not None:
        # fused 1x1 downsample conv: SAME padding of a 1x1 conv is zero,
        # so output o reads x[o*stride] = xp[pad_lo + o*stride]
        accd = dot(xs.reshape(bt * oh * ow, -1),
                   wd[0, 0]).reshape(bt, oh, ow, co)
        skip = shift_align(accd + bd, skip_shift)
    else:
        skip = shift_align(xs, skip_shift)
    # conv1 with add-fold: skip initializes the accumulator
    acc1 = conv_taps(pad_same(y0, 1), w1, skip + b1, 1, dot)
    return requant(acc1, shift1)
