"""Pure-jnp oracle for the fused residual block (unfused dataflow graph:
conv0 -> relu/requant -> [1x1 ds conv ->] +skip -> conv1 -> relu/requant,
each tensor round-tripping through 'HBM').  Takes the *unpadded* input and
uses lax SAME padding so strided blocks match the integer network graph.
Shift/requant arithmetic comes from the shared helpers (core.quant.shift_align,
kernels.common.requant_u8) — the structural independence from the kernel is
the lax conv vs the per-tap MXU accumulation."""
import jax
import jax.numpy as jnp

from repro.core.quant import shift_align
from repro.kernels.common import requant_u8


def _conv(x, w, b, stride=1):
    acc = jax.lax.conv_general_dilated(
        x.astype(jnp.int32), w.astype(jnp.int32), (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        preferred_element_type=jnp.int32)
    return acc + b.astype(jnp.int32)


def resblock_ref(x, w0, b0, w1, b1, wd=None, bd=None, *, stride=1,
                 shift0, shift1, skip_shift=0):
    """x: (N,H,W,C) uint8 *unpadded* (pre-PR callers passed a pre-padded
    tensor; padding now lives in lax SAME so stride-2 blocks are exact)."""
    acc0 = _conv(x, w0, b0, stride)
    y0 = requant_u8(acc0, shift0)
    if wd is not None:
        skip = shift_align(_conv(x, wd, bd, stride), skip_shift)
    else:
        skip = shift_align(x, skip_shift)
    acc1 = _conv(y0, w1, b1) + skip
    return requant_u8(acc1, shift1)


def bottleneck_ref(x, w0, b0, w1, b1, w2, b2, wd=None, bd=None, *, stride=1,
                   shift0, shift1, shift2, skip_shift=0):
    """The bottleneck block unfused: conv0 (1x1) -> relu/requant -> conv1
    (3x3, ``stride``, lax SAME) -> relu/requant -> conv2 (1x1) + the skip
    (block input, or the 1x1 projection at ``stride``) shifted onto its
    product grid -> relu/requant.  x: (N,H,W,Cin) uint8 unpadded."""
    y0 = requant_u8(_conv(x, w0, b0), shift0)
    y1 = requant_u8(_conv(y0, w1, b1, stride), shift1)
    if wd is not None:
        skip = shift_align(_conv(x, wd, bd, stride), skip_shift)
    else:
        skip = shift_align(x.astype(jnp.int32), skip_shift)
    return requant_u8(_conv(y1, w2, b2) + skip, shift2)
