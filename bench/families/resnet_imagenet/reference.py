"""Plain reference of the ImageNet bottleneck ResNet family (ResNet-50 v1.5),
and the seeded weights.

Written from the configuration file alone, in ``jax.numpy``: it imports
nothing of the program under test.  The network is He et al. 2016
(arXiv:1512.03385) Table 1: a 7x7 stride-2 stem conv, a 3x3 stride-2 max
pool, then stages of bottleneck blocks (1x1 -> 3x3 -> 1x1, the last
``expansion`` times wider), with a 1x1 projection shortcut on the first
block of every stage and that stage's stride on the 3x3 conv (v1.5, as
MLPerf Inference's ResNet-50).  The arithmetic is the paper's integer
scheme (arXiv:2309.15631 §III-A), as in the CIFAR family:

* the float image in [0, 1) is quantized to u8 on the grid ``2**input_exp``,
  rounding half away from zero;
* every conv is an exact integer sum ``x . w + b`` (u8 activations, s8
  weights, s16 bias on the product grid ``s_x + s_w``), with "SAME"
  padding as ``jax.lax`` splits it ((2, 3) for the 7x7 stride-2 stem,
  (0, 1) for a 3x3 stride-2 conv);
* ReLU, then a rounding right shift ``(acc + half) >> s`` onto the u8
  activation grid ``2**act_exp``, clipped to [0, 255];
* the max pool takes the largest of each 3x3 window at stride 2, "SAME"
  padded with 0 (the map is post-ReLU, so 0 acts as -inf);
* in a block the skip stream (the block input, or the projection's sum) is
  shifted onto conv2's product grid and added to conv2's sum before the
  ReLU;
* the head sums the last map per channel, divides by its pixel count
  rounding half up (``(s + hw // 2) // hw``) back onto the u8 grid, dots
  that with the s8 fc weights in integers, then scales once to float and
  adds the float bias.

The helpers (the exact conv, the rounding shift, the control's coarsening,
the blockwise loop) are the families' shared ones (``bench/families/
common.py``).  ``forward(..., bits=4)`` is the control: weights, bias and
activations rounded onto grids 16x coarser.
"""
from __future__ import annotations

import dataclasses
import math
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench.families.common import (ConvShape, blockwise, coarsen, conv,
                                   seed_key, shift, w_exp)


@dataclasses.dataclass(frozen=True)
class Block:
    conv0: ConvShape       # 1x1
    conv1: ConvShape       # 3x3 at the block's stride
    conv2: ConvShape       # 1x1, the add-fold conv
    ds: Optional[ConvShape]


@dataclasses.dataclass(frozen=True)
class Net:
    """Static structure of one configuration: shapes and pow2 grids."""

    stem: ConvShape
    blocks: Tuple[Block, ...]
    fc_in: int
    num_classes: int
    fc_w_exp: int
    act_exp: int
    input_exp: int
    head_hw: int         # side of the final feature map

    def convs(self) -> List[ConvShape]:
        out = [self.stem]
        for b in self.blocks:
            out += [b.conv0, b.conv1, b.conv2] + ([b.ds] if b.ds else [])
        return out


STEM_K = 7
# He-init scale factors, as the CIFAR family's STEM_GAIN/CONV1_GAIN: the
# stem is drawn 4x larger, so the u8 maps use more of their range than the
# [0, 1) image gives them; conv2 of every block (the add-fold conv) and the
# projection 2x smaller, so the residual stream neither saturates nor dies
# through 16 blocks (on two images of seed 7 at weight_seed 0 the block
# outputs' means stay within 14-52 of 255; the last map holds ~28% zeros
# and ~0.2% 255s, pinned in bench/tests/test_resnet_imagenet.py)
GAINS = {"stem": 4.0, "conv0": 1.0, "conv1": 1.0, "conv2": 0.5, "ds": 0.5}


def _gain(c: ConvShape) -> float:
    return GAINS[c.name.rsplit(".", 1)[-1]]


def build_net(cfg: dict) -> Net:
    """The network's static structure from a configuration file's dict."""
    w0, exp = cfg["base_width"], cfg["expansion"]
    a_exp, in_exp = cfg["act_exp"], cfg["input_exp"]
    side = cfg["img"]
    cin = cfg["in_channels"]
    stem = ConvShape("stem", STEM_K, cin, w0, 2, side,
                     w_exp(STEM_K * STEM_K * cin, GAINS["stem"]), in_exp)
    side //= 4                                  # stem stride, max pool
    blocks = []
    cin = w0
    for stage, n in enumerate(cfg["stage_blocks"]):
        mid = w0 * 2 ** stage
        cout = mid * exp
        for bi in range(n):
            i = len(blocks)
            stride = 2 if (stage > 0 and bi == 0) else 1
            c0 = ConvShape(f"b{i}.conv0", 1, cin, mid, 1, side,
                           w_exp(cin, GAINS["conv0"]), a_exp)
            c1 = ConvShape(f"b{i}.conv1", 3, mid, mid, stride, side,
                           w_exp(9 * mid, GAINS["conv1"]), a_exp)
            c2 = ConvShape(f"b{i}.conv2", 1, mid, cout, 1, side // stride,
                           w_exp(mid, GAINS["conv2"]), a_exp)
            ds = None
            if bi == 0:
                ds = ConvShape(f"b{i}.ds", 1, cin, cout, stride, side,
                               w_exp(cin, GAINS["ds"]), a_exp)
            blocks.append(Block(c0, c1, c2, ds))
            cin, side = cout, side // stride
    return Net(stem=stem, blocks=tuple(blocks), fc_in=cin,
               num_classes=cfg["num_classes"], fc_w_exp=w_exp(cin, 0.5),
               act_exp=a_exp, input_exp=in_exp, head_hw=side)


def make_weights(net: Net, seed: int) -> dict:
    """Seeded integer weights, made on the device in one jitted call:
    ``{conv name: (w s8 (k,k,cin,cout), b s16 (cout,))}`` plus
    ``"fc": (w s8 (fc_in, classes), b f32 (classes,))``."""
    convs = net.convs()

    def draw(key):
        keys = jax.random.split(key, 2 * len(convs) + 2)
        out = {}
        for j, c in enumerate(convs):
            sigma = _gain(c) * math.sqrt(2.0 / (c.k * c.k * c.cin))
            w = jax.random.normal(keys[2 * j], (c.k, c.k, c.cin, c.cout))
            wq = jnp.clip(jnp.round(w * sigma * 2.0 ** -c.w_exp), -128, 127)
            # biases of about a tenth of a unit of the output activation
            b = jax.random.normal(keys[2 * j + 1], (c.cout,)) * 0.1
            bq = jnp.clip(jnp.round(b * 2.0 ** -c.b_exp), -32768, 32767)
            out[c.name] = (wq.astype(jnp.int8), bq.astype(jnp.int16))
        sigma = 0.5 * math.sqrt(2.0 / net.fc_in)
        w = jax.random.normal(keys[-2], (net.fc_in, net.num_classes))
        wq = jnp.clip(jnp.round(w * sigma * 2.0 ** -net.fc_w_exp), -128, 127)
        b = jax.random.normal(keys[-1], (net.num_classes,)) * 0.1
        out["fc"] = (wq.astype(jnp.int8), b.astype(jnp.float32))
        return out

    return jax.jit(draw)(seed_key(seed))


# ---------------------------------------------------------------------------
# The forward pass
# ---------------------------------------------------------------------------


def last_map(net: Net, weights: dict, images, bits: int = 8):
    """The u8-valued (N, head_hw, head_hw, fc_in) int32 map the head reads.

    ``bits`` < 8 runs the control: every s8 weight, u8 activation and s16
    bias grid is ``2**(8 - bits)`` times coarser."""
    drop = 8 - bits
    amax = (1 << bits) - 1
    wlo, whi = -(1 << (bits - 1)), (1 << (bits - 1)) - 1
    a_exp = net.act_exp + drop

    def conv_bias(x, c: ConvShape):
        w, b = weights[c.name]
        w = coarsen(w.astype(jnp.int32), drop, wlo, whi).astype(jnp.int32)
        b = coarsen(b.astype(jnp.int32), 2 * drop, -32768, 32767)
        return (conv(x, w, c.stride) + b.astype(jnp.int32),
                c.x_exp + c.w_exp + 2 * drop)

    def relu_requant(acc, p_exp: int):
        return jnp.clip(shift(jnp.maximum(acc, 0), a_exp - p_exp), 0, amax)

    in_exp = net.input_exp + drop
    f = images.astype(jnp.float32) * 2.0 ** -in_exp
    h = jnp.clip(jnp.sign(f) * jnp.floor(jnp.abs(f) + 0.5), 0, amax)
    h = relu_requant(*conv_bias(h.astype(jnp.int32), net.stem))
    h = jax.lax.reduce_window(h, jnp.int32(0), jax.lax.max, (1, 3, 3, 1),
                              (1, 2, 2, 1), "SAME")
    for blk in net.blocks:
        y = relu_requant(*conv_bias(h, blk.conv0))
        y = relu_requant(*conv_bias(y, blk.conv1))
        acc2, p2 = conv_bias(y, blk.conv2)
        if blk.ds is not None:
            skip, p_skip = conv_bias(h, blk.ds)
        else:
            skip, p_skip = h, a_exp
        h = relu_requant(acc2 + shift(skip, p2 - p_skip), p2)
    return h


def forward(net: Net, weights: dict, images, bits: int = 8):
    """Logits (N, classes) float32 of float images (N, H, W, 3) in [0, 1)."""
    drop = 8 - bits
    wlo, whi = -(1 << (bits - 1)), (1 << (bits - 1)) - 1
    h = last_map(net, weights, images, bits)
    hw = net.head_hw ** 2
    pooled = (jnp.sum(h, axis=(1, 2)) + hw // 2) // hw
    w, b = weights["fc"]
    w = coarsen(w.astype(jnp.int32), drop, wlo, whi).astype(jnp.int32)
    acc = jnp.dot(pooled, w, preferred_element_type=jnp.int32)
    scale = 2.0 ** (net.act_exp + net.fc_w_exp + 2 * drop)
    return acc.astype(jnp.float32) * jnp.float32(scale) + b


def reference_logits(net: Net, weights: dict, images: np.ndarray,
                     bits: int = 8, block: int = 64) -> np.ndarray:
    """:func:`forward` over ``images`` in blocks of ``block`` rows
    (``common.blockwise``)."""
    return blockwise(forward, net, weights, images, bits, block)
