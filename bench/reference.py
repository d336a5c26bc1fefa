"""Plain reference of the CIFAR ResNet family, and the seeded weights.

Written from the configuration file alone, in ``jax.numpy``: it imports
nothing of the program under test.  The arithmetic is the paper's integer
scheme (arXiv:2309.15631 §III-A):

* the float image in [0, 1) is quantized to u8 on the grid ``2**input_exp``,
  rounding half away from zero;
* every conv is an exact integer sum ``x . w + b`` (u8 activations, s8
  weights, s16 bias on the product grid ``s_x + s_w``);
* ReLU, then a rounding right shift ``(acc + half) >> s`` onto the u8
  activation grid ``2**act_exp``, clipped to [0, 255];
* in a residual block the skip stream (the block input, or the 1x1
  downsample conv's sum) is shifted onto conv1's product grid and added to
  conv1's sum before the ReLU;
* the head sums the 8x8 map per channel, dots it with the s8 fc weights in
  integers, then scales once to float and adds the float bias.

Each conv runs as ``lax.conv_general_dilated`` on int32 operands with an
int32 result: the plain, exact definition, with no precision to choose.

The weights are made here too, from the seed alone, in one jitted call on
the device: the program is handed these arrays, and the reference never
sees anything the program made.

``forward(..., bits=4)`` is the control: the same network with weights,
bias and activations rounded onto grids 16x coarser (int4 weights and
activations), the next precision below the configuration's int8.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class ConvShape:
    name: str            # "stem", "b{i}.conv0", "b{i}.conv1", "b{i}.ds"
    k: int               # square kernel size
    cin: int
    cout: int
    stride: int
    hin: int             # input height (= width)
    w_exp: int           # pow2 exponent of the s8 weights
    x_exp: int           # pow2 exponent of the u8 input

    @property
    def hout(self) -> int:
        return self.hin // self.stride

    @property
    def b_exp(self) -> int:
        return self.x_exp + self.w_exp


@dataclasses.dataclass(frozen=True)
class Block:
    conv0: ConvShape
    conv1: ConvShape
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
            out += [b.conv0, b.conv1] + ([b.ds] if b.ds else [])
        return out


def _w_exp(fan_in: int, gain: float) -> int:
    """Pow2 weight grid that covers ~4.5 standard deviations of an He-init
    normal of this fan-in with the s8 range."""
    sigma = gain * math.sqrt(2.0 / fan_in)
    return int(math.ceil(math.log2(4.5 * sigma / 127.0)))


# He-init scale factors: the stem is drawn 4x larger, so the u8 maps use
# more of their range than the [0, 1) image gives them; conv1 of every
# block (the add-fold conv) 2x smaller, so the residual stream neither
# saturates nor dies through 9 blocks (ResNet20's last map: ~4% of values
# at 255, ~25% at 0)
STEM_GAIN = 4.0
CONV1_GAIN = 0.5


def build_net(cfg: dict) -> Net:
    """The network's static structure from a configuration file's dict."""
    w0 = cfg["base_width"]
    a_exp, in_exp = cfg["act_exp"], cfg["input_exp"]
    side = cfg["img"]
    stem = ConvShape("stem", 3, cfg["in_channels"], w0, 1, side,
                     _w_exp(9 * cfg["in_channels"], STEM_GAIN), in_exp)
    blocks = []
    cin = w0
    for stage in range(cfg["stages"]):
        cout = w0 * 2 ** stage
        for bi in range(cfg["blocks_per_stage"]):
            i = len(blocks)
            stride = 2 if (stage > 0 and bi == 0) else 1
            c0 = ConvShape(f"b{i}.conv0", 3, cin, cout, stride, side,
                           _w_exp(9 * cin, 1.0), a_exp)
            side //= stride
            c1 = ConvShape(f"b{i}.conv1", 3, cout, cout, 1, side,
                           _w_exp(9 * cout, CONV1_GAIN), a_exp)
            ds = None
            if stride != 1 or cin != cout:
                ds = ConvShape(f"b{i}.ds", 1, cin, cout, stride, side * stride,
                               _w_exp(cin, 1.0), a_exp)
            blocks.append(Block(c0, c1, ds))
            cin = cout
    return Net(stem=stem, blocks=tuple(blocks), fc_in=cin,
               num_classes=cfg["num_classes"],
               fc_w_exp=_w_exp(cin, 0.5), act_exp=a_exp, input_exp=in_exp,
               head_hw=side)


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any non-negative whole number, 64-bit ones too."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be >= 0: {seed}")
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def _gain(c: ConvShape) -> float:
    if c.name == "stem":
        return STEM_GAIN
    return CONV1_GAIN if c.name.endswith("conv1") else 1.0


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


def _shift(acc, s: int):
    """acc * 2**-s on integers: a rounding right shift ``(acc + 2**(s-1))
    >> s`` (floor(x + 0.5)) for s > 0, a left shift for s < 0."""
    if s > 0:
        return (acc + (1 << (s - 1))) >> s
    if s < 0:
        return acc << (-s)
    return acc


def _conv(x, w, stride: int):
    return jax.lax.conv_general_dilated(
        x.astype(jnp.int32), w.astype(jnp.int32), (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        preferred_element_type=jnp.int32)


def _coarsen(q, drop: int, lo: int, hi: int):
    """Round integers onto a grid 2**drop coarser (half away from zero)."""
    if drop == 0:
        return q
    f = q.astype(jnp.float32) * 2.0 ** -drop
    return jnp.clip(jnp.sign(f) * jnp.floor(jnp.abs(f) + 0.5), lo, hi)


def forward(net: Net, weights: dict, images, bits: int = 8):
    """Logits (N, classes) float32 of float images (N, H, W, 3) in [0, 1).

    ``bits`` < 8 runs the control: every s8 weight, u8 activation and s16
    bias grid is ``2**(8 - bits)`` times coarser."""
    drop = 8 - bits
    amax = (1 << bits) - 1
    wlo, whi = -(1 << (bits - 1)), (1 << (bits - 1)) - 1
    a_exp = net.act_exp + drop

    def param(c: ConvShape):
        w, b = weights[c.name]
        w = _coarsen(w.astype(jnp.int32), drop, wlo, whi).astype(jnp.int32)
        b = _coarsen(b.astype(jnp.int32), 2 * drop, -32768, 32767)
        return w, b.astype(jnp.int32), c.w_exp + drop, c.x_exp + drop

    def conv(x, c: ConvShape):
        w, b, w_exp, x_exp = param(c)
        return _conv(x, w, c.stride) + b, x_exp + w_exp

    def relu_requant(acc, p_exp: int):
        return jnp.clip(_shift(jnp.maximum(acc, 0), a_exp - p_exp), 0, amax)

    in_exp = net.input_exp + drop
    f = images.astype(jnp.float32) * 2.0 ** -in_exp
    h = jnp.clip(jnp.sign(f) * jnp.floor(jnp.abs(f) + 0.5), 0, amax)
    h = relu_requant(*conv(h.astype(jnp.int32), net.stem))
    for blk in net.blocks:
        y = relu_requant(*conv(h, blk.conv0))
        acc1, p1 = conv(y, blk.conv1)
        if blk.ds is not None:
            skip, p_skip = conv(h, blk.ds)
        else:
            skip, p_skip = h, a_exp
        h = relu_requant(acc1 + _shift(skip, p1 - p_skip), p1)
    w, b = weights["fc"]
    w = _coarsen(w.astype(jnp.int32), drop, wlo, whi).astype(jnp.int32)
    pooled = jnp.sum(h, axis=(1, 2))
    acc = jnp.dot(pooled, w, preferred_element_type=jnp.int32)
    scale = 2.0 ** (a_exp + net.fc_w_exp + drop) / net.head_hw ** 2
    return acc.astype(jnp.float32) * jnp.float32(scale) + b


@functools.lru_cache(maxsize=None)
def _jitted(net: Net, bits: int):
    return jax.jit(lambda weights, x: forward(net, weights, x, bits=bits))


def reference_logits(net: Net, weights: dict, images: np.ndarray,
                     bits: int = 8, block: int = 256) -> np.ndarray:
    """:func:`forward` over ``images`` in blocks of ``block`` rows (the last
    block zero-padded, so one program serves every block).  The weights are
    an argument of that program, not constants in it, so one compile serves
    every seed."""
    fn = _jitted(net, bits)
    n = len(images)
    out = np.empty((n, net.num_classes), np.float32)
    for i in range(0, n, block):
        x = np.asarray(images[i:i + block], np.float32)
        m = len(x)
        if m < block:
            x = np.concatenate([x, np.zeros((block - m,) + x.shape[1:],
                                            np.float32)])
        out[i:i + m] = np.asarray(fn(weights, x))[:m]
    return out
