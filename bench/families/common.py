"""Integer helpers that every family's plain reference shares.

Written in ``jax.numpy`` with no import of the program under test: the
conv shape with its pow2 grids, the seed-to-key rule, the rounding shift,
the exact int32 conv, the coarsening the int4 control applies, and the
blockwise jitted loop that evaluates a family's ``forward`` over a pool.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class ConvShape:
    name: str            # the weights' key, e.g. "stem", "b{i}.conv0"
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


def w_exp(fan_in: int, gain: float) -> int:
    """Pow2 weight grid that covers ~4.5 standard deviations of an He-init
    normal of this fan-in with the s8 range."""
    sigma = gain * math.sqrt(2.0 / fan_in)
    return int(math.ceil(math.log2(4.5 * sigma / 127.0)))


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any non-negative whole number, 64-bit ones too."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be >= 0: {seed}")
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def shift(acc, s: int):
    """acc * 2**-s on integers: a rounding right shift ``(acc + 2**(s-1))
    >> s`` (floor(x + 0.5)) for s > 0, a left shift for s < 0."""
    if s > 0:
        return (acc + (1 << (s - 1))) >> s
    if s < 0:
        return acc << (-s)
    return acc


def conv(x, w, stride: int):
    """The exact integer conv: int32 operands, int32 sums, "SAME" padding."""
    return jax.lax.conv_general_dilated(
        x.astype(jnp.int32), w.astype(jnp.int32), (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        preferred_element_type=jnp.int32)


def coarsen(q, drop: int, lo: int, hi: int):
    """Round integers onto a grid 2**drop coarser (half away from zero)."""
    if drop == 0:
        return q
    f = q.astype(jnp.float32) * 2.0 ** -drop
    return jnp.clip(jnp.sign(f) * jnp.floor(jnp.abs(f) + 0.5), lo, hi)


@functools.lru_cache(maxsize=None)
def _jitted(forward: Callable, net, bits: int):
    return jax.jit(lambda weights, x: forward(net, weights, x, bits=bits))


def blockwise(forward: Callable, net, weights: dict, images: np.ndarray,
              bits: int, block: int) -> np.ndarray:
    """``forward(net, weights, x, bits=bits)`` over ``images`` in blocks of
    ``block`` rows (the last block zero-padded, so one program serves every
    block).  The weights are an argument of that program, not constants in
    it, so one compile serves every seed."""
    fn = _jitted(forward, net, bits)
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
