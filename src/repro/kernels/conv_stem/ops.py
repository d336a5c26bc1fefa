"""Jitted public wrapper for the stem conv kernel."""
from functools import partial

import jax

from repro.kernels.common import use_interpret
from repro.kernels.conv_stem.conv_stem import conv_stem
from repro.kernels.conv_stem.stem_pool import conv_stem_pool
from repro.tune.config import DEFAULT, KernelConfig


@partial(jax.jit, static_argnames=("shift", "config", "name"))
def conv_stem_op(x, w, b, *, shift, config: KernelConfig = None,
                 name: str = None):
    """x: (N,H,W,Cin) uint8 (unpadded; the kernel applies SAME padding).
    b may be int16 (bias_spec) — widened to the int32 accumulator dtype.
    ``config`` carries the tuned tiling knobs (default: one image per grid
    step, all output channels in one block); ``name`` names the kernel's
    device op."""
    cfg = (config or DEFAULT).normalize(x.shape[0], w.shape[-1])
    return conv_stem(x, w, b, shift=shift, batch_tile=cfg.batch_tile,
                     cout_block=cfg.cout_block, interpret=use_interpret(),
                     name=name)


@partial(jax.jit, static_argnames=("shift", "config", "name"))
def conv_stem_pool_op(x, w, b, *, shift, config: KernelConfig = None,
                      name: str = None):
    """x: (N,S,S,Cin) uint8 unpadded; the strided stem conv + 3x3/2 max
    pool.  ``config`` carries a tuned ``batch_tile`` (default one image a
    grid step)."""
    cfg = (config or DEFAULT).normalize(x.shape[0], w.shape[-1])
    return conv_stem_pool(x, w, b, shift=shift, batch_tile=cfg.batch_tile,
                          interpret=use_interpret(), name=name)
