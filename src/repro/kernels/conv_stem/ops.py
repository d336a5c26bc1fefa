"""Jitted public wrapper for the stem conv kernel."""
from functools import partial

import jax

from repro.kernels.common import use_interpret
from repro.kernels.conv_stem.conv_stem import conv_stem
from repro.tune.config import DEFAULT, KernelConfig


@partial(jax.jit, static_argnames=("shift", "config"))
def conv_stem_op(x, w, b, *, shift, config: KernelConfig = None):
    """x: (N,H,W,Cin) uint8 (unpadded; the kernel applies SAME padding).
    b may be int16 (bias_spec) — widened to the int32 accumulator dtype.
    ``config`` carries the tuned tiling knobs (default: one image per grid
    step, all output channels in one block)."""
    cfg = (config or DEFAULT).normalize(x.shape[0], w.shape[-1])
    return conv_stem(x, w, b, shift=shift, batch_tile=cfg.batch_tile,
                     cout_block=cfg.cout_block, interpret=use_interpret())
