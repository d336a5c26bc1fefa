"""Jitted public wrapper for the fused residual block."""
from functools import partial

import jax

from repro.kernels.common import use_interpret
from repro.kernels.resblock_fused.resblock_fused import resblock_fused
from repro.tune.config import DEFAULT, KernelConfig


@partial(jax.jit,
         static_argnames=("stride", "shift0", "shift1", "skip_shift",
                          "config"))
def resblock_fused_op(x, w0, b0, w1, b1, wd=None, bd=None, *, stride=1,
                      shift0, shift1, skip_shift=0,
                      config: KernelConfig = None):
    """x: (N,H,W,Cin) uint8 (unpadded; the kernel applies SAME padding).
    Pass wd/bd to fuse the 1x1 downsample conv on the skip path.  ``config``
    carries the tuned ``batch_tile`` (channel blocking is illegal for the
    fused block — see the kernel docstring)."""
    cfg = (config or DEFAULT).normalize(x.shape[0], w1.shape[-1])
    return resblock_fused(x, w0, b0, w1, b1, wd, bd, stride=stride,
                          shift0=shift0, shift1=shift1,
                          skip_shift=skip_shift, batch_tile=cfg.batch_tile,
                          interpret=use_interpret())
