"""Jitted public wrapper for the block-chain streaming megakernel."""
from functools import partial

import jax

from repro.kernels.common import use_interpret
from repro.kernels.megakernel.megakernel import block_chain
from repro.tune.config import DEFAULT, KernelConfig


@partial(jax.jit, static_argnames=("specs", "stem_shift", "config"))
def block_chain_op(x, blocks, *, specs, stem=None, stem_shift=None,
                   config: KernelConfig = None):
    """x: (N,H,W,Cin) uint8 (unpadded) — the quantized image batch when
    ``stem`` is fused, else the previous kernel's activation.  ``blocks`` is
    one (w0,b0,w1,b1[,wd,bd]) array tuple per chain link and ``specs`` the
    matching static :class:`ChainBlockSpec` schedule; SAME padding for the
    chain's first op is applied by the kernel wrapper, every later pad
    happens in VMEM inside the kernel.  ``config`` carries the tuned
    ``batch_tile`` (``cout_block`` is fusion-illegal, as for
    ``resblock_fused``)."""
    cfg = (config or DEFAULT).normalize(x.shape[0], blocks[-1][2].shape[-1])
    return block_chain(x, blocks, specs=specs, stem=stem,
                       stem_shift=stem_shift, batch_tile=cfg.batch_tile,
                       interpret=use_interpret())
