"""Production meshes.

Defined as FUNCTIONS (never module-level constants) so importing this module
never touches jax device state.  The dry-run launcher sets
XLA_FLAGS=--xla_force_host_platform_device_count=512 before any jax import.
"""
from __future__ import annotations

import jax

from repro.parallel.sharding import auto_axes

# TPU v5e hardware constants (per chip) — used by the roofline analysis.
PEAK_BF16_FLOPS = 197e12          # FLOP/s
PEAK_INT8_OPS = 394e12            # int8 Op/s (2x bf16)
HBM_BW = 819e9                    # bytes/s
ICI_BW = 50e9                     # bytes/s per link
HBM_BYTES = 16 * 2 ** 30          # 16 GiB


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return auto_axes(jax.make_mesh(shape, axes))


def make_local_mesh(data: int = 1, model: int = 1):
    """Small mesh over the locally available devices (tests/examples)."""
    return auto_axes(jax.make_mesh((data, model), ("data", "model")))
