"""Pure-jnp oracle for the stem conv kernel (lax SAME conv + shift requant)."""
import jax
import jax.numpy as jnp

from repro.kernels.common import requant_u8


def conv_stem_ref(x, w, b, *, shift):
    """x: (N,H,W,Cin) uint8 unpadded; mirrors compile.backends._int_conv +
    _relu_requant for the stem layer."""
    acc = jax.lax.conv_general_dilated(
        x.astype(jnp.int32), w.astype(jnp.int32), (1, 1), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        preferred_element_type=jnp.int32)
    return requant_u8(acc + b.astype(jnp.int32), shift)


def conv_stem_pool_ref(x, w, b, *, shift):
    """The strided stem unfused: a k x k stride-2 lax SAME conv + ReLU +
    requant, then ``lax.reduce_window`` max over 3x3 windows at stride 2
    (SAME, padded with 0: the map is post-ReLU)."""
    acc = jax.lax.conv_general_dilated(
        x.astype(jnp.int32), w.astype(jnp.int32), (2, 2), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        preferred_element_type=jnp.int32)
    y = requant_u8(acc + b.astype(jnp.int32), shift)
    return jax.lax.reduce_window(y, jnp.uint8(0), jax.lax.max, (1, 3, 3, 1),
                                 (1, 2, 2, 1), "SAME")
