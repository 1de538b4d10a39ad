"""Pure-jnp oracle for the conv2d kernel."""

import jax
import jax.numpy as jnp

from ...core.graph import LEAKY_SLOPE


def conv2d_ref(x: jax.Array, w: jax.Array,
               stride: tuple[int, int] = (1, 1)) -> jax.Array:
    """x: (N, H, W, CI); w: (KH, KW, CI, CO).  VALID conv, (sh, sw) stride."""
    return jax.lax.conv_general_dilated(
        x, w, window_strides=stride, padding="VALID",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


def activation(y: jax.Array, act: str) -> jax.Array:
    """A conv epilogue's activation: ``"relu"``, ``"leaky"`` (slope
    ``LEAKY_SLOPE``), or ``"linear"`` (none)."""
    if act == "relu":
        return jax.nn.relu(y)
    if act == "leaky":
        return jax.nn.leaky_relu(y, LEAKY_SLOPE)
    if act == "linear":
        return y
    raise ValueError(f"unknown activation {act!r}")


def conv2d_fused_ref(x: jax.Array, w: jax.Array, b: jax.Array | None = None,
                     *, stride: tuple[int, int] = (1, 1),
                     act: str = "linear",
                     pool: tuple[int, int] | None = None) -> jax.Array:
    """Composed-ops oracle for the fused conv epilogue: VALID conv,
    + bias, the activation ``act``, then a VALID non-overlapping
    (kernel == stride) max-pool — the eager sequence the fused kernel
    collapses."""
    y = conv2d_ref(x, w, stride)
    if b is not None:
        y = y + b
    y = activation(y, act)
    if pool is not None:
        ph, pw = pool
        y = jax.lax.reduce_window(
            y, -jnp.inf, jax.lax.max,
            window_dimensions=(1, ph, pw, 1),
            window_strides=(1, ph, pw, 1), padding="VALID")
    return y
