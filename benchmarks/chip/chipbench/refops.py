"""Plain layer arithmetic shared by the reference families.

Every op takes a ``mode``:

``f32``
    float32 operands, ``Precision.HIGHEST``: the reference itself.
``bf16``
    everything stored and multiplied in bfloat16, as a model cast to
    bfloat16 would run.
``fp8``
    float8 (e4m3) operands with one scale per tensor, products summed
    in float32: the control one step below the bf16 operands that the
    program's float32 at default precision feeds the TPU's matrix unit.

Nothing here imports the program.  Weights are drawn as the program's
``CNNDef.init`` draws them, one ``split`` per weighted layer in graph
order, so the reference rebuilds the same weights from the same key.
Where the benchmark feeds the program its parameters, the biases are the
benchmark's own (:class:`Fold`): non-zero, so that a path that drops the
bias add (or the folded BatchNorm shift it stands for) cannot pass.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

HIGHEST = lax.Precision.HIGHEST
MODES = ("f32", "bf16", "fp8")
_F8 = jnp.float8_e4m3fn
_F8_MAX = float(jnp.finfo(_F8).max)
#: a folded bias's spread over its channel's pre-activation spread
BIAS_SHARE = 0.2


def init(layers, key) -> list[tuple[jax.Array, jax.Array]]:
    """``(w, b)`` per weighted layer of ``layers`` (see the families'
    ``layers``): conv weights HWIO, normal over sqrt(fan in), zero bias."""
    params = []
    for layer in layers:
        key, k1 = jax.random.split(key)
        if layer["kind"] == "conv":
            k, cin, cout = layer["k"], layer["cin"], layer["cout"]
            w = jax.random.normal(k1, (k, k, cin, cout), jnp.float32) \
                / math.sqrt(k * k * cin)
        else:
            cin, cout = layer["cin"], layer["cout"]
            w = jax.random.normal(k1, (cin, cout), jnp.float32) \
                / math.sqrt(cin)
        params.append((w, jnp.zeros((cout,), jnp.float32)))
    return params


class Fold:
    """BatchNorm folded into each layer's bias on calibration frames: run
    a forward pass with ``fold=Fold()`` and a unit normal draw ``z`` in
    place of each bias, and every layer's bias becomes, per channel,
    ``BIAS_SHARE * std(y) * z - mean(y)`` of its pre-activation ``y``
    over the frames and positions.  :attr:`biases` then holds them in
    graph order.  Centred so, the layers keep the frame's signal: with
    zero biases a deep ReLU stack maps every frame to nearly the same
    logits, and a wrong frame's answer would read as rounding."""

    def __init__(self):
        self.biases: list[jax.Array] = []

    def __call__(self, y, z):
        axes = tuple(range(y.ndim - 1))
        b = BIAS_SHARE * jnp.std(y, axis=axes) * z - jnp.mean(y, axis=axes)
        self.biases.append(b)
        return b


def _fp8(a: jax.Array) -> jax.Array:
    """Round to float8 e4m3 with one scale for the whole tensor."""
    scale = jnp.maximum(jnp.max(jnp.abs(a)), 1e-30) / _F8_MAX
    return (a / scale).astype(_F8).astype(jnp.float32) * scale


def operands(x, w, mode: str):
    if mode == "f32":
        return x, w
    if mode == "bf16":
        return x.astype(jnp.bfloat16), w.astype(jnp.bfloat16)
    if mode == "fp8":
        return _fp8(x), _fp8(w)
    raise ValueError(f"unknown mode {mode!r}, want one of {MODES}")


def conv(x, w, b, stride: int, pad: int, mode: str, relu: bool = True,
         fold: Fold | None = None):
    """NHWC conv + bias (+ ReLU)."""
    xq, wq = operands(x, w, mode)
    y = lax.conv_general_dilated(
        xq, wq, (stride, stride), ((pad, pad), (pad, pad)),
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=HIGHEST)
    if fold is not None:
        b = fold(y, b)
    y = y + b.astype(y.dtype)
    return jnp.maximum(y, 0) if relu else y


def dense(x, w, b, mode: str, fold: Fold | None = None):
    xq, wq = operands(x, w, mode)
    y = jnp.dot(xq, wq, precision=HIGHEST)
    if fold is not None:
        b = fold(y, b)
    return y + b.astype(y.dtype)


def max_pool(x, k: int, stride: int, pad: int = 0):
    return lax.reduce_window(
        x, jnp.array(-jnp.inf, x.dtype), lax.max, (1, k, k, 1),
        (1, stride, stride, 1),
        ((0, 0), (pad, pad), (pad, pad), (0, 0)))


def out_size(n: int, k: int, stride: int, pad: int) -> int:
    return (n + 2 * pad - k) // stride + 1
