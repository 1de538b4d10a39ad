"""Pluggable per-layer execution backends.

A backend is how a *conv* vertex is lowered — everything else (pool,
fc, connectors) is backend-independent XLA.  Backends are registered in
a process-wide table but *selected* explicitly: :class:`CNNDef` carries
a ``backend`` field and the stage executors thread it through, so there
is no mutable module global deciding the numerics of an already-built
model (the seed's ``_CONV_BACKEND`` failure mode).

Registered backends:

``xla``
    ``lax.conv_general_dilated`` — the reference path on every platform.
``pallas``
    The repro's implicit-GEMM Pallas kernel (``kernels.conv2d``), which
    handles any stride >= 1 and any channel count (tails are padded up
    to the channel block) and carries the conv epilogue — bias, the
    layer's activation, optional non-overlapping max-pool — inside the
    kernel.
    ``interpret`` is auto-detected from the JAX platform: on TPU the
    kernel actually compiles; on the CPU it runs in interpret mode
    (slow but bit-faithful); any other platform raises.  Channel block
    sizes come from
    ``exec.autotune``'s installed winners when present.

A backend may additionally register a *fused* lowering: the signature
covers the whole conv epilogue (conv + bias + activation + optional pool) in
one call, and ``exec.compiler.fusable_chains`` only rewrites segments
for backends that have one — backends without it (xla) keep the exact
composed-op sequence, preserving bit-equality with the eager oracle.
"""

from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp

from ..core.graph import LayerSpec
from ..kernels.conv2d.ref import activation

# conv backend signature: (spec, params, x, pad_w) -> y  (NHWC, VALID +
# explicit pad_w/ph padding, no bias, no activation)
ConvFn = Callable[[LayerSpec, dict, jax.Array, tuple[int, int]], jax.Array]

# fused lowering: (conv_spec, pool_spec | None, params, x, pad_w) -> y,
# with bias + conv_spec.act (+ pool) applied — one kernel call per chain
FusedConvFn = Callable[
    [LayerSpec, Optional[LayerSpec], dict, jax.Array, tuple[int, int]],
    jax.Array]

_REGISTRY: dict[str, ConvFn] = {}
_FUSED: dict[str, FusedConvFn] = {}
DEFAULT_BACKEND = "xla"


def register_backend(name: str, fn: ConvFn,
                     fused: FusedConvFn | None = None) -> None:
    _REGISTRY[name] = fn
    if fused is not None:
        _FUSED[name] = fused
    else:
        _FUSED.pop(name, None)


def available_backends() -> list[str]:
    return sorted(_REGISTRY)


def get_backend(name: str | None) -> ConvFn:
    name = name or DEFAULT_BACKEND
    try:
        return _REGISTRY[name]
    except KeyError:
        raise ValueError(f"unknown exec backend {name!r}; "
                         f"registered: {available_backends()}") from None


def has_fused(name: str | None) -> bool:
    """Does ``name`` register a fused conv-epilogue lowering?"""
    return (name or DEFAULT_BACKEND) in _FUSED


def default_interpret() -> bool:
    """Pallas interpret mode: compiled on a TPU, interpreted on the CPU.

    Any other platform raises: the kernel has no lowering there, and
    interpreting it would hide the device the run was meant for."""
    platform = jax.default_backend()
    if platform not in ("cpu", "tpu"):
        raise RuntimeError(f"the pallas conv backend runs compiled on tpu "
                           f"or interpreted on cpu, not on {platform!r}")
    return platform == "cpu"


# ---------------------------------------------------------------------------
# built-in backends
# ---------------------------------------------------------------------------

def _conv_xla(spec: LayerSpec, p: dict, x: jax.Array,
              pad_w: tuple[int, int]) -> jax.Array:
    ph = spec.padding[1]
    return jax.lax.conv_general_dilated(
        x, p["w"],
        window_strides=(spec.stride[1], spec.stride[0]),
        padding=((ph, ph), pad_w),
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
    )


def _tuned(xp: jax.Array, w: jax.Array, stride, act: str,
           pool) -> tuple[int | None, int | None]:
    from .autotune import tuned_blocks
    return tuned_blocks(xp.shape, w.shape, stride, act, pool,
                        backend="pallas")


def _conv_pallas(spec: LayerSpec, p: dict, x: jax.Array,
                 pad_w: tuple[int, int]) -> jax.Array:
    from ..kernels.conv2d.ops import conv2d as conv2d_kernel
    ph = spec.padding[1]
    xp = jnp.pad(x, ((0, 0), (ph, ph), pad_w, (0, 0)))
    stride = (spec.stride[1], spec.stride[0])
    bci, bco = _tuned(xp, p["w"], stride, "linear", None)
    return conv2d_kernel(xp, p["w"], stride=stride, block_ci=bci,
                         block_co=bco, interpret=default_interpret())


def _conv_pallas_fused(spec: LayerSpec, pool_spec: LayerSpec | None, p: dict,
                       x: jax.Array, pad_w: tuple[int, int]) -> jax.Array:
    from ..kernels.conv2d.ops import conv2d_fused
    ph = spec.padding[1]
    xp = jnp.pad(x, ((0, 0), (ph, ph), pad_w, (0, 0)))
    stride = (spec.stride[1], spec.stride[0])
    pool = None if pool_spec is None \
        else (pool_spec.kernel[1], pool_spec.kernel[0])
    bci, bco = _tuned(xp, p["w"], stride, spec.act, pool)
    return conv2d_fused(xp, p["w"], p["b"], stride=stride, act=spec.act,
                        pool=pool, block_ci=bci, block_co=bco,
                        interpret=default_interpret())


register_backend("xla", _conv_xla)
register_backend("pallas", _conv_pallas, fused=_conv_pallas_fused)


# ---------------------------------------------------------------------------
# layer application (backend-dispatching successor of builder._apply)
# ---------------------------------------------------------------------------

def apply_conv(spec: LayerSpec, p, x: jax.Array,
               pad_w: tuple[int, int] = (0, 0),
               backend: str | None = None,
               pool_spec: LayerSpec | None = None) -> jax.Array:
    """Apply one conv epilogue chain (conv + bias + the layer's own
    activation ``spec.act`` + optional non-overlapping max-pool) to an
    NHWC tile.

    Backends with a fused lowering execute the whole chain as one
    kernel call; others compose the exact eager sequence, so a backend
    without fusion stays bit-identical to the oracle.  ``pool_spec``
    must describe a VALID kernel==stride pool (the only shape
    ``fusable_chains`` emits).
    """
    name = backend or DEFAULT_BACKEND
    fused = _FUSED.get(name)
    if fused is not None:
        return fused(spec, pool_spec, p, x, pad_w)
    y = activation(get_backend(name)(spec, p, x, pad_w) + p["b"], spec.act)
    if pool_spec is not None:
        y = jax.lax.reduce_window(
            y, -jnp.inf, jax.lax.max,
            window_dimensions=(1, pool_spec.kernel[1], pool_spec.kernel[0], 1),
            window_strides=(1, pool_spec.stride[1], pool_spec.stride[0], 1),
            padding="VALID",
        )
    return y


def reorg(x: jax.Array, block: tuple[int, int]) -> jax.Array:
    """Space-to-depth of an NHWC tile: output ``(i, j)`` holds input
    ``(i*bh + dy, j*bw + dx)`` at channel ``(dy*bw + dx)*C + c``."""
    bw, bh = block
    n, h, w, c = x.shape
    x = x.reshape(n, h // bh, bh, w // bw, bw, c)
    return x.transpose(0, 1, 3, 2, 4, 5).reshape(n, h // bh, w // bw,
                                                 bh * bw * c)


def apply_layer(spec: LayerSpec, p, x: jax.Array,
                pad_w: tuple[int, int] = (0, 0),
                backend: str | None = None) -> jax.Array:
    """Apply one layer to an NHWC tile.

    ``pad_w`` is the tile's share of the layer's zero padding along W
    (only boundary tiles get any); H is never tiled, so the full
    (p_h, p_h) padding always applies.  ``backend`` selects the conv
    lowering; every other kind is plain XLA.
    """
    ph = spec.padding[1]
    if spec.kind == "conv":
        return apply_conv(spec, p, x, pad_w, backend)
    if spec.kind == "pool":
        return jax.lax.reduce_window(
            x, -jnp.inf, jax.lax.max,
            window_dimensions=(1, spec.kernel[1], spec.kernel[0], 1),
            window_strides=(1, spec.stride[1], spec.stride[0], 1),
            padding=((0, 0), (ph, ph), pad_w, (0, 0)),
        )
    if spec.kind == "reorg":
        return reorg(x, spec.kernel)
    if spec.kind == "gpool":
        return jnp.mean(x, axis=(1, 2), keepdims=True)
    if spec.kind == "fc":
        flat = x.reshape(x.shape[0], -1)
        y = flat @ p["w"] + p["b"]
        return y.reshape(x.shape[0], 1, 1, -1)  # stay NHWC for uniformity
    if spec.kind in ("identity", "input", "output"):
        return x
    raise NotImplementedError(spec.kind)
