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
    On a platform with 128-lane vector tiles (TPU) its fused lowering,
    :func:`conv_pool_phase`, takes the conv -> 2x2/2 pool chains of
    stride-1 convs whose space-to-depth input fits one lane tile
    (``4 * Cin <= 128``): they run in space-to-depth phase form, the
    pool a max over the phase groups.  Every other conv, and every
    chain elsewhere, is the composed eager sequence.
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
one call.  Beside it the backend registers which (conv, pool) pairs the
fused lowering takes (:func:`fuses`; by default all of them), and
``exec.compiler.CompiledStage`` fuses only those chains: a conv or chain
it does not take keeps the exact composed-op sequence, preserving
bit-equality with the eager oracle.
"""

from __future__ import annotations

import functools
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

# which (conv_spec, pool_spec | None) the fused lowering takes
EngageFn = Callable[[LayerSpec, Optional[LayerSpec]], bool]

_REGISTRY: dict[str, ConvFn] = {}
_FUSED: dict[str, tuple[FusedConvFn, EngageFn]] = {}
DEFAULT_BACKEND = "xla"


def register_backend(name: str, fn: ConvFn,
                     fused: FusedConvFn | None = None,
                     engages: EngageFn | None = None) -> None:
    """``engages`` picks the (conv, pool) pairs ``fused`` takes; None
    means all of them, unpooled convs included."""
    _REGISTRY[name] = fn
    if fused is not None:
        _FUSED[name] = (fused, engages or (lambda spec, pool_spec: True))
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


def fuses(name: str | None, spec: LayerSpec,
          pool_spec: LayerSpec | None) -> bool:
    """Does backend ``name`` lower conv ``spec`` (with the VALID
    kernel == stride pool ``pool_spec``, or alone) through its fused
    lowering?"""
    entry = _FUSED.get(name or DEFAULT_BACKEND)
    return entry is not None and entry[1](spec, pool_spec)


def phase_form(name: str | None) -> bool:
    """Does backend ``name`` fuse its chains in phase form
    (:func:`conv_pool_phase`)?"""
    entry = _FUSED.get(name or DEFAULT_BACKEND)
    return entry is not None and entry[0] is conv_pool_phase


def lane_tiled() -> bool:
    """Does the platform keep a tensor's minor dimension in 128-lane
    vector tiles (TPU), so that a 3- or 32-channel tensor pays for 128
    lanes?  The same observation :func:`default_interpret` makes."""
    return jax.default_backend() == "tpu"


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


#: the lane tile of a TPU vector register: a space-to-depth input of at
#: most this many channels stays within one tile
LANES = 128


def phase_engages(spec: LayerSpec, pool_spec: LayerSpec | None) -> bool:
    """The chains :func:`conv_pool_phase` takes: on a lane-tiled
    platform, a stride-1 conv into a VALID 2x2/2 pool whose
    space-to-depth input, ``4 * Cin`` channels, fits one lane tile —
    where the conv's lane-sparse input and output cost more than its
    FLOPs."""
    return (pool_spec is not None and lane_tiled()
            and tuple(spec.stride) == (1, 1)
            and tuple(pool_spec.kernel) == (2, 2)
            and tuple(pool_spec.stride) == (2, 2)
            and tuple(pool_spec.padding) == (0, 0)
            and 4 * spec.in_channels <= LANES)


def phase_kernel(w: jax.Array, p: int, bh: int, bw: int) -> jax.Array:
    """The ``(p*bh, p*bw, CI, p*p*CO)`` kernel that computes all ``p*p``
    output phases of a stride-1 ``(KH, KW, CI, CO)`` conv at stride
    ``p``: output channel ``(a, b, co)`` is the conv output at phase
    ``(a, b)``, i.e. ``w`` shifted by ``(a, b)`` in a zero window.  On
    the ``p``-space-to-depth grid it is the ``bh x bw`` block kernel
    from ``p*p*CI`` to ``p*p*CO`` channels."""
    kh, kw, _, _ = w.shape
    return jnp.concatenate(
        [jnp.pad(w, ((a, p * bh - kh - a), (b, p * bw - kw - b),
                     (0, 0), (0, 0)))
         for a in range(p) for b in range(p)], axis=-1)


def conv_pool_phase(spec: LayerSpec, pool_spec: LayerSpec, p: dict,
                    x: jax.Array, pad_w: tuple[int, int]) -> jax.Array:
    """A stride-1 conv + bias + ``spec.act`` + VALID P x P/P max-pool
    in space-to-depth phase form.

    The P*P conv outputs of each pool window become channels: one VALID
    stride-P conv with :func:`phase_kernel` (the ``bh x bw`` block
    kernel of the space-to-depth grid, 2x2 for a 3x3 conv, so 16/9 of
    the MACs) writes P*P*Cout dense channels, and the pool is the
    elementwise max of the P*P phase groups' channel slices.  Bias and
    activation follow the max: every activation is non-decreasing, so
    this equals the pool of the activated conv, with only the conv's
    f32 sums in another order.  The tile is padded (or cropped) to the
    grid the phase kernel reads; taps past the conv's own window meet
    zero weights.

    Written as a strided conv on the tile, not as :func:`reorg` plus a
    stride-1 block conv, and with slices, not a reshape, for the max:
    in a v5e compile of yolov2 both of those lowered to relayouts of
    lane-padded tensors that cost more than the conv they served."""
    pp = pool_spec.kernel[0]
    kh, kw = spec.kernel[1], spec.kernel[0]
    ph = spec.padding[1]
    h, w = x.shape[1:3]
    ho = (h + 2 * ph - kh + 1) // pp               # pooled output grid
    wo = (w + pad_w[0] + pad_w[1] - kw + 1) // pp
    bh, bw = -(-(kh + pp - 1) // pp), -(-(kw + pp - 1) // pp)
    hz, wz = pp * (ho + bh - 1), pp * (wo + bw - 1)
    xp = jax.lax.pad(x, jnp.zeros((), x.dtype),
                     ((0, 0, 0), (ph, hz - h - ph, 0),
                      (pad_w[0], wz - w - pad_w[0], 0), (0, 0, 0)))
    y = jax.lax.conv_general_dilated(
        xp, phase_kernel(p["w"], pp, bh, bw),
        window_strides=(pp, pp), padding="VALID",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    co = y.shape[-1] // (pp * pp)
    y = functools.reduce(jnp.maximum, (y[..., i * co:(i + 1) * co]
                                       for i in range(pp * pp)))
    return activation(y + p["b"], spec.act)


register_backend("xla", _conv_xla, fused=conv_pool_phase,
                 engages=phase_engages)
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

    A chain the backend's fused lowering takes (:func:`fuses`) runs
    as one fused call; any other composes the exact eager sequence, so
    it stays bit-identical to the oracle.  ``pool_spec`` must describe
    a VALID kernel==stride pool (the only shape ``fusable_chains``
    emits).
    """
    name = backend or DEFAULT_BACKEND
    if fuses(name, spec, pool_spec):
        return _FUSED[name][0](spec, pool_spec, p, x, pad_w)
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
