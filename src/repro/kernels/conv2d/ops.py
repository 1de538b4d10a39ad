"""jit'd public wrapper for the conv2d Pallas kernel with shape guards."""

import time as _time
import warnings

import jax

from .conv2d import conv2d_fused as _conv2d_fused_pallas
from .ref import conv2d_fused_ref, conv2d_ref
from ...obs import trace as obs_trace
from ...obs.metrics import default_registry

_warned: set[tuple] = set()


def _warn_once(key: tuple, msg: str) -> None:
    if key in _warned:
        return
    _warned.add(key)
    warnings.warn(msg, RuntimeWarning, stacklevel=4)


def _fallback(reason: str, x_shape: tuple, w_shape: tuple,
              stride: tuple, msg: str) -> None:
    """Account one Pallas->XLA fallback: a ``conv.fallback`` counter
    labelled with the offending shape/stride (countable per run via
    ``Deployment.metrics_snapshot()``), a ``conv.fallback`` instant in
    the active tracer, and the once-per-shape RuntimeWarning."""
    default_registry().counter(
        "conv.fallback", reason=reason, x_shape=str(x_shape),
        w_shape=str(w_shape), stride=str(stride)).inc()
    tr = obs_trace.current()
    if tr:
        tr.instant("conv.fallback", _time.perf_counter() - tr.epoch,
                   reason=reason, x_shape=x_shape, w_shape=w_shape,
                   stride=stride)
    _warn_once((reason, x_shape, w_shape, stride), msg)


def fallback_count() -> int:
    """Total Pallas->XLA fallbacks recorded since process start or the
    last :func:`reset_fallbacks` (all shapes)."""
    return int(default_registry().total("conv.fallback"))


def reset_fallbacks() -> None:
    """Zero the fallback accounting so ``fallback_count()`` can be
    scoped per run instead of per process: drops every labelled
    ``conv.fallback`` counter from the default registry and clears the
    (otherwise unbounded) warn-once shape set along with it."""
    default_registry().drop("conv.fallback")
    _warned.clear()


def normalize_stride(stride) -> tuple[int, int]:
    """Accept ``int | tuple[int, int]``; an int applies to both axes."""
    if isinstance(stride, int):
        stride = (stride, stride)
    sh, sw = (int(s) for s in stride)
    if sh < 1 or sw < 1:
        raise ValueError(f"conv2d: stride must be >= 1, got {stride!r}")
    return (sh, sw)


def conv2d_fused(x: jax.Array, w: jax.Array, b: jax.Array | None = None, *,
                 stride=(1, 1), act: str = "linear",
                 pool: tuple[int, int] | None = None,
                 block_ci: int | None = None, block_co: int | None = None,
                 use_pallas: bool = True, interpret: bool = False
                 ) -> jax.Array:
    """VALID NHWC conv with a fused epilogue (bias + activation ``act`` +
    optional non-overlapping max-pool) in one Pallas call.

    The implicit-GEMM kernel handles any stride >= 1 and any channel
    count (tails are zero-padded up to the channel block); the only
    remaining fallback is an input spatially smaller than the kernel,
    which falls back to the composed XLA reference *inside this
    wrapper*, so the caller's backend choice is honored for every conv
    in a segment instead of silently bypassing it.  Each fallback is
    structured — a labelled ``conv.fallback`` metric plus a trace
    instant carrying the shape and stride — and still warns once per
    distinct shape.
    """
    stride = normalize_stride(stride)
    N, H, W, CI = x.shape
    KH, KW, CI2, CO = w.shape
    assert CI == CI2, (x.shape, w.shape)
    if pool is not None:
        pool = tuple(int(p) for p in pool)
    if not use_pallas:
        return conv2d_fused_ref(x, w, b, stride=stride, act=act, pool=pool)
    if H < KH or W < KW:
        _fallback("shape", tuple(x.shape), tuple(w.shape), stride,
                  f"conv2d: input {x.shape} smaller than kernel {w.shape}; "
                  "falling back to the XLA reference")
        return conv2d_fused_ref(x, w, b, stride=stride, act=act, pool=pool)
    return _conv2d_fused_pallas(x, w, b, stride=stride, act=act, pool=pool,
                                block_ci=block_ci, block_co=block_co,
                                interpret=interpret)


def conv2d(x: jax.Array, w: jax.Array, *, stride=(1, 1),
           use_pallas: bool = True, block_ci: int | None = None,
           block_co: int | None = None, interpret: bool = False
           ) -> jax.Array:
    """VALID NHWC conv, no epilogue — :func:`conv2d_fused` without the
    fused tail.  Kept as the plain-kernel entry point for sweeps and
    benchmarks."""
    stride = normalize_stride(stride)
    N, H, W, CI = x.shape
    KH, KW, CI2, CO = w.shape
    assert CI == CI2, (x.shape, w.shape)
    if not use_pallas:
        return conv2d_ref(x, w, stride)
    if H < KH or W < KW:
        _fallback("shape", tuple(x.shape), tuple(w.shape), stride,
                  f"conv2d: input {x.shape} smaller than kernel {w.shape}; "
                  "falling back to the XLA reference")
        return conv2d_ref(x, w, stride)
    return _conv2d_fused_pallas(x, w, None, stride=stride,
                                block_ci=block_ci, block_co=block_co,
                                interpret=interpret)
