"""Channel-block autotuning for the Pallas conv kernel.

The implicit-GEMM kernel (``kernels.conv2d``) takes two tunable block
sizes — ``block_ci``/``block_co``, the in/out-channel tiles fed to the
MXU.  The default heuristic (128, or the axis rounded up to a power of
two) is safe everywhere but not best everywhere; this module searches
the candidate space per conv shape, records each trial as a
compile-adjacent ``autotune`` span + ``exec.autotune.*`` metrics, and
persists winners into the :class:`~repro.core.cost.CostTable` artifact
(``kernels`` field) so calibration ratios and kernel tunings share one
versioned store, survive ``Deployment.save()/load()``, and feed the
planner costs measured on the *tuned* kernels.

Keys (:func:`shape_key`) are deliberately spatial-size-agnostic —
``conv:<backend>:c{ci}x{co}:k..:s..:r..:p..`` — because the pipeline
runs the same conv on many tile widths; channel blocking is a
channel-geometry decision, so one winner covers every tile of a layer.

Winners are *installed* process-wide (:func:`install`); the pallas
backend lowering consults :func:`tuned_blocks` on every conv call and
silently uses the kernel default when no entry matches.
"""

from __future__ import annotations

import time as _time
from dataclasses import dataclass, field
from typing import Iterable, Mapping, Sequence

import jax
import jax.numpy as jnp

from ..core.cost import CostTable
from ..obs import trace as obs_trace
from ..obs.metrics import default_registry

# (block_ci, block_co) candidates.  The kernel zero-pads channel tails
# up to the block, so every candidate is legal for every channel count;
# small blocks win on small layers (less padding waste), 128s on big
# ones (MXU-aligned).
DEFAULT_CANDIDATES: tuple[tuple[int, int], ...] = (
    (128, 128), (128, 64), (64, 128), (64, 64), (32, 32), (16, 16), (8, 8))


#: the activation's part of a key; ``r1`` (ReLU) and ``r0`` (none) are
#: the tags stored CostTables already hold, so their entries stay valid
_ACT_TAGS = {"relu": "r1", "linear": "r0", "leaky": "rleaky"}


def shape_key(x_shape, w_shape, stride, act="linear", pool=None,
              backend: str = "pallas") -> str:
    """Stable CostTable key for one conv-epilogue configuration.

    Spatial dims are excluded on purpose (see module docstring); the
    key captures channels, filter, stride, epilogue (activation
    ``act``, pool), and backend.
    """
    ci = x_shape[-1]
    kh, kw, _, co = w_shape
    sh, sw = stride
    p = "-" if pool is None else f"{pool[0]}x{pool[1]}"
    return (f"conv:{backend}:c{ci}x{co}:k{kh}x{kw}:s{sh}x{sw}"
            f":{_ACT_TAGS[act]}:p{p}")


# ---------------------------------------------------------------------------
# installed winners (process-wide, consulted by exec.backends)
# ---------------------------------------------------------------------------

_TUNED: dict[str, dict] = {}


def install(kernels: Mapping[str, Mapping]) -> None:
    """Merge CostTable ``kernels`` entries into the process-wide tuned
    registry (last write wins per key).  ``Deployment`` calls this on
    construction/load, so a saved artifact re-arms the fast path."""
    for k, e in kernels.items():
        _TUNED[k] = dict(e)


def installed() -> dict[str, dict]:
    """Copy of the currently installed tuned entries."""
    return {k: dict(e) for k, e in _TUNED.items()}


def clear_installed() -> None:
    _TUNED.clear()


def tuned_blocks(x_shape, w_shape, stride, act="linear", pool=None, *,
                 backend: str = "pallas") -> tuple[int | None, int | None]:
    """(block_ci, block_co) for this conv call, or (None, None) when no
    tuned entry is installed (the kernel default applies)."""
    e = _TUNED.get(shape_key(x_shape, w_shape, stride, act, pool, backend))
    if e is None:
        return (None, None)
    return (int(e["block_ci"]), int(e["block_co"]))


# ---------------------------------------------------------------------------
# search
# ---------------------------------------------------------------------------

@dataclass
class TuneResult:
    key: str
    block_ci: int
    block_co: int
    best_us: float
    trials: list[tuple[int, int, float]] = field(default_factory=list)

    def entry(self, backend: str = "pallas") -> dict:
        """The CostTable ``kernels`` entry for this winner."""
        return {"block_ci": self.block_ci, "block_co": self.block_co,
                "best_us": self.best_us, "backend": backend}


def _time_call(fn, *args, iters: int) -> float:
    fn(*args).block_until_ready()  # compile outside the timed region
    t0 = _time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    out.block_until_ready()
    return (_time.perf_counter() - t0) / iters


def autotune_conv(x_shape: Sequence[int], w_shape: Sequence[int], *,
                  stride=(1, 1), act: str = "linear",
                  pool: tuple[int, int] | None = None, bias: bool = True,
                  backend: str = "pallas",
                  candidates: Iterable[tuple[int, int]] = DEFAULT_CANDIDATES,
                  iters: int = 3, interpret: bool | None = None,
                  key: jax.Array | None = None) -> TuneResult:
    """Search ``candidates`` for the fastest (block_ci, block_co) on one
    conv-epilogue shape; emits an ``autotune`` span per shape and an
    ``exec.autotune.trial_s`` histogram sample per candidate."""
    from ..kernels.conv2d.ops import conv2d_fused
    from .backends import default_interpret
    if interpret is None:
        interpret = default_interpret()
    key = jax.random.PRNGKey(0) if key is None else key
    k1, k2, k3 = jax.random.split(key, 3)
    x = jax.random.normal(k1, tuple(x_shape), jnp.float32)
    w = jax.random.normal(k2, tuple(w_shape), jnp.float32) * 0.1
    b = jax.random.normal(k3, (w_shape[-1],), jnp.float32) if bias else None
    stride = tuple(int(s) for s in stride)
    skey = shape_key(x_shape, w_shape, stride, act, pool, backend)
    reg = default_registry()
    tr = obs_trace.current()
    trials: list[tuple[int, int, float]] = []
    with tr.wall_span("autotune", key=skey) if tr else _null():
        for bci, bco in candidates:
            dt = _time_call(
                lambda xx, ww: conv2d_fused(
                    xx, ww, b, stride=stride, act=act, pool=pool,
                    block_ci=bci, block_co=bco, interpret=interpret),
                x, w, iters=iters)
            trials.append((bci, bco, dt))
            reg.histogram("exec.autotune.trial_s").observe(dt)
    bci, bco, best = min(trials, key=lambda t: t[2])
    reg.counter("exec.autotune.tuned", backend=backend).inc()
    return TuneResult(skey, bci, bco, best * 1e6, trials)


def _null():
    from contextlib import nullcontext
    return nullcontext()


def conv_shapes(model) -> list[dict]:
    """Distinct conv-epilogue invocation shapes of a model, fused the
    way the compiler will fuse them (conv->pool chains collapse into
    one shape with ``pool`` set).  Spatial dims come from the model's
    full (untiled) geometry — representative, and irrelevant to the
    spatial-size-agnostic key."""
    from .compiler import fusable_chains
    g = model.graph
    fusion = fusable_chains(g, frozenset(g.layers))
    shapes: dict[str, dict] = {}
    for n, spec in g.layers.items():
        if spec.kind != "conv":
            continue
        ps = g.preds[n]
        w_in, h_in = (model.full_sizes[ps[0]] if ps else model.input_size)
        pw, ph = spec.padding
        x_shape = (1, h_in + 2 * ph, w_in + 2 * pw, spec.in_channels)
        w_shape = (spec.kernel[1], spec.kernel[0], spec.in_channels,
                   spec.out_channels)
        stride = (spec.stride[1], spec.stride[0])
        pool = None
        if n in fusion:
            pspec = g.layers[fusion[n]]
            pool = (pspec.kernel[1], pspec.kernel[0])
        d = dict(x_shape=x_shape, w_shape=w_shape, stride=stride,
                 act=spec.act, pool=pool)
        shapes.setdefault(
            shape_key(x_shape, w_shape, stride, spec.act, pool), d)
    return list(shapes.values())


def autotune_model(model, *, backend: str = "pallas",
                   table: CostTable | None = None,
                   candidates: Iterable[tuple[int, int]] = DEFAULT_CANDIDATES,
                   iters: int = 3, install_winners: bool = True,
                   key: jax.Array | None = None
                   ) -> tuple[CostTable, list[TuneResult]]:
    """Tune every distinct conv shape of ``model`` not already present
    in ``table.kernels`` (a loaded artifact re-tunes nothing), merge the
    winners into the table, and (by default) install them process-wide.

    Returns ``(table, results)`` where ``results`` holds only the
    shapes actually tuned this call."""
    table = table if table is not None else CostTable()
    results: list[TuneResult] = []
    for d in conv_shapes(model):
        skey = shape_key(d["x_shape"], d["w_shape"], d["stride"],
                         d["act"], d["pool"], backend)
        if skey in table.kernels:
            continue
        res = autotune_conv(d["x_shape"], d["w_shape"], stride=d["stride"],
                            act=d["act"], pool=d["pool"], backend=backend,
                            candidates=candidates, iters=iters, key=key)
        table.kernels[skey] = res.entry(backend)
        results.append(res)
    if install_winners and table.kernels:
        install(table.kernels)
    return table, results
