"""Pallas TPU conv2d kernel (implicit GEMM) — the paper's compute hot spot.

The kernel decomposes the convolution into KH*KW shifted matmuls feeding
the MXU, with an fp32 VMEM accumulator.  The grid is (batch, output-row
band, out-channel block, in-channel block); the in-channel axis is
innermost so the accumulator lives across its iterations (sequential
grid on TPU).

Layout: NHWC x HWIO -> NHWC, VALID (the executable zoo's tiled stages
present exactly this: padding is materialized by the stage boundary).

What bounds the kernel's VMEM footprint: output rows are tiled into
bands of ``rows`` (about ``_BAND_M`` GEMM rows each), and every band
reads its input rows plus a ``KH-1``-row halo.  The wrapper materializes
the halo'd bands once in HBM, so no block or accumulator scales with the
whole H x W — VGG16's first convs at 224x224 fit the scoped VMEM.

Supported conv space:

* any stride >= 1 per spatial axis — the wrapper splits the input into
  its stride phases (space-to-depth: phase (p, q) moves into channels)
  and the filter to match, so every conv the kernel sees has stride 1
  and every in-kernel slice is contiguous (Mosaic lowers a strided
  in-kernel slice to a gather it refuses);
* any channel count — inputs/weights are zero-padded up to the channel
  block in the wrapper (zeros contribute nothing to the accumulation and
  the padded out-channel tail is sliced off), so the MXU block size never
  degrades to a tiny divisor tile for channel tails;
* a fused epilogue executed inside the accumulator emit: bias add, the
  activation (relu, leaky relu or none), and an optional non-overlapping
  max-pool (kernel == stride, e.g. 2x2), all in fp32 before the final
  cast, so a conv->bias->activation->pool chain is
  one Pallas call with no VMEM round-trips between the ops.  Bands hold a
  multiple of the pool height, so a band pools on the global pool grid.

Channel block sizes (``block_ci``/``block_co``) are tunable —
``repro.exec.autotune`` searches them per conv shape and persists the
winners in the CostTable artifact.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...core.graph import LEAKY_SLOPE

# GEMM rows (band rows x output width) per grid step: large enough to
# keep the MXU fed, small enough that the f32 accumulator (<= 1 MiB at
# a 128-wide out-channel block) and the input band fit the scoped VMEM
_BAND_M = 2048


def _conv2d_kernel(*refs, kh: int, kw: int, rows: int, w_out: int,
                   n_ci_blocks: int, act: str,
                   pool: tuple[int, int] | None, has_bias: bool):
    if has_bias:
        x_ref, w_ref, b_ref, o_ref, acc_ref = refs
    else:
        x_ref, w_ref, o_ref, acc_ref = refs
        b_ref = None
    ci = pl.program_id(3)

    @pl.when(ci == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # x_ref: (rows + kh - 1, W_in, TCI) halo'd band; w_ref: (KH, KW, TCI, TCO)
    acc = acc_ref[...]
    for dh in range(kh):
        for dw in range(kw):
            patch = x_ref[dh:dh + rows, dw:dw + w_out, :]    # (rows, WO, TCI)
            lhs = patch.reshape(rows * w_out, patch.shape[-1])
            acc += jnp.dot(lhs, w_ref[dh, dw],
                           preferred_element_type=jnp.float32)
    acc_ref[...] = acc

    @pl.when(ci == n_ci_blocks - 1)
    def _emit():
        y = acc.reshape(rows, w_out, -1)
        if b_ref is not None:
            y = y + b_ref[0]
        if act == "relu":
            y = jnp.maximum(y, 0.0)
        elif act == "leaky":
            y = jnp.where(y >= 0.0, y, LEAKY_SLOPE * y)
        if pool is not None:
            ph, pw = pool
            wp = w_out // pw
            y = y[:, :wp * pw, :]
            y = y.reshape(rows // ph, ph, wp, pw, y.shape[-1]).max(axis=(1, 3))
        o_ref[...] = y.astype(o_ref.dtype)


def _space_to_depth(x: jax.Array, w: jax.Array, stride: tuple[int, int],
                    h_out: int, w_out: int) -> tuple[jax.Array, jax.Array]:
    """Rewrite a strided VALID conv as a stride-1 one.

    Input row ``i*sh + p`` becomes row ``i`` of phase ``p`` and the phases
    move into channels; filter tap ``a*sh + p`` moves the same way, with
    zeros where ``a*sh + p`` runs past the filter.  Phases no tap reads
    (``p >= KH``, e.g. a 1x1 stride-2 projection) are dropped."""
    N, H, W, CI = x.shape
    KH, KW, _, CO = w.shape
    sh, sw = stride
    kh2, kw2 = -(-KH // sh), -(-KW // sw)
    nph, npw = min(sh, KH), min(sw, KW)
    hs, ws = h_out + kh2 - 1, w_out + kw2 - 1
    x = jnp.pad(x, ((0, 0), (0, max(0, hs * sh - H)),
                    (0, max(0, ws * sw - W)), (0, 0)))[:, :hs * sh, :ws * sw]
    x = x.reshape(N, hs, sh, ws, sw, CI)[:, :, :nph, :, :npw]
    x = x.transpose(0, 1, 3, 2, 4, 5).reshape(N, hs, ws, nph * npw * CI)
    w = jnp.pad(w, ((0, kh2 * sh - KH), (0, kw2 * sw - KW), (0, 0), (0, 0)))
    w = w.reshape(kh2, sh, kw2, sw, CI, CO)[:, :nph, :, :npw]
    w = w.transpose(0, 2, 1, 3, 4, 5).reshape(kh2, kw2, nph * npw * CI, CO)
    return x, w


def _band_rows(h_out: int, w_out: int, pool_h: int) -> int:
    """Output rows per band: about ``_BAND_M`` GEMM rows, a multiple of
    the pool height, and no more than the (pool-rounded) output."""
    rows = min(max(1, _BAND_M // w_out), h_out)
    return max(pool_h, rows // pool_h * pool_h)


def _pick_tile(c: int, pref: int = 128) -> int:
    """Pre-padding tile heuristic: largest power-of-two *divisor* of the
    channel count.  Kept as the legacy reference the microbench compares
    tuned blocks against; the fast path no longer needs a divisor (the
    wrapper pads channel tails up to the block)."""
    if c % pref == 0:
        return pref
    for t in (64, 32, 16, 8):
        if c % t == 0:
            return t
    return c


def _pick_block(c: int, pref: int = 128) -> int:
    """Default channel block: the MXU-aligned 128 when the axis reaches
    it, else the axis rounded up to the next power of two >= 8 (a single
    zero-padded block)."""
    if c >= pref:
        return pref
    b = 8
    while b < c:
        b *= 2
    return b


@functools.partial(jax.jit, static_argnames=(
    "stride", "act", "pool", "block_ci", "block_co", "interpret"))
def conv2d_fused(x: jax.Array, w: jax.Array, b: jax.Array | None = None, *,
                 stride: tuple[int, int] = (1, 1), act: str = "linear",
                 pool: tuple[int, int] | None = None,
                 block_ci: int | None = None, block_co: int | None = None,
                 interpret: bool = False) -> jax.Array:
    """x: (N, H, W, CI); w: (KH, KW, CI, CO); b: (CO,) or None.

    Strided VALID conv with the fused epilogue described in the module
    docstring.  ``act`` is ``"relu"``, ``"leaky"`` or ``"linear"`` (no
    activation).  ``pool`` is the max-pool window (== its stride); the
    pooled output is ``(H_out // ph, W_out // pw)`` — identical to a
    VALID non-overlapping ``lax.reduce_window``.  ``block_ci`` /
    ``block_co`` override the channel block sizes (autotune winners).
    """
    N, H, W, CI = x.shape
    KH, KW, CI2, CO = w.shape
    assert CI == CI2, (x.shape, w.shape)
    sh, sw = stride
    HO = (H - KH) // sh + 1
    WO = (W - KW) // sw + 1
    if (sh, sw) != (1, 1):
        x, w = _space_to_depth(x, w, (sh, sw), HO, WO)
        N, H, W, CI = x.shape
        KH, KW = w.shape[:2]
    tci = block_ci or _pick_block(CI)
    tco = block_co or _pick_block(CO)
    ci_pad = -CI % tci
    co_pad = -CO % tco
    if ci_pad:
        x = jnp.pad(x, ((0, 0), (0, 0), (0, 0), (0, ci_pad)))
        w = jnp.pad(w, ((0, 0), (0, 0), (0, ci_pad), (0, 0)))
    if co_pad:
        w = jnp.pad(w, ((0, 0), (0, 0), (0, 0), (0, co_pad)))
        if b is not None:
            b = jnp.pad(b, (0, co_pad))
    n_ci = (CI + ci_pad) // tci
    n_co = (CO + co_pad) // tco
    ph, pw = pool if pool is not None else (1, 1)
    HP, WP = HO // ph, WO // pw
    rows = _band_rows(HO, WO, ph)
    n_h = -(-HO // rows)
    # halo'd row bands (N, n_h, rows + KH - 1, W, C): band i holds input
    # rows [i*rows, i*rows + rows + KH - 1), zero-padded past the bottom
    x = jnp.pad(x, ((0, 0), (0, max(0, n_h * rows + KH - 1 - H)),
                    (0, 0), (0, 0)))
    x = x[:, np.arange(n_h)[:, None] * rows + np.arange(rows + KH - 1)]

    grid = (N, n_h, n_co, n_ci)
    kernel = functools.partial(
        _conv2d_kernel, kh=KH, kw=KW, rows=rows, w_out=WO,
        n_ci_blocks=n_ci, act=act, pool=pool, has_bias=b is not None)
    in_specs = [
        pl.BlockSpec((None, None, rows + KH - 1, W, tci),
                     lambda n, h, co, ci: (n, h, 0, 0, ci)),
        pl.BlockSpec((KH, KW, tci, tco), lambda n, h, co, ci: (0, 0, ci, co)),
    ]
    args = [x, w]
    if b is not None:
        in_specs.append(pl.BlockSpec((1, tco), lambda n, h, co, ci: (0, co)))
        args.append(b.reshape(1, -1))
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((None, rows // ph, WP, tco),
                               lambda n, h, co, ci: (n, h, 0, co)),
        out_shape=jax.ShapeDtypeStruct(
            (N, n_h * rows // ph, WP, CO + co_pad), x.dtype),
        scratch_shapes=[pltpu.VMEM((rows * WO, tco), jnp.float32)],
        interpret=interpret,
    )(*args)
    return out[:, :HP, :, :CO]


def conv2d(x: jax.Array, w: jax.Array, *,
           stride: tuple[int, int] = (1, 1),
           block_ci: int | None = None, block_co: int | None = None,
           interpret: bool = False) -> jax.Array:
    """Plain strided VALID conv (no epilogue) — thin alias over
    :func:`conv2d_fused`."""
    return conv2d_fused(x, w, None, stride=stride, block_ci=block_ci,
                        block_co=block_co, interpret=interpret)
