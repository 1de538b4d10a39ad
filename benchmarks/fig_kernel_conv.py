"""Conv-kernel microbenchmark: tuned Pallas vs XLA ref vs pre-tuning tiles.

For every *distinct* conv-epilogue shape in the zoo (channels, filter,
stride, fused activation/pool — spatial sizes shrunk to smoke scale), times
three lowerings of the same fused chain:

* ``tuned``  — the Pallas kernel at the autotuner's winning
  (block_ci, block_co);
* ``legacy`` — the Pallas kernel at the pre-autotune ``_pick_tile``
  divisor blocks (what every conv used before tuning existed);
* ``xla``    — the composed ``lax`` reference sequence.

On CPU the Pallas kernel runs in interpret mode, so absolute wall
times are not meaningful to gate; the *structural* outcomes are: the
summary row pins ``fallbacks`` (must be 0 — every zoo conv now has a
Pallas lowering) and ``shapes`` (coverage), both deterministic.  On a
TPU the same rows become real kernel speedups.

Rows::

    kernel_conv/<key>      tuned us; xla_us, legacy_us, tuned_vs_legacy
    kernel_conv/summary    total tuned us; shapes, fallbacks, tuned counts

``export_autotune(path)`` writes the accumulated winners as a
versioned CostTable artifact (CI uploads it from the bench-smoke job).
"""

from __future__ import annotations

import time
import warnings

import jax

from .common import csv_row
from repro.api import artifacts
from repro.core.cost import CostTable
from repro.exec.autotune import autotune_conv, conv_shapes, install, installed
from repro.kernels.conv2d.conv2d import _pick_tile
from repro.kernels.conv2d.ops import (conv2d_fused, fallback_count,
                                      reset_fallbacks)
from repro.models.cnn import zoo

# tiny zoo builds: every distinct conv *channel geometry* of the seven
# models at smoke scale (interpret mode makes full-size spatial dims
# pointless on CPU)
ZOO_TINY = {
    "vgg16": dict(input_size=(40, 40), scale=0.1, head=False),
    "yolov2": dict(input_size=(64, 64), scale=0.05),
    "resnet34": dict(input_size=(64, 64), scale=0.1),
    "inceptionv3": dict(input_size=(96, 96), scale=0.1),
    "squeezenet": dict(input_size=(64, 64), scale=0.1),
    "mobilenetv3": dict(input_size=(64, 64), scale=0.1),
    "nasnet": dict(n_cells=2, input_size=(48, 48), scale=0.15),
}

# smoke candidate set: small blocks only — zoo-tiny channel counts never
# reach 128, and interpret-mode trials are wall-time-expensive
SMOKE_CANDIDATES = ((32, 32), (16, 16), (8, 8))
SMOKE_SHAPE_CAP = 12    # distinct shapes benched in --smoke mode


def _bench(fn, iters: int = 2) -> float:
    jax.block_until_ready(fn())   # compile outside the timed region
    best = float("inf")
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        best = min(best, time.perf_counter() - t0)
    return best


def distinct_conv_shapes(smoke: bool = False) -> list[dict]:
    """Distinct conv-epilogue shapes across the whole zoo, round-robin
    interleaved across models so a capped smoke subset still covers
    every model's characteristic convs (strided stems, fused pools)
    rather than just the first model's.  The cap itself is logged in
    the summary row, not silent."""
    seen: set[tuple] = set()
    per_model: list[list[dict]] = []
    for name, cfg in ZOO_TINY.items():
        m = zoo.build(name, **cfg)
        mine = []
        for d in conv_shapes(m):
            k = (d["w_shape"][-2], d["w_shape"][-1], d["w_shape"][:2],
                 d["stride"], d["pool"])
            if k not in seen:
                seen.add(k)
                mine.append(d)
        per_model.append(mine)
    out: list[dict] = []
    for i in range(max(len(m) for m in per_model)):
        out.extend(m[i] for m in per_model if i < len(m))
    return out


def run(smoke: bool = False) -> list[str]:
    rows: list[str] = []
    shapes = distinct_conv_shapes(smoke)
    total = len(shapes)
    if smoke:
        shapes = shapes[:SMOKE_SHAPE_CAP]
    candidates = SMOKE_CANDIDATES if smoke else None
    iters = 1 if smoke else 3
    reset_fallbacks()
    t_tuned_sum = 0.0
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)
        for d in shapes:
            kw = dict(stride=d["stride"], act=d["act"], pool=d["pool"])
            res = autotune_conv(
                d["x_shape"], d["w_shape"], iters=iters,
                **(dict(candidates=candidates) if candidates else {}), **kw)
            install({res.key: res.entry()})
            key, k1, k2, k3 = jax.random.split(jax.random.PRNGKey(0), 4)
            x = jax.random.normal(k1, d["x_shape"])
            w = jax.random.normal(k2, d["w_shape"]) * 0.1
            b = jax.random.normal(k3, (d["w_shape"][-1],))
            t_tuned = _bench(lambda: conv2d_fused(
                x, w, b, block_ci=res.block_ci, block_co=res.block_co,
                interpret=True, **kw), iters)
            t_legacy = _bench(lambda: conv2d_fused(
                x, w, b, block_ci=_pick_tile(d["w_shape"][-2]),
                block_co=_pick_tile(d["w_shape"][-1]),
                interpret=True, **kw), iters)
            t_xla = _bench(lambda: conv2d_fused(
                x, w, b, use_pallas=False, **kw), iters)
            t_tuned_sum += t_tuned
            ci, co = d["w_shape"][-2], d["w_shape"][-1]
            kh, kw_ = d["w_shape"][:2]
            sh, sw = d["stride"]
            tag = (f"c{ci}-c{co}-k{kh}x{kw_}-s{sh}x{sw}"
                   + ("-pool" if d["pool"] else ""))
            rows.append(csv_row(
                f"kernel_conv/{tag}", t_tuned * 1e6,
                f"xla_us={t_xla * 1e6:.1f};legacy_us={t_legacy * 1e6:.1f};"
                f"tuned_vs_legacy={t_legacy / t_tuned:.2f};"
                f"blocks={res.block_ci}x{res.block_co}"))
    rows.append(csv_row(
        "kernel_conv/summary", t_tuned_sum * 1e6,
        f"shapes={len(shapes)};shapes_total={total};"
        f"fallbacks={fallback_count()};tuned={len(installed())}"))
    return rows


def export_autotune(path: str) -> str:
    """Write the winners installed by :func:`run` as a versioned
    CostTable artifact JSON (the autotune-results CI artifact)."""
    table = CostTable(kernels=installed())
    with open(path, "w") as fh:
        fh.write(artifacts.cost_table_to_json(table, indent=1))
        fh.write("\n")
    return path


if __name__ == "__main__":
    for r in run(smoke=True):
        print(r)
