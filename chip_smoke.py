"""Smoke run of the main path on a TPU: VGG16 @224, published widths.

    python chip_smoke.py              # one chip: every phase below
    python chip_smoke.py --chips 4    # four chips: the dist phase only

The model is ``zoo.vgg16()`` (224x224, scale 1.0: 13 convs, 14.7 M conv
parameters) with random weights from ``--seed``, planned onto the
four-device Raspberry-Pi cluster of the paper's testbed, which gives a
four-stage pipeline.  Everything goes through the entry points a user
calls: ``repro.compile`` -> ``Deployment.run`` (one frame, then a stack
of four through the ``lax.scan`` path) -> ``Deployment.fleet`` (thread
workers, memory links), and ``Deployment.run`` again with the Pallas
conv backend compiled for the chip.

Every phase's outputs are checked against :func:`reference_forward`, a
plain float32 VGG16 written here at ``Precision.HIGHEST``.  The
thread-mode dist outputs must equal ``Deployment.run``'s exactly.  Any
failed check raises, so the exit code is non-zero and no result line is
printed.  With no TPU the script exits non-zero and names the platform
it found; it never falls back to the CPU.

The times printed are one-off smoke numbers, not benchmark results.  The
last line of stdout is ``{"ok": true, "device": {...}}``.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import repro  # noqa: E402
from repro.core import make_pi_cluster  # noqa: E402
from repro.exec.backends import default_interpret  # noqa: E402
from repro.exec.cache import enable_compile_cache  # noqa: E402
from repro.kernels.conv2d.ops import fallback_count, reset_fallbacks  # noqa: E402
from repro.models.cnn import zoo  # noqa: E402

# Normalised error bound: max |out - ref| over max |ref|, per phase.  The
# reference runs f32 at HIGHEST precision.  The path under test runs
# f32 convs and dots at default precision, which on a TPU is one bf16
# pass: each operand rounds to 8 mantissa bits (relative error <= 2^-9),
# so a layer's output carries ~0.3% relative error, and 15 layers
# compound that to ~1% at the logits.  5e-2 leaves a 5x margin over that
# while any indexing, halo or layout fault gives errors of order 1.
TOL = 5e-2
CLUSTER_GHZ = (1.5, 1.2, 1.0, 0.8)


def log(msg: str) -> None:
    print(msg, flush=True)


def reference_forward(model, params, x):
    """Plain f32 VGG16: 3x3 SAME convs + bias + ReLU, 2x2 max-pools, a
    global mean and two dense layers, all at ``Precision.HIGHEST``.
    Independent of the path under test (no ``apply_layer``).  Returns
    the logits, shape ``(N, classes)``."""
    hi = lax.Precision.HIGHEST
    g = model.graph
    for name in g.topo_order:
        spec, p = g.layers[name], params.get(name)
        if spec.kind == "conv":
            if spec.kernel != (3, 3) or spec.stride != (1, 1) \
                    or spec.padding != (1, 1):
                raise ValueError(f"{name}: not a VGG16 conv: {spec}")
            x = lax.conv_general_dilated(
                x, p["w"], (1, 1), "SAME",
                dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=hi)
            x = jnp.maximum(x + p["b"], 0.0)
        elif spec.kind == "pool":
            if spec.kernel != (2, 2) or spec.stride != (2, 2):
                raise ValueError(f"{name}: not a VGG16 pool: {spec}")
            x = lax.reduce_window(x, -jnp.inf, lax.max, (1, 2, 2, 1),
                                  (1, 2, 2, 1), "VALID")
        elif spec.kind == "gpool":
            x = jnp.mean(x, axis=(1, 2))
        elif spec.kind == "fc":
            x = jnp.dot(x, p["w"], precision=hi) + p["b"]
        else:
            raise ValueError(f"{name}: unexpected layer kind {spec.kind!r}")
    return x


def build(size: int = 224, scale: float = 1.0, seed: int = 0):
    """VGG16 and the paper's four-Pi cluster; random frames from ``seed``:
    one single frame and a stack of four."""
    model = zoo.vgg16(input_size=(size, size), scale=scale)
    rng = np.random.default_rng(seed)
    frames = [rng.standard_normal((1, size, size, 3), dtype=np.float32)
              for _ in range(5)]
    return model, make_pi_cluster(list(CLUSTER_GHZ)), frames


def _sink(out: dict):
    """The single graph sink (VGG16's logits) as a ``(N, classes)`` array."""
    (y,) = out.values()
    y = np.asarray(y)
    return y.reshape(y.shape[0], -1)


def check_close(label: str, outs: list, refs: list) -> None:
    """Normalised max error of each output against its reference; raises
    past :data:`TOL`."""
    abs_err = max(float(np.max(np.abs(o - r))) for o, r in zip(outs, refs))
    scale = max(float(np.max(np.abs(r))) for r in refs)
    rel = abs_err / scale
    log(f"{label}: max abs err {abs_err:.3e}, max rel err {rel:.3e} "
        f"(tol {TOL:g}, relative to max |ref| {scale:.3e})")
    if not (np.isfinite(rel) and rel <= TOL):
        raise AssertionError(f"{label}: rel err {rel:.3e} exceeds {TOL:g}")


def _timed(fn):
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn())
    return out, time.perf_counter() - t0


def phase_run(label: str, dep, frames, refs, iters: int = 5) -> None:
    """``Deployment.run``: the single frame, then the four-frame stack
    through ``run_frames``; checks both against ``refs``."""
    single, stack = frames[0], frames[1:]
    out, first_s = _timed(lambda: dep.run(single))
    log(f"{label}: first single-frame call (trace + compile + run) "
        f"{first_s:.2f} s")
    t0 = time.perf_counter()
    for _ in range(iters):
        _timed(lambda: dep.run(single))
    ms = (time.perf_counter() - t0) / iters * 1e3
    log(f"{label}: single-frame wall {ms:.2f} ms after warm-up "
        f"(one-off smoke number, not a benchmark)")
    outs, scan_s = _timed(lambda: dep.run(stack))
    log(f"{label}: first 4-frame run_frames call (trace + compile + run) "
        f"{scan_s:.2f} s")
    check_close(label, [_sink(out)] + [_sink(o) for o in outs], refs)


def single_frame_outputs(dep, frames) -> list[dict]:
    """``Deployment.run`` one frame at a time, on the default device: what
    the thread-mode dist workers must reproduce bit for bit."""
    return [{k: np.asarray(v) for k, v in dep.run(f).items()}
            for f in frames]


def phase_dist(dep, frames, refs, single_outs) -> list[int]:
    """Thread workers over memory links on the four stacked frames: no
    frame dropped, outputs equal to ``single_outs`` exactly, stage ``i``
    on local device ``i`` (mod the device count).  Returns the device id
    each worker reports."""
    stack = frames[1:]
    rep = dep.fleet(repro.DistSpec(transport="memory",
                                   workers="thread")).run(stack)
    ids = [rep.worker_stats[f"w{i}"]["device_id"]
           for i in range(rep.n_stages)]
    log(f"dist: {rep.completed}/{rep.submitted} frames, "
        f"{len(rep.dropped)} dropped, {rep.n_stages} thread workers on "
        f"device ids {ids}, run wall {rep.wall_s:.2f} s "
        f"(start + warm-up probe + 4 frames + drain)")
    if rep.dropped or rep.completed != len(stack):
        raise AssertionError(f"dist dropped frames: {rep.dropped}")
    local = jax.local_devices()
    want = [local[i % len(local)].id for i in range(rep.n_stages)]
    if ids != want:
        raise AssertionError(f"dist workers on devices {ids}, want {want}")
    for fid, ref in enumerate(single_outs):
        got = rep.outputs[fid]
        for k, v in ref.items():
            if got[k].shape != v.shape or not np.array_equal(got[k], v):
                raise AssertionError(
                    f"dist frame {fid} sink {k!r} differs from "
                    f"Deployment.run (max |diff| "
                    f"{float(np.max(np.abs(got[k] - v))):.3e})")
    log("dist: outputs equal Deployment.run bit for bit")
    check_close("dist", [_sink(rep.outputs[i]) for i in range(len(stack))],
                refs[1:])
    return ids


def phase_pallas(model, cluster, frames, refs, *, interpret: bool) -> int:
    """``Deployment.run`` with the Pallas conv backend.  ``interpret`` is
    the mode the platform must pick (False on a TPU).  Raises on any
    ``conv.fallback``; returns the fallback count (0)."""
    if default_interpret() != interpret:
        raise AssertionError(f"pallas interpret mode is "
                             f"{default_interpret()}, want {interpret}")
    dep = repro.compile(model, cluster,
                        exec_spec=repro.ExecSpec(backend="pallas"))
    reset_fallbacks()
    phase_run("pallas", dep, frames, refs)
    n = fallback_count()
    log(f"pallas: interpret={default_interpret()}, conv fallbacks {n}")
    if n:
        raise AssertionError(f"pallas backend fell back {n} time(s)")
    return n


def references(model, params, frames) -> list:
    fwd = jax.jit(lambda p, x: reference_forward(model, p, x))
    return [np.asarray(fwd(params, f)) for f in frames]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-chip dist phase and the "
                         "one-chip Deployment.run it is compared with")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random frames (weights: PRNGKey(0))")
    args = ap.parse_args(argv)

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found platform "
              f"{dev.platform!r}", file=sys.stderr)
        return 2
    n = len(jax.devices())
    if n < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, JAX found {n}", file=sys.stderr)
        return 2
    log(f"compile cache: {enable_compile_cache()}")
    log(f"platform {dev.platform}")
    log(f"device_kind {dev.device_kind}")
    log(f"device_count {n}")

    model, cluster, frames = build(seed=args.seed)
    dep = repro.compile(model, cluster)
    log(f"plan: {len(dep.pico.pipeline.stages)} stages")
    params = dep.load_params().params
    refs = references(model, params, frames)
    if args.chips == 1:
        phase_run("xla", dep, frames, refs)
    ids = phase_dist(dep, frames, refs,
                     single_frame_outputs(dep, frames[1:]))
    if args.chips == 4 and len(set(ids)) != 4:
        raise AssertionError(f"want 4 distinct device ids, got {ids}")
    if args.chips == 1:
        phase_pallas(model, cluster, frames, refs, interpret=False)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": n}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
