"""Core of the chip benchmark: finds a cell's files by the names in
``BENCHMARK.json``, drives the cell's entry, reads the metrics, checks
the outputs against the plain reference and forms the result line.

Everything that belongs to one configuration, traffic mix, metric,
reference family or entry sits in a file of its own under the
benchmark's directory (``paths[0]``), found by name:

    configs/<config>.json        the configuration (also named by ``file``)
    traffic/<traffic>.json       the traffic mix: entry, loop, rate, sizes
    checks/<cell>.json           the limits of the numbers ``correct`` compares
    entries/<entry>.py           the client that drives one entry point
    references/<family>.py       the plain float32 reference
    metrics/<metric>.py          ``read(run) -> float | None``
"""

from __future__ import annotations

import gc
import importlib.util
import json
import math
import sys
import time
from dataclasses import dataclass, field
from pathlib import Path

import numpy as np

#: frames the reference runs at once; one shape, compiled once
REF_BLOCK = 8
#: the longest window a traced run traces (and runs): a trace grows with
#: the window, the profiler slows the host, and reading it must end soon
TRACE_SECONDS = 10.0


def _load_module(path: Path, prefix: str):
    if not path.is_file():
        raise FileNotFoundError(f"no {path}")
    name = "chipbench_" + prefix + "_" + "".join(
        c if c.isalnum() else "_" for c in path.stem)
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Bench:
    """``BENCHMARK.json`` at ``root`` and the files it names."""

    def __init__(self, root):
        self.root = Path(root)
        self.spec = json.loads((self.root / "BENCHMARK.json").read_text())
        self.base = self.root / self.spec["paths"][0]
        if str(self.base) not in sys.path:
            sys.path.insert(0, str(self.base))     # references: chipbench
        self._modules: dict[tuple[str, str], object] = {}

    def _named(self, key: str, name: str) -> dict:
        for entry in self.spec[key]:
            if entry["name"] == name:
                return entry
        raise KeyError(f"BENCHMARK.json {key} has no {name!r}")

    def workload(self, name: str) -> dict:
        return self._named("workloads", name)

    def config(self, name: str) -> dict:
        return json.loads((self.root / self._named("configs", name)["file"])
                          .read_text())

    def traffic(self, name: str) -> dict:
        return json.loads((self.base / "traffic" / f"{name}.json")
                          .read_text())

    def module(self, kind: str, name: str):
        """``kind`` is ``metrics``, ``references`` or ``entries``."""
        key = (kind, name)
        if key not in self._modules:
            self._modules[key] = _load_module(
                self.base / kind / f"{name}.py", kind)
        return self._modules[key]

    def peaks(self, device_kind: str) -> dict:
        table = json.loads((self.base / "peaks.json").read_text())
        if device_kind not in table:
            raise KeyError(f"peaks.json has no device kind {device_kind!r}")
        return table[device_kind]

    def reports(self, metric: dict, cell: str) -> bool:
        """Does ``cell`` report ``metric``?  A per-layer metric without a
        ``workloads`` key goes wherever the metric it moves goes."""
        if "workloads" in metric:
            return cell in metric["workloads"]
        if metric in self.spec["end_to_end"]:
            return True
        return self.reports(self._named("end_to_end", metric["moves"]), cell)

    def metrics(self, cell: str, traced: bool) -> list[dict]:
        key = "per_layer" if traced else "end_to_end"
        return [m for m in self.spec[key] if self.reports(m, cell)]

    def cell(self, name: str) -> "Cell":
        w = self.workload(name)
        cfg = self.config(w["config"])
        traffic = self.traffic(w["traffic"])
        limits = json.loads((self.base / "checks" / f"{name}.json")
                            .read_text())
        return Cell(name=name, chips=int(w["chips"]), config=cfg,
                    traffic=traffic, limits=limits,
                    family=self.module("references", cfg["family"]),
                    entry=self.module("entries", traffic["entry"]))


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    family: object
    entry: object

    @property
    def layers(self) -> list[dict]:
        return self.family.layers(self.config)


def sub_seed(seed: int, stream: int) -> np.random.Generator:
    """An independent generator per use of ``--seed`` (any size)."""
    return np.random.default_rng([int(seed) % (1 << 64), stream])


def weight_seed(seed: int) -> int:
    """The ``PRNGKey`` integer of a run's weights, drawn from ``--seed``."""
    return int(sub_seed(seed, 0).integers(0, 2 ** 31 - 1))


def bias_seed(seed: int) -> int:
    """The ``PRNGKey`` integer of the draws behind a run's biases."""
    return int(sub_seed(seed, 4).integers(0, 2 ** 31 - 1))


@dataclass
class Run:
    """What one run measured; the metric readers read it."""

    cell: Cell
    seed: int
    seconds: float
    traced: bool
    t_start: float                          # process start, perf_counter
    peak: dict | None = None                # peaks.json row, on a chip
    setup_s: float = math.nan
    warmup_s: float = math.nan
    window_s: float = math.nan
    frames_in_window: int = 0
    latencies_s: list[float] | None = None  # every frame due in the window
    attempted: int = 0
    failed: int = 0                         # dropped by the program
    biases: list | None = None              # fed to the program, or zero
    group: int = 1                          # frames per call of the entry
    missing: int = 0                        # never came back
    outputs: dict[int, np.ndarray] = field(default_factory=dict)
    frame_of: dict[int, int] = field(default_factory=dict)
    pool: np.ndarray | None = None          # (n, 1, H, W, C) frames
    dep: object = None
    dist_report: object = None
    trace: object = None                    # tracing.Trace
    memory_peak_bytes: int = 0

    def window_opened(self) -> None:
        self.setup_s = time.perf_counter() - self.t_start

    def note(self, msg: str) -> None:
        print(msg, file=sys.stderr, flush=True)

    def mark(self, phase: str) -> None:
        """Note how far into set-up ``phase`` ended."""
        t = time.perf_counter() - self.t_start
        self.note(f"setup: {phase} by {t:.2f} s")


# ---------------------------------------------------------------------------
# helpers the entries share
# ---------------------------------------------------------------------------

def deploy(run: Run):
    """The cell's model through ``repro.compile`` on
    ``make_tpu_cluster(chips)``, with the program's default specs."""
    import repro
    from repro.core import make_tpu_cluster
    from repro.models.cnn import zoo
    cfg = run.cell.config
    model = getattr(zoo, cfg["zoo"])(
        input_size=tuple(cfg["input_size"]), scale=cfg["scale"])
    dep = repro.compile(model, make_tpu_cluster(run.cell.chips))
    run.dep = dep
    run.note(f"plan: {len(dep.pico.pipeline.stages)} stage(s) on "
             f"{run.cell.chips} chip(s)")
    run.mark("compile (plan)")
    return dep, model


#: calibration frames the benchmark's biases are folded on
FOLD_FRAMES = 8


def _fold(run: Run, kw, kz, x):
    """Traced body of :func:`fold_biases`, on calibration frames ``x``."""
    import jax

    from . import refops
    fam, cfg, layers = run.cell.family, run.cell.config, run.cell.layers
    zs = jax.random.normal(kz, (len(layers), max(
        layer["cout"] for layer in layers)))
    params = [(p[0], z[:layer["cout"]]) for p, z, layer in
              zip(fam.init(cfg, kw), zs, layers)]
    fold = refops.Fold()
    fam.forward(cfg, params, x, "f32", fold)
    return fold.biases


def _keys(run: Run):
    import jax
    return (jax.random.PRNGKey(weight_seed(run.seed)),
            jax.random.PRNGKey(bias_seed(run.seed)))


def _calibration(run: Run) -> np.ndarray:
    """The ``FOLD_FRAMES`` calibration frames, drawn from the seed.  They
    go into the jitted fold as an argument: as a constant they would
    make a program of their own for every seed, compiled in set-up."""
    cfg = run.cell.config
    w, h = cfg["input_size"]
    return sub_seed(run.seed, 5).standard_normal(
        (FOLD_FRAMES, h, w, cfg["in_channels"]), dtype=np.float32)


def fold_biases(run: Run) -> list:
    """The benchmark's non-zero biases for the run (``refops.Fold``): the
    reference family, with its own weights from the run's key and unit
    normal draws from ``bias_seed`` as biases, folded on
    ``FOLD_FRAMES`` calibration frames drawn from the seed, in float32
    at ``Precision.HIGHEST``; kept in ``run.biases``."""
    import jax
    run.biases = jax.block_until_ready(jax.jit(
        lambda kw, kz, x: _fold(run, kw, kz, x))(*_keys(run),
                                                   _calibration(run)))
    return run.biases


def program_params(run: Run, model):
    """The program's ``model.init`` weights from the run's key with the
    benchmark's biases (:func:`fold_biases`) in place of its zero ones,
    made on the device in one jitted call."""
    import jax

    def make(kw, kz, x):
        bs = _fold(run, kw, kz, x)
        params = model.init(kw)
        assert len(params) == len(bs), (len(params), len(bs))
        return {n: dict(p, b=b) for (n, p), b in
                zip(params.items(), bs)}, bs
    params, run.biases = jax.block_until_ready(
        jax.jit(make)(*_keys(run), _calibration(run)))
    run.mark("weights")
    return params


def frames(run: Run, n: int) -> np.ndarray:
    """``n`` distinct standard-normal frames ``(n, 1, H, W, C)`` from the
    seed, made once at set-up."""
    cfg = run.cell.config
    w, h = cfg["input_size"]
    pool = sub_seed(run.seed, 1).standard_normal(
        (n, 1, h, w, cfg["in_channels"]), dtype=np.float32)
    run.pool = pool
    return pool


def window(run: Run):
    """The measured window: a host annotation (and the profiler, in a
    traced run) around the block; marks the end of set-up."""
    import contextlib

    import jax

    from .tracing import WINDOW, Profile

    @contextlib.contextmanager
    def ctx():
        prof = Profile(run.cell.chips) if run.traced else None
        with contextlib.ExitStack() as stack:
            if prof is not None:
                stack.enter_context(prof)
            with jax.profiler.TraceAnnotation(WINDOW):
                run.window_opened()
                yield
        if prof is not None:
            run.trace = prof.trace
    return ctx()


# ---------------------------------------------------------------------------
# the comparison that decides ``correct``
# ---------------------------------------------------------------------------

def sample(run: Run, n: int) -> list[int]:
    """Finished frames drawn from the seed, the last one in the window
    always among them: ``n`` frames, or, where the entry passes
    ``run.group`` frames a call, ``ceil(n / group)`` whole calls, so
    that every slot of a call is compared."""
    keys = sorted(run.outputs)
    if not keys:
        return []
    rng = sub_seed(run.seed, 3)
    g = run.group
    calls = sorted({k // g for k in keys})
    pick = rng.choice(len(calls), size=min(-(-n // g), len(calls)),
                      replace=False)
    chosen = {calls[i] for i in pick.tolist()}
    return sorted({k for k in keys if k // g in chosen} | {keys[-1]})


def reference_logits(run: Run, idx: list[int],
                     mode: str = "f32") -> dict[int, np.ndarray]:
    """The reference family's logits of frames ``run.pool[idx]`` with its
    own weights from the run's key (and the benchmark's biases where the
    program was fed them, ``run.biases``), ``REF_BLOCK`` frames per
    call."""
    import jax
    import jax.numpy as jnp

    cell = run.cell
    fam, cfg = cell.family, cell.config

    def make(kw, bs):
        params = fam.init(cfg, kw)
        if bs is not None:
            params = [(w, b) for (w, _), b in zip(params, bs)]
        return params
    params = jax.jit(make)(_keys(run)[0], run.biases)
    fwd = jax.jit(lambda p, x: fam.forward(cfg, p, x, mode))
    out = {}
    for i in range(0, len(idx), REF_BLOCK):
        blk = idx[i:i + REF_BLOCK]
        x = run.pool[blk + [blk[-1]] * (REF_BLOCK - len(blk)), 0]
        y = np.asarray(fwd(params, jnp.asarray(x)))
        out.update({k: y[j] for j, k in enumerate(blk)})
    return out


def logit_err(outputs: dict[int, np.ndarray], frame_of: dict[int, int],
              refs: dict[int, np.ndarray], keys: list[int]) -> float:
    """Widest gap over ``keys``: per frame, max |out - ref| over
    max |ref|."""
    worst = 0.0
    for k in keys:
        ref = refs[frame_of[k]].reshape(-1)
        out = np.asarray(outputs[k], np.float32).reshape(-1)
        if out.shape != ref.shape:
            return math.inf
        e = float(np.max(np.abs(out - ref)) / np.max(np.abs(ref)))
        worst = max(worst, e if math.isfinite(e) else math.inf)
    return worst


def check(run: Run) -> dict[str, dict]:
    """Numbers compared, each with its limit (``checks/<cell>.json``)."""
    limits = run.cell.limits
    keys = sample(run, run.cell.traffic["check_frames"])
    idx = sorted({run.frame_of[k] for k in keys})
    if keys:
        refs = reference_logits(run, idx)
        err = logit_err(run.outputs, run.frame_of, refs, keys)
    else:
        err = math.inf
    run.note(f"check: {len(keys)} frames sampled, {len(idx)} distinct")
    return {"logit_err": {"value": err, "limit": limits["logit_err"]},
            "missing": {"value": run.missing, "limit": limits["missing"]}}


def passed(checks: dict[str, dict]) -> bool:
    return all(c["limit"] is not None and math.isfinite(c["value"])
               and c["value"] <= c["limit"] for c in checks.values())


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------

def memory_peak(chips: int) -> int:
    import jax
    peak = 0
    for d in jax.local_devices()[:chips]:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


def execute(bench: Bench, name: str, seed: int, seconds: float,
            traced: bool, t_start: float, peak: dict | None) -> tuple[Run, dict]:
    """Drive cell ``name`` once and check it; returns the run and its
    result line (a dict, ``checks`` last).  A traced run's window is at
    most :data:`TRACE_SECONDS` long."""
    if traced:
        seconds = min(seconds, TRACE_SECONDS)
    cell = bench.cell(name)
    run = Run(cell=cell, seed=seed, seconds=seconds, traced=traced,
              t_start=t_start, peak=peak)
    cell.entry.run(run)
    metrics = {}
    for m in bench.metrics(name, traced):
        v = bench.module("metrics", m["name"]).read(run)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    run.memory_peak_bytes = memory_peak(cell.chips)
    # free the program's state before the reference takes the chip
    run.dep = run.dist_report = None
    gc.collect()
    checks = check(run)
    import jax
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()),
              "memory_peak_bytes": run.memory_peak_bytes}
    result = {"correct": passed(checks), "attempted": run.attempted,
              "failed": run.failed, "metrics": metrics, "device": device}
    if run.trace is not None:
        device["busy_s"] = run.trace.busy_mean_s
        device["window_s"] = run.trace.window_s
        result["breakdown"] = run.trace.breakdown()
    result["checks"] = checks
    return run, result

