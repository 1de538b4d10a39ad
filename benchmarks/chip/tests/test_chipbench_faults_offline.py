"""The offline entry driven on the CPU at a tiny size with the chip's
look skipped: ``correct`` holds for the sound path and fails for each
fault planted underneath, and for the control put in the program's
place.  Also the refusals: no TPU, no program beside the benchmark."""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from chipbench_tiny import BASE, ROOT, load_cli, run_cell, tiny_config, \
    tiny_root

from chipbench import bench as cb

CELL = "vgg16-224.offline32"
CELLS = [w["name"] for w in
         json.loads((ROOT / "BENCHMARK.json").read_text())["workloads"]]
SEED = 2 ** 40 + 11


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny_root(tmp_path_factory.mktemp("chipbench"))


def alter_answers(outs: dict) -> dict:
    """Each frame's answer altered where it is produced: the first logit
    moved by the largest magnitude of its frame."""
    return {k: (v.at[..., 0].add(jnp.max(jnp.abs(v)))
                if v.shape[-1] == 1000 else v) for k, v in outs.items()}


def test_sound_run_is_correct(root, capsys):
    res = run_cell(root, CELL, SEED, capsys)
    assert res["correct"] is True
    assert list(res)[-1] == "checks"
    assert set(res["metrics"]) == {"frames_per_s", "setup_s"}
    assert res["checks"]["logit_err"]["value"] < 1e-4
    assert res["checks"]["missing"] == {"value": 0, "limit": 0}
    assert res["attempted"] > 0 and res["failed"] == 0


def test_half_the_batch_left_out(root, capsys, monkeypatch):
    from repro.api.deployment import Deployment
    orig = Deployment.run

    def half(self, frames, params=None):
        frames = list(frames)
        n = len(frames) // 2
        outs = orig(self, frames[:n], params)
        return (outs * 2)[:len(frames)]
    monkeypatch.setattr(Deployment, "run", half)
    res = run_cell(root, CELL, SEED, capsys)
    assert res["correct"] is False
    assert res["checks"]["logit_err"]["value"] > 0.1


def test_answer_altered_where_produced(root, capsys, monkeypatch):
    from repro.pipeline.stage import StageExecutor
    orig = StageExecutor.run_frames
    monkeypatch.setattr(StageExecutor, "run_frames",
                        lambda self, *a: alter_answers(orig(self, *a)))
    res = run_cell(root, CELL, SEED, capsys)
    assert res["correct"] is False


def test_control_in_the_program_place(root, capsys, monkeypatch):
    """The reference in fp8 (one step below the stated bf16 operands)
    put where ``Deployment.run`` is: the check refuses it."""
    from repro.api.deployment import Deployment
    b = cb.Bench(root)
    cell = b.cell(CELL)
    fam, cfg = cell.family, cell.config
    weights = fam.init(cfg, jax.random.PRNGKey(cb.weight_seed(SEED)))

    def control(self, frames, params=None):  # noqa: ARG001
        names = [n for n, sp in self.model.graph.layers.items()
                 if sp.kind in ("conv", "fc")]
        ref_params = [(w, params[n]["b"]) for (w, _), n in
                      zip(weights, names)]
        y = fam.forward(cfg, ref_params, jnp.concatenate(list(frames)),
                        "fp8")
        return [{"sink": y[i][None]} for i in range(y.shape[0])]
    monkeypatch.setattr(Deployment, "run", control)
    res = run_cell(root, CELL, SEED, capsys)
    assert res["correct"] is False
    assert res["checks"]["logit_err"]["value"] > \
        res["checks"]["logit_err"]["limit"]


def test_biases_dropped(root, capsys, monkeypatch):
    """The program adds no bias (nor the folded BatchNorm shift it stands
    for): the check refuses it."""
    from repro.api.deployment import Deployment
    orig = Deployment.run

    def unbiased(self, frames, params=None):
        return orig(self, frames, {n: dict(p, b=0 * p["b"])
                                   for n, p in params.items()})
    monkeypatch.setattr(Deployment, "run", unbiased)
    res = run_cell(root, CELL, SEED, capsys)
    assert res["correct"] is False


@pytest.mark.parametrize("seed", [1, 2 ** 33 + 3, 7777])
@pytest.mark.parametrize("cell", CELLS)
def test_control_reads_above_the_limit(cell, seed):
    """The fp8 control's widest gap on frames drawn from the seed lies
    above the cell's limit, at a CPU size, with the biases the cell's
    entry gives the program."""
    b = cb.Bench(ROOT)
    c = b.cell(cell)
    c.config = tiny_config(c.config)
    run = cb.Run(cell=c, seed=seed, seconds=1.0, traced=False, t_start=0.0)
    if c.traffic["entry"] == "offline":
        cb.fold_biases(run)
    run.pool = cb.sub_seed(seed, 1).standard_normal(
        (4, 1, 32, 32, 3), dtype=np.float32)
    keys = list(range(4))
    ref = cb.reference_logits(run, keys)
    ctrl = cb.reference_logits(run, keys, "fp8")
    err = cb.logit_err(ctrl, {k: k for k in keys}, ref, keys)
    assert err > c.limits["logit_err"]


@pytest.mark.parametrize("cell", CELLS)
def test_bias_fold_is_one_program_for_every_seed(cell):
    """The fold's calibration frames are an argument, not a constant:
    one program serves every seed, so set-up finds it in the cache."""
    b = cb.Bench(ROOT)
    c = b.cell(cell)
    c.config = tiny_config(c.config)
    texts = set()
    for seed in (5, 2 ** 34 + 9):
        run = cb.Run(cell=c, seed=seed, seconds=1.0, traced=False,
                     t_start=0.0)
        texts.add(jax.jit(lambda kw, kz, x: cb._fold(run, kw, kz, x)).lower(
            *cb._keys(run), cb._calibration(run)).as_text())
    assert len(texts) == 1


def test_no_tpu_exits_non_zero_and_names_the_platform(root, capsys):
    rc = load_cli().main(["--workload", CELL, "--seed", "1", "--seconds",
                          "1"], root=root, cache_dir=None, require_tpu=True)
    cap = capsys.readouterr()
    assert rc == 2
    assert "'cpu'" in cap.err and cap.out == ""


def test_benchmark_files_alone_exit_non_zero(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(BASE, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns(".jax_cache",
                                                  "__pycache__"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    proc = subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload", CELL,
         "--seed", "1", "--seconds", "1"], cwd=tmp_path, env=env,
        capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert proc.stdout == ""
    assert json.loads((tmp_path / "BENCHMARK.json").read_text())
