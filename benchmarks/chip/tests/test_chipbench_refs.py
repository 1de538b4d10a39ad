"""Each reference family against the program's ``CNNDef.forward`` at a
CPU size (widths x 0.1, 32x32): same weights from the same key, same
logits; and the controls' lower precisions read far from it."""

from __future__ import annotations

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from chipbench_tiny import ROOT, tiny_config

from chipbench import bench as cb
from chipbench.bench import Bench

CONFIGS = [c["name"] for c in
           json.loads((ROOT / "BENCHMARK.json").read_text())["configs"]]


@pytest.mark.parametrize("config", CONFIGS)
def test_reference_matches_the_program(config):
    from repro.models.cnn import zoo
    b = Bench(ROOT)
    cfg = tiny_config(b.config(config))
    fam = b.module("references", cfg["family"])
    model = getattr(zoo, cfg["zoo"])(
        input_size=tuple(cfg["input_size"]), scale=cfg["scale"])
    key = jax.random.PRNGKey(1234)
    mine = fam.init(cfg, key)
    theirs = model.init(key)
    weighted = [n for n, s in model.graph.layers.items()
                if s.kind in ("conv", "fc")]
    assert len(mine) == len(weighted)
    for (w, b_), n in zip(mine, weighted):
        np.testing.assert_array_equal(w, theirs[n]["w"])
        np.testing.assert_array_equal(b_, theirs[n]["b"])
    x = jnp.asarray(np.random.default_rng(0).standard_normal(
        (2, 32, 32, 3), dtype=np.float32))
    with jax.default_matmul_precision("highest"):
        (want,) = model.forward(theirs, x).values()
    want = np.asarray(want).reshape(2, -1)
    got = np.asarray(fam.forward(cfg, mine, x))
    err = np.max(np.abs(got - want)) / np.max(np.abs(want))
    assert err < 1e-5
    for mode in ("fp8", "bf16"):
        low = np.asarray(fam.forward(cfg, mine, x, mode))
        assert low.shape == want.shape
        assert np.max(np.abs(low - want)) / np.max(np.abs(want)) > 10 * err


@pytest.mark.parametrize("config", CONFIGS)
def test_folded_biases_match_the_program(config):
    """With the benchmark's folded biases fed to both, the program's
    forward and the reference agree; the biases are non-zero and centre
    each layer on the calibration frames."""
    from repro.models.cnn import zoo
    b = Bench(ROOT)
    cell = next(b.cell(w["name"]) for w in b.spec["workloads"]
                if w["config"] == config)
    cell.config = cfg = tiny_config(cell.config)
    run = cb.Run(cell=cell, seed=2 ** 36 + 9, seconds=1.0, traced=False,
                 t_start=0.0)
    model = getattr(zoo, cfg["zoo"])(
        input_size=tuple(cfg["input_size"]), scale=cfg["scale"])
    params = cb.program_params(run, model)
    assert all(float(jnp.max(jnp.abs(x))) > 0 for x in run.biases)
    x = jnp.asarray(np.random.default_rng(1).standard_normal(
        (3, 32, 32, 3), dtype=np.float32))
    with jax.default_matmul_precision("highest"):
        (want,) = model.forward(params, x).values()
    want = np.asarray(want).reshape(3, -1)
    run.pool = np.asarray(x)[:, None]
    got = cb.reference_logits(run, [0, 1, 2])
    got = np.stack([got[i] for i in range(3)])
    assert np.max(np.abs(got - want)) / np.max(np.abs(want)) < 1e-4
