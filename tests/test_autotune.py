"""Autotuner contract: search, shape keys, CostTable persistence, and
the Deployment save/load symmetry (a loaded artifact re-tunes nothing).

All searches here use tiny candidate sets and ``iters=1`` — the point
is the plumbing (winner selection, key stability, artifact round-trip,
process-wide install), not interpret-mode wall times.
"""

import json

import jax
import pytest

import repro
from repro.api import ExecSpec, artifacts
from repro.core import CostTable, make_pi_cluster
from repro.exec.autotune import (DEFAULT_CANDIDATES, autotune_conv,
                                 autotune_model, clear_installed,
                                 conv_shapes, install, installed,
                                 shape_key, tuned_blocks)
from repro.models.cnn import zoo

TINY = ((16, 16), (8, 8))


@pytest.fixture(autouse=True)
def _isolate_installed():
    """Each test starts and ends with an empty tuned registry."""
    clear_installed()
    yield
    clear_installed()


def test_shape_key_is_spatial_size_agnostic():
    a = shape_key((1, 32, 32, 8), (3, 3, 8, 16), (1, 1))
    b = shape_key((1, 7, 9, 8), (3, 3, 8, 16), (1, 1))
    assert a == b
    # but channels, stride, epilogue and backend all distinguish
    assert a != shape_key((1, 32, 32, 9), (3, 3, 9, 16), (1, 1))
    assert a != shape_key((1, 32, 32, 8), (3, 3, 8, 16), (2, 2))
    assert a != shape_key((1, 32, 32, 8), (3, 3, 8, 16), (1, 1), act="relu")
    assert a != shape_key((1, 32, 32, 8), (3, 3, 8, 16), (1, 1),
                          pool=(2, 2))
    assert a != shape_key((1, 32, 32, 8), (3, 3, 8, 16), (1, 1),
                          backend="xla")


def test_autotune_conv_picks_a_candidate():
    res = autotune_conv((1, 10, 10, 5), (3, 3, 5, 7), stride=(1, 1),
                        act="relu", pool=(2, 2), candidates=TINY, iters=1)
    assert (res.block_ci, res.block_co) in TINY
    assert len(res.trials) == len(TINY)
    assert res.best_us > 0
    assert res.best_us == pytest.approx(
        min(t[2] for t in res.trials) * 1e6)
    e = res.entry()
    assert set(e) == {"block_ci", "block_co", "best_us", "backend"}


def test_tuned_blocks_consults_installed_registry():
    k = shape_key((1, 10, 10, 5), (3, 3, 5, 7), (1, 1))
    assert tuned_blocks((1, 10, 10, 5), (3, 3, 5, 7), (1, 1)) == (None, None)
    install({k: {"block_ci": 16, "block_co": 8, "best_us": 1.0,
                 "backend": "pallas"}})
    # any spatial size hits the same entry
    assert tuned_blocks((1, 99, 3, 5), (3, 3, 5, 7), (1, 1)) == (16, 8)
    assert installed()[k]["block_co"] == 8


def test_conv_shapes_fuses_like_the_compiler():
    m = zoo.build("vgg16", input_size=(40, 40), scale=0.1, head=False)
    shapes = conv_shapes(m)
    assert shapes  # dedup by key, so strictly fewer than conv layers
    assert len(shapes) <= sum(
        1 for s in m.graph.layers.values() if s.kind == "conv")
    assert any(d["pool"] for d in shapes)   # vgg conv->pool chains fuse
    assert all(d["act"] == "relu" for d in shapes)


def test_autotune_model_skips_warm_table_entries():
    m = zoo.build("squeezenet", input_size=(48, 48), scale=0.1)
    table, results = autotune_model(m, candidates=TINY, iters=1)
    assert results and len(table.kernels) == len(results)
    assert installed() == table.kernels   # winners installed by default
    # a warm table re-tunes nothing — the save/load acceptance property
    table2, results2 = autotune_model(m, table=table, candidates=TINY,
                                      iters=1)
    assert results2 == []
    assert table2.kernels == table.kernels


def test_cost_table_artifact_round_trips_kernels():
    t = CostTable(kernels={
        "conv:pallas:c3x8:k3x3:s1x1:r1:p2x2":
            {"block_ci": 8, "block_co": 16, "best_us": 12.5,
             "backend": "pallas"}})
    s = artifacts.cost_table_to_json(t)
    t2 = artifacts.cost_table_from_json(s)
    assert t2.kernels == t.kernels
    # additive field: tables without tunings serialize without it, and
    # old payloads (no "kernels") still load
    assert "kernels" not in json.loads(
        artifacts.cost_table_to_json(CostTable()))["payload"]
    assert artifacts.cost_table_from_json(
        artifacts.cost_table_to_json(CostTable())).kernels == {}


def test_exec_spec_autotune_validation():
    assert ExecSpec().autotune is False
    with pytest.raises(ValueError):
        ExecSpec(autotune_iters=0)


def test_deployment_autotunes_and_save_load_retunes_nothing(tmp_path):
    m = zoo.build("squeezenet", input_size=(48, 48), scale=0.1)
    cluster = make_pi_cluster([1.0, 0.8])
    es = ExecSpec(backend="pallas", autotune=True, autotune_iters=1)
    # patch in the tiny candidate set: full default search is too slow
    # for a unit test in interpret mode
    import repro.exec.autotune as at
    orig = at.autotune_conv

    calls = []

    def counting(*a, **kw):
        calls.append(a)
        kw["candidates"] = TINY
        kw["iters"] = 1
        return orig(*a, **kw)

    at.autotune_conv = counting
    try:
        dep = repro.compile(m, cluster, exec_spec=es,
                            key=jax.random.PRNGKey(0))
        assert calls, "compile(autotune=True) must run the tuner"
        n_tuned = len(dep.cost_table.kernels)
        assert n_tuned == len(calls)
        assert "autotuned" in dep.describe()
        path = dep.save(tmp_path / "dep.json")

        calls.clear()
        clear_installed()
        dep2 = repro.Deployment.load(path, model=m)
        # load() re-arms the fast path from the artifact: kernels
        # round-trip exactly, install happens on construction, and the
        # tuner never runs again
        assert dep2.cost_table.kernels == dep.cost_table.kernels
        assert installed() == dep2.cost_table.kernels
        assert calls == []
        # a re-compile against the loaded table is also a no-op search
        repro.compile(m, cluster, exec_spec=es,
                      cost_table=dep2.cost_table,
                      key=jax.random.PRNGKey(0))
        assert calls == [], "warm CostTable must re-tune nothing"
        assert len(dep2.cost_table.kernels) == n_tuned
    finally:
        at.autotune_conv = orig


def test_default_candidates_cover_mxu_and_tails():
    assert (128, 128) in DEFAULT_CANDIDATES
    assert (8, 8) in DEFAULT_CANDIDATES
