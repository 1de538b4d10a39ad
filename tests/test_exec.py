"""`repro.exec`: backend registry, segment compiler, executable cache,
scan micro-batching, and cost calibration feeding the planner."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import exec as rexec
from repro.api import ExecSpec
from repro.core import CostTable, make_pi_cluster, plan, recost, replan
from repro.models.cnn import zoo
from repro.models.cnn.builder import GB
from repro.pipeline import PipelineRunner
from repro.pipeline.stage import StageExecutor


@pytest.fixture(autouse=True)
def _fresh_cache():
    rexec.clear_cache()
    yield
    rexec.clear_cache()


def _small_model():
    b = GB("small", (24, 24))
    x = b.conv(None, 8, 3, p=1)
    x = b.conv(x, 8, 3, p=1)
    x = b.pool(x, 2, 2)
    x = b.conv(x, 16, 3, p=1)
    return b.done()


# ---------------------------------------------------------------------------
# backend registry
# ---------------------------------------------------------------------------

def test_registry_contents_and_unknown_backend():
    assert set(rexec.available_backends()) >= {"xla", "pallas"}
    with pytest.raises(ValueError, match="unknown exec backend"):
        rexec.get_backend("cudnn")


def test_custom_backend_is_dispatched():
    calls = []

    def traced(spec, p, x, pad_w):
        calls.append(spec.name)
        return rexec.get_backend("xla")(spec, p, x, pad_w)

    rexec.register_backend("traced", traced)
    try:
        m = _small_model()
        m.backend = "traced"
        params = m.init(jax.random.PRNGKey(0))
        x = jax.random.normal(jax.random.PRNGKey(1), (1, 24, 24, 3))
        ref = m.forward(params, x, backend="xla")
        out = m.forward(params, x)
        for k in ref:
            np.testing.assert_array_equal(np.asarray(out[k]),
                                          np.asarray(ref[k]))
        assert len(calls) == 3            # every conv went through it
    finally:
        rexec.backends._REGISTRY.pop("traced", None)


def test_backend_resolution_order():
    m = _small_model()
    m.backend = "pallas"
    ex = StageExecutor(m, frozenset(m.graph.layers), [0.5, 0.5])
    assert ex.backend == "pallas"         # model default wins over registry
    ex2 = StageExecutor(m, frozenset(m.graph.layers), [0.5, 0.5],
                        backend="xla")
    assert ex2.backend == "xla"           # explicit arg wins over model


# ---------------------------------------------------------------------------
# executable cache
# ---------------------------------------------------------------------------

def test_cache_hit_on_identical_stage_and_rebuilt_model():
    m = _small_model()
    params = m.init(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 24, 24, 3))
    ex = StageExecutor(m, frozenset(m.graph.layers), [0.5, 0.5])
    ex(params, {}, x)
    s = rexec.cache_stats()
    assert (s.misses, s.hits) == (1, 0)
    ex(params, {}, x)                     # same executor: hit
    # a *rebuilt* identical model + fresh executor: still a hit (the key
    # is the segment signature, not object identity)
    m2 = _small_model()
    StageExecutor(m2, frozenset(m2.graph.layers), [0.5, 0.5])(params, {}, x)
    s = rexec.cache_stats()
    assert (s.misses, s.hits) == (1, 2)


def test_cache_miss_on_shape_or_tiling_change():
    m = _small_model()
    params = m.init(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 24, 24, 3))
    nodes = frozenset(m.graph.layers)
    StageExecutor(m, nodes, [0.5, 0.5])(params, {}, x)
    StageExecutor(m, nodes, [0.75, 0.25])(params, {}, x)   # tiling differs
    StageExecutor(m, nodes, [0.5, 0.5])(
        params, {}, jax.random.normal(jax.random.PRNGKey(2), (2, 24, 24, 3)))
    s = rexec.cache_stats()
    assert s.misses == 3 and s.hits == 0


def test_cache_eviction_bound():
    rexec.set_cache_size(2)
    try:
        m = _small_model()
        params = m.init(jax.random.PRNGKey(0))
        nodes = frozenset(m.graph.layers)
        for n in (1, 2, 3):
            x = jax.random.normal(jax.random.PRNGKey(1), (n, 24, 24, 3))
            StageExecutor(m, nodes, [0.5, 0.5])(params, {}, x)
        s = rexec.cache_stats()
        assert s.entries == 2 and s.evictions == 1
    finally:
        rexec.set_cache_size(256)


# ---------------------------------------------------------------------------
# compiler: scan micro-batching + donation flag
# ---------------------------------------------------------------------------

def test_run_frames_matches_per_frame_calls():
    m = zoo.squeezenet(input_size=(48, 48), scale=0.1)
    cluster = make_pi_cluster([1.5, 1.0])
    p = plan(m.graph, cluster, m.input_size)
    params = m.init(jax.random.PRNGKey(0))
    frames = jax.random.normal(jax.random.PRNGKey(1), (5, 1, 48, 48, 3))
    runner = PipelineRunner(m, p.pipeline)
    stacked = runner.run_frames(params, frames)
    for f in range(5):
        one = runner(params, frames[f])
        for k, v in one.items():
            np.testing.assert_array_equal(np.asarray(stacked[k][f]),
                                          np.asarray(v))
    # the eager oracle honors run_frames too (loops + stacks)
    eager_stacked = PipelineRunner(m, p.pipeline, ExecSpec(mode="eager")
                                   ).run_frames(params, frames)
    for k in stacked:
        np.testing.assert_array_equal(np.asarray(stacked[k]),
                                      np.asarray(eager_stacked[k]))


def test_compile_stage_direct_and_donation_cpu_noop():
    m = _small_model()
    params = m.init(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 24, 24, 3))
    cs = rexec.compile_stage(m, frozenset(m.graph.layers), [0.5, 0.5],
                             donate=True)
    if jax.default_backend() == "cpu":
        assert cs.donate is False         # CPU can't alias; flag is dropped
    out = cs(params, {k: x for k in cs.needs})
    ref = m.forward(params, x)
    for k in ref:
        np.testing.assert_allclose(np.asarray(out[k]), np.asarray(ref[k]),
                                   rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# calibration -> CostTable -> planner
# ---------------------------------------------------------------------------

def test_cost_table_lookup_and_fallback():
    key = frozenset({"conv1"})
    t = CostTable({key: 2.0})
    assert t.ratio({"conv1"}) == 2.0
    assert t.ratio({"convX"}) == 2.0      # mean fallback
    t2 = CostTable({key: 2.0}, default=1.5)
    assert t2.ratio({"convX"}) == 1.5
    assert CostTable().ratio({"a"}) == 1.0


def test_cost_table_scales_plan_costs():
    m = zoo.squeezenet(input_size=(64, 64), scale=0.1)
    cluster = make_pi_cluster([1.5, 1.2, 1.0, 0.8])
    base = plan(m.graph, cluster, m.input_size)
    double = CostTable(default=2.0)
    rc = recost(base.pipeline, cluster, m.graph, m.input_size,
                cost_table=double)
    for st, st2 in zip(base.pipeline.stages, rc.stages):
        assert st2.cost.t_comp == pytest.approx(2.0 * st.cost.t_comp)
        assert st2.cost.t_comm == pytest.approx(st.cost.t_comm)


def test_calibrate_plan_produces_usable_table():
    m = zoo.squeezenet(input_size=(64, 64), scale=0.1)
    cluster = make_pi_cluster([1.5, 1.2, 1.0, 0.8])
    params = m.init(jax.random.PRNGKey(0))
    p = plan(m.graph, cluster, m.input_size)
    rep = rexec.calibrate_plan(m, params, p.pipeline.stages, iters=1)
    assert rep.host_flops > 0
    assert len(rep.stages) == len(p.pipeline.stages)
    for s in rep.stages:
        assert s.measured_s > 0
    table = rep.table()
    p2 = replan(m.graph, cluster, m.input_size, prev=p, cost_table=table)
    assert p2.period > 0
    # measured ratios shift the modeled period away from pure analytic
    assert p2.period != pytest.approx(p.period)
