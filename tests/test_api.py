"""The public ``repro.api`` facade: specs, compile() -> Deployment,
artifact serialization, and the legacy-kwarg deprecation shims.

Pins the contract of the API redesign: the facade produces plans
identical to the legacy entry points, every artifact JSON round-trips
exactly, and a saved deployment reloads with zero re-planning or
re-calibration while behaving bit-identically."""

import json
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro
from repro.api import (DeploySpec, ExecSpec, PlanSpec, artifacts,
                       reset_legacy_warnings)
from repro.api.deployment import FEED_CHUNK, feed_chunks, stack_frames
from repro.core import (CostTable, make_pi_cluster, plan, replan, simulate)
from repro.core.partition import PartitionResult
from repro.models.cnn import zoo
from repro.serving import PipelineServer
from repro.runtime import PipelineRuntime


def _tiny(name, size=64, scale=0.25):
    return zoo.build(name, input_size=(size, size), scale=scale)


def _canon(pico) -> dict:
    """Plan payload with the (non-deterministic) wall-time scrubbed."""
    d = artifacts.plan_to_dict(pico)
    d["partition"]["wall_time_s"] = 0.0
    d["pipeline"]["wall_time_s"] = 0.0
    return d


# ---------------------------------------------------------------------------
# specs
# ---------------------------------------------------------------------------

def test_spec_validation():
    with pytest.raises(ValueError):
        PlanSpec(t_lim=0.0)
    with pytest.raises(ValueError):
        PlanSpec(max_diameter=0)
    with pytest.raises(ValueError):
        PlanSpec(n_split=1)
    with pytest.raises(ValueError):
        ExecSpec(mode="sideways")
    with pytest.raises(ValueError):
        ExecSpec(cache_size=0)
    with pytest.raises(ValueError):
        DeploySpec(max_batch=0)
    with pytest.raises(ValueError):
        DeploySpec(ewma_beta=0.0)


@pytest.mark.parametrize("spec", [
    PlanSpec(), PlanSpec(t_lim=0.25, max_diameter=3, n_split=4),
    ExecSpec(), ExecSpec(backend="xla", mode="eager", donate=True,
                         cache_size=8, calibrate=True, calibrate_iters=2),
    DeploySpec(), DeploySpec(seed=3, max_batch=4, compute_noise=0.1,
                             migration_bandwidth=1e9),
])
def test_spec_json_roundtrip(spec):
    s = spec.to_json()
    json.loads(s)                       # strict JSON (inf spelled out)
    assert type(spec).from_json(s) == spec


def test_spec_json_rejects_garbage():
    with pytest.raises(ValueError):
        PlanSpec.from_dict({"kind": "ExecSpec", "version": 1})
    with pytest.raises(ValueError):
        PlanSpec.from_dict({"kind": "PlanSpec", "version": 99})
    with pytest.raises(ValueError):
        PlanSpec.from_dict({"kind": "PlanSpec", "version": 1, "nope": 1})


def test_spec_inf_is_strict_json():
    s = PlanSpec(t_lim=float("inf")).to_json()
    assert "Infinity" in s and json.loads(s)["t_lim"] == "Infinity"
    assert PlanSpec.from_json(s).t_lim == float("inf")


def test_artifact_nan_is_strict_json():
    table = CostTable({frozenset({"a"}): float("nan")}, default=1.0)
    s = artifacts.cost_table_to_json(table)
    json.loads(s, parse_constant=lambda c: pytest.fail(f"bare {c} in JSON"))
    back = artifacts.cost_table_from_json(s)
    assert np.isnan(back.ratios[frozenset({"a"})])


def test_deploy_spec_maps_to_runtime_config():
    spec = DeploySpec(seed=7, max_batch=3, drift_threshold=0.5, trace=True)
    cfg = spec.to_runtime_config()
    assert (cfg.seed, cfg.max_batch, cfg.drift_threshold, cfg.trace) \
        == (7, 3, 0.5, True)


# ---------------------------------------------------------------------------
# facade vs legacy equivalence (the model zoo)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name,scale", [
    ("vgg16", 0.125), ("squeezenet", 0.25), ("mobilenetv3", 0.25),
    ("resnet34", 0.125), ("inceptionv3", 0.25),
])
def test_compile_matches_legacy_plan(name, scale):
    m = _tiny(name, scale=scale)
    cluster = make_pi_cluster([1.5, 1.0, 0.8])
    legacy = plan(m.graph, cluster, m.input_size)
    dep = repro.compile(m, cluster)
    assert _canon(dep.pico) == _canon(legacy)


def test_compile_spec_knobs_equal_legacy_kwargs():
    m = _tiny("squeezenet")
    cluster = make_pi_cluster([1.5, 1.2, 1.0])
    reset_legacy_warnings()
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        legacy = plan(m.graph, cluster, m.input_size, t_lim=0.02,
                      max_diameter=3, n_split=4)
    dep = repro.compile(m, cluster,
                        PlanSpec(t_lim=0.02, max_diameter=3, n_split=4))
    assert _canon(dep.pico) == _canon(legacy)


def test_plan_rejects_spec_plus_legacy_kwargs():
    m = _tiny("squeezenet")
    cluster = make_pi_cluster([1.5, 1.0])
    with pytest.raises(TypeError):
        plan(m.graph, cluster, m.input_size, t_lim=0.5, spec=PlanSpec())


# ---------------------------------------------------------------------------
# artifact round-trips
# ---------------------------------------------------------------------------

def test_plan_artifact_roundtrip_exact():
    m = _tiny("mobilenetv3")
    cluster = make_pi_cluster([1.5, 1.2, 0.8])
    pico = plan(m.graph, cluster, m.input_size)
    s = artifacts.plan_to_json(pico)
    back = artifacts.plan_from_json(s)
    assert artifacts.plan_to_dict(back) == artifacts.plan_to_dict(pico)
    assert simulate(back.pipeline, 32) == simulate(pico.pipeline, 32)
    assert back.period == pico.period and back.latency == pico.latency


def test_partition_and_cost_table_roundtrip():
    m = _tiny("squeezenet")
    cluster = make_pi_cluster([1.5, 1.0])
    pico = plan(m.graph, cluster, m.input_size)
    pr = artifacts.partition_from_json(
        artifacts.partition_to_json(pico.partition))
    assert [p.nodes for p in pr] == [p.nodes for p in pico.partition]
    assert pr.objective == pico.partition.objective

    table = CostTable({frozenset({"conv1"}): 1.5,
                       frozenset({"conv2", "pool1"}): 0.75}, default=1.1)
    back = artifacts.cost_table_from_json(artifacts.cost_table_to_json(table))
    assert back.ratios == table.ratios and back.default == table.default


def test_artifact_envelope_guards():
    table = CostTable({frozenset({"a"}): 2.0})
    d = json.loads(artifacts.cost_table_to_json(table))
    with pytest.raises(ValueError):
        artifacts.plan_from_json(json.dumps(d))        # wrong kind
    d["version"] = artifacts.SCHEMA_VERSION + 1
    with pytest.raises(ValueError):
        artifacts.cost_table_from_json(json.dumps(d))  # future version


def test_model_roundtrip_preserves_init_and_forward():
    m = _tiny("squeezenet")
    back = artifacts.model_from_dict(artifacts.model_to_dict(m))
    assert back.name == m.name
    assert list(back.graph.layers) == list(m.graph.layers)
    assert back.graph.edges == m.graph.edges
    p1 = m.init(jax.random.PRNGKey(0))
    p2 = back.init(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 64, 64, 3))
    o1, o2 = m.forward(p1, x), back.forward(p2, x)
    for k in o1:
        np.testing.assert_array_equal(np.asarray(o1[k]), np.asarray(o2[k]))


# ---------------------------------------------------------------------------
# Deployment save/load: bit-identical, zero re-plan / re-calibration
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name,scale", [
    ("squeezenet", 0.25), ("mobilenetv3", 0.25), ("vgg16", 0.125),
])
def test_save_load_bit_identical(tmp_path, name, scale, monkeypatch):
    m = _tiny(name, size=48, scale=scale)
    cluster = make_pi_cluster([1.5, 1.0, 0.8])
    dep = repro.compile(m, cluster)
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 48, 48, 3))
    out1 = dep.run(x)
    sim1 = dep.simulate(32)
    path = dep.save(tmp_path / f"{name}.json")

    # loading must touch neither the planner nor the calibrator — patch
    # both the defining modules and deployment.py's module-level binding
    import repro.api.deployment as deployment_mod
    import repro.core.planner as planner_mod
    import repro.exec.calibrate as calibrate_mod

    def _boom(*a, **k):
        raise AssertionError("re-planning/re-calibration on load")

    monkeypatch.setattr(planner_mod, "plan_with_spec", _boom)
    monkeypatch.setattr(planner_mod, "plan", _boom)
    monkeypatch.setattr(deployment_mod, "plan_with_spec", _boom)
    monkeypatch.setattr(calibrate_mod, "calibrate_plan", _boom)

    dep2 = repro.Deployment.load(path)
    assert dep2.simulate(32) == sim1
    assert artifacts.plan_to_dict(dep2.pico) == artifacts.plan_to_dict(dep.pico)
    out2 = dep2.run(x)
    assert out1.keys() == out2.keys()
    for k in out1:
        np.testing.assert_array_equal(np.asarray(out1[k]),
                                      np.asarray(out2[k]))


def test_artifact_refuses_reserved_string_names():
    from repro.core.graph import Graph, LayerSpec
    g = Graph()
    g.add(LayerSpec("NaN", "conv", (1, 1), (1, 1), (0, 0), 3, 4))
    with pytest.raises(ValueError, match="collides"):
        artifacts.dumps_payload("model", artifacts.graph_to_dict(g))


def test_compile_key_seeds_weights_without_calibration(tmp_path):
    m = _tiny("squeezenet", size=48)
    cluster = make_pi_cluster([1.5, 1.0])
    k = jax.random.PRNGKey(7)
    dep = repro.compile(m, cluster, key=k)
    assert dep.params is not None
    ref = m.init(jax.random.PRNGKey(7))
    for name in ref:
        for leaf in ref[name]:
            np.testing.assert_array_equal(
                np.asarray(ref[name][leaf]),
                np.asarray(dep.params[name][leaf]))
    # trained/custom weights reattach on load
    path = dep.save(tmp_path / "d.json")
    dep2 = repro.Deployment.load(path, params=dep.params)
    assert dep2.params is dep.params


def test_save_load_preserves_cost_table(tmp_path):
    m = _tiny("vgg16", scale=0.125)
    cluster = make_pi_cluster([1.5, 1.0])
    dep = repro.compile(m, cluster,
                        exec_spec=ExecSpec(calibrate=True,
                                           calibrate_iters=1))
    assert dep.cost_table is not None and len(dep.cost_table) > 0
    path = dep.save(tmp_path / "cal.json")
    dep2 = repro.Deployment.load(path)
    assert dep2.cost_table.ratios == dep.cost_table.ratios
    assert dep2.cost_table.default == dep.cost_table.default
    assert dep2.exec_spec == dep.exec_spec
    assert dep2.plan_spec == dep.plan_spec


def test_deployment_replan_reuses_piece_chain():
    m = _tiny("squeezenet")
    cluster = make_pi_cluster([1.5, 1.2, 1.0, 0.8])
    dep = repro.compile(m, cluster)
    shrunk = make_pi_cluster([1.5, 1.0])
    dep2 = dep.replan(shrunk)
    assert [p.nodes for p in dep2.partition] == \
        [p.nodes for p in dep.partition]
    assert dep2.partition.states_explored == dep.partition.states_explored
    used = {d.name for st in dep2.pipeline.stages for d in st.devices}
    assert used == {d.name for d in shrunk.devices}


def test_deployment_online_forms():
    m = _tiny("squeezenet")
    cluster = make_pi_cluster([1.5, 1.0])
    dep = repro.compile(m, cluster)
    # timing-only runtime (no params loaded)
    rep = dep.runtime(DeploySpec(seed=0)).run(8)
    assert rep.completed == 8
    # closed-form server reuses the deployment's plan object
    srv = dep.server()
    assert srv.pico is dep.pico
    from repro.data.pipeline import Request
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 64, 64, 3))
    outs, stats = srv.load().serve([Request(0, 0.0, x)])
    assert stats.served == 1 and outs[0]
    # streaming server accepts a DeploySpec
    srv2 = dep.server(DeploySpec(seed=1), streaming=True)
    outs2, stats2 = srv2.load().serve([Request(0, 0.0, x)])
    assert stats2.served == 1
    for k in outs[0]:
        np.testing.assert_array_equal(np.asarray(outs[0][k]),
                                      np.asarray(outs2[0][k]))
    # deploy knobs have no closed-form counterpart: loud, not silent
    with pytest.raises(TypeError):
        dep.server(DeploySpec(max_batch=4))


def test_server_load_keeps_deployment_params():
    m = _tiny("squeezenet", size=48)
    cluster = make_pi_cluster([1.5, 1.0])
    dep = repro.compile(m, cluster, key=jax.random.PRNGKey(5))
    srv = dep.server().load()           # the canonical load().serve() flow
    assert srv.params is dep.params
    srv2 = dep.server().load(jax.random.PRNGKey(9))   # explicit re-key wins
    assert srv2.params is not dep.params


def test_run_scan_batch_matches_per_frame():
    m = _tiny("squeezenet", size=48)
    cluster = make_pi_cluster([1.5, 1.0])
    xs = [jax.random.normal(jax.random.PRNGKey(i), (1, 48, 48, 3))
          for i in range(3)]
    dep = repro.compile(m, cluster)
    scanned = dep.run(xs)
    assert len(scanned) == 3
    looped = repro.compile(
        m, cluster, exec_spec=ExecSpec(scan_batch=False))
    looped.params = dep.params
    plain = looped.run(xs)
    for a, b in zip(scanned, plain):
        assert a.keys() == b.keys()
        for k in a:
            np.testing.assert_allclose(np.asarray(a[k]), np.asarray(b[k]),
                                       rtol=1e-6, atol=1e-7)


@pytest.fixture(scope="module")
def scan_pair():
    """A scanning deployment and its per-frame twin on the same weights."""
    m = _tiny("squeezenet", size=48)
    cluster = make_pi_cluster([1.5, 1.0])
    dep = repro.compile(m, cluster).load_params()
    looped = repro.compile(m, cluster,
                           exec_spec=ExecSpec(scan_batch=False))
    looped.params = dep.params
    return dep, looped


def _host_frames(n, dtype=np.float32, shape=(1, 48, 48, 3)):
    rng = np.random.default_rng(7)
    return [rng.standard_normal(shape).astype(dtype) for _ in range(n)]


@pytest.mark.parametrize("kind", ["numpy", "jax"])
def test_run_scan_frames_bit_exact_with_per_frame(scan_pair, kind):
    dep, looped = scan_pair
    host = _host_frames(3)
    frames = host if kind == "numpy" else [jnp.asarray(x) for x in host]
    outs = dep.run(frames)
    plain = looped.run(host)
    assert len(outs) == 3
    for a, b in zip(outs, plain):
        assert list(a) == list(b)
        for k in a:
            assert isinstance(a[k], jax.Array)
            assert a[k].shape == b[k].shape and a[k].dtype == b[k].dtype
            np.testing.assert_array_equal(np.asarray(a[k]),
                                          np.asarray(b[k]))


@pytest.fixture(scope="module", params=[1, 2], ids=["1-stage", "2-stage"])
def chunk_pair(request):
    """A scanning deployment of ``request.param`` stages and its
    per-frame twin on the same weights."""
    m = _tiny("squeezenet", size=48)
    cluster = make_pi_cluster([1.5, 1.0][:request.param])
    dep = repro.compile(m, cluster).load_params()
    looped = repro.compile(m, cluster,
                           exec_spec=ExecSpec(scan_batch=False))
    looped.params = dep.params
    assert len(dep.pico.pipeline.stages) == request.param
    return dep, looped


@pytest.mark.parametrize("sizes", [
    [FEED_CHUNK], [FEED_CHUNK] * 2, [FEED_CHUNK, 3], [FEED_CHUNK] * 2 + [1]],
    ids=["one-chunk", "even", "ragged", "ragged-1"])
def test_run_chunked_host_feed_bit_exact(chunk_pair, sizes):
    dep, looped = chunk_pair
    n = sum(sizes)
    host = _host_frames(n)
    assert [len(c) for c in feed_chunks(host)] == sizes
    fed = dep.run(host)
    one_stack = dep.run([jnp.asarray(x) for x in host])
    plain = looped.run(host)
    assert len(fed) == len(one_stack) == len(plain) == n
    for a, b, c in zip(fed, one_stack, plain):
        assert list(a) == list(b) == list(c)
        for k in a:
            np.testing.assert_array_equal(np.asarray(a[k]),
                                          np.asarray(b[k]))
            np.testing.assert_array_equal(np.asarray(a[k]),
                                          np.asarray(c[k]))


def test_feed_chunks_one_chunk_unless_uniform_host_frames():
    n = 2 * FEED_CHUNK + 1
    host = _host_frames(n, shape=_SMALL)
    assert len(feed_chunks(host)) == 3
    for odd in ([jnp.asarray(x) for x in host],
                host[:-1] + [jnp.asarray(host[-1])],
                host[:-1] + [host[-1].astype(np.float16)],
                host[:-1] + [host[-1][..., :2]]):
        (only,) = feed_chunks(odd)
        assert only is odd


def test_run_float64_frames_canonicalised_as_jnp_stack(scan_pair):
    dep, _ = scan_pair
    f64 = _host_frames(3, np.float64)
    assert jnp.stack(f64).dtype == jnp.float32
    got = dep.run(f64)
    want = dep.run([x.astype(np.float32) for x in f64])
    for a, b in zip(got, want):
        for k in a:
            assert a[k].dtype == b[k].dtype == jnp.float32
            np.testing.assert_array_equal(np.asarray(a[k]),
                                          np.asarray(b[k]))


_SMALL = (1, 2, 4, 3)


@pytest.mark.parametrize("case, src", [
    ("float32", "host"), ("float64", "host"), ("float16", "host"),
    ("int64", "host"), ("uint8", "host"),
    ("jax", "device"), ("mixed-types", "device"),
    ("mixed-dtypes", "host")])
def test_stack_frames_keeps_jnp_stack_dtype(case, src):
    if case == "jax":
        frames = [jnp.asarray(x) for x in _host_frames(3, shape=_SMALL)]
    elif case == "mixed-types":
        frames = _host_frames(3, shape=_SMALL)
        frames[1] = jnp.asarray(frames[1])
    elif case == "mixed-dtypes":
        frames = _host_frames(3, shape=_SMALL)
        frames[2] = frames[2].astype(np.float16)
    else:
        frames = [(np.arange(24).reshape(_SMALL) + i).astype(case)
                  for i in range(3)]
    got, how = stack_frames(frames)
    want = jnp.stack(frames)
    assert how == src and isinstance(got, jax.Array)
    assert got.dtype == want.dtype and got.weak_type == want.weak_type
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ---------------------------------------------------------------------------
# deprecation shims: warn exactly once, bit-identical results
# ---------------------------------------------------------------------------

def _one_deprecation(wlist):
    return [w for w in wlist if issubclass(w.category, DeprecationWarning)]


def test_plan_legacy_kwargs_warn_exactly_once():
    m = _tiny("squeezenet")
    cluster = make_pi_cluster([1.5, 1.0])
    reset_legacy_warnings()
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        legacy = plan(m.graph, cluster, m.input_size, t_lim=0.05)
        legacy2 = plan(m.graph, cluster, m.input_size, t_lim=0.05)
    assert len(_one_deprecation(w)) == 1
    spec_plan = plan(m.graph, cluster, m.input_size,
                     spec=PlanSpec(t_lim=0.05))
    assert _canon(legacy) == _canon(spec_plan) == _canon(legacy2)


def test_replan_legacy_t_lim_warns_once():
    m = _tiny("squeezenet")
    cluster = make_pi_cluster([1.5, 1.0])
    prev = plan(m.graph, cluster, m.input_size)
    reset_legacy_warnings()
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        a = replan(m.graph, cluster, m.input_size, prev=prev, t_lim=0.05)
        replan(m.graph, cluster, m.input_size, prev=prev, t_lim=0.05)
    assert len(_one_deprecation(w)) == 1
    b = replan(m.graph, cluster, m.input_size, prev=prev,
               spec=PlanSpec(t_lim=0.05))
    assert _canon(a) == _canon(b)


def test_pipeline_server_legacy_kwargs_warn_once_and_match():
    m = _tiny("squeezenet")
    cluster = make_pi_cluster([1.5, 1.0])
    reset_legacy_warnings()
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        legacy = PipelineServer(m, cluster, t_lim=0.05)
        PipelineServer(m, cluster, t_lim=0.05)
    assert len(_one_deprecation(w)) == 1
    fresh = PipelineServer(m, cluster, plan_spec=PlanSpec(t_lim=0.05))
    assert _canon(legacy.pico) == _canon(fresh.pico)


def test_pipeline_runtime_legacy_kwargs_warn_once_and_match():
    m = _tiny("squeezenet")
    cluster = make_pi_cluster([1.5, 1.0])
    reset_legacy_warnings()
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        rt1 = PipelineRuntime(m.graph, cluster, m.input_size, t_lim=0.05)
        rt2 = PipelineRuntime(m.graph, cluster, m.input_size, t_lim=0.05)
    assert len(_one_deprecation(w)) == 1
    rt3 = PipelineRuntime(m.graph, cluster, m.input_size,
                          plan_spec=PlanSpec(t_lim=0.05))
    assert _canon(rt1.pico) == _canon(rt2.pico) == _canon(rt3.pico)


def test_mixing_spec_and_legacy_kwargs_raises():
    m = _tiny("squeezenet")
    cluster = make_pi_cluster([1.5, 1.0])
    with pytest.raises(TypeError):
        PipelineRuntime(m.graph, cluster, m.input_size, t_lim=0.05,
                        plan_spec=PlanSpec())
    with pytest.raises(TypeError):
        PipelineServer(m, cluster, backend="xla", exec_spec=ExecSpec())


# ---------------------------------------------------------------------------
# PartitionResult.from_pieces (honest reused-chain stats)
# ---------------------------------------------------------------------------

def test_from_pieces_honest_stats():
    m = _tiny("squeezenet")
    cluster = make_pi_cluster([1.5, 1.0])
    full = plan(m.graph, cluster, m.input_size)
    pr = PartitionResult.from_pieces(full.partition.pieces)
    assert pr.objective == max(p.redundancy for p in pr.pieces)
    assert [p.index for p in pr.pieces] == list(range(len(pr.pieces)))
    with pytest.raises(ValueError):
        PartitionResult.from_pieces([])


def test_plan_with_pieces_keeps_honest_partition():
    m = _tiny("squeezenet")
    cluster = make_pi_cluster([1.5, 1.0])
    full = plan(m.graph, cluster, m.input_size)
    reused = plan(m.graph, cluster, m.input_size,
                  pieces=full.partition.pieces)
    assert reused.partition.objective == full.partition.objective
    assert len(reused.partition) == len(full.partition)


def test_replan_carries_partition_provenance():
    m = _tiny("squeezenet")
    cluster = make_pi_cluster([1.5, 1.2, 1.0])
    prev = plan(m.graph, cluster, m.input_size)
    assert prev.partition.states_explored > 0
    new = replan(m.graph, make_pi_cluster([1.5, 1.0]), m.input_size,
                 prev=prev)
    # the reused chain keeps its true search stats instead of zeros
    assert new.partition.states_explored == prev.partition.states_explored
    assert new.partition.wall_time_s == prev.partition.wall_time_s
    assert new.partition.objective == prev.partition.objective


# ---------------------------------------------------------------------------
# scheduler through the spec surface
# ---------------------------------------------------------------------------

def test_scheduler_exec_spec_and_tenant_plan_spec():
    from repro.serving import SchedulerConfig, ServingScheduler, TenantConfig
    cluster = make_pi_cluster([1.5, 1.2, 1.0])
    tenants = [
        TenantConfig("a", zoo.squeezenet(input_size=(64, 64), scale=0.1),
                     plan_spec=PlanSpec()),
        TenantConfig("b", zoo.mobilenetv3(input_size=(64, 64), scale=0.25)),
    ]
    sched = ServingScheduler(tenants, cluster,
                             config=SchedulerConfig(seed=0),
                             exec_spec=ExecSpec())
    assert sched.backend is None
    from repro.data.pipeline import Request
    workload = {"a": [Request(i, 0.01 * i, None) for i in range(4)],
                "b": [Request(i, 0.01 * i, None) for i in range(4)]}
    report = sched.serve(workload)
    assert report.served == 8 and report.dropped_inflight == 0


def test_scheduler_legacy_backend_kwarg_warns_once():
    from repro.serving import ServingScheduler, TenantConfig
    cluster = make_pi_cluster([1.5, 1.0])
    tenants = [TenantConfig(
        "a", zoo.squeezenet(input_size=(64, 64), scale=0.1))]
    reset_legacy_warnings()
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        ServingScheduler(tenants, cluster, backend=None)
        ServingScheduler(tenants, cluster, backend=None)
    assert len(_one_deprecation(w)) == 1


# ---------------------------------------------------------------------------
# plan CLI
# ---------------------------------------------------------------------------

def test_plan_cli_save_load_validate(tmp_path, capsys):
    from repro.tools.plan import main
    out = tmp_path / "plan.json"
    assert main(["--model", "squeezenet", "--scale", "0.25",
                 "--input", "48", "--devices", "2",
                 "--out", str(out)]) == 0
    assert out.exists()
    assert main(["--load", str(out), "--validate"]) == 0
    text = capsys.readouterr().out
    assert "validate: schema v1 ok" in text


def test_top_level_exports():
    assert callable(repro.compile)
    assert repro.Deployment is not None
    assert repro.PlanSpec is PlanSpec
    assert "compile" in dir(repro)
