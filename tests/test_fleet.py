"""Fleet tier: plan registry, router, autoscaler, incremental planner
equivalence (property-style), and PlanSource provenance threading."""

import dataclasses

import hypothesis.strategies as st
import pytest
from hypothesis import given, settings

from repro.api import FleetSpec, PlanSpec
from repro.api import artifacts
from repro.api.specs import spec_from_dict
from repro.core import Cluster, make_pi_cluster
from repro.core.pipeline_dp import PlannerCache
from repro.core.planner import PicoPlan, plan_with_spec
from repro.fleet import (Autoscaler, FleetRouter, PlanRegistry, Tenant,
                         cluster_signature, fingerprint_model)
from repro.models.cnn import zoo
from repro.obs.metrics import MetricsRegistry


def _renamed(cluster, prefix):
    return Cluster([dataclasses.replace(d, name=f"{prefix}.{d.name}")
                    for d in cluster.devices], bandwidth=cluster.bandwidth)


def _sig(p: PicoPlan) -> tuple:
    """Exact plan identity — no tolerance anywhere."""
    return (p.period, p.latency, p.pipeline.feasible,
            tuple((sp.first_piece, sp.last_piece,
                   tuple(d.name for d in sp.devices), tuple(sp.fractions),
                   sp.cost.total, sp.cost.t_comp, sp.cost.t_comm)
                  for sp in p.pipeline.stages))


# ---------------------------------------------------------------------------
# incremental PipelineDP == full recompute (property-style)
# ---------------------------------------------------------------------------

_MODELS = [
    zoo.squeezenet(input_size=(64, 64), scale=0.25),
    zoo.mobilenetv3(input_size=(64, 64), scale=0.25),
    zoo.resnet34(input_size=(64, 64), scale=0.1),
]
_BASE_CAPS = [1.5, 1.2, 1.0, 1.0, 0.8, 0.8]


@settings(max_examples=10, deadline=None)
@given(model_i=st.integers(0, len(_MODELS) - 1),
       toggles=st.lists(st.integers(0, len(_BASE_CAPS) - 1),
                        min_size=1, max_size=4))
def test_incremental_equals_scratch_under_churn(model_i, toggles):
    """Random single-device drop/join sequences: the incremental path
    (shared PlannerCache) must produce bit-identical plans to a full
    recompute at every step."""
    model = _MODELS[model_i]
    base = make_pi_cluster(_BASE_CAPS)
    spec = PlanSpec()
    cache = PlannerCache()
    seed = plan_with_spec(model.graph, base, model.input_size, spec,
                          planner_cache=cache)
    assert seed.source == "scratch"
    active = set(range(len(_BASE_CAPS)))
    for i in toggles:
        if i in active and len(active) > 1:
            active.remove(i)       # device drop
        else:
            active.add(i)          # device (re)join
        cluster = base.restricted([base.devices[k] for k in sorted(active)])
        inc = plan_with_spec(model.graph, cluster, model.input_size, spec,
                             partition=seed.partition, planner_cache=cache)
        full = plan_with_spec(model.graph, cluster, model.input_size, spec,
                              partition=seed.partition)
        assert inc.source == "incremental"
        assert full.source == "scratch"
        assert _sig(inc) == _sig(full)


def test_incremental_equals_scratch_one_drop():
    """Fixed-input twin of the property test: one drop on the
    heterogeneous 8-device cluster."""
    model = _MODELS[0]
    base = make_pi_cluster([1.5, 1.5, 1.2, 1.2, 1.0, 1.0, 0.8, 0.8])
    cache = PlannerCache()
    seed = plan_with_spec(model.graph, base, model.input_size,
                          planner_cache=cache)
    smaller = base.restricted(base.devices[1:])
    inc = plan_with_spec(model.graph, smaller, model.input_size,
                         partition=seed.partition, planner_cache=cache)
    full = plan_with_spec(model.graph, smaller, model.input_size,
                          partition=seed.partition)
    assert inc.source == "incremental" and full.source == "scratch"
    assert _sig(inc) == _sig(full)
    assert cache.hits > 0


# ---------------------------------------------------------------------------
# PlanRegistry
# ---------------------------------------------------------------------------

def _cluster4():
    return make_pi_cluster([1.5, 1.2, 1.0, 0.8])


def test_registry_hit_miss_and_isolation():
    reg = PlanRegistry(capacity=8, metrics=MetricsRegistry())
    model = _MODELS[0]
    c = _cluster4()
    first = reg.get_or_plan(model, c)
    assert first.source == "scratch" and reg.misses == 1
    second = reg.get_or_plan(model, c)
    assert second.source == "registry" and reg.hits == 1
    assert _sig(second)[:2] == _sig(first)[:2]
    # hits decode fresh objects: mutating one never corrupts the cache
    second.pipeline.stages[0].fractions[0] = -1.0
    third = reg.get_or_plan(model, c)
    assert third.pipeline.stages[0].fractions[0] != -1.0


def test_registry_name_insensitive_rebind():
    """Identical hardware under different device names is one planning
    problem; the served plan's devices are rebound onto the caller's."""
    reg = PlanRegistry(metrics=MetricsRegistry())
    model = _MODELS[1]
    a, b = _cluster4(), _renamed(_cluster4(), "podB")
    assert cluster_signature(a) == cluster_signature(b)
    pa = reg.get_or_plan(model, a)
    pb = reg.get_or_plan(model, b)
    assert pb.source == "registry"
    assert pb.period == pa.period and pb.latency == pa.latency
    served = {d.name for sp in pb.pipeline.stages for d in sp.devices}
    assert served <= {d.name for d in b.devices}


def test_registry_key_discriminates():
    reg = PlanRegistry(metrics=MetricsRegistry())
    model = _MODELS[0]
    c = _cluster4()
    reg.get_or_plan(model, c, PlanSpec())
    # different spec, different cluster shape, different model: all miss
    assert reg.get(model, c, PlanSpec(t_lim=0.5)) is None
    assert reg.get(model, make_pi_cluster([1.0, 1.0]), PlanSpec()) is None
    assert reg.get(_MODELS[2], c, PlanSpec()) is None
    assert fingerprint_model(_MODELS[0]) != fingerprint_model(_MODELS[2])


def test_registry_lru_eviction():
    reg = PlanRegistry(capacity=2, metrics=MetricsRegistry())
    model = _MODELS[0]
    c1, c2, c3 = (make_pi_cluster([1.0] * n) for n in (2, 3, 4))
    reg.get_or_plan(model, c1)
    reg.get_or_plan(model, c2)
    reg.get_or_plan(model, c1)          # refresh c1
    reg.get_or_plan(model, c3)          # evicts c2 (least recent)
    assert len(reg) == 2
    assert reg.get(model, c1) is not None
    assert reg.get(model, c2) is None


def test_registry_json_round_trip():
    reg = PlanRegistry(capacity=4, metrics=MetricsRegistry())
    model = _MODELS[0]
    c = _cluster4()
    reg.get_or_plan(model, c)
    loaded = PlanRegistry.from_json(reg.to_json())
    assert len(loaded) == 1
    hit = loaded.get(model, c)
    assert hit is not None and hit.source == "registry"


# ---------------------------------------------------------------------------
# FleetRouter + Autoscaler
# ---------------------------------------------------------------------------

def _router(routing="least_loaded", **kw):
    cells = {"a": make_pi_cluster([1.5, 1.2, 1.0, 0.8]),
             "b": _renamed(make_pi_cluster([1.5, 1.2, 1.0, 0.8]), "b")}
    return FleetRouter(cells, spec=FleetSpec(routing=routing, **kw),
                       metrics=MetricsRegistry())


def test_router_least_loaded_follows_ewma():
    r = _router()
    r.observe("a", 0.9)
    r.observe("b", 0.1)
    adm = r.admit(Tenant("t0", _MODELS[0]))
    assert adm.cell == "b"
    # the load picture flips: beta=0.3 smoothing needs a few samples
    for _ in range(4):
        r.observe("b", 0.95)
        r.observe("a", 0.05)
    assert r.cell_load("a") < r.cell_load("b")
    assert r.admit(Tenant("t1", _MODELS[1])).cell == "a"


def test_router_round_robin_and_registry_hits():
    r = _router(routing="round_robin")
    adms = [r.admit(Tenant(f"t{i}", _MODELS[0])) for i in range(4)]
    assert [a.cell for a in adms] == ["a", "b", "a", "b"]
    # cells a and b are identical hardware: after the first scratch
    # plan, every admission is a registry hit (name-insensitive)
    assert [a.plan_source for a in adms] == \
        ["scratch", "registry", "registry", "registry"]


def test_router_round_robin_survives_topology_change():
    """Regression: the cursor used to be an integer index into
    sorted(cells), so add_cell/remove_cell shifted which cell it landed
    on (repeating or skipping cells).  Keyed on the last-served *name*,
    the rotation resumes fairly after any topology change."""
    r = _router(routing="round_robin")
    assert [r.admit(Tenant(f"t{i}", _MODELS[0])).cell
            for i in range(2)] == ["a", "b"]
    # "ab" sorts between the existing cells; the old index-based cursor
    # would now serve "b" twice in a row
    r.add_cell("ab", _renamed(make_pi_cluster([1.5, 1.2, 1.0, 0.8]), "ab"))
    assert [r.admit(Tenant(f"u{i}", _MODELS[0])).cell
            for i in range(4)] == ["a", "ab", "b", "a"]
    # removing the last-served cell: rotation continues from its name
    # ("a" held t0/u0/u3; they re-admit round-robin as ab, b, ab)
    moved = r.remove_cell("a")
    assert [m.cell for m in moved] == ["ab", "b", "ab"]
    assert r.admit(Tenant("v0", _MODELS[0])).cell == "b"


def test_router_zero_capacity_cell_routed_around():
    """A degraded cell (zero total capacity) must never be a routing
    target — and must not crash load accounting with a
    ZeroDivisionError."""
    from repro.core import Device
    dead = Cluster([Device("dead0", 0.0)], bandwidth=50e6 / 8)
    cells = {"a": make_pi_cluster([1.5, 1.2, 1.0, 0.8]), "z": dead}
    r = FleetRouter(cells, spec=FleetSpec(), metrics=MetricsRegistry())
    assert r.cell_load("z") == float("inf")
    for i in range(3):
        assert r.admit(Tenant(f"t{i}", _MODELS[0])).cell == "a"
    # round_robin skips it too
    rr = FleetRouter({"a": make_pi_cluster([1.5, 1.2, 1.0, 0.8]),
                      "b": _renamed(make_pi_cluster([1.5, 1.2, 1.0, 0.8]),
                                    "b"),
                      "z": dead},
                     spec=FleetSpec(routing="round_robin"),
                     metrics=MetricsRegistry())
    assert [rr.admit(Tenant(f"t{i}", _MODELS[0])).cell
            for i in range(4)] == ["a", "b", "a", "b"]
    # a fleet with no routable cell fails loudly, not with a crash
    only_dead = FleetRouter({"z": dead}, metrics=MetricsRegistry())
    with pytest.raises(ValueError, match="zero capacity"):
        only_dead.admit(Tenant("t9", _MODELS[0]))


def test_router_churn_emits_spans_and_counters():
    """Regression: churn used to re-plan silently while admit emitted
    fleet.route spans and plan-source counters — repartition audits
    could not see churn-driven plans."""
    from repro.obs import Tracer
    from repro.obs import trace as obs_trace
    reg = MetricsRegistry()
    cells = {"a": make_pi_cluster([1.5, 1.2, 1.0, 0.8])}
    r = FleetRouter(cells, spec=FleetSpec(), metrics=reg)
    r.admit(Tenant("t0", _MODELS[0]))
    tr = Tracer()
    with obs_trace.scoped(tr):
        replanned = r.churn("a", cells["a"].restricted(
            cells["a"].devices[:-1]))
    assert replanned["t0"].source == "incremental"
    churn_spans = [s for s in tr.spans if s.name == "fleet.churn"]
    route_spans = [s for s in tr.spans if s.name == "fleet.route"]
    assert len(churn_spans) == 1
    assert churn_spans[0].attr("cell") == "a"
    assert len(route_spans) == 1
    assert route_spans[0].attr("policy") == "churn"
    assert route_spans[0].attr("tenant") == "t0"
    assert reg.value("fleet.replans", source="incremental") == 1.0


def test_router_churn_is_incremental():
    r = _router()
    r.admit(Tenant("t0", _MODELS[0]))
    cell = next(c for c in r.cells.values() if c.tenants)
    smaller = cell.cluster.restricted(cell.cluster.devices[:-1])
    replanned = r.churn(cell.name, smaller)
    assert replanned["t0"].source == "incremental"
    # the twin cell's 4-device shape is already registered: admitting
    # the same model there is a pure registry hit
    adm = r.admit(Tenant("t1", _MODELS[0]))
    assert adm.cell != cell.name
    assert adm.plan_source == "registry"


def test_router_evict_and_remove_cell():
    r = _router(max_clusters=3)
    r.admit(Tenant("t0", _MODELS[0]))
    assert r.evict("t0") is not None
    assert r.evict("t0") is None and not r.plans
    r.observe("a", 0.5)
    r.observe("b", 0.1)
    adm = r.admit(Tenant("t1", _MODELS[0]))
    moved = r.remove_cell(adm.cell)
    assert [m.tenant for m in moved] == ["t1"]
    assert len(r.cells) == 1
    with pytest.raises(ValueError):
        r.remove_cell(next(iter(r.cells)))     # min_clusters=1


def test_autoscaler_watermarks_and_hooks():
    r = _router(max_clusters=4)
    r.observe("a", 0.95)                       # above scale_up_load=0.8
    r.observe("b", 0.05)                       # below scale_down_load=0.25
    supplied = []

    def provision(router, decision):
        name = f"new{len(supplied)}"
        supplied.append(name)
        return name, make_pi_cluster([1.0, 1.0])

    sc = Autoscaler(r, provision=provision,
                    decommission=lambda router, d: True,
                    metrics=MetricsRegistry())
    decisions = {d.cell: d for d in sc.evaluate()}
    assert decisions["a"].action == "scale_up" and decisions["a"].applied
    assert decisions["b"].action == "scale_down" and decisions["b"].applied
    assert supplied == ["new0"] and "new0" in r.cells
    assert "b" not in r.cells


def test_autoscaler_holds_in_band_and_respects_bounds():
    r = _router(max_clusters=2)
    r.observe("a", 0.5)
    r.observe("b", 0.95)
    sc = Autoscaler(r, provision=lambda rt, d: ("x", make_pi_cluster([1.0])),
                    metrics=MetricsRegistry())
    decisions = {d.cell: d for d in sc.evaluate()}
    assert decisions["a"].action == "hold"
    assert decisions["b"].action == "scale_up" and not decisions["b"].applied
    assert decisions["b"].detail == "at max_clusters"


# ---------------------------------------------------------------------------
# PlanSource provenance threading
# ---------------------------------------------------------------------------

def test_plan_source_validation_and_artifact_round_trip():
    plan = plan_with_spec(_MODELS[0].graph, _cluster4(),
                          _MODELS[0].input_size)
    with pytest.raises(ValueError):
        PicoPlan(plan.partition, plan.pipeline, source="cached")
    plan.source = "incremental"
    loaded = artifacts.plan_from_json(artifacts.plan_to_json(plan))
    assert loaded.source == "incremental"
    # pre-provenance artifacts (no "source" field) load as scratch
    d = artifacts.plan_to_dict(plan)
    d.pop("source")
    assert artifacts.plan_from_dict(d).source == "scratch"


def test_scheduler_repartition_audits_plan_sources():
    from repro.runtime import DeviceLeave
    from repro.serving import (OpenLoopGenerator, SchedulerConfig,
                               ServingScheduler, TenantConfig)
    cluster = make_pi_cluster([1.5, 1.2, 1.0, 1.0, 0.8, 0.8])
    tenants = [TenantConfig("a", _MODELS[0]), TenantConfig("b", _MODELS[2])]
    sched = ServingScheduler(tenants, cluster,
                             config=SchedulerConfig(
                                 seed=5, migration_bandwidth=1e9))
    wl = {}
    for i, ts in enumerate(sched._tenants.values()):
        rate = 0.6 / ts.share.pico.period
        wl[ts.cfg.name] = OpenLoopGenerator(rate_per_s=rate,
                                            seed=3 + i).generate(40)
    horizon = max(r.arrival for rs in wl.values() for r in rs)
    weakest = min(cluster.devices, key=lambda d: d.capacity)
    rep = sched.serve(wl, churn=[DeviceLeave(0.5 * horizon, weakest.name)])
    leaves = [r for r in rep.repartitions if r.reason == "leave"]
    assert leaves
    for r in leaves:
        assert set(r.plan_sources) == {"a", "b"}
        # surviving tenants re-plan on the warm path, never from scratch
        assert set(r.plan_sources.values()) <= {"incremental", "registry"}


def test_deployment_replan_is_incremental():
    import repro
    dep = repro.compile(_MODELS[0], make_pi_cluster([1.5, 1.2, 1.0, 0.8]))
    assert dep.pico.source == "scratch"
    dep2 = dep.replan(make_pi_cluster([1.5, 1.2, 1.0]))
    assert dep2.pico.source == "incremental"


# ---------------------------------------------------------------------------
# FleetSpec
# ---------------------------------------------------------------------------

def test_fleet_spec_validation_and_round_trip():
    spec = FleetSpec(registry_capacity=8, routing="round_robin",
                     scale_up_load=0.9, scale_down_load=0.1,
                     max_clusters=3)
    again = spec_from_dict(spec.to_dict())
    assert again == spec
    for bad in (dict(registry_capacity=0), dict(routing="random"),
                dict(ewma_beta=0.0), dict(ewma_beta=1.5),
                dict(scale_up_load=0.2, scale_down_load=0.3),
                dict(min_clusters=0), dict(min_clusters=3, max_clusters=2)):
        with pytest.raises(ValueError):
            FleetSpec(**bad)


# ---------------------------------------------------------------------------
# PlanStore: file-backed shared registry
# ---------------------------------------------------------------------------

def test_registry_file_store_shared_across_instances(tmp_path):
    """A plan persisted by one registry instance is a hit for a fresh
    instance pointed at the same directory — cross-process sharing with
    no coordination, and the served plan is exact."""
    from repro.fleet import PlanStore
    model, c = _MODELS[0], _cluster4()
    root = tmp_path / "store"
    r1 = PlanRegistry(store=root, metrics=MetricsRegistry())
    p1 = r1.get_or_plan(model, c)
    assert p1.source == "scratch" and len(r1.store) == 1
    r2 = PlanRegistry(store=PlanStore(root), metrics=MetricsRegistry())
    p2 = r2.get_or_plan(model, c)
    assert p2.source == "registry"
    assert r2.hits == 1 and r2.misses == 0
    assert _sig(p1) == _sig(p2)
    # a different content key stays a miss even with the store attached
    assert r2.get(model, c, PlanSpec(t_lim=0.123)) is None


def test_registry_store_survives_lru_eviction(tmp_path):
    """The store outlives the in-memory LRU horizon: an evicted entry
    is re-served from disk, not re-planned."""
    model = _MODELS[0]
    c1, c2, c3 = (make_pi_cluster([1.0] * n) for n in (2, 3, 4))
    reg = PlanRegistry(capacity=2, store=tmp_path, metrics=MetricsRegistry())
    for c in (c1, c2, c3):                     # c1 evicted from memory
        reg.get_or_plan(model, c)
    assert len(reg) == 2 and len(reg.store) == 3
    hit = reg.get_or_plan(model, c1)
    assert hit.source == "registry"


def test_plan_store_tolerates_corrupt_files(tmp_path):
    """Corrupt/foreign files in a shared directory read as misses —
    one bad writer must not poison every consumer."""
    from repro.fleet import PlanStore
    model, c = _MODELS[0], _cluster4()
    r1 = PlanRegistry(store=tmp_path, metrics=MetricsRegistry())
    r1.get_or_plan(model, c)
    for p in tmp_path.glob("*.json"):
        p.write_text("{ not json")
    (tmp_path / "foreign.json").write_text("{}")
    r2 = PlanRegistry(store=tmp_path, metrics=MetricsRegistry())
    assert r2.get(model, c) is None            # miss, never an error
    p2 = r2.get_or_plan(model, c)              # re-plans, re-publishes
    assert p2.source == "scratch"
    assert PlanStore(tmp_path).get(r2.key(model, c, PlanSpec())) is not None
    assert PlanStore(tmp_path).keys() == [r2.key(model, c, PlanSpec())]


def test_plan_store_atomic_publish_and_delete(tmp_path):
    from repro.fleet import PlanStore
    store = PlanStore(tmp_path)
    key = ("m", "c", "{}", "")
    store.put(key, {"plan": 1})
    assert key in store and store.get(key) == {"plan": 1}
    assert not list(tmp_path.glob("*.tmp"))    # temp files never linger
    store.put(key, {"plan": 2})                # overwrite is atomic too
    assert store.get(key) == {"plan": 2}
    assert store.delete(key) and key not in store
    assert not store.delete(key)


# ---------------------------------------------------------------------------
# FleetRouter.observe_report: real telemetry -> load-EWMA
# ---------------------------------------------------------------------------

def test_router_observe_report_serve_and_dist_shapes():
    r = _router()

    class FakeServe:                            # ServeReport-shaped
        device_busy_s = {"d0": 2.0, "d1": 1.0}
        makespan = 2.0

    class FakeDist:                             # DistReport-shaped
        def utilization(self):
            return 0.4

    first = r.observe_report("a", FakeServe())
    assert first == pytest.approx(0.75)         # 3.0 / (2 * 2.0)
    beta = r.spec.ewma_beta
    second = r.observe_report("a", FakeDist())
    assert second == pytest.approx(beta * 0.4 + (1 - beta) * 0.75)
    assert r.cell_load("a") == pytest.approx(second)

    class Saturated:
        def utilization(self):
            return 7.3                          # clamped before smoothing

    r2 = _router()
    assert r2.observe_report("b", Saturated()) == 1.0

    class Idle:                                 # zero makespan -> zero load
        device_busy_s = {}
        makespan = 0.0

    assert r2.observe_report("a", Idle()) == 0.0
    with pytest.raises(TypeError):
        r.observe_report("a", object())


def test_router_observe_report_steers_routing():
    """Telemetry-driven regression: the cell whose reports show load
    stops winning least_loaded placement."""
    r = _router()

    class Busy:
        def utilization(self):
            return 0.95

    class Quiet:
        def utilization(self):
            return 0.05

    for _ in range(5):
        r.observe_report("a", Busy())
        r.observe_report("b", Quiet())
    assert r.admit(Tenant("t0", _MODELS[0])).cell == "b"
