"""repro.obs: span round-trips, Perfetto validity, metrics snapshots,
and the end-to-end wiring through Deployment / runtime / conv fallbacks
(ISSUE 6 acceptance: fig13 VGG16 with ``DeploySpec(trace=True)``)."""

import json
import math
import time

import pytest

import repro
from repro.obs import (
    HOST_TRACK, METRICS_SCHEMA_VERSION, NULL_REGISTRY, NULL_TRACER,
    Histogram, MetricsRegistry, Tracer, flatten, from_chrome_trace,
    open_snapshot, quantile, span_tree, validate_chrome_trace,
)
from repro.obs import trace as obs_trace
from repro.runtime.monitor import Monitor
from repro.serving.server import ServeStats


# --------------------------------------------------------------- tracing


def _sample_tracer() -> Tracer:
    tr = Tracer()
    tr.emit("plan", 0.0, 0.002, n_devices=4)
    tr.emit("frame", 0.0, 0.03, track="pipeline", frame=0)
    tr.emit("stage.compute", 0.0, 0.01, track="pi0", stage=0, frame=0,
            modeled_s=0.009, observed_s=0.01)
    tr.emit("stage.comm", 0.01, 0.002, track="link:0", stage=0)
    tr.emit("stage.compute", 0.012, 0.012, track="pi1", stage=1, frame=0)
    tr.instant("sched.admit", 0.0, track="pipeline", frames=[0])
    return tr


def test_trace_roundtrip_identical_span_tree(tmp_path):
    tr = _sample_tracer()
    path = tr.save(tmp_path / "t.json")
    doc = json.loads(open(path).read())
    assert validate_chrome_trace(doc) == []
    back = from_chrome_trace(doc)
    assert back == tr.spans                       # exact, incl. float ts
    assert span_tree(back) == span_tree(tr.spans)


def test_chrome_trace_device_rows():
    doc = _sample_tracer().to_chrome_trace()
    names = {ev["args"]["name"] for ev in doc["traceEvents"]
             if ev.get("ph") == "M" and ev.get("name") == "process_name"}
    assert {"pi0", "pi1", "link:0", "pipeline", HOST_TRACK} <= names
    pids = {ev["pid"] for ev in doc["traceEvents"] if ev.get("ph") == "X"}
    assert len(pids) == len(_sample_tracer().tracks())


def test_validate_rejects_garbage():
    assert validate_chrome_trace({"no": "events"})
    bad = _sample_tracer().to_chrome_trace()
    bad["traceEvents"][0] = {"ph": "X"}           # missing name/ts/pid
    assert validate_chrome_trace(bad)
    with pytest.raises(ValueError):
        from_chrome_trace(bad)


def test_null_tracer_is_inert():
    assert not NULL_TRACER
    NULL_TRACER.emit("frame", 0.0, 1.0)
    NULL_TRACER.instant("sched.admit", 0.0)
    with NULL_TRACER.wall_span("plan"):
        pass
    assert NULL_TRACER.spans == ()


def test_scoped_activation_restores_previous():
    tr = Tracer()
    assert obs_trace.current() is NULL_TRACER
    with obs_trace.scoped(tr):
        assert obs_trace.current() is tr
        with obs_trace.scoped(None):              # None coerces to the null
            assert obs_trace.current() is NULL_TRACER
        assert obs_trace.current() is tr
    assert obs_trace.current() is NULL_TRACER


# --------------------------------------------------------- quantiles


def test_nearest_rank_quantile_tiny_windows():
    assert quantile([], 50) == 0.0
    assert quantile([7.0], 50) == quantile([7.0], 99) == 7.0
    # n=2: p50 -> rank ceil(1.0)=1 -> smaller sample; p95/p99 -> larger
    assert quantile([3.0, 9.0], 50) == 3.0
    assert quantile([3.0, 9.0], 95) == 9.0
    vals = [float(i) for i in range(1, 101)]
    assert quantile(vals, 50) == 50.0
    assert quantile(vals, 99) == 99.0


@pytest.mark.parametrize("n", [1, 2, 3, 10])
def test_servestats_histogram_percentile_parity(n):
    lat = [0.01 * (i + 1) for i in range(n)]
    st = ServeStats()
    h = Histogram("serve.latency_s")
    for x in lat:
        st.record(x)
        h.observe(x)
    for q in (50.0, 95.0, 99.0):
        assert st.latency_percentile(q) == h.percentile(q)
    assert (st.latency_percentile(50) <= st.latency_percentile(95)
            <= st.latency_percentile(99))


# ----------------------------------------------------------- metrics


def test_registry_snapshot_flatten_roundtrip():
    reg = MetricsRegistry()
    reg.counter("runtime.replans", reason="drift").inc(2)
    reg.gauge("monitor.ratio", device="pi0").set(1.3)
    reg.gauge("weird").set(math.inf)
    for x in (1.0, 2.0, 3.0, 4.0):
        reg.histogram("frame.latency_s").observe(x)
    snap = reg.snapshot(meta={"run": "test"})
    assert snap["artifact"] == "metrics"
    assert snap["version"] == METRICS_SCHEMA_VERSION
    json.dumps(snap)                              # strict-JSON encodable
    flat = flatten(snap)
    assert flat["runtime.replans{reason=drift}"] == 2.0
    assert flat["monitor.ratio{device=pi0}"] == 1.3
    assert flat["weird"] == math.inf
    assert flat["frame.latency_s.count"] == 4.0
    assert flat["frame.latency_s.p50"] == 2.0
    assert flat["frame.latency_s.max"] == 4.0


def test_snapshot_rejects_newer_version():
    snap = MetricsRegistry().snapshot()
    snap["version"] = METRICS_SCHEMA_VERSION + 1
    with pytest.raises(ValueError, match="newer"):
        open_snapshot(snap)
    with pytest.raises(ValueError):
        open_snapshot({"artifact": "plan", "version": 1, "payload": {}})


def test_registry_merge_semantics():
    a, b = MetricsRegistry(), MetricsRegistry()
    a.counter("c").inc(1)
    b.counter("c").inc(2)
    a.gauge("g").set(1.0)
    b.gauge("g").set(5.0)
    a.histogram("h").observe(1.0)
    b.histogram("h").observe(3.0)
    a.merge(b)
    assert a.value("c") == 3.0                    # counters add
    assert a.value("g") == 5.0                    # gauges overwrite
    flat = flatten(a.snapshot())
    assert flat["h.count"] == 2.0 and flat["h.max"] == 3.0


def test_null_registry_is_inert():
    assert not NULL_REGISTRY
    NULL_REGISTRY.counter("x").inc()
    NULL_REGISTRY.gauge("y").set(1.0)
    NULL_REGISTRY.histogram("z").observe(1.0)


# ----------------------------------------------------------- monitor


def test_monitor_zero_modeled_seconds():
    m = Monitor(metrics=MetricsRegistry())
    m.record(0, "pi0", 0.0, 0.01)
    assert m.samples == 1
    assert m.device_ratio("pi0") == 1.0           # no ratio from 0 model
    assert m.drifted_devices() == []
    assert m.stage_time[0].n == 1
    assert m.metrics.value("monitor.samples") == 1.0


def test_monitor_first_sample_ewma_exact():
    m = Monitor()
    m.record(0, "pi0", 1.0, 2.0)
    assert m.device_ratio("pi0") == 2.0           # not blended with init 1.0
    m.record(0, "pi0", 1.0, 2.0)
    assert m.device_ratio("pi0") == 2.0


def test_monitor_drift_boundary_is_strict():
    m = Monitor(drift_threshold=0.25)
    m.record(0, "at", 1.0, 1.25)                  # |ewma-1| == threshold
    m.record(0, "over", 1.0, 1.2500001)
    assert m.drifted_devices() == ["over"]


# ------------------------------------------------------ conv fallback


def test_conv_fallback_is_structured():
    jnp = pytest.importorskip("jax.numpy")
    from repro.kernels.conv2d.ops import conv2d, fallback_count
    from repro.obs.metrics import default_registry

    # strided convs now run on the Pallas kernel; the one remaining
    # fallback is an input spatially smaller than the filter
    from repro.kernels.conv2d.ref import conv2d_ref
    x = jnp.ones((1, 2, 2, 4), jnp.float32)
    w = jnp.ones((3, 3, 4, 8), jnp.float32)
    # compile the fallback's XLA conv outside the tracer: where an earlier
    # test in this process installed the compile listener, that compile
    # would add a ``compile`` span to the one asserted below
    conv2d_ref(x, w, (1, 1)).block_until_ready()
    before = fallback_count()
    tr = Tracer()
    with obs_trace.scoped(tr), pytest.warns(RuntimeWarning):
        import warnings
        warnings.simplefilter("always")           # defeat the once-cache
        conv2d(x, w, stride=(1, 1))
    assert fallback_count() == before + 1
    flat = flatten(default_registry().snapshot())
    labelled = [k for k in flat
                if k.startswith("conv.fallback{") and "reason=shape" in k
                and "x_shape=(1, 2, 2, 4)" in k]
    assert labelled, sorted(k for k in flat if k.startswith("conv.fallback"))
    assert [s.name for s in tr.spans] == ["conv.fallback"]
    assert tr.spans[0].attr("reason") == "shape"


# ------------------------------------- end-to-end: fig13 VGG16 deployment


@pytest.fixture(scope="module")
def traced_deployment():
    from repro.core import make_pi_cluster
    from repro.models.cnn import zoo
    model = zoo.vgg16(input_size=(64, 64), scale=0.125)
    cluster = make_pi_cluster([1.5, 1.2, 1.0, 0.8], bandwidth_mbps=50.0)
    dep = repro.compile(model, cluster)
    rt = dep.runtime(repro.DeploySpec(trace=True), real_compute=False)
    rt.run(n_frames=8)
    return dep, rt


def test_fig13_trace_acceptance(traced_deployment, tmp_path):
    dep, rt = traced_deployment
    n_stages = len(dep.pico.pipeline.stages)
    n_frames = 8
    path = dep.save_trace(tmp_path / "fig13.json")
    doc = json.load(open(path))
    assert validate_chrome_trace(doc) == []
    # one process row per device actor
    rows = {ev["args"]["name"] for ev in doc["traceEvents"]
            if ev.get("ph") == "M" and ev.get("name") == "process_name"}
    for d in dep.cluster.devices:
        assert d.name in rows
    # >= 1 span per stage per frame
    spans = from_chrome_trace(doc)
    compute = [s for s in spans if s.name == "stage.compute"]
    assert len(compute) >= n_stages * n_frames
    for s_idx in range(n_stages):
        assert sum(1 for s in compute if s.attr("stage") == s_idx) >= n_frames
    assert sum(1 for s in spans if s.name == "frame") == n_frames
    # compile-time spans (plan) land on the deployment tracer too
    assert any(s.name == "plan" for s in spans)


def test_deployment_metrics_snapshot(traced_deployment):
    dep, rt = traced_deployment
    snap = dep.metrics_snapshot()
    assert snap["version"] == METRICS_SCHEMA_VERSION
    assert snap["payload"]["meta"]["model"]
    flat = flatten(snap)
    assert flat["runtime.frames_completed"] == 8.0
    assert flat["frame.latency_s.count"] == 8.0
    assert flat["frame.latency_s.p50"] <= flat["frame.latency_s.p99"]
    assert "exec.cache.hits" in flat              # default-registry merge


def test_trace_cli_summary_and_validation(traced_deployment, tmp_path, capsys):
    from repro.tools.trace import bubble_fraction, main
    dep, rt = traced_deployment
    path = str(dep.save_trace(tmp_path / "cli.json"))
    assert main([path, "--validate"]) == 0
    assert main([path]) == 0
    out = capsys.readouterr().out
    assert "per-device compute" in out and "bubble fraction" in out
    assert 0.0 <= bubble_fraction(dep.tracer.spans) < 1.0
    bad = tmp_path / "bad.json"
    bad.write_text(json.dumps({"traceEvents": [{"ph": "X"}]}))
    assert main([str(bad), "--validate"]) == 1


def test_untraced_runtime_overhead_under_2pct():
    """With tracing off the runtime must pay only a falsy branch per
    event: an untraced run may not be measurably slower than a traced
    one (best-of-N wall clock, interleaved to decorrelate noise)."""
    from repro.core import make_pi_cluster
    from repro.models.cnn import zoo
    model = zoo.vgg16(input_size=(64, 64), scale=0.125)
    cluster = make_pi_cluster([1.5, 1.2, 1.0, 0.8], bandwidth_mbps=50.0)
    dep = repro.compile(model, cluster)

    def run(trace: bool) -> float:
        rt = dep.runtime(repro.DeploySpec(trace=trace), real_compute=False)
        t0 = time.perf_counter()
        rt.run(n_frames=64)
        return time.perf_counter() - t0

    run(False), run(True)                         # warm both paths
    off, on = [], []
    for _ in range(5):
        off.append(run(False))
        on.append(run(True))
    assert min(off) <= min(on) * 1.02, (off, on)


def test_untraced_runtime_uses_null_singletons():
    from repro.core import make_pi_cluster
    from repro.models.cnn import zoo
    model = zoo.vgg16(input_size=(64, 64), scale=0.125)
    dep = repro.compile(model, make_pi_cluster([1.0, 1.0]))
    rt = dep.runtime(repro.DeploySpec(trace=False, metrics=False),
                     real_compute=False)
    assert rt.tracer is NULL_TRACER
    assert rt.metrics is NULL_REGISTRY


# ---------------------------------------------------- bench-gate bridge


def test_bench_gate_reads_snapshot():
    from tools.bench_gate import check, flatten_snapshot, metrics_view
    reg = MetricsRegistry()
    reg.counter("runtime.frames_dropped").inc(0)
    reg.gauge("serving_mt.throughput_ratio").set(2.4)
    for x in (0.01, 0.02, 0.03):
        reg.histogram("frame.latency_s").observe(x)
    snap = reg.snapshot()
    # the gate's dependency-free flatten agrees with repro.obs.flatten
    assert flatten_snapshot(snap) == flatten(snap)
    baseline = {"metrics": {
        "serving_mt.throughput_ratio": {"value": 2.0, "direction": "higher"},
        "frame.latency_s.p95": {"value": 0.03, "direction": "lower"},
    }}
    assert check(snap, baseline) == []            # bare snapshot form
    combined = {"metrics": {"legacy.metric": 1.0}, "snapshot": snap}
    view = metrics_view(combined)
    assert view["legacy.metric"] == 1.0
    assert view["frame.latency_s.count"] == 3.0
    newer = dict(snap, version=METRICS_SCHEMA_VERSION + 1)
    with pytest.raises(ValueError, match="newer"):
        flatten_snapshot(newer)


def test_servestats_publish_idempotent():
    st = ServeStats(period_model_s=0.05, wall_s=1.0)
    st.record(0.01)
    st.record(0.02, missed_deadline=True)
    reg = MetricsRegistry()
    st.publish(reg, tenant="a")
    st.publish(reg, tenant="a")                   # re-publish: no double count
    flat = flatten(reg.snapshot())
    assert flat["serve.served{tenant=a}"] == 2.0
    assert flat["serve.deadline_misses{tenant=a}"] == 1.0
    assert flat["serve.latency_s{tenant=a}.count"] == 2.0
    st.record(0.03)
    st.publish(reg, tenant="a")                   # incremental append
    assert flatten(reg.snapshot())["serve.latency_s{tenant=a}.count"] == 3.0
