"""Program spans on the two serving paths: ``Deployment.run`` and the
dist fleet's frame path, their bounded rings, their profiler
annotations, and the XLA compile counter."""

import glob
import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro
from repro.api.deployment import FEED_CHUNK
from repro.api.specs import DistSpec, ExecSpec
from repro.core import make_pi_cluster
from repro.dist import make_frames
from repro.models.cnn import zoo
from repro.obs import trace as obs_trace
from repro.obs.metrics import default_registry
from repro.obs.trace import NULL_TRACER, Tracer


def _model():
    return zoo.squeezenet(input_size=(32, 32), scale=0.1)


@pytest.fixture(scope="module")
def dep3():
    """Three stages on three devices, weights loaded."""
    dep = repro.compile(_model(), make_pi_cluster([1.5, 1.2, 1.0],
                                                  bandwidth_mbps=50.0))
    return dep.load_params()


@pytest.fixture(scope="module")
def dep2():
    """Two stages on two devices."""
    dep = repro.compile(_model(), make_pi_cluster([1.0, 1.0],
                                                  bandwidth_mbps=50.0))
    assert len(dep.pico.pipeline.stages) == 2
    return dep


def _inside(child, parent):
    return parent.ts <= child.ts and child.end <= parent.end


def _call_spans(dep, call):
    spans = dep.tracer.spans
    (run,) = [s for s in spans if s.name == "run" and s.attr("call") == call]
    return run, [s for s in spans if s.name != "run" and _inside(s, run)
                 and s.name in ("run.stack", "run.split", "stage")]


def test_deployment_run_records_one_span_tree(dep3):
    host = list(make_frames(dep3.model, 32))
    n_stages = len(dep3.pico.pipeline.stages)
    reg = default_registry()
    n_host = math.ceil(32 / FEED_CHUNK)
    assert n_host > 1
    for frames, src, n_chunks in (
            (host, "host", n_host),
            ([jnp.asarray(x) for x in host], "device", 1)):
        dep3.run(frames)                               # warm
        call = dep3._calls + 1
        fed = reg.total("run.feed_chunks")
        outs = dep3.run(frames)
        assert reg.total("run.feed_chunks") - fed == n_chunks
        assert len(outs) == 32
        run, kids = _call_spans(dep3, call)
        assert run.attr("frames") == 32
        assert run.attr("chunks") == n_chunks
        names = sorted(s.name for s in kids)
        assert names == sorted(["run.stack"] * n_chunks + ["run.split"]
                               + ["stage"] * (n_stages * n_chunks))
        for s in kids:
            assert _inside(s, run)
            if s.name != "stage":
                assert s.attr("call") == call

        def by_ts(name):
            return sorted((s for s in kids if s.name == name),
                          key=lambda s: s.ts)
        stacks, stages = by_ts("run.stack"), by_ts("stage")
        split, = by_ts("run.split")
        assert [s.attr("chunk") for s in stacks] == list(range(n_chunks))
        assert all(s.attr("src") == src for s in stacks)
        for k, stack in enumerate(stacks):
            mine = stages[k * n_stages:(k + 1) * n_stages]
            assert [s.attr("stage") for s in mine] == \
                [f"stage{i}" for i in range(n_stages)]
            assert stack.end <= mine[0].ts
            if k + 1 < n_chunks:
                assert mine[-1].end <= stacks[k + 1].ts
        assert stages[-1].end <= split.ts
        assert split.attr("dispatches") == 1


def test_same_shape_run_adds_no_compile(dep3):
    reg = default_registry()
    # a scan length of its own; then chunks with a ragged tail
    for n in (5, FEED_CHUNK + 3):
        frames = list(make_frames(dep3.model, n))
        before = reg.total("xla.compiles")
        dep3.run(frames)                 # a new scan length: compiles
        assert reg.total("xla.compiles") > before
        assert reg.total("xla.compile_s") > 0.0
        assert any(s.name == "compile" and s.attr("fun")
                   for s in dep3.tracer.spans)
        n_spans = len(dep3.tracer.by_name("compile"))
        n_compiles = reg.total("xla.compiles")
        dep3.run(frames)
        assert len(dep3.tracer.by_name("compile")) == n_spans
        assert reg.total("xla.compiles") == n_compiles


def _by_fid(spans, fid):
    return [s for s in spans if s.attr("fid") == fid]


@pytest.mark.parametrize("transport", ["memory", "tcp"])
def test_fleet_frame_spans_in_order(dep2, transport):
    tracer = Tracer()
    xs = make_frames(dep2.model, 4)
    rep = dep2.fleet(DistSpec(transport=transport), tracer=tracer).run(xs)
    assert rep.completed == 4
    spans = tracer.spans
    last = "dist:w1"
    for fid in range(4):
        mine = _by_fid(spans, fid)

        def one(name, track, link=None):
            got = [s for s in mine if s.name == name and s.track == track
                   and (link is None or s.attr("link") == link)]
            assert len(got) == 1, (fid, name, track, link, got)
            return got[0]

        submit = one("dist.submit", "dist:launcher")
        feed_wait = one("link.wait", "dist:w0", "feed")
        assert submit.ts <= feed_wait.ts
        prev_send = None
        for w in ("dist:w0", "dist:w1"):
            compute = one("stage.compute", w)
            h2d, d2h = one("worker.h2d", w), one("worker.d2h", w)
            send = one("worker.send", w)
            wait = one("link.wait", w)
            assert wait.end <= compute.ts
            if prev_send is not None:
                assert prev_send.ts <= wait.ts     # stamped in the send
            assert _inside(h2d, compute) and _inside(d2h, compute)
            assert h2d.end <= d2h.ts
            assert compute.end <= send.ts
            assert _inside(one("link.encode", w), send)
            prev_send = send
        collect = one("dist.collect", "dist:launcher")
        sink_wait = one("link.wait", "dist:launcher", "sink")
        assert one("worker.send", last).ts <= collect.ts
        assert collect.ts <= sink_wait.end <= collect.end
        assert _inside(one("link.decode", "dist:launcher"), collect)
        frame = one("frame", "dist:launcher")
        assert submit.ts <= frame.ts and frame.end <= collect.end
    # the executor's stage dispatch lies inside each stage.compute
    for w in ("dist:w0", "dist:w1"):
        computes = [s for s in spans if s.name == "stage.compute"
                    and s.track == w and s.attr("fid") >= 0]
        stages = [s for s in spans if s.name == "stage" and s.track == w]
        for c in computes:
            assert sum(_inside(s, c) for s in stages) == 1


def test_ring_keeps_lifecycle_and_counts_evictions():
    tr = Tracer()
    tr.emit("plan", 0.0, 0.5)
    n = 100_000
    for i in range(n):
        tr.emit("frame", float(i), 0.25, fid=i)
    cap = obs_trace.RING_SPANS
    assert len(tr) == cap + 1
    assert [s.name for s in tr.by_name("plan")] == ["plan"]
    assert tr.evicted == n - cap
    assert tr.evicted_until == (n - cap - 1) + 0.25
    frames = tr.by_name("frame")
    assert len(frames) == cap and frames[0].attr("fid") == n - cap
    assert tr.spans[0].name == "plan"          # emission order kept


def test_wall_span_on_one_timeline_and_null_tracer_inert():
    tr = Tracer()
    follower = Tracer(epoch=tr.epoch)
    with tr.wall_span("run", call=1) as outer:
        with follower.wall_span("run.stack", call=1) as inner:
            inner.set(extra=2)
    (a,), (b,) = tr.spans, follower.spans
    assert a.ts <= b.ts and b.end <= a.end and outer.t0 <= inner.t0
    assert b.attr("extra") == 2
    with NULL_TRACER.wall_span("run") as span:
        span.set(frames=3)
    assert NULL_TRACER.spans == ()


def test_fleet_tracers_stay_bounded(dep2, monkeypatch):
    monkeypatch.setattr(obs_trace, "RING_SPANS", 16)
    tracer = Tracer()
    launcher = dep2.fleet(DistSpec(), tracer=tracer)
    rep = launcher.run(make_frames(dep2.model, 24))
    assert rep.completed == 24
    assert tracer._rings and all(len(r) <= 16
                                 for r in tracer._rings.values())
    assert tracer.evicted > 0 and tracer.evicted_until > 0
    for w in launcher.workers:
        assert w.stats["evicted"] > 0
        assert len(w.stats["spans"]) <= 16      # one ring, no lifecycle


def test_profiler_sees_repro_annotations(dep2, tmp_path):
    from jax.profiler import ProfileData
    xs = make_frames(dep2.model, 2)
    dep2.run(list(xs))                             # warm
    launcher = dep2.fleet(DistSpec())
    launcher.start()
    with jax.profiler.trace(str(tmp_path)):
        dep2.run(list(xs))
        launcher.run(xs)
    (path,) = glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"),
                        recursive=True)
    pd = ProfileData.from_file(path)
    names = {ev.name for plane in pd.planes
             if plane.name.startswith("/host:")
             for line in plane.lines for ev in line.events}
    assert {"repro.run", "repro.run.stack", "repro.stage",
            "repro.run.split", "repro.worker.d2h", "repro.dist.submit",
            "repro.stage.compute"} <= names


#: ExecSpec fields that were retired, each with a value it once took
#: other than its default
RETIRED = {"profile": True, "scan_batch": False, "fuse": False,
           "donate": True}


@pytest.mark.parametrize("field", sorted(RETIRED))
def test_exec_spec_profile_field_retired(field):
    old = dict(ExecSpec().to_dict(), **{field: RETIRED[field]})
    assert ExecSpec.from_dict(old) == ExecSpec()
    assert field not in ExecSpec().to_dict()


@pytest.mark.parametrize("field", sorted(RETIRED))
def test_artifact_carrying_profile_loads(dep2, field):
    doc = json.loads(dep2.to_json())
    doc["payload"]["exec_spec"][field] = RETIRED[field]
    back = repro.Deployment.from_json(json.dumps(doc), model=dep2.model)
    assert back.exec_spec == dep2.exec_spec


def test_compiled_stage_ops_carry_stage_and_layer_scopes(dep2):
    import re
    params = dep2.load_params().params
    ex = dep2.runner.stages[1]
    produced = dep2.runner.stages[0](
        params, {}, np.zeros((1, 32, 32, 3), np.float32))
    boundary = ex.boundary_inputs(produced, None)
    cs = ex._executable(boundary)
    text = cs._fn.lower(params, *(boundary[k] for k in cs.needs)) \
        .as_text(dialect="hlo", debug_info=True)
    names = set(re.findall(r'op_name="([^"]+)"', text))
    convs = [n for n in ex.nodes
             if dep2.model.graph.layers[n].kind == "conv"]
    assert convs and any("/stage1/" in n for n in names)
    for node in convs:
        assert any(f"/stage1/{node}/" in n for n in names), node
