"""YOLOv2 on the normal path: the space-to-depth ``reorg``, per-layer
leaky/linear conv epilogues on both backends, the published graph at
608, the ReLU models left as they were, the passthrough across stage
boundaries of a fleet, and the ``stage.layers`` counter."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro
from repro.api import artifacts
from repro.api.specs import DistSpec
from repro.core import make_pi_cluster
from repro.core.graph import LayerSpec
from repro.dist import make_frames
from repro.exec.backends import apply_conv, apply_layer
from repro.exec.compiler import compile_stage, fusable_chains
from repro.kernels.conv2d.ref import conv2d_fused_ref
from repro.models.cnn import zoo
from repro.models.cnn.builder import GB
from repro.obs.metrics import default_registry
from repro.pipeline.stage import StageExecutor

TINY = dict(input_size=(64, 64), scale=0.1)


def _reorg_loop(x: np.ndarray, s: int) -> np.ndarray:
    n, h, w, c = x.shape
    out = np.zeros((n, h // s, w // s, s * s * c), x.dtype)
    for i in range(h // s):
        for j in range(w // s):
            for dy in range(s):
                for dx in range(s):
                    out[:, i, j, (dy * s + dx) * c:(dy * s + dx + 1) * c] = \
                        x[:, i * s + dy, j * s + dx, :]
    return out


@pytest.mark.parametrize("shape", [(2, 6, 8, 3), (1, 38, 38, 5)])
def test_reorg_matches_a_numpy_loop(shape):
    spec = LayerSpec("r", "reorg", (2, 2), (2, 2), (0, 0), shape[-1],
                     4 * shape[-1])
    x = np.random.default_rng(0).standard_normal(shape, dtype=np.float32)
    got = np.asarray(apply_layer(spec, None, jnp.asarray(x)))
    np.testing.assert_array_equal(got, _reorg_loop(x, 2))
    assert spec.out_size((shape[2], shape[1])) == (shape[2] // 2,
                                                    shape[1] // 2)
    assert spec.flops((19, 19)) == 0.0


@pytest.mark.parametrize("out_range", [(0, 1), (3, 7), (5, 8), (0, 8)])
def test_reorg_range_map(out_range):
    """Output columns [a, b) of the reorg need input columns [2a, 2b)."""
    b = GB("r", (16, 16))
    x = b.conv(None, 4, 3, p=1)
    r = b.reorg(x)
    m = b.done()
    a, e = out_range
    _, req_in = m.segment_ranges({r}, {r: out_range})
    assert req_in[r] == (2 * a, 2 * e)
    assert m.graph.layers[r].out_channels == 16


@pytest.mark.parametrize("fracs", [[0.3, 0.3, 0.4], [0.55, 0.45],
                                   [0.2, 0.2, 0.2, 0.4]])
def test_reorg_and_concat_tile_bit_exactly(fracs):
    """A passthrough (conv -> reorg, concatenated with a pooled path)
    tiled over the width equals the monolithic forward bit for bit."""
    b = GB("pt", (32, 24))
    x = b.conv(None, 4, 3, p=1, act="leaky")
    r = b.reorg(b.conv(x, 2, 1, act="leaky"))
    m_ = b.conv(b.pool(x), 6, 3, p=1, act="leaky")
    b.conv(b.concat([r, m_]), 5, 1, act="linear")
    m = b.done()
    params = m.init(jax.random.PRNGKey(0))
    img = jax.random.normal(jax.random.PRNGKey(1), (1, 24, 32, 3))
    ref = m.forward(params, img)
    for mode in ("eager", "compiled"):
        out = StageExecutor(m, frozenset(m.graph.layers), fracs,
                            mode=mode)(params, {}, img)
        for k in ref:
            np.testing.assert_array_equal(np.asarray(out[k]),
                                          np.asarray(ref[k]))


@pytest.mark.parametrize("act", ["relu", "leaky", "linear"])
@pytest.mark.parametrize("pool", [None, (2, 2)])
@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_conv_epilogue_per_layer_activation(act, pool, backend):
    """Each conv applies its own activation on both backends (pallas in
    interpret mode on the CPU), as ``kernels/conv2d/ref.py`` composes
    it; negative outputs survive leaky and linear, none survive relu."""
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 10, 12, 5))
    w = jax.random.normal(jax.random.PRNGKey(1), (3, 3, 5, 7)) / np.sqrt(45)
    bias = jax.random.normal(jax.random.PRNGKey(2), (7,))
    spec = LayerSpec("c", "conv", (3, 3), (1, 1), (1, 1), 5, 7, act=act)
    pool_spec = None if pool is None else \
        LayerSpec("p", "pool", pool, pool, (0, 0), 7, 7)
    out = apply_conv(spec, {"w": w, "b": bias}, x, (1, 1), backend=backend,
                     pool_spec=pool_spec)
    xp = jnp.pad(x, ((0, 0), (1, 1), (1, 1), (0, 0)))
    ref = conv2d_fused_ref(xp, w, bias, act=act, pool=pool)
    assert out.shape == ref.shape
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    assert bool(jnp.any(out < 0)) == (act != "relu")
    if act == "leaky":
        lin = conv2d_fused_ref(xp, w, bias, act="linear", pool=None)
        neg = np.asarray(lin) < 0
        if pool is None:
            np.testing.assert_allclose(np.asarray(out)[neg],
                                       0.1 * np.asarray(lin)[neg],
                                       rtol=2e-5, atol=2e-5)


def test_unknown_activation_is_refused():
    with pytest.raises(ValueError, match="act"):
        LayerSpec("c", "conv", act="gelu")


#: (kernel, stride, cin, cout) of the 23 convs of yolov2.cfg at 608
PUBLISHED = [
    (3, 1, 3, 32), (3, 1, 32, 64),
    (3, 1, 64, 128), (1, 1, 128, 64), (3, 1, 64, 128),
    (3, 1, 128, 256), (1, 1, 256, 128), (3, 1, 128, 256),
    (3, 1, 256, 512), (1, 1, 512, 256), (3, 1, 256, 512),
    (1, 1, 512, 256), (3, 1, 256, 512),
    (3, 1, 512, 1024), (1, 1, 1024, 512), (3, 1, 512, 1024),
    (1, 1, 1024, 512), (3, 1, 512, 1024),
    (3, 1, 1024, 1024), (3, 1, 1024, 1024),
    (1, 1, 512, 64),                    # route, on the last 38x38 conv
    (3, 1, 1280, 1024), (1, 1, 1024, 425)]


def test_yolov2_is_the_published_graph_at_608():
    m = zoo.yolov2()
    g, sizes = m.graph, m.full_sizes
    assert m.input_size == (608, 608)
    convs = [n for n, s in g.layers.items() if s.kind == "conv"]
    assert [(g.layers[n].kernel[0], g.layers[n].stride[0],
             g.layers[n].in_channels, g.layers[n].out_channels)
            for n in convs] == PUBLISHED
    assert all(g.layers[n].padding[0] == g.layers[n].kernel[0] // 2
               for n in convs)
    assert [g.layers[n].act for n in convs] == ["leaky"] * 22 + ["linear"]
    pools = [s for s in g.layers.values() if s.kind == "pool"]
    assert len(pools) == 5 and all(
        s.kernel == (2, 2) and s.stride == (2, 2) for s in pools)
    macs = sum(g.layers[n].flops(sizes[n]) for n in convs)
    assert 2 * macs / 1e9 == pytest.approx(62.94, abs=0.01)
    assert sum(g.layers[n].param_bytes for n in convs) / 4 / 1e6 == \
        pytest.approx(50.95, abs=0.01)
    # the passthrough: R (38x38x512) -> 1x1-64 -> reorg -> concat first
    (cat,) = [n for n, s in g.layers.items() if s.kind == "concat"]
    reorg, main = g.preds[cat]
    assert g.layers[reorg].kind == "reorg" and sizes[reorg] == (19, 19)
    (route,) = g.preds[reorg]
    (r,) = g.preds[route]
    assert sizes[r] == (38, 38) and g.layers[r].out_channels == 512
    assert g.layers[main].out_channels == 1024 and sizes[main] == (19, 19)
    assert g.layers[cat].out_channels == 1280
    (sink,) = g.sinks()
    assert sizes[sink] == (19, 19) and g.layers[sink].out_channels == 425


@pytest.mark.parametrize("size", [32, 64, 448])
def test_yolov2_builds_at_any_multiple_of_32(size):
    m = zoo.yolov2(input_size=(size, size), scale=0.05)
    (sink,) = m.graph.sinks()
    assert m.full_sizes[sink] == (size // 32, size // 32)


def test_conv_feeding_pool_and_route_is_not_fused():
    m = zoo.yolov2(**TINY)
    g = m.graph
    (reorg,) = [n for n, s in g.layers.items() if s.kind == "reorg"]
    (r,) = g.preds[g.preds[reorg][0]]
    assert sorted(g.layers[s].kind for s in g.succs[r]) == ["conv", "pool"]
    chains = fusable_chains(g, frozenset(g.layers))
    assert r not in chains
    assert len(chains) == 4             # the other four pools fuse


@pytest.mark.parametrize("name", ["vgg16", "resnet34"])
def test_relu_models_keep_their_epilogue(name):
    """VGG16 and ResNet34 are ReLU after every conv, and their eager
    forward is a conv + bias + ReLU composition, bit for bit."""
    m = zoo.build(name, input_size=(64, 64), scale=0.25)
    g = m.graph
    assert {s.act for s in g.layers.values() if s.kind == "conv"} == \
        {"relu"}
    params = m.init(jax.random.PRNGKey(3))
    params = {n: dict(p, b=p["b"] + 0.1) for n, p in params.items()}
    x = jax.random.normal(jax.random.PRNGKey(4), (1, 64, 64, 3))
    vals = {}
    for n in g.topo_order:
        spec, ps = g.layers[n], g.preds[n]
        xs = [vals[p] for p in ps] if ps else [x]
        if spec.kind == "conv":
            full_w = (m.full_sizes[ps[0]] if ps else m.input_size)[0]
            y = jax.lax.conv_general_dilated(
                xs[0], params[n]["w"], (spec.stride[1], spec.stride[0]),
                ((spec.padding[1],) * 2,
                 g.tile_padding(n, (0, m.full_sizes[n][0]), full_w)),
                dimension_numbers=("NHWC", "HWIO", "NHWC"))
            vals[n] = jax.nn.relu(y + params[n]["b"])
        elif spec.kind == "add":
            vals[n] = sum(xs[1:], xs[0])
        else:
            full_w = (m.full_sizes[ps[0]] if ps else m.input_size)[0]
            pad = g.tile_padding(n, (0, m.full_sizes[n][0]), full_w) \
                if spec.kind == "pool" else (0, 0)
            vals[n] = apply_layer(spec, params.get(n), xs[0], pad)
    out = m.forward(params, x)
    for k in out:
        np.testing.assert_array_equal(np.asarray(out[k]),
                                      np.asarray(vals[k]))


def test_activation_survives_the_artifact_round_trip():
    m = zoo.yolov2(**TINY)
    d = artifacts.model_to_dict(m)
    acts = [ls.get("act") for ls in d["graph"]["layers"]
            if ls["kind"] == "conv"]
    assert acts == ["leaky"] * 22 + ["linear"]
    m2 = artifacts.model_from_dict(d)
    assert [s.act for s in m2.graph.layers.values()] == \
        [s.act for s in m.graph.layers.values()]
    # ReLU layers serialize as before: no act key at all
    v = artifacts.model_to_dict(zoo.vgg16(input_size=(32, 32), scale=0.1))
    assert all("act" not in ls for ls in v["graph"]["layers"])


def test_fleet_equals_run_with_the_route_across_stages():
    """A tiny YOLOv2 planned over four devices: the route leaves R's
    stage and meets the main path stages later, a stage boundary
    carries two tensors, and four thread workers over memory links
    give Deployment.run's outputs bit for bit."""
    model = zoo.yolov2(**TINY)
    dep = repro.compile(model, make_pi_cluster([1.5, 1.2, 1.0, 0.8]))
    stages = dep.pico.pipeline.stages
    assert len(stages) == 4
    g = model.graph
    where = {n: i for i, st in enumerate(stages) for n in st.nodes}
    (cat,) = [n for n, s in g.layers.items() if s.kind == "concat"]
    (reorg,) = [n for n, s in g.layers.items() if s.kind == "reorg"]
    (r,) = g.preds[g.preds[reorg][0]]
    assert where[r] < where[cat]
    assert any(len({p for _, p in model.boundary_needs(st.nodes)
                    if p is not None}) >= 2 for st in stages)
    spec = DistSpec(transport="memory", workers="thread")
    xs = make_frames(model, 3)
    rep = dep.fleet(spec).run(xs)
    assert not rep.dropped
    want = dep.run(xs, params=model.init(jax.random.PRNGKey(spec.seed)))
    for fid, w in enumerate(want):
        for sink, arr in w.items():
            np.testing.assert_array_equal(rep.outputs[fid][sink],
                                          np.asarray(arr))


def test_stage_layers_counter_counts_the_compiled_stage():
    """Compiling YOLOv2's whole graph as one stage counts 22 leaky and
    1 linear conv, 1 reorg and 1 concat: a silent fall-back to ReLU
    would show."""
    reg = default_registry()
    keys = {("conv", "leaky"): 22, ("conv", "linear"): 1,
            ("conv", "relu"): 0, ("reorg", "none"): 1,
            ("concat", "none"): 1, ("pool", "none"): 5}

    def counts():
        return {k: reg.counter("stage.layers", kind=k[0], act=k[1]).value
                for k in keys}
    before = counts()
    m = zoo.yolov2(**TINY)
    compile_stage(m, frozenset(m.graph.layers), [1.0])
    after = counts()
    assert {k: after[k] - before[k] for k in keys} == keys


def test_passthrough_and_activations_run_under_their_layer_scopes():
    """The reorg and the concat lower under ``stage0/<layer>``, and each
    conv's activation under its conv's scope: leaky's select (or the
    call of the jitted ``jax.nn.leaky_relu``) in every leaky conv,
    nothing past the bias add in the linear detection conv."""
    import re
    m = zoo.yolov2(**TINY)
    ex = StageExecutor(m, frozenset(m.graph.layers), [1.0], name="stage0")
    params = m.init(jax.random.PRNGKey(0))
    boundary = ex.boundary_inputs({}, np.zeros((1, 64, 64, 3), np.float32))
    cs = ex._executable(boundary)
    text = cs._fn.lower(params, *(boundary[k] for k in cs.needs)) \
        .as_text(dialect="hlo", debug_info=True)
    ops = re.findall(r'= \S+ (\w+)\(.*?op_name="([^"]+)"', text)
    g = m.graph
    for kind in ("reorg", "concat"):
        (n,) = [x for x, s in g.layers.items() if s.kind == kind]
        assert any(f"/stage0/{n}/" in name for _, name in ops), n
    for n, s in g.layers.items():
        if s.kind != "conv":
            continue
        mine = {op for op, name in ops if f"/stage0/{n}/" in name}
        assert "convolution" in mine, n
        assert bool(mine & {"select", "call"}) == (s.act == "leaky"), \
            (n, mine)
