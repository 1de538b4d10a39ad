"""The xla backend's space-to-depth phase form of lane-sparse conv ->
2x2/2 pool chains (``exec.backends.conv_pool_phase``): it matches the
composed conv + bias + activation + pool, it engages only where the
platform has 128-lane tiles and the chain's input is lane-sparse (on
the CPU the platform observation is patched), the ``stage.phase_pools``
counter counts it, and a stage with no engaging chain lowers exactly as
it does with fusion off."""

import itertools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.graph import LayerSpec
from repro.exec import backends
from repro.exec.backends import apply_conv, conv_pool_phase, phase_engages
from repro.exec.compiler import compile_stage, fusable_chains
from repro.kernels.conv2d.ref import conv2d_fused_ref
from repro.models.cnn import zoo
from repro.obs.metrics import default_registry

POOL = LayerSpec("p", "pool", (2, 2), (2, 2), (0, 0), 8, 8)


@pytest.fixture
def lane_tiled(monkeypatch):
    """The platform observation of a TPU, on the CPU."""
    monkeypatch.setattr(backends, "lane_tiled", lambda: True)


def _chain(k, cin, act, h, w, cout=8, seed=0):
    ph = k // 2
    spec = LayerSpec("c", "conv", (k, k), (1, 1), (ph, ph), cin, cout,
                     act=act)
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    x = jax.random.normal(ks[0], (2, h, w, cin))
    p = {"w": jax.random.normal(ks[1], (k, k, cin, cout))
         / np.sqrt(k * k * cin),
         "b": jax.random.normal(ks[2], (cout,))}
    return spec, p, x


def _composed(spec, p, x, pad_w):
    ph = spec.padding[1]
    xp = jnp.pad(x, ((0, 0), (ph, ph), pad_w, (0, 0)))
    return conv2d_fused_ref(xp, p["w"], p["b"], act=spec.act, pool=(2, 2))


# (h, w) of the tile: the pooled width even or odd once pad_w is added
TILES = {"even": (8, 14), "odd": (9, 12)}


@pytest.mark.parametrize("cin,act,pad_w,tile", list(itertools.product(
    (3, 32), ("relu", "leaky", "linear"), ((1, 1), (1, 0), (0, 1), (0, 0)),
    sorted(TILES))))
def test_phase_form_matches_the_composed_chain(cin, act, pad_w, tile):
    """A 3x3 conv at the stem's channel counts, with the ``pad_w`` a
    boundary tile ((1, 1), (1, 0), (0, 1)) or an inner tile ((0, 0))
    gets, against the composed conv + bias + act + pool."""
    spec, p, x = _chain(3, cin, act, *TILES[tile])
    got = conv_pool_phase(spec, POOL, p, x, pad_w)
    ref = _composed(spec, p, x, pad_w)
    assert got.shape == ref.shape
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("k,h,w", [(1, 7, 10), (2, 8, 9), (5, 11, 13)])
def test_phase_form_holds_for_other_kernel_sizes(k, h, w):
    """1x1, even and 5x5 kernels: the phase kernel is 2 x ceil((k+1)/2)
    wide and the tile is padded or cropped to the grid it reads."""
    spec, p, x = _chain(k, 4, "leaky", h, w, seed=k)
    got = conv_pool_phase(spec, POOL, p, x, (1, 0))
    ref = _composed(spec, p, x, (1, 0))
    assert got.shape == ref.shape
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_engage_rule(lane_tiled, monkeypatch):
    """Stride 1, a VALID 2x2/2 pool, 4 * Cin <= 128, on a lane-tiled
    platform; a conv without a pool never."""
    def conv(cin=32, s=1):
        return LayerSpec("c", "conv", (3, 3), (s, s), (1, 1), cin, 8)
    assert phase_engages(conv(), POOL)
    assert phase_engages(conv(cin=3), POOL)
    assert not phase_engages(conv(cin=33), POOL)
    assert not phase_engages(conv(s=2), POOL)
    assert not phase_engages(conv(), None)
    assert not phase_engages(
        conv(), LayerSpec("p", "pool", (3, 3), (3, 3), (0, 0), 8, 8))
    monkeypatch.setattr(backends, "lane_tiled", lambda: False)
    assert not phase_engages(conv(), POOL)


def _chains(name):
    g = zoo.build(name).graph
    return {c: (g.layers[c].in_channels, p) for c, p in
            fusable_chains(g, frozenset(g.layers), "xla").items()}


@pytest.mark.parametrize("name,want", [
    ("yolov2", {"conv1": (3, "pool2"), "conv3": (32, "pool4")}),
    ("vgg16", {}), ("resnet34", {})])
def test_engage_rule_at_published_widths(name, want, lane_tiled):
    """On a TPU it takes yolov2's 3->32 and 32->64 convs with their
    pools, and no chain of vgg16 (pooled convs have Cin >= 64) or
    resnet34 (its stem pool is 3x3/2 padded)."""
    assert _chains(name) == want


def test_nothing_engages_on_the_cpu():
    assert _chains("yolov2") == {}
    g = zoo.build("yolov2").graph
    assert len(fusable_chains(g, frozenset(g.layers), "pallas")) == 4


def _phase_pools(cins=(3, 32)):
    reg = default_registry()
    return {c: reg.counter("stage.phase_pools", cin=c).value for c in cins}


@pytest.mark.parametrize("name,platform_tpu,want", [
    ("yolov2", True, {3: 1, 32: 1}), ("yolov2", False, {3: 0, 32: 0}),
    ("vgg16", True, {3: 0, 32: 0})])
def test_phase_pools_counter(name, platform_tpu, want, monkeypatch):
    """One ``stage.phase_pools{cin}`` per chain a compiled stage builds
    in phase form: yolov2's two on a TPU, none on the CPU or for vgg16;
    pallas fuses its chains in its own kernel and counts none."""
    monkeypatch.setattr(backends, "lane_tiled", lambda: platform_tpu)
    m = zoo.build(name)
    before = _phase_pools()
    compile_stage(m, frozenset(m.graph.layers), [1.0], backend="xla")
    compile_stage(m, frozenset(m.graph.layers), [1.0], backend="pallas")
    after = _phase_pools()
    assert {c: after[c] - before[c] for c in after} == want


#: the debug text's tables of Python source locations, which name the
#: caller's own lines
_SOURCE_TABLES = ("FileNames", "FunctionNames", "FileLocations",
                  "StackFrames")


def _hlo(m, fuse):
    """The stage's HLO with every op's scope (``op_name``), less the
    Python source locations."""
    cs = compile_stage(m, frozenset(m.graph.layers), [0.5, 0.5],
                       backend="xla", fuse=fuse)
    params = jax.eval_shape(lambda: m.init(jax.random.PRNGKey(0)))
    w, h = m.input_size
    x = jax.ShapeDtypeStruct((1, h, w, 3), jnp.float32)
    text = cs._fn.lower(params, *(x for _ in cs.needs)).as_text(
        dialect="hlo", debug_info=True)
    text = re.sub(r" stack_frame_id=\d+", "", text)
    return "\n\n".join(b for b in text.split("\n\n")
                       if not b.startswith(_SOURCE_TABLES))


@pytest.mark.parametrize("name,platform_tpu", [
    ("vgg16", True), ("resnet34", True), ("yolov2", False)])
def test_non_engaging_stage_lowers_as_without_fusion(name, platform_tpu,
                                                     monkeypatch):
    """A stage none of whose chains engages lowers to the same HLO, ops,
    order and scopes, as the unfused stage (xla's only lowering before
    the phase form existed), at published widths."""
    monkeypatch.setattr(backends, "lane_tiled", lambda: platform_tpu)
    m = zoo.build(name, input_size=(64, 64))
    assert _hlo(m, fuse=True) == _hlo(m, fuse=False)


def test_engaging_stage_lowers_the_phase_conv(lane_tiled):
    """yolov2's stem chain lowers, under the conv's scope, as one 4x4
    stride-2 conv (the 2x2 block kernel on the space-to-depth grid) to
    128 (4 x 32) channels at the pooled grid."""
    m = zoo.build("yolov2", input_size=(64, 64))
    text = _hlo(m, fuse=True)
    assert text != _hlo(m, fuse=False)
    assert re.search(r"= f32\[1,32,32,128\]\S* convolution\(.*"
                     r"window=\{size=4x4 stride=2x2\}.*/stage/conv1/", text)


def test_engaging_stage_matches_the_eager_oracle(lane_tiled):
    """A tiled yolov2 stage with its lane-sparse chains in phase form
    (boundary and inner tiles, one frame and the scan) against the
    eager tile loop, which composes every chain."""
    m = zoo.build("yolov2", input_size=(64, 64), scale=0.05)
    params = m.init(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 1, 64, 64, 3))
    nodes = frozenset(m.graph.layers)
    cs = compile_stage(m, nodes, [0.4, 0.35, 0.25], backend="xla")
    assert len(cs.fusion) >= 2
    (src,) = cs.needs
    ref = [m.forward(params, x[f], backend="xla") for f in range(2)]
    one = cs(params, {src: x[0]})
    scan = cs.run_frames(params, {src: x})
    for k in ref[0]:
        np.testing.assert_allclose(np.asarray(one[k]), np.asarray(ref[0][k]),
                                   rtol=1e-5, atol=1e-5)
        for f in range(2):
            np.testing.assert_allclose(np.asarray(scan[k][f]),
                                       np.asarray(ref[f][k]),
                                       rtol=1e-5, atol=1e-5)


def test_apply_conv_routes_only_engaging_chains(lane_tiled):
    """``apply_conv`` on xla: an engaging chain takes the phase form,
    a conv without a pool the composed sequence bit for bit."""
    spec, p, x = _chain(3, 3, "leaky", 8, 8)
    np.testing.assert_array_equal(
        np.asarray(apply_conv(spec, p, x, (1, 1), backend="xla",
                              pool_spec=POOL)),
        np.asarray(conv_pool_phase(spec, POOL, p, x, (1, 1))))
    plain = backends.activation(
        backends.get_backend("xla")(spec, p, x, (1, 1)) + p["b"], "leaky")
    np.testing.assert_array_equal(
        np.asarray(apply_conv(spec, p, x, (1, 1), backend="xla")),
        np.asarray(plain))
