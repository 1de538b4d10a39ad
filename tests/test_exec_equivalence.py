"""Bit-exactness of the pipelined/tiled execution vs monolithic forward.

This is the system's core correctness property (paper §5.3: split and
stitch must be lossless), property-tested over random CNN chains with
hypothesis and over the real zoo DAGs.
"""

import hypothesis.strategies as st
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings

from repro.api import ExecSpec
from repro.core import make_pi_cluster, plan
from repro.models.cnn import zoo
from repro.models.cnn.builder import GB
from repro.pipeline import PipelineRunner
from repro.pipeline.stage import StageExecutor

# tiny-but-representative build of every zoo model (pallas runs in
# interpret mode on CPU, so sizes are kept small)
ZOO_TINY = {
    "vgg16": dict(input_size=(40, 40), scale=0.1, head=False),
    "yolov2": dict(input_size=(64, 64), scale=0.05),
    "resnet34": dict(input_size=(64, 64), scale=0.1),
    "inceptionv3": dict(input_size=(96, 96), scale=0.1),
    "squeezenet": dict(input_size=(64, 64), scale=0.1),
    "mobilenetv3": dict(input_size=(64, 64), scale=0.1),
    "nasnet": dict(n_cells=2, input_size=(48, 48), scale=0.15),
}


@pytest.mark.parametrize("name", sorted(ZOO_TINY))
@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_compiled_stage_bit_exact_with_eager(name, backend):
    """The `repro.exec` compiled stage path reproduces the seed's eager
    tile loop for every zoo model on both backends: bit-for-bit on xla;
    to ULP tolerance on pallas, which runs via interpret on CPU where
    whole-stage fusion can reassociate the emulated kernel's ops."""
    m = zoo.build(name, **ZOO_TINY[name])
    params = m.init(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1),
                          (1, m.input_size[1], m.input_size[0], 3))
    fracs = [0.4, 0.35, 0.25]
    eager = StageExecutor(m, frozenset(m.graph.layers), fracs,
                          backend=backend, mode="eager")(params, {}, x)
    compiled = StageExecutor(m, frozenset(m.graph.layers), fracs,
                             backend=backend)(params, {}, x)
    assert eager.keys() == compiled.keys()
    for k in eager:
        if backend == "xla":
            np.testing.assert_array_equal(np.asarray(compiled[k]),
                                          np.asarray(eager[k]))
        else:
            # interpret-mode pallas emulates the kernel with XLA ops; on
            # CPU the whole-stage jit may fuse those ops differently
            # than the seed's standalone-jit kernel call, shifting deep
            # models (mobilenetv3: ~50 layers) by a few ULP — everything
            # else is identical
            np.testing.assert_allclose(np.asarray(compiled[k]),
                                       np.asarray(eager[k]),
                                       rtol=1e-6, atol=1e-7)
    # and both match the monolithic reference numerically
    ref = m.forward(params, x)
    for k in ref:
        np.testing.assert_allclose(np.asarray(compiled[k]),
                                   np.asarray(ref[k]),
                                   rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("name", sorted(ZOO_TINY))
def test_zoo_pallas_runs_without_fallbacks(name):
    """The generalized Pallas kernel is the *only* conv path: every zoo
    model — strided stems, 1x1 projections, channel tails, fused
    conv->pool chains — runs the pallas backend with ZERO recorded
    ``conv.fallback``s, matching the XLA reference to ULP tolerance
    (interpret mode on CPU)."""
    from repro.kernels.conv2d.ops import fallback_count, reset_fallbacks
    m = zoo.build(name, **ZOO_TINY[name])
    params = m.init(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1),
                          (1, m.input_size[1], m.input_size[0], 3))
    reset_fallbacks()
    ref = m.forward(params, x, backend="xla")
    out = m.forward(params, x, backend="pallas")          # monolithic
    tiled = StageExecutor(m, frozenset(m.graph.layers), [0.6, 0.4],
                          backend="pallas")(params, {}, x)  # fused+tiled
    assert fallback_count() == 0, \
        f"{name}: pallas backend fell back {fallback_count()} time(s)"
    for k in ref:
        np.testing.assert_allclose(np.asarray(out[k]), np.asarray(ref[k]),
                                   rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(np.asarray(tiled[k]), np.asarray(ref[k]),
                                   rtol=2e-5, atol=2e-5)


def test_fused_conv_pool_chain_matches_unfused():
    """fusable_chains finds the zoo's conv->pool chains and the fused
    lowering matches the unfused compiled path to ULP tolerance."""
    from repro.exec.compiler import fusable_chains
    m = zoo.build("vgg16", **ZOO_TINY["vgg16"])
    chains = fusable_chains(m.graph, frozenset(m.graph.layers))
    assert len(chains) >= 4   # vgg16: one fusable pool per conv block
    for conv, pool in chains.items():
        assert m.graph.layers[conv].kind == "conv"
        assert m.graph.layers[pool].kind == "pool"
    params = m.init(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 40, 40, 3))
    fused = StageExecutor(m, frozenset(m.graph.layers), [0.5, 0.5],
                          backend="pallas")(params, {}, x)
    unfused = StageExecutor(m, frozenset(m.graph.layers), [0.5, 0.5],
                            backend="pallas", fuse=False)(params, {}, x)
    for k in fused:
        np.testing.assert_allclose(np.asarray(fused[k]),
                                   np.asarray(unfused[k]),
                                   rtol=1e-6, atol=1e-7)


def test_fuse_flag_is_part_of_cache_key():
    """Fused and unfused executables of the same stage must not collide
    in the executable cache."""
    from repro.exec import clear_cache, cache_stats
    m = zoo.build("vgg16", **ZOO_TINY["vgg16"])
    params = m.init(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 40, 40, 3))
    clear_cache()
    StageExecutor(m, frozenset(m.graph.layers), [1.0],
                  backend="pallas")(params, {}, x)
    StageExecutor(m, frozenset(m.graph.layers), [1.0],
                  backend="pallas", fuse=False)(params, {}, x)
    assert cache_stats().misses == 2   # distinct keys -> two builds
    clear_cache()


def test_compiled_multi_stage_plan_bit_exact_with_eager():
    """Whole-plan check: compiled and eager runners agree stage by stage
    on a real PICO plan (not just the single fused stage)."""
    m = zoo.resnet34(input_size=(96, 96), scale=0.1)
    cluster = make_pi_cluster([1.5, 1.2, 1.0, 0.8])
    p = plan(m.graph, cluster, m.input_size)
    params = m.init(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 96, 96, 3))
    out_c = PipelineRunner(m, p.pipeline)(params, x)
    out_e = PipelineRunner(m, p.pipeline, ExecSpec(mode="eager"))(params, x)
    for k in out_c:
        np.testing.assert_array_equal(np.asarray(out_c[k]),
                                      np.asarray(out_e[k]))


@pytest.mark.parametrize("name,kw", [
    ("resnet34", dict(input_size=(96, 96), scale=0.1)),
    ("inceptionv3", dict(input_size=(96, 96), scale=0.1)),
    ("nasnet", dict(n_cells=3, input_size=(64, 64), scale=0.15)),
])
def test_pipeline_equals_monolithic(name, kw):
    m = zoo.build(name, **kw)
    cluster = make_pi_cluster([1.5, 1.2, 1.0, 0.8])
    p = plan(m.graph, cluster, m.input_size)
    params = m.init(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1),
                          (2, m.input_size[1], m.input_size[0], 3))
    ref = m.forward(params, x)
    out = PipelineRunner(m, p.pipeline)(params, x)
    for k in ref:
        assert not np.isnan(np.asarray(ref[k])).any()
        np.testing.assert_allclose(np.asarray(out[k]), np.asarray(ref[k]),
                                   rtol=1e-5, atol=1e-5)


def test_uneven_multiway_tile_split():
    m = zoo.resnet34(input_size=(96, 96), scale=0.1)
    params = m.init(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 96, 96, 3))
    ref = m.forward(params, x)
    ex = StageExecutor(m, frozenset(m.graph.layers),
                       [0.35, 0.3, 0.2, 0.15])
    out = ex(params, {}, x)
    for k in ref:
        np.testing.assert_allclose(np.asarray(out[k]), np.asarray(ref[k]),
                                   rtol=1e-5, atol=1e-5)


@settings(max_examples=12, deadline=None)
@given(
    st.lists(st.sampled_from(
        [("conv", 3, 1, 1), ("conv", 1, 1, 0), ("conv", 5, 1, 2),
         ("conv", 3, 2, 1), ("pool", 2, 2, 0), ("conv", 3, 1, 0)]),
        min_size=2, max_size=5),
    st.integers(2, 4),
    st.booleans(),
)
def test_random_chain_tiled_exact(ops, parts, with_skip):
    """Random small chains (optionally with an add-skip) tile exactly."""
    b = GB("rand", (24, 24))
    x = b.conv(None, 4, 3, p=1)
    skip_src = x
    depth_since_skip = 0
    for kind, k, s, p in ops:
        if kind == "conv":
            x = b.conv(x, 4, k, s=s, p=p)
        else:
            x = b.pool(x, k, s)
        depth_since_skip += 1
        if with_skip and depth_since_skip == 1 and s == 1 and \
                b.sz[x] == b.sz[skip_src]:
            x = b.add([x, skip_src])
    m = b.done()
    params = m.init(jax.random.PRNGKey(0))
    img = jax.random.normal(jax.random.PRNGKey(1), (1, 24, 24, 3))
    ref = m.forward(params, img)
    sink_w = min(m.full_sizes[s][0] for s in m.graph.sinks())
    if sink_w < parts:
        return
    ex = StageExecutor(m, frozenset(m.graph.layers), [1 / parts] * parts)
    out = ex(params, {}, img)
    for k in ref:
        np.testing.assert_allclose(np.asarray(out[k]), np.asarray(ref[k]),
                                   rtol=1e-5, atol=1e-5)
