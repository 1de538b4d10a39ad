"""Ahead-of-time compiles for a described (not attached) TPU v5e.

The TPU compiler is installed next to JAX, so the Pallas conv kernel and
a whole-model XLA stage can be compiled for a v5e here, with no chip.
This catches what interpret mode cannot: blocks past the scoped VMEM and
in-kernel slices Mosaic refuses.  Nothing runs, so nothing here is a
result or a time.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every xdist worker
imports this file.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.exec.autotune import conv_shapes
from repro.kernels.conv2d.conv2d import conv2d_fused
from repro.models.cnn import zoo
from repro.pipeline.stage import StageExecutor


def _zoo_conv_cases():
    """Distinct conv-epilogue shapes of vgg16 and resnet34 at 224x224
    and yolov2 at 608x608, published widths, fused the way the compiler
    fuses them; yolov2's carry its leaky or linear epilogue."""
    cases = {}
    for name in ("vgg16", "resnet34", "yolov2"):
        for d in conv_shapes(zoo.build(name)):
            key = (d["x_shape"], d["w_shape"], d["stride"], d["pool"],
                   d["act"])
            cases.setdefault(key, pytest.param(d, id=(
                f"{name}-x{'x'.join(map(str, d['x_shape'][1:]))}"
                f"-k{d['w_shape'][0]}-co{d['w_shape'][3]}"
                f"-s{d['stride'][0]}" + ("-pool" if d["pool"] else "")
                + ("" if d["act"] == "relu" else f"-{d['act']}"))))
    return list(cases.values())


CASES = _zoo_conv_cases()


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    mp = pytest.MonkeyPatch()
    mp.setenv("TPU_LOG_DIR", "disabled")   # else the compiler logs to /tmp
    try:
        t = topologies.get_topology_desc(platform="tpu",
                                         topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        mp.undo()
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache off meanwhile
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield t
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()
    mp.undo()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def test_zoo_shapes_cover_the_hard_cases():
    ids = [c.id for c in CASES]
    assert "vgg16-x226x226x64-k3-co64-s1-pool" in ids      # conv1_2 + pool
    assert "vgg16-x226x226x3-k3-co64-s1" in ids            # 3-channel tail
    assert "resnet34-x230x230x3-k7-co64-s2" in ids         # 7x7/2 stem
    assert any(i.startswith("resnet34") and "-k1-" in i and i.endswith("-s2")
               for i in ids)                               # 1x1/2 projection


@pytest.mark.parametrize("d", CASES)
def test_pallas_conv_compiles_for_v5e(d, one_chip):
    x = jax.ShapeDtypeStruct(d["x_shape"], jnp.float32, sharding=one_chip)
    w = jax.ShapeDtypeStruct(d["w_shape"], jnp.float32, sharding=one_chip)
    b = jax.ShapeDtypeStruct(d["w_shape"][-1:], jnp.float32,
                             sharding=one_chip)
    compiled = jax.jit(lambda x, w, b: conv2d_fused(
        x, w, b, stride=d["stride"], act=d["act"], pool=d["pool"],
        interpret=False)).lower(x, w, b).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _whole_model_stage(m, one_chip):
    ex = StageExecutor(m, frozenset(m.graph.layers), [1.0], backend="xla")

    def sds(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)

    params = jax.tree.map(sds, jax.eval_shape(
        lambda: m.init(jax.random.PRNGKey(0))))
    w, h = m.input_size
    boundary = ex.boundary_inputs(
        {}, jax.ShapeDtypeStruct((1, h, w, 3), jnp.float32,
                                 sharding=one_chip))
    stage = ex._executable(boundary)
    return stage._fn.lower(
        params, *(boundary[k] for k in stage.needs)).compile()


def test_yolov2_whole_model_xla_stage_compiles_for_v5e(one_chip):
    """The published YOLOv2 at 608 — leaky and linear epilogues, the
    reorg and the concat — as the one-chip stage the benchmark runs."""
    m = zoo.yolov2()
    compiled = _whole_model_stage(m, one_chip)
    (sink,) = m.graph.sinks()
    assert compiled.out_info[sink].shape == (1, 19, 19, 425)


def test_vgg16_whole_model_xla_stage_compiles_for_v5e(one_chip):
    m = zoo.vgg16()
    compiled = _whole_model_stage(m, one_chip)
    (sink,) = m.graph.sinks()
    assert compiled.out_info[sink].shape == (1, 1, 1, 1000)
