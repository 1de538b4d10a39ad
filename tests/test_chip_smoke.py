"""``chip_smoke.py`` on the CPU at a tiny size (vgg16, scale 0.1, 64x64,
Pallas in interpret mode), its refusal of a CPU-only platform, the
compile-cache helper, and the refusals that keep a run on its device."""

import sys
from pathlib import Path

import jax
import numpy as np
import pytest

import repro
from repro.exec.backends import default_interpret
from repro.exec.cache import COMPILE_CACHE_DIR, enable_compile_cache

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))
import chip_smoke  # noqa: E402


@pytest.fixture(scope="module")
def tiny():
    model, cluster, frames = chip_smoke.build(size=64, scale=0.1)
    dep = repro.compile(model, cluster)
    assert len(dep.pico.pipeline.stages) > 1
    refs = chip_smoke.references(model, dep.load_params().params, frames)
    return model, cluster, frames, dep, refs


def test_reference_matches_model_forward(tiny):
    model, _, frames, dep, refs = tiny
    out = model.forward(dep.params, frames[0])
    np.testing.assert_allclose(chip_smoke._sink(out), refs[0],
                               rtol=1e-5, atol=1e-6)


def test_check_close_raises_past_tolerance():
    ref = np.ones((1, 10), np.float32)
    chip_smoke.check_close("ok", [ref * (1 + chip_smoke.TOL / 2)], [ref])
    with pytest.raises(AssertionError, match="exceeds"):
        chip_smoke.check_close("bad", [ref * (1 + 2 * chip_smoke.TOL)], [ref])


def test_phase_run_xla(tiny, capsys):
    _, _, frames, dep, refs = tiny
    chip_smoke.phase_run("xla", dep, frames, refs, iters=1)
    assert "not a benchmark" in capsys.readouterr().out


def test_phase_dist_matches_deployment_run_exactly(tiny):
    _, _, frames, dep, refs = tiny
    singles = chip_smoke.single_frame_outputs(dep, frames[1:])
    ids = chip_smoke.phase_dist(dep, frames, refs, singles)
    assert ids == [jax.local_devices()[0].id] * len(dep.pico.pipeline.stages)


def test_phase_pallas_interpreted_without_fallbacks(tiny):
    model, cluster, frames, _, refs = tiny
    assert chip_smoke.phase_pallas(model, cluster, frames, refs,
                                   interpret=True) == 0
    with pytest.raises(AssertionError, match="interpret"):
        chip_smoke.phase_pallas(model, cluster, frames, refs,
                                interpret=False)


def test_main_refuses_a_cpu_only_platform(capsys):
    assert chip_smoke.main([]) != 0
    captured = capsys.readouterr()
    assert "'cpu'" in captured.err
    assert '"ok"' not in captured.out


def test_compile_cache_honours_env(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_fixed_repo_path(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        assert enable_compile_cache() == str(ROOT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == str(COMPILE_CACHE_DIR)
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_process_workers_refused_on_a_tpu_host(tiny, monkeypatch):
    dep = tiny[3]
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(RuntimeError, match="workers='thread'"):
        dep.fleet(repro.DistSpec(transport="tcp", workers="process"))
    dep.fleet(repro.DistSpec(workers="thread"))      # threads stay allowed


@pytest.mark.parametrize("platform,want", [("cpu", True), ("tpu", False),
                                           ("gpu", None)])
def test_default_interpret_only_on_cpu(platform, want, monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: platform)
    if want is None:
        with pytest.raises(RuntimeError, match="'gpu'"):
            default_interpret()
    else:
        assert default_interpret() is want
