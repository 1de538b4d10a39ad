"""Compiled per-stage execution backend with calibrated costs.

The execution layer between the planner's :class:`StagePlan` and JAX:

* :mod:`~repro.exec.backends` — pluggable conv backends (``xla``,
  ``pallas``) selected per model/executor, no mutable module global;
  backends may register a *fused* conv-epilogue lowering and the
  chains it takes (xla: lane-sparse conv->pool chains on a TPU, in
  space-to-depth phase form);
* :mod:`~repro.exec.compiler` — lowers one stage's fused segment (all
  device tiles) into a single jitted callable, pattern-matching
  conv->pool chains into single fused calls, with optional
  buffer donation and ``lax.scan`` micro-batching over frames;
* :mod:`~repro.exec.cache` — executable cache keyed on (segment
  signature, tile shapes, dtype, backend, fuse);
* :mod:`~repro.exec.calibrate` — times compiled stages and feeds a
  measured :class:`~repro.core.cost.CostTable` back into the planner;
* :mod:`~repro.exec.autotune` — searches the Pallas kernel's channel
  block sizes per conv shape and persists winners into the same
  CostTable artifact.
"""

from .backends import (apply_conv, apply_layer, available_backends,
                       default_interpret, fuses, get_backend,
                       register_backend)
from .compiler import (CompiledStage, compile_stage, fusable_chains,
                       segment_signature)
from .cache import (CacheStats, cache_stats, clear_cache, compiled_stage,
                    set_cache_size, stage_cache_key, static_stage_key)
from .calibrate import (CalibrationReport, StageCalibration, calibrate_plan,
                        calibrated_plan, measure_host_flops)
from .autotune import (DEFAULT_CANDIDATES, TuneResult, autotune_conv,
                       autotune_model, clear_installed, install, installed,
                       shape_key, tuned_blocks)

__all__ = [
    "apply_conv", "apply_layer", "available_backends", "default_interpret",
    "fuses", "get_backend", "register_backend", "CompiledStage",
    "compile_stage", "fusable_chains", "segment_signature", "CacheStats",
    "cache_stats", "clear_cache", "compiled_stage", "set_cache_size",
    "stage_cache_key", "static_stage_key",
    "CalibrationReport", "StageCalibration", "calibrate_plan",
    "calibrated_plan", "measure_host_flops",
    "DEFAULT_CANDIDATES", "TuneResult", "autotune_conv", "autotune_model",
    "clear_installed", "install", "installed", "shape_key", "tuned_blocks",
]
