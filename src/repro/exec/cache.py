"""Executable cache for compiled stage segments.

Keyed on (segment signature, tile shapes, boundary dtypes, backend) —
NOT on model object identity — so a re-plan that reproduces the same
stage structure, or a rebuilt but identical model, reuses the existing
jitted executable instead of re-tracing.  Bounded LRU: past ``maxsize``
the least-recently-used entry is dropped.

Observability: every probe emits a ``cache.lookup`` instant into the
active tracer (:func:`repro.obs.trace.current`), every miss observes
the ``exec.compile.build_s`` histogram — the host time of building the
stage's ``jax.jit`` wrappers, not an XLA compile: that happens at the
executable's first call and is counted by :mod:`repro.obs.compiles` —
and the hit/miss/eviction counters are published into the
process-default metrics registry by a registered collector — hot paths
only bump plain ints.

Across processes, compiled XLA executables persist in JAX's own
compilation cache; :func:`enable_compile_cache` points it at one fixed
directory.
"""

from __future__ import annotations

import os
import time as _time
from collections import OrderedDict
from dataclasses import dataclass
from pathlib import Path
from typing import Mapping, Sequence

import jax

from .compiler import CompiledStage, segment_signature
from ..obs import trace as obs_trace
from ..obs.metrics import default_registry
from ..pipeline.halo import tile_signature


@dataclass
class CacheStats:
    hits: int = 0
    misses: int = 0
    evictions: int = 0

    @property
    def entries(self) -> int:
        return len(_CACHE)

    def snapshot(self) -> "CacheStats":
        """Frozen copy, for windowed accounting (``since``)."""
        return CacheStats(self.hits, self.misses, self.evictions)

    def since(self, mark: "CacheStats") -> "CacheStats":
        """Counter deltas accumulated after ``mark`` — how many stage
        compilations a serve / re-plan actually paid vs reused."""
        return CacheStats(self.hits - mark.hits, self.misses - mark.misses,
                          self.evictions - mark.evictions)


_CACHE: "OrderedDict[tuple, CompiledStage]" = OrderedDict()
_STATS = CacheStats()
_MAXSIZE = 256


def _publish_stats(reg) -> None:
    """Collector: mirror the cache counters into a metrics registry at
    snapshot time (the hot path only bumps the plain ints above)."""
    reg.gauge("exec.cache.hits").set(_STATS.hits)
    reg.gauge("exec.cache.misses").set(_STATS.misses)
    reg.gauge("exec.cache.evictions").set(_STATS.evictions)
    reg.gauge("exec.cache.entries").set(len(_CACHE))


default_registry().register_collector(_publish_stats)


#: JAX's persistent compilation cache when the environment names none:
#: a fixed path (part of the cache key, so it must not move between
#: runs), inside the checkout and git-ignored
COMPILE_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    A set ``JAX_COMPILATION_CACHE_DIR`` wins: JAX reads it itself and
    this sets no other directory.  Otherwise the cache lives at
    :data:`COMPILE_CACHE_DIR`.  Entry points call this before their
    first compile."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(COMPILE_CACHE_DIR))
    return str(COMPILE_CACHE_DIR)


def cache_stats() -> CacheStats:
    return _STATS


def clear_cache() -> None:
    _CACHE.clear()
    _STATS.hits = _STATS.misses = _STATS.evictions = 0


def set_cache_size(n: int) -> int:
    """Bound the executable cache; returns the previous bound so a
    scoped caller (tests, benchmarks) can restore it afterwards.  The
    cache is process-global, so the bound is last-write-wins across
    deployments."""
    global _MAXSIZE
    prev = _MAXSIZE
    _MAXSIZE = max(1, int(n))
    while len(_CACHE) > _MAXSIZE:
        _CACHE.popitem(last=False)
        _STATS.evictions += 1
    return prev


def static_stage_key(model, nodes, plans, needs) -> tuple:
    """The per-call-invariant part of a stage's cache key.  Callers on a
    hot path (StageExecutor) compute this once and pass it back via
    ``static_key=`` so the signature sort is not re-done per frame."""
    return (segment_signature(model.graph, nodes, model.input_size),
            tile_signature(plans), tuple(needs))


def stage_cache_key(model, nodes, plans, needs, *, backend, donate,
                    boundary: Mapping, static_key: tuple | None = None,
                    fuse: bool = True) -> tuple:
    """The static key (whose segment signature holds every layer's
    activation), then the backend, donation, fusion and boundary
    shapes and dtypes."""
    shapes = tuple((k, tuple(boundary[k].shape), str(boundary[k].dtype))
                   for k in needs)
    if static_key is None:
        static_key = static_stage_key(model, nodes, plans, needs)
    return (*static_key, backend, bool(donate), bool(fuse), shapes)


def compiled_stage(model, nodes, plans, needs: Sequence, sinks: Sequence,
                   *, backend: str, boundary: Mapping,
                   static_key: tuple | None = None,
                   fuse: bool = True, name: str = "stage") -> CompiledStage:
    """Fetch-or-build the executable for one stage + boundary shapes.
    ``name`` scopes the ops a miss lowers (``jax.named_scope``); it is
    not part of the key, so identical stages share one executable.
    Stages of a plan share boundary tensors, so none donates them."""
    key = stage_cache_key(model, nodes, plans, needs, backend=backend,
                          donate=False, boundary=boundary,
                          static_key=static_key, fuse=fuse)
    hit = _CACHE.get(key)
    tr = obs_trace.current()
    if hit is not None:
        _STATS.hits += 1
        _CACHE.move_to_end(key)
        if tr:
            tr.instant("cache.lookup", _time.perf_counter() - tr.epoch,
                       hit=True)
        return hit
    _STATS.misses += 1
    if tr:
        tr.instant("cache.lookup", _time.perf_counter() - tr.epoch,
                   hit=False)
    t0 = _time.perf_counter()
    cs = CompiledStage(model, nodes, plans, needs, sinks, backend=backend,
                       fuse=fuse, name=name)
    default_registry().histogram("exec.compile.build_s").observe(
        _time.perf_counter() - t0)
    _CACHE[key] = cs
    while len(_CACHE) > _MAXSIZE:
        _CACHE.popitem(last=False)
        _STATS.evictions += 1
    return cs
