"""Stage executor: run one pipeline stage's fused segment on device tiles.

The default mode compiles the whole stage — all device tiles — into a
single jitted executable through :mod:`repro.exec` (fetched from the
executable cache, so identical stages across re-plans share one
lowering).  ``mode="eager"`` keeps the seed's per-tile Python loop as
the bit-exactness oracle and for one-shot runs where compilation would
not amortize.  ``run_frames`` micro-batches a stack of frames through
``lax.scan`` in one dispatch.

Every call records a ``stage`` span (``stage`` attribute: the
executor's name) around the stage's dispatch — into ``tracer`` when the
owner hands one over (a dist worker), else into the active tracer —
which also names the call in JAX profiler traces.

:func:`stage_executor` is the one place an
:class:`~repro.api.specs.ExecSpec` and a planned stage become an
executor: the runner, the runtime, the dist workers, the calibrator
and the servers all build their stages through it, so one spec gives
one executable (and one executable-cache key) on every path.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Mapping, Sequence

import jax
import jax.numpy as jnp

from ..api.specs import ExecSpec
from ..core.pipeline_dp import StagePlan
from ..obs import trace as obs_trace
from .halo import TilePlan, plan_tiles, split_inputs, stitch_outputs


@dataclass
class StageExecutor:
    """Executable form of one StagePlan for a CNNDef."""

    model: "CNNDef"                  # noqa: F821 (models.cnn.builder)
    nodes: frozenset[str]
    fractions: list[float]
    name: str = "stage"
    backend: str | None = None       # None -> model.backend -> registry default
    mode: str = "compiled"           # "compiled" | "eager"
    fuse: bool = True                # lower conv->pool chains as one fused
    #                                  kernel call (compiled mode, backends
    #                                  with a fused lowering only)
    tracer: object = None            # span sink; None -> the active tracer

    def __post_init__(self):
        g = self.model.graph
        self.nodes = frozenset(self.nodes)
        self.sinks = g.sinks(self.nodes)
        self.plans: list[TilePlan] = plan_tiles(
            g, self.nodes, self.model.full_sizes, self.model.input_size,
            self.fractions)
        # (node, outside_pred) pairs fed across the stage boundary
        self.needs = self.model.boundary_needs(self.nodes)
        self.backend = conv_backend(self.model, self.backend)
        if self.mode not in ("compiled", "eager"):
            raise ValueError(f"unknown mode {self.mode!r}")
        # per-call-invariant part of the executable-cache key, computed
        # once so the per-frame lookup only hashes boundary shapes
        from ..exec.cache import static_stage_key
        self._static_key = static_stage_key(self.model, self.nodes,
                                            self.plans, self.needs)

    def boundary_inputs(self, produced: Mapping[str, jax.Array],
                        image: jax.Array | None
                        ) -> dict[tuple[str, str | None], jax.Array]:
        """Full-width boundary tensors for every (node, pred) need."""
        return {(n, p): (image if p is None else produced[p])
                for (n, p) in self.needs}

    def __call__(self, params, produced: Mapping[str, jax.Array],
                 image: jax.Array | None = None) -> dict[str, jax.Array]:
        boundary = self.boundary_inputs(produced, image)
        with self._span():
            if self.mode == "eager":
                return self._run_eager(params, boundary)
            return self._executable(boundary)(params, boundary)

    def run_frames(self, params, produced: Mapping[str, jax.Array],
                   images: jax.Array | None = None) -> dict[str, jax.Array]:
        """Frame-stack form of ``__call__``: every boundary tensor (and
        ``images``) carries a leading frame axis; sinks come back stacked
        the same way.  Compiled mode scans the stack in one dispatch;
        eager mode loops frames through the oracle path and stacks."""
        boundary = self.boundary_inputs(produced, images)
        with self._span():
            if self.mode == "eager":
                n = next(iter(boundary.values())).shape[0]
                per = [self._run_eager(params, {k: v[f] for k, v in
                                                boundary.items()})
                       for f in range(n)]
                return {s: jnp.stack([o[s] for o in per])
                        for s in self.sinks}
            return self._executable(boundary).run_frames(params, boundary)

    # ------------------------------------------------------------------

    def _span(self):
        tr = self.tracer if self.tracer is not None else obs_trace.current()
        return tr.wall_span("stage", stage=self.name)

    def _executable(self, boundary):
        from ..exec.cache import compiled_stage
        return compiled_stage(self.model, self.nodes, self.plans,
                              self.needs, self.sinks, backend=self.backend,
                              boundary=boundary, static_key=self._static_key,
                              fuse=self.fuse, name=self.name)

    def _run_eager(self, params, boundary) -> dict[str, jax.Array]:
        """The seed path: eager Python loop over device tiles."""
        tiles_in = split_inputs(self.plans, self.needs, boundary)
        tiles_out = []
        for tp, tin in zip(self.plans, tiles_in):
            if tp.empty:
                tiles_out.append({})
                continue
            res = self.model.run_segment(params, self.nodes, tin,
                                         ranges=(tp.out_ranges, tp.in_ranges),
                                         backend=self.backend)
            tiles_out.append(res)
        return stitch_outputs(self.plans, self.sinks, tiles_out)


def conv_backend(model, backend: str | None = None) -> str:
    """The conv lowering a stage of ``model`` runs: ``backend``, else
    the model's own, else the registry default."""
    from ..exec import backends
    return backend or model.backend or backends.DEFAULT_BACKEND


def stage_executor(model: "CNNDef", stage: StagePlan, index: int,  # noqa: F821
                   spec: ExecSpec | None = None, *, tracer=None,
                   prefix: str = "stage") -> StageExecutor:
    """The executor of stage ``index`` of a plan under ``spec``
    (default :class:`ExecSpec`), named ``<prefix><index>``; ``tracer``
    takes its ``stage`` spans (None: the active tracer)."""
    spec = spec or ExecSpec()
    return StageExecutor(model, stage.nodes, list(stage.fractions),
                         name=f"{prefix}{index}", backend=spec.backend,
                         mode=spec.mode, tracer=tracer)


def executors_from_plan(model: "CNNDef", stages: Sequence[StagePlan],  # noqa: F821
                        spec: ExecSpec | None = None) -> list[StageExecutor]:
    """One :func:`stage_executor` per stage of a plan."""
    return [stage_executor(model, st, i, spec)
            for i, st in enumerate(stages)]
