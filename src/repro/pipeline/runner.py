"""Pipeline runner: execute a full PICO plan over a stream of frames.

Two execution modes:

* :class:`PipelineRunner` — functional mode: stages run in plan order for
  each frame (single host, bit-exact; used by tests/examples and to
  validate plans produced by the optimizer).
* :func:`microbatch_pipeline` — GPipe-style pipelined execution with
  ``shard_map`` + ``lax.ppermute`` over a dedicated mesh axis: the form
  PICO takes on a real TPU mesh, where each stage lives on its own
  slice of the ``stage`` (or ``pod``) axis and microbatches stream
  through (DESIGN.md §5).  Works on any mesh whose ``stage`` axis size
  equals the number of pipeline stages.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Callable, Mapping, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from ..core.pipeline_dp import PipelinePlan
from .stage import StageExecutor, executors_from_plan


@dataclass
class PipelineRunner:
    model: "CNNDef"                  # noqa: F821 (models.cnn.builder)
    plan: PipelinePlan
    backend: str | None = None       # conv lowering; None -> model default
    mode: str = "compiled"           # "compiled" | "eager" stage execution
    exec_spec: object = None         # ExecSpec; supersedes backend/mode

    def __post_init__(self):
        if self.exec_spec is not None:
            # donate is deliberately NOT taken from the spec: stages here
            # share `produced` boundary tensors across the whole plan, so
            # donation would let XLA clobber buffers later stages read
            self.backend = self.exec_spec.backend
            self.mode = self.exec_spec.mode
        self.stages = executors_from_plan(self.model, self.plan.stages,
                                          backend=self.backend,
                                          mode=self.mode)

    def __call__(self, params, image: jax.Array) -> dict[str, jax.Array]:
        produced: dict[str, jax.Array] = {}
        for ex in self.stages:
            outs = ex(params, produced, image)
            produced.update(outs)
        sinks = self.model.graph.sinks()
        return {s: produced[s] for s in sinks}

    def run_stream(self, params, frames: Sequence[jax.Array]
                   ) -> list[dict[str, jax.Array]]:
        return [self(params, f) for f in frames]

    def run_frames(self, params, frames: jax.Array) -> dict[str, jax.Array]:
        """Micro-batched stream: ``frames`` is a (F, N, H, W, C) stack;
        each stage scans over the frame axis in one compiled dispatch
        (``lax.scan``), so the Python overhead is per *stage*, not per
        frame x stage x tile.  Returns sinks stacked along F."""
        produced: dict[str, jax.Array] = {}
        for ex in self.stages:
            outs = ex.run_frames(params, produced, frames)
            produced.update(outs)
        sinks = self.model.graph.sinks()
        return {s: produced[s] for s in sinks}


# ---------------------------------------------------------------------------
# GPipe-style microbatch pipeline over a mesh axis
# ---------------------------------------------------------------------------

def microbatch_pipeline(
    stage_fn: Callable[[int, jax.Array, jax.Array], jax.Array],
    stage_params: jax.Array,
    x_microbatches: jax.Array,
    mesh: Mesh,
    axis: str = "stage",
):
    """Run ``n_stages`` chained functions as a pipeline over mesh ``axis``.

    ``stage_fn(stage_id, params_slice, x)`` applies one stage to one
    microbatch; all stages must share the activation shape (pad the
    channel/feature dim to the max if needed).  ``stage_params`` is
    stacked along axis 0 (one slice per stage) and sharded over ``axis``;
    ``x_microbatches`` has shape (n_micro, ...) and is replicated.

    Classic GPipe schedule with n_stages + n_micro - 1 ticks; the
    inter-stage hand-off is a single ``lax.ppermute`` per tick — on a
    multi-pod mesh this is the only cross-pod communication, which is
    exactly PICO's thesis (stage boundaries are the narrow waist).
    """
    n_stages = mesh.shape[axis]
    n_micro = x_microbatches.shape[0]

    def per_stage(params_sl, xs):
        # params_sl: (1, ...) slice of stacked params; xs: (n_micro, ...)
        sid = jax.lax.axis_index(axis)
        p = jax.tree.map(lambda a: a[0], params_sl)
        n_ticks = n_stages + n_micro - 1
        buf = jnp.zeros_like(xs[0])
        outs = jnp.zeros_like(xs)

        def tick(t, carry):
            buf, outs = carry
            # stage 0 ingests microbatch t (if any)
            feed = jax.lax.dynamic_index_in_dim(
                xs, jnp.clip(t, 0, n_micro - 1), axis=0, keepdims=False)
            cur = jnp.where(sid == 0, feed, buf)
            y = stage_fn(sid, p, cur)
            # shift y to the next stage; last stage's y is the output of
            # microbatch (t - n_stages + 1)
            perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]
            nxt = jax.lax.ppermute(y, axis, perm)
            out_idx = t - (n_stages - 1)
            valid = jnp.logical_and(out_idx >= 0, out_idx < n_micro)
            outs = jax.lax.cond(
                valid,
                lambda o: jax.lax.dynamic_update_index_in_dim(
                    o, y, jnp.clip(out_idx, 0, n_micro - 1), axis=0),
                lambda o: o,
                outs)
            return nxt, outs

        buf, outs = jax.lax.fori_loop(0, n_ticks, tick, (buf, outs))
        # only the LAST stage's `outs` holds the final results; broadcast
        # via a masked psum so every shard returns the same value.
        outs = jax.lax.psum(
            jnp.where(sid == n_stages - 1, outs, jnp.zeros_like(outs)), axis)
        return outs

    spec_p = P(axis)
    spec_x = P()
    fn = shard_map(per_stage, mesh=mesh,
                   in_specs=(spec_p, spec_x), out_specs=P(),
                   check_vma=False)
    return fn(stage_params, x_microbatches)
