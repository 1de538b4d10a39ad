"""Segment compiler: one StagePlan's fused segment -> one jitted callable.

The seed executed a stage as an eager Python loop — re-interpreting the
segment DAG per tile, per frame, with one XLA dispatch per layer.  This
module lowers the *whole* stage — split, every device tile's sub-DAG,
stitch — into a single ``jax.jit`` callable, so the planner's per-stage
cost has an executable counterpart that can actually be measured
(see :mod:`repro.exec.calibrate`).

Two entry points per :class:`CompiledStage`:

* ``__call__(params, boundary)`` — one frame;
* ``run_frames(params, boundary)`` — a stack of frames with a leading
  frame axis, micro-batched through ``lax.scan`` so the whole stream is
  one dispatch with constant memory in the number of frames.

Buffer donation (``donate=True``, through :func:`compile_stage` only)
hands the boundary buffers to XLA for in-place reuse — safe only when
the caller will not read them again.  Stage executors never donate:
the stages of a plan share boundary tensors.
"""

from __future__ import annotations

from typing import Mapping, Sequence

import jax

from ..obs.metrics import default_registry
from ..pipeline.halo import (TilePlan, plan_tiles, split_inputs,
                             stitch_outputs)
from .backends import fuses, phase_form


def fusable_chains(graph, nodes, backend: str | None = None
                   ) -> dict[str, str]:
    """conv -> pool pairs in ``nodes`` lowerable as one fused call.

    A pool is fusable into its producing conv when the chain is private
    and the pool collapses onto the conv's output grid:

    * the pool is VALID (no padding) and non-overlapping
      (kernel == stride — e.g. the zoo's 2x2/s2 pools), which is the
      shape the kernel epilogue implements as an in-register reshape;
    * its only predecessor is an in-segment conv;
    * that conv feeds nothing else — no other in-segment successor and
      not a segment sink — so skipping its materialization is safe.

    Together with ``Graph.required_ranges``'s width-range arithmetic
    these conditions also pin the tile geometry: the conv tile is
    exactly the pool's input and starts on the pool grid, which
    ``run_segment`` re-checks per tile before fusing.

    With ``backend`` given, only the chains that backend's fused
    lowering takes (``backends.fuses``) are kept.
    """
    nodes = frozenset(nodes)
    sinks = set(graph.sinks(nodes))
    chains: dict[str, str] = {}
    for n in nodes:
        spec = graph.layers[n]
        if spec.kind != "pool":
            continue
        if (tuple(spec.kernel) != tuple(spec.stride)
                or tuple(spec.padding) != (0, 0)):
            continue
        ps = graph.preds[n]
        if len(ps) != 1 or ps[0] not in nodes:
            continue
        conv = ps[0]
        if graph.layers[conv].kind != "conv" or conv in sinks:
            continue
        if [s for s in graph.succs[conv] if s in nodes] != [n]:
            continue
        if backend is not None \
                and not fuses(backend, graph.layers[conv], spec):
            continue
        chains[conv] = n
    return chains


def segment_signature(graph, nodes, input_size) -> tuple:
    """Hashable fingerprint of a fused segment's geometry + weights.

    Two models whose segments agree on this signature lower to the same
    executable, so cache entries survive re-plans and model rebuilds.
    """
    nodes = frozenset(nodes)
    layers = tuple(sorted(
        (n, s.kind, s.kernel, s.stride, s.padding, s.in_channels,
         s.out_channels, s.flops_coeff, s.global_rf, s.act)
        for n, s in ((n, graph.layers[n]) for n in nodes)))
    edges = tuple(sorted((u, v) for u, v in graph.edges
                         if u in nodes and v in nodes))
    return (layers, edges, tuple(input_size))


class CompiledStage:
    """All device tiles of one stage as a single jitted executable."""

    def __init__(self, model, nodes, plans: Sequence[TilePlan],
                 needs: Sequence[tuple[str, str | None]],
                 sinks: Sequence[str], *, backend: str,
                 donate: bool = False,
                 fuse: bool = True, name: str = "stage"):
        self.model = model
        # the traced body runs under jax.named_scope(name) (each layer
        # under its node name, by run_segment), so device ops carry both
        # in their op_name metadata; the computation is unchanged
        self.name = name
        self.nodes = frozenset(nodes)
        self.plans = list(plans)
        self.needs = list(needs)
        self.sinks = list(sinks)
        self.backend = backend
        # conv->pool chains lowered as one fused call: only those the
        # backend's fused lowering takes (pallas: all; xla: lane-sparse
        # chains on a TPU, in phase form).  Every other chain keeps the
        # composed-op sequence and with it bit-equality vs the eager
        # oracle
        self.fuse = bool(fuse)
        self.fusion = fusable_chains(model.graph, self.nodes, backend) \
            if self.fuse else {}
        # XLA on CPU cannot alias donated buffers; donation there only
        # produces warnings, so honor the flag on accelerators only
        self.donate = bool(donate) and jax.default_backend() != "cpu"
        dn = tuple(range(1, 1 + len(self.needs))) if self.donate else ()
        self._fn = jax.jit(self._run, donate_argnums=dn)
        self._scan_fn = jax.jit(self._run_frames, donate_argnums=dn)
        # what the stage computes, by layer kind and (for convs) the
        # activation its epilogue applies: a layer that silently fell
        # back to another activation shows here
        reg = default_registry()
        for n in self.nodes:
            spec = model.graph.layers[n]
            act = spec.act if spec.kind == "conv" else "none"
            reg.counter("stage.layers", kind=spec.kind, act=act).inc()
        # chains built in space-to-depth phase form, by input channels
        if phase_form(backend):
            for conv in self.fusion:
                reg.counter("stage.phase_pools",
                            cin=model.graph.layers[conv].in_channels).inc()

    # traced bodies ------------------------------------------------------

    def _run(self, params, *bufs):
        with jax.named_scope(self.name):
            boundary = dict(zip(self.needs, bufs))
            tiles_in = split_inputs(self.plans, self.needs, boundary)
            tiles_out = []
            for tp, tin in zip(self.plans, tiles_in):
                if tp.empty:
                    tiles_out.append({})
                    continue
                tiles_out.append(self.model.run_segment(
                    params, self.nodes, tin,
                    ranges=(tp.out_ranges, tp.in_ranges),
                    backend=self.backend,
                    fusion=self.fusion))
            return stitch_outputs(self.plans, self.sinks, tiles_out)

    def _run_frames(self, params, *bufs):
        def body(carry, xs):
            return carry, self._run(params, *xs)
        _, outs = jax.lax.scan(body, None, bufs)
        return outs

    # public -------------------------------------------------------------

    def __call__(self, params, boundary: Mapping) -> dict[str, jax.Array]:
        return self._fn(params, *(boundary[k] for k in self.needs))

    def run_frames(self, params, boundary: Mapping) -> dict[str, jax.Array]:
        """``boundary`` tensors carry a leading frame axis (F, N, H, W, C);
        returns sink tensors stacked the same way."""
        return self._scan_fn(params, *(boundary[k] for k in self.needs))

def compile_stage(model, nodes, fractions: Sequence[float], *,
                  backend: str | None = None,
                  donate: bool = False, fuse: bool = True) -> CompiledStage:
    """Convenience: plan tiles for ``fractions`` and compile the stage
    alone.  ``donate`` is for a caller that will not read the boundary
    buffers again; ``backend`` None means the model's own, else the
    registry default."""
    from ..pipeline.stage import conv_backend
    nodes = frozenset(nodes)
    g = model.graph
    plans = plan_tiles(g, nodes, model.full_sizes, model.input_size,
                       list(fractions))
    return CompiledStage(model, nodes, plans, model.boundary_needs(nodes),
                         g.sinks(nodes), backend=conv_backend(model, backend),
                         donate=donate, fuse=fuse)
