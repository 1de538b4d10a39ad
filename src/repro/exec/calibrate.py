"""Cost calibration: time compiled stage executables, emit a CostTable.

The planner's analytic model prices a segment as FLOPs/capacity times a
per-device regression coefficient alpha (Eq. 7).  That coefficient was
never measured against anything the system actually executes — the seed
timed nothing.  This module runs each stage of a plan through its
*compiled* executable (:mod:`repro.exec.compiler`), measures wall time,
and expresses the result as a per-segment ratio

    ratio(seg) = measured_seconds / (executed_FLOPs / host_FLOPs)

i.e. how much slower (or faster, via fusion) the segment runs than the
pure roofline estimate on the calibration host.  The resulting
:class:`~repro.core.cost.CostTable` plugs into ``core.cost.stage_cost``
and the planner's ``plan``/``replan``/``recost``, replacing the purely
analytic alpha with measured numbers — the DistrEdge/DynO lesson that
partition quality hinges on measured per-stage costs.
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Sequence

import jax
import jax.numpy as jnp

from ..core.cost import CostTable
from ..obs import trace as obs_trace
from ..obs.metrics import default_registry


def measure_host_flops(n: int = 512, iters: int = 5) -> float:
    """Estimate the host's achievable matmul FLOP/s with a jitted GEMM."""
    a = jnp.ones((n, n), jnp.float32)
    f = jax.jit(lambda x, y: x @ y)
    f(a, a).block_until_ready()          # compile
    best = float("inf")
    for _ in range(iters):
        t0 = time.perf_counter()
        f(a, a).block_until_ready()
        best = min(best, time.perf_counter() - t0)
    return 2.0 * n ** 3 / best


@dataclass
class StageCalibration:
    index: int
    nodes: frozenset[str]
    flops: float
    measured_s: float
    analytic_s: float

    @property
    def ratio(self) -> float:
        return self.measured_s / self.analytic_s if self.analytic_s > 0 else 1.0


@dataclass
class CalibrationReport:
    host_flops: float
    stages: list[StageCalibration] = field(default_factory=list)

    def table(self) -> CostTable:
        ratios = {s.nodes: s.ratio for s in self.stages if s.analytic_s > 0}
        mean = (sum(ratios.values()) / len(ratios)) if ratios else 1.0
        return CostTable(ratios, default=mean)


def calibrate_plan(model, params, stages: Sequence, *,
                   spec=None, image=None,
                   iters: int = 3, host_flops: float | None = None,
                   key: int = 0) -> CalibrationReport:
    """Time every stage of a plan through its compiled executable.

    ``stages`` is the ``PicoPlan.pipeline.stages`` list (each entry
    carries nodes, fractions and the analytic SegmentCost).  Boundary
    tensors are produced by actually running the pipeline in plan order,
    so each stage is timed on its real input shapes, through the
    executor ``spec`` (:class:`~repro.api.specs.ExecSpec`, default
    ``ExecSpec()``) builds for it on every other path.  Returns a
    report whose :meth:`~CalibrationReport.table` feeds the planner.
    """
    from ..pipeline.stage import stage_executor     # lazy: avoid cycle
    host_flops = host_flops or measure_host_flops()
    if image is None:
        w, h = model.input_size
        image = jax.random.normal(jax.random.PRNGKey(key),
                                  (1, h, w, model.in_channels))
    report = CalibrationReport(host_flops)
    produced: dict = {}
    for si, st in enumerate(stages):
        with obs_trace.current().wall_span("calibrate", stage=si,
                                           n_nodes=len(st.nodes)):
            ex = stage_executor(model, st, si, spec, prefix="calib")
            outs = ex(params, produced, image)          # compile + warm
            jax.block_until_ready(outs)
            best = float("inf")
            for _ in range(max(1, iters)):
                t0 = time.perf_counter()
                jax.block_until_ready(ex(params, produced, image))
                best = min(best, time.perf_counter() - t0)
        flops = float(sum(st.cost.seg.per_device_flops))
        default_registry().histogram("exec.calibrate.stage_s").observe(best)
        report.stages.append(StageCalibration(
            si, frozenset(st.nodes), flops, best, flops / host_flops))
        produced.update(outs)
    return report


def calibrated_plan(g, cluster, input_size, model, params, *,
                    plan_spec=None, exec_spec=None, iters: int = 3):
    """Plan -> calibrate -> re-plan on measured costs (one closed loop).

    Returns ``(pico, report)`` where ``pico`` was re-planned with the
    measured :class:`CostTable` and ``report`` holds the raw timings.
    ``plan_spec``/``exec_spec`` default to ``PlanSpec()``/``ExecSpec()``.
    """
    from ..api.specs import PlanSpec
    from ..core.planner import plan_with_spec
    spec = plan_spec or PlanSpec()
    first = plan_with_spec(g, cluster, input_size, spec)
    report = calibrate_plan(model, params, first.pipeline.stages,
                            spec=exec_spec, iters=iters)
    table = report.table()
    return plan_with_spec(g, cluster, input_size, spec,
                          partition=first.partition,
                          cost_table=table), report
