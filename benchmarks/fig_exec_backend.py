"""Exec-backend microbenchmark: eager tile loop vs compiled whole-stage.

Runs the fig13 pipeline model (VGG16) on a paper-style Pi cluster in
two forms and times the seed's eager per-tile Python loop against the
``repro.exec`` compiled executables:

* ``stage_*`` — the whole network as ONE fused stage tile-split across
  every device (the paper's fused-layer scheme §2.4.2): the eager path
  re-interprets the DAG per tile, the compiled path is a single jitted
  program over all tiles.  This is the headline compiled/eager speedup
  (acceptance bar: >= 2x on CPU, where per-op dispatch dominates).
* ``pipeline_*`` — the full PICO plan executed stage by stage, plus
  the ``lax.scan`` micro-batched stream path.

The calibration row closes the loop: measured CostTable -> re-plan,
reporting how far the analytic period was from measured reality.

Rows::

    exec/<model>_stage_eager        us per frame
    exec/<model>_stage_compiled     us per frame, speedup vs eager
    exec/<model>_pipeline_eager     us per frame
    exec/<model>_pipeline_compiled  us per frame, speedup + cache stats
    exec/<model>_pipeline_scan      us per frame (micro-batched stream)
    exec/<model>_calibration        calibration wall us, ratio stats
"""

from __future__ import annotations

import time

import jax

from .common import csv_row, paper_cluster
from repro.api import ExecSpec
from repro.core import plan, replan
from repro.exec import cache_stats, calibrate_plan, clear_cache
from repro.models.cnn import zoo
from repro.pipeline import PipelineRunner
from repro.pipeline.stage import StageExecutor

# the fig13 pipeline model (VGG16), scaled so both paths run in seconds
# on CPU while the eager loop still pays its per-tile dispatch tax
FULL = dict(model=dict(input_size=(112, 112), scale=0.2, head=False),
            n_devices=8, n_frames=6)
SMOKE = dict(model=dict(input_size=(64, 64), scale=0.1, head=False),
             n_devices=4, n_frames=4)


def _time_per_frame(fn, frames, warmup: int = 1, iters: int = 2) -> float:
    for _ in range(warmup):
        jax.block_until_ready(fn(frames[0]))
    best = float("inf")
    for _ in range(iters):
        t0 = time.perf_counter()
        for f in frames:
            jax.block_until_ready(fn(f))
        best = min(best, (time.perf_counter() - t0) / len(frames))
    return best


def run(smoke: bool = False) -> list[str]:
    rows = []
    cfg = SMOKE if smoke else FULL
    m = zoo.vgg16(**cfg["model"])
    cluster = paper_cluster(cfg["n_devices"])
    params = m.init(jax.random.PRNGKey(0))
    w, h = m.input_size
    frames = [jax.random.normal(jax.random.PRNGKey(i), (1, h, w, 3))
              for i in range(cfg["n_frames"])]
    clear_cache()

    # ---- whole network as one fused, tile-split stage ----------------
    nodes = frozenset(m.graph.layers)
    fracs = [d.capacity / cluster.total_capacity for d in cluster.devices]
    eager_st = StageExecutor(m, nodes, fracs, mode="eager")
    comp_st = StageExecutor(m, nodes, fracs)
    t_e = _time_per_frame(lambda f: eager_st(params, {}, f), frames)
    rows.append(csv_row(f"exec/{m.name}_stage_eager", t_e * 1e6,
                        f"tiles={cfg['n_devices']}"))
    t_c = _time_per_frame(lambda f: comp_st(params, {}, f), frames)
    rows.append(csv_row(f"exec/{m.name}_stage_compiled", t_c * 1e6,
                        f"speedup={t_e / t_c:.2f}"))

    # ---- full PICO plan, stage by stage ------------------------------
    clear_cache()            # report this section's cache behavior alone
    pico = plan(m.graph, cluster, m.input_size)
    eager_pl = PipelineRunner(m, pico.pipeline, ExecSpec(mode="eager"))
    comp_pl = PipelineRunner(m, pico.pipeline)
    t_pe = _time_per_frame(lambda f: eager_pl(params, f), frames)
    rows.append(csv_row(f"exec/{m.name}_pipeline_eager", t_pe * 1e6,
                        f"stages={len(pico.pipeline.stages)}"))
    t_pc = _time_per_frame(lambda f: comp_pl(params, f), frames)
    st = cache_stats()
    rows.append(csv_row(f"exec/{m.name}_pipeline_compiled", t_pc * 1e6,
                        f"speedup={t_pe / t_pc:.2f};cache_hits={st.hits};"
                        f"cache_misses={st.misses}"))

    # micro-batched stream: one lax.scan dispatch per stage for the
    # whole frame stack
    stack = jax.numpy.stack(frames)
    jax.block_until_ready(comp_pl.run_frames(params, stack))   # compile
    t0 = time.perf_counter()
    jax.block_until_ready(comp_pl.run_frames(params, stack))
    t_scan = (time.perf_counter() - t0) / len(frames)
    rows.append(csv_row(f"exec/{m.name}_pipeline_scan", t_scan * 1e6,
                        f"speedup={t_pe / t_scan:.2f};"
                        f"frames={cfg['n_frames']}"))

    # ---- calibration round-trip: measured CostTable -> re-plan -------
    t0 = time.perf_counter()
    rep = calibrate_plan(m, params, pico.pipeline.stages, iters=1)
    calib_wall = time.perf_counter() - t0
    pico2 = replan(m.graph, cluster, m.input_size, prev=pico,
                   cost_table=rep.table())
    ratios = [s.ratio for s in rep.stages]
    rows.append(csv_row(
        f"exec/{m.name}_calibration", calib_wall * 1e6,
        f"ratio_min={min(ratios):.2f};ratio_max={max(ratios):.2f};"
        f"analytic_period_s={pico.period:.4f};"
        f"measured_period_s={pico2.period:.4f}"))
    return rows


if __name__ == "__main__":
    run()
