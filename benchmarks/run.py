"""Run every paper-artifact benchmark.  Prints ``name,us_per_call,derived``
CSV rows (one per measurement), mirroring the paper's tables/figures:

  table4     Algorithm 1 runtime/pieces per CNN         (paper Table 4)
  fig5       FLOPs vs fused layers x devices            (paper Fig. 5)
  fig12      piece- vs block-granularity speedup        (paper Fig. 12)
  fig13      throughput: LW/EFL/OFL/CE/PICO             (paper Figs. 13-14)
  table5     heterogeneous utilization/redundancy/mem   (paper Table 5)
  fig15      memory + energy vs devices                 (paper Figs. 15-16)
  table67    PICO vs BFS-optimal                        (paper Tables 6-7)
  runtime    event-runtime churn adaptivity             (repro.runtime)
  exec       eager tile loop vs compiled stage path     (repro.exec)
  serving    multi-tenant scheduler vs time-sliced      (repro.serving)
  fleet      planner throughput + plan registry         (repro.fleet)
  pareto     multi-objective Pareto front sweep         (repro.plan_front)

Use --fast to trim the slowest sweeps (full mode is the default for
``python -m benchmarks.run``).  --smoke runs a tiny-config subset for
CI.  --out <path> additionally writes the rows, a flattened ``metrics``
dict, and a versioned ``repro.obs`` metrics snapshot (the same envelope
``Deployment.metrics_snapshot()`` emits, carrying the run's executable
-cache and conv-fallback counters) as JSON — the one code path CI's
bench-regression gate (``tools/bench_gate.py``) and local runs share.
--trace-out <path> additionally runs a small traced VGG16 pipeline and
writes its Perfetto trace (validated in CI by
``python -m repro.tools.trace --validate``).
"""

import argparse
import json
import sys
import time


def write_trace(path: str, frames: int = 16) -> str:
    """Run the fig13 VGG16 pipeline (tiny config, virtual time) with
    tracing on and save the Perfetto trace to ``path``."""
    import repro
    from repro.core import make_pi_cluster
    from repro.models.cnn import zoo
    model = zoo.build("vgg16", scale=0.25, input_size=(64, 64))
    cluster = make_pi_cluster([1.5, 1.2, 1.0, 0.8], bandwidth_mbps=50.0)
    dep = repro.compile(model, cluster)
    rt = dep.runtime(repro.DeploySpec(trace=True), real_compute=False)
    rt.run(n_frames=frames)
    return dep.save_trace(path)


def parse_metrics(rows: list[str]) -> dict[str, float]:
    """Flatten CSV rows into gateable metrics.

    ``name,us,derived`` becomes ``{name}.us -> us`` plus, when
    ``derived`` is a bare number, ``{name} -> value``, or, when it is
    ``k=v[;k=v...]``, ``{name}.{k} -> v`` for every numeric ``v``.
    """
    metrics: dict[str, float] = {}
    for row in rows:
        name, us, derived = row.split(",", 2)
        metrics[f"{name}.us"] = float(us)
        try:
            metrics[name] = float(derived)
            continue
        except ValueError:
            pass
        for part in derived.split(";"):
            if "=" not in part:
                continue
            k, v = part.split("=", 1)
            try:
                metrics[f"{name}.{k}"] = float(v)
            except ValueError:
                pass
    return metrics


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny-config CI subset (implies --fast configs)")
    ap.add_argument("--only", default=None,
                    help="comma-separated benchmark names")
    ap.add_argument("--out", default=None, metavar="PATH",
                    help="also write rows + flattened metrics as JSON")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="also run a small traced VGG16 pipeline and "
                         "write its Perfetto trace JSON")
    ap.add_argument("--autotune-out", default=None, metavar="PATH",
                    help="also write the kernel-autotune winners "
                         "accumulated by the kernel bench as a versioned "
                         "CostTable artifact JSON")
    args = ap.parse_args()

    from repro.exec.cache import enable_compile_cache
    enable_compile_cache()
    from . import (table4_partition, fig5_redundancy, fig12_piece_vs_block,
                   fig13_throughput, table5_hetero, fig15_memory,
                   table67_optimal, fig_runtime_adapt, fig_exec_backend,
                   fig_serving_mt, fig_kernel_conv, fig_fleet_planner,
                   fig_pareto, fig_dist_exec)
    benches = {
        "table4": lambda: table4_partition.run(),
        "fig5": lambda: fig5_redundancy.run(),
        "fig13": lambda: fig13_throughput.run(
            models=("vgg16",) if args.fast else ("vgg16", "yolov2")),
        "fig12": lambda: fig12_piece_vs_block.run(),
        "table5": lambda: table5_hetero.run(),
        "fig15": lambda: fig15_memory.run(),
        "table67": lambda: table67_optimal.run(fast=args.fast),
        "runtime": lambda: fig_runtime_adapt.run(
            models=("squeezenet",) if args.fast else ("vgg16", "squeezenet"),
            frames=120 if args.fast else fig_runtime_adapt.FRAMES),
        "exec": lambda: fig_exec_backend.run(smoke=args.smoke or args.fast),
        "serving": lambda: fig_serving_mt.run(smoke=args.smoke or args.fast),
        "kernel": lambda: fig_kernel_conv.run(smoke=args.smoke or args.fast),
        "fleet": lambda: fig_fleet_planner.run(smoke=args.smoke or args.fast),
        "pareto": lambda: fig_pareto.run(smoke=args.smoke or args.fast),
        "dist": lambda: fig_dist_exec.run(smoke=args.smoke or args.fast),
    }
    if args.smoke:
        # CI smoke: the exec-backend microbenchmark, the conv-kernel
        # autotune microbenchmark, the multi-tenant serving comparison,
        # the fleet planner-throughput check, the multi-objective
        # Pareto-front contract, and the cheapest paper artifacts, all
        # in tiny configs
        smoke = {
            "exec": benches["exec"],
            "kernel": benches["kernel"],
            "serving": benches["serving"],
            "fleet": benches["fleet"],
            "pareto": benches["pareto"],
            "dist": benches["dist"],
            "table4": benches["table4"],
            "fig5": benches["fig5"],
            # >= 2x DROP_AFTER frames so the churn event actually fires
            "runtime": lambda: fig_runtime_adapt.run(
                models=("squeezenet",), frames=2 * fig_runtime_adapt.DROP_AFTER),
        }
        benches = smoke
    only = args.only.split(",") if args.only else list(benches)
    unknown = [n for n in only if n not in benches]
    if unknown:
        sys.exit(f"unknown benchmark(s) {unknown}; available"
                 f"{' in --smoke mode' if args.smoke else ''}: "
                 f"{sorted(benches)}")
    t0 = time.time()
    all_rows: list[str] = []
    print("name,us_per_call,derived")
    for name in only:
        all_rows.extend(benches[name]())
    wall = time.time() - t0
    print(f"# {len(all_rows)} rows in {wall:.1f}s", file=sys.stderr)
    mode = "smoke" if args.smoke else "fast" if args.fast else "full"
    if args.out:
        # embed the versioned repro.obs snapshot next to the legacy
        # flat-metrics dict: the bench run's process-global counters
        # (executable-cache hits, conv fallbacks, compile times) ride
        # along, and tools/bench_gate.py can gate on either form
        from repro.obs.metrics import registry_from_values, default_registry
        metrics = parse_metrics(all_rows)
        reg = registry_from_values(metrics)
        reg.merge(default_registry())
        snapshot = reg.snapshot(meta={"mode": mode, "wall_s": wall,
                                      "source": "benchmarks.run"})
        with open(args.out, "w") as fh:
            json.dump({"rows": all_rows,
                       "metrics": metrics,
                       "snapshot": snapshot,
                       "wall_s": wall,
                       "mode": mode},
                      fh, indent=2, sort_keys=True)
        print(f"# wrote {args.out}", file=sys.stderr)
    if args.trace_out:
        write_trace(args.trace_out)
        print(f"# wrote {args.trace_out}", file=sys.stderr)
    if args.autotune_out:
        fig_kernel_conv.export_autotune(args.autotune_out)
        print(f"# wrote {args.autotune_out}", file=sys.stderr)


if __name__ == "__main__":
    main()
