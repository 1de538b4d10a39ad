"""Knee sweep of an open-loop ``fleet`` cell, in one process on the chip.

    python3 benchmarks/chip/sweep.py --workload resnet34-224.stream \\
        --seed N --seconds S --rates 200,300,400

Starts the cell's fleet once, as ``run.py`` does, then offers each rate
for ``--seconds`` on the cell's schedule, and last runs a closed loop
that keeps ``max_inflight`` frames out.  One JSON line per window:
offered and completed frames/s, p50 and p95 frame latency, and the
frames still out when the window closed.  The knee is the highest rate
whose completions keep up with what is offered without a growing
backlog; a cell's fixed rate is set from it once, by hand.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402

import run as cli  # noqa: E402
from chipbench.bench import Bench, Run  # noqa: E402
from chipbench.stats import quantile  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(cli.ROOT / "src"))
    import jax
    cli.use_compile_cache(jax, cli.CACHE_DIR)
    if jax.devices()[0].platform != "tpu":
        print("sweep.py: needs a TPU", file=sys.stderr)
        return 2

    bench = Bench(cli.ROOT)
    cell = bench.cell(args.workload)
    fleet = cell.entry
    base = Run(cell=cell, seed=args.seed, seconds=args.seconds,
               traced=False, t_start=T_START)
    launcher, client, pool = fleet.start(base)
    windows = [dict(cell.traffic, rate_per_s=float(x))
               for x in args.rates.split(",")]
    windows.append(dict(cell.traffic, loop="closed"))
    try:
        for traffic in windows:
            r = dataclasses.replace(
                base, cell=dataclasses.replace(cell, traffic=traffic))
            due_of, w0, deadline = fleet.measure(r, client, pool)
            end = w0 + r.seconds
            done = [client.done.get(f, deadline) for f in due_of]
            lat = fleet.latencies(client, due_of, deadline)
            line = {"loop": traffic["loop"],
                    "offered_per_s": len(due_of) / r.seconds,
                    "completed_per_s": sum(d <= end for d in done)
                    / r.seconds,
                    "out_at_close": sum(d > end for d in done),
                    "p50_ms": quantile(lat, 50) * 1e3,
                    "p95_ms": quantile(lat, 95) * 1e3}
            if traffic["loop"] == "open":
                line["rate_per_s"] = traffic["rate_per_s"]
            print(json.dumps(line), flush=True)
    finally:
        launcher.shutdown(abort=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
