"""Readings behind the limits of ``correct``, in one process on the chip.

    python3 benchmarks/chip/limits.py --workload NAME --seeds 1,2,3 \\
        --seconds S

For each seed, one run of the cell as ``run.py`` makes it, at the
cell's own size and load (a short window), gives the program's numbers.
Then, on the same sampled frames, the reference is put in the program's
place in the control precisions and compared in the same way:

``fp8``
    float8 (e4m3) operands with one scale per tensor, float32 sums: one
    step below the bfloat16 operands that the configuration's float32 at
    default precision feeds the TPU's matrix unit.  This is the control.
``bf16``
    the whole model cast to bfloat16: a witness, printed beside it.

One JSON line per seed.  The lower reading of a limit is the largest the
program gives over a dozen seeds or more, the upper the smallest the
control gives; ``PERF.md`` records both and the limit set between them.
The benchmark's own runs never run this.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402

import run as cli  # noqa: E402
from chipbench import bench as cb  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(cli.ROOT / "src"))
    import jax
    cli.use_compile_cache(jax, cli.CACHE_DIR)
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print("limits.py: needs a TPU", file=sys.stderr)
        return 2
    bench = cb.Bench(cli.ROOT)
    peak = bench.peaks(dev.device_kind)
    for seed in (int(s) for s in args.seeds.split(",")):
        run, result = cb.execute(bench, args.workload, seed, args.seconds,
                                 False, time.perf_counter(), peak)
        keys = cb.sample(run, run.cell.traffic["check_frames"])
        idx = sorted({run.frame_of[k] for k in keys})
        refs = cb.reference_logits(run, idx)
        line = {"workload": args.workload, "seed": seed,
                "program": result["checks"]["logit_err"]["value"],
                "missing": run.missing, "frames": len(run.outputs)}
        for mode in ("fp8", "bf16"):
            ctrl = cb.reference_logits(run, idx, mode)
            outs = {k: ctrl[run.frame_of[k]] for k in keys}
            line[mode] = cb.logit_err(outs, run.frame_of, refs, keys)
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
