"""Chip benchmark of PICO on a TPU: one run of one cell.

    python3 benchmarks/chip/run.py --workload NAME --seed N \\
        --seconds S --trace 0|1

Run from the root of a checkout.  ``NAME`` is a ``workloads`` entry of
``BENCHMARK.json``; its configuration, traffic mix, entry, reference and
metric readers are found by name under this directory (see
``chipbench/bench.py``).  Set-up (imports, device start, plan, weights,
compile or cache load, warm-up) is timed as ``setup_s``; then the entry
drives the program for ``--seconds``.  With ``--trace 1`` the window
(at most ``bench.TRACE_SECONDS`` long) runs under JAX's profiler and the
per-layer metrics are reported in place of the end-to-end ones.  After the window the outputs of a sample
of frames drawn from the seed are compared with the plain float32
reference; each number compared is printed beside its limit, as the
last lines on standard error and under ``checks`` in the result.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and with
``--trace 1`` ``breakdown``), then ``checks``.  With no TPU, or fewer
chips than the cell asks for, it prints no result and exits 2, naming
what JAX found; without the program's sources beside it, 3.

JAX's persistent compilation cache lives in ``.jax_cache`` beside this
file, a fixed path inside the checkout, with no size limit.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BASE = Path(__file__).resolve().parent
ROOT = BASE.parents[1]
CACHE_DIR = BASE / ".jax_cache"

if str(BASE) not in sys.path:
    sys.path.insert(0, str(BASE))

from chipbench.bench import Bench, execute  # noqa: E402


def use_compile_cache(jax, cache_dir: Path) -> None:
    """JAX's persistent compilation cache at ``cache_dir``: every program,
    however quick to compile, and no eviction (a size limit set in the
    environment would let one cell's programs push out another's, and
    the next run compile them again)."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(cache_dir)
    jax.config.update("jax_compilation_cache_dir", str(cache_dir))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_compilation_cache_max_size", -1)


def _finite(v):
    return v if isinstance(v, (int, float)) and math.isfinite(v) else None


def main(argv=None, *, root: Path = ROOT, cache_dir: Path | None = CACHE_DIR,
         require_tpu: bool = True, t_start: float = T_START) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    bench = Bench(root)
    chips = int(bench.workload(args.workload)["chips"])
    src = Path(root) / "src"
    if not (src / "repro").is_dir():
        print(f"run.py: the program's sources are not at {src}",
              file=sys.stderr)
        return 3
    if str(src) not in sys.path:
        sys.path.insert(0, str(src))

    import jax
    if cache_dir is not None:
        use_compile_cache(jax, cache_dir)
    devices = jax.devices()
    print(f"setup: devices by {time.perf_counter() - t_start:.2f} s",
          file=sys.stderr, flush=True)
    peak = None
    if require_tpu:
        if devices[0].platform != "tpu":
            print(f"run.py: needs a TPU, JAX found platform "
                  f"{devices[0].platform!r}", file=sys.stderr)
            return 2
        if len(devices) < chips:
            print(f"run.py: {args.workload} needs {chips} chips, JAX found "
                  f"{len(devices)}", file=sys.stderr)
            return 2
        peak = bench.peaks(devices[0].device_kind)

    _, result = execute(bench, args.workload, args.seed, args.seconds,
                        bool(args.trace), t_start, peak)
    for name, c in result["checks"].items():
        c["value"] = _finite(c["value"])
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
