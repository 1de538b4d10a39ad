"""A tiny copy of the chip benchmark for CPU tests.

:func:`tiny_root` lays out, under a temporary directory, a
``BENCHMARK.json`` with the real cells, the real entries, references,
metric readers and peaks (linked), the program's sources (linked), and
configurations and traffic mixes cut to CPU size: widths times 0.1 (as
``zoo``'s ``scale`` rounds them), 32x32 frames, a few frames per call.
"""

from __future__ import annotations

import json
import os
import shutil
import sys
from pathlib import Path

BASE = Path(__file__).resolve().parents[1]
ROOT = BASE.parents[1]
if str(BASE) not in sys.path:
    sys.path.insert(0, str(BASE))

SCALE = 0.1
SIZE = 32


def scaled(ch: int, scale: float = SCALE) -> int:
    """Channels as ``zoo._c`` scales them."""
    return max(1, int(round(ch * scale)))


def tiny_config(cfg: dict, scale: float = SCALE, size: int = SIZE) -> dict:
    cfg = json.loads(json.dumps(cfg))
    cfg["scale"] = scale
    cfg["input_size"] = [size, size]
    if cfg["family"] == "vgg":
        cfg["stages"] = [[r, scaled(c, scale)] for r, c in cfg["stages"]]
        cfg["head"]["dense"] = [scaled(c, scale)
                                for c in cfg["head"]["dense"]]
    else:
        cfg["stem"]["out"] = scaled(cfg["stem"]["out"], scale)
        cfg["stages"] = [[r, scaled(c, scale), s]
                         for r, c, s in cfg["stages"]]
    return cfg


TINY_TRAFFIC = {
    "offline32": {"frames_per_call": 4, "stacks": 2, "warmup_calls": 2,
                  "check_frames": 6},
    "stream": {"rate_per_s": 20.0, "pool_frames": 8, "warmup_frames": 4,
               "check_frames": 6},
    "pipe4": {"pool_frames": 8, "warmup_frames": 4, "check_frames": 6},
}

#: a four-stage fleet on four (virtual) devices: the benchmark has no
#: four-chip cell yet, and the fleet's exchange between stages is
#: checked here all the same
PIPE4 = {"name": "vgg16-224.pipe4", "config": "vgg16-224", "traffic": "pipe4",
         "chips": 4, "why": "four pipeline stages over memory links"}
PIPE4_TRAFFIC = {"entry": "fleet", "loop": "closed",
                 "dist": {"transport": "memory", "workers": "thread"}}


def tiny_root(tmp: Path) -> Path:
    """A benchmark root under ``tmp`` at CPU size; returns it."""
    tmp = Path(tmp)
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    base = tmp / "bench"
    (base / "configs").mkdir(parents=True)
    (base / "traffic").mkdir()
    shutil.copytree(BASE / "checks", base / "checks")
    for name in ("entries", "references", "metrics", "peaks.json"):
        os.symlink(BASE / name, base / name)
    os.symlink(ROOT / "src", tmp / "src")
    spec["paths"] = ["bench"]
    if not any(w["name"] == PIPE4["name"] for w in spec["workloads"]):
        spec["workloads"].append(PIPE4)
        (base / "checks" / f"{PIPE4['name']}.json").write_text(
            json.dumps({"logit_err": 0.03, "missing": 0}))
        for m in spec["end_to_end"]:
            if m["name"] == "frames_per_s":
                m["workloads"].append(PIPE4["name"])
    for c in spec["configs"]:
        cfg = tiny_config(json.loads((ROOT / c["file"]).read_text()))
        c["file"] = f"bench/configs/{c['name']}.json"
        (tmp / c["file"]).write_text(json.dumps(cfg))
    for w in spec["workloads"]:
        path = BASE / "traffic" / f"{w['traffic']}.json"
        t = json.loads(path.read_text()) if path.is_file() \
            else dict(PIPE4_TRAFFIC)
        t.update(TINY_TRAFFIC[w["traffic"]])
        (base / "traffic" / f"{w['traffic']}.json").write_text(json.dumps(t))
    (tmp / "BENCHMARK.json").write_text(json.dumps(spec))
    return tmp


def load_cli():
    """``run.py`` as a module of its own name (no clash with another
    ``run``)."""
    import importlib.util
    spec = importlib.util.spec_from_file_location("chipbench_cli",
                                                  BASE / "run.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def run_cell(root: Path, workload: str, seed: int, capsys,
             seconds: float = 0.5) -> dict:
    """One run of ``workload`` on the CPU with the chip's look skipped;
    returns its result line."""
    import time
    cli = load_cli()
    rc = cli.main(["--workload", workload, "--seed", str(seed),
                   "--seconds", str(seconds), "--trace", "0"],
                  root=root, cache_dir=None, require_tpu=False,
                  t_start=time.perf_counter())
    out = capsys.readouterr().out.strip().splitlines()
    assert rc == 0 and out, f"rc {rc}"
    return json.loads(out[-1])
