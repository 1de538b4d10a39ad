import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) combo.

For each combination this builds the real step function (train_step for
train_4k, prefill for prefill_32k, serve/decode_step for decode shapes),
lowers it with ShapeDtypeStruct stand-ins under the production mesh,
compiles, and records memory_analysis + cost_analysis + roofline terms
to experiments/dryrun/*.json (resumable; one JSON per combo).

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --all
  PYTHONPATH=src python -m repro.launch.dryrun --arch mixtral-8x7b \
      --shape decode_32k --multi-pod
"""

import argparse
import gzip
import json
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from .. import configs
from ..configs.shapes import SHAPES, input_specs, arch_for_shape
from ..models.transformer import model as M
from ..training.optim import AdamW
from ..training.steps import make_train_step
from .mesh import make_production_mesh, batch_axes
from .sharding import param_pspecs, batch_pspecs, cache_pspecs
from .roofline import Roofline
from .hlo_analysis import analyze_hlo

OUT_DIR = Path(__file__).resolve().parents[3] / "experiments" / "dryrun"

DTYPE = jnp.bfloat16


def _named(mesh, spec_tree):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), spec_tree,
                        is_leaf=lambda x: isinstance(x, P))


def build_lowered(arch_name: str, shape_name: str, multi_pod: bool,
                  extra_opts: dict | None = None):
    """Build + lower the step for one combo; returns (lowered, meta).

    extra_opts (the §Perf levers, all default-off = paper-baseline):
      seqshard  — shard the (B, S, d) activations' sequence dim over
                  'model' (sequence parallelism)
      cacheseq  — shard the decode KV cache's sequence dim over 'model'
                  (flash-decoding-style split)
    """
    opts = extra_opts or {}
    cfg = configs.get(arch_name)
    shape = SHAPES[shape_name]
    cfg = arch_for_shape(cfg, shape)
    if opts.get("headpad") and cfg.n_heads and cfg.n_heads % 16:
        # Perf lever: pad attention heads to a multiple of the model
        # axis so GSPMD shards them fully instead of replicating.
        # Logically identity: the padded heads' wo rows are zero (here,
        # random-init dry-run, the layout is what matters).
        from dataclasses import replace as _rep
        pad = lambda h: ((h + 15) // 16) * 16
        cfg = _rep(cfg, n_heads=pad(cfg.n_heads),
                   n_kv_heads=pad(cfg.n_kv_heads),
                   head_dim=cfg.hd, name=f"{cfg.name}-headpad")

    mesh = make_production_mesh(multi_pod=multi_pod)
    daxes = batch_axes(mesh)
    specs = input_specs(cfg, shape, DTYPE)

    params_shape = jax.eval_shape(
        lambda: M.init_params(cfg, jax.random.PRNGKey(0), dtype=DTYPE))
    p_specs = param_pspecs(cfg, params_shape, mesh)
    p_shard = _named(mesh, p_specs)
    act_pspec = None
    if opts.get("seqshard") and shape.seq_len % mesh.shape["model"] == 0:
        act_pspec = P(daxes, "model", None)
    moe_pspec = P(daxes, None, None, None) if opts.get("moeshard") else None
    ring = ("model", mesh.shape["model"]) if opts.get("ring") else None

    with mesh, jax.set_mesh(mesh):
        if shape.kind == "train":
            opt = AdamW(lr=1e-4)
            opt_shape = jax.eval_shape(lambda: opt.init(params_shape))
            # opt state mirrors params (m, v) + a scalar step
            from ..training.optim import AdamWState
            o_specs = AdamWState(P(), param_pspecs(cfg, opt_shape.m, mesh),
                                 param_pspecs(cfg, opt_shape.v, mesh))
            b_specs = batch_pspecs(cfg, specs, mesh, daxes)
            step = make_train_step(cfg, opt, act_pspec=act_pspec,
                                   moe_pspec=moe_pspec)
            jitted = jax.jit(step, in_shardings=(
                p_shard, _named(mesh, o_specs), _named(mesh, b_specs)))
            lowered = jitted.lower(params_shape, opt_shape, specs)
        elif shape.kind == "prefill":
            b_specs = batch_pspecs(cfg, specs, mesh, daxes)
            fn = lambda p, b: M.prefill(cfg, p, b, act_pspec=act_pspec,
                                        moe_pspec=moe_pspec, ring=ring)
            jitted = jax.jit(fn, in_shardings=(p_shard,
                                               _named(mesh, b_specs)))
            lowered = jitted.lower(params_shape, specs)
        else:  # decode
            c_specs = cache_pspecs(cfg, specs["cache"], mesh, daxes,
                                   mode="sequence" if opts.get("cacheseq")
                                   else "feature")
            i_specs = batch_pspecs(cfg, specs["inputs"], mesh, daxes)
            fn = lambda p, c, i: M.decode_step(cfg, p, c, i)
            jitted = jax.jit(fn, in_shardings=(
                p_shard, _named(mesh, c_specs), _named(mesh, i_specs)))
            lowered = jitted.lower(params_shape, specs["cache"],
                                   specs["inputs"])
    meta = {
        "arch": arch_name, "shape": shape_name,
        "mesh": "pod2x16x16" if multi_pod else "16x16",
        "n_devices": 512 if multi_pod else 256,
        "kind": shape.kind,
        "params": cfg.param_count(),
        "active_params": cfg.active_param_count(),
        "variant": cfg.name,
    }
    return lowered, meta, mesh


def run_combo(arch_name: str, shape_name: str, multi_pod: bool,
              out_dir: Path = OUT_DIR, force: bool = False,
              save_hlo: bool = False, opts: dict | None = None) -> dict:
    mesh_tag = "pod2" if multi_pod else "pod1"
    opt_tag = ("__" + "+".join(sorted(k for k, v in (opts or {}).items()
                                      if v))) if opts else ""
    out = out_dir / f"{arch_name}__{shape_name}__{mesh_tag}{opt_tag}.json"
    if out.exists() and not force:
        return json.loads(out.read_text())
    out_dir.mkdir(parents=True, exist_ok=True)
    rec: dict = {"arch": arch_name, "shape": shape_name, "mesh": mesh_tag,
                 "opts": sorted(k for k, v in (opts or {}).items() if v)}
    t0 = time.time()
    try:
        lowered, meta, mesh = build_lowered(arch_name, shape_name, multi_pod,
                                            extra_opts=opts)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower
        mem = compiled.memory_analysis()
        hlo_text = compiled.as_text()
        if save_hlo:
            with gzip.open(out.with_suffix(".hlo.gz"), "wt") as fh:
                fh.write(hlo_text)
        census = analyze_hlo(hlo_text)
        roof = Roofline(census.flops, census.hbm_bytes,
                        census.total_coll_bytes,
                        {"bytes": census.coll_bytes,
                         "counts": census.coll_counts})
        rec.update(meta)
        rec.update({
            "ok": True,
            "lower_s": round(t_lower, 2),
            "compile_s": round(t_compile, 2),
            "memory": {
                "argument_bytes": getattr(mem, "argument_size_in_bytes", 0),
                "output_bytes": getattr(mem, "output_size_in_bytes", 0),
                "temp_bytes": getattr(mem, "temp_size_in_bytes", 0),
                "generated_code_bytes":
                    getattr(mem, "generated_code_size_in_bytes", 0),
            },
            "roofline": roof.to_dict(),
        })
        # MODEL_FLOPS = 6 N D (dense) / 6 N_active D — per device
        shape = SHAPES[shape_name]
        tokens = shape.global_batch * (shape.seq_len
                                       if shape.kind == "train" else
                                       shape.seq_len if shape.kind == "prefill"
                                       else 1)
        n_act = rec["active_params"]
        mult = 6 if shape.kind == "train" else 2
        rec["model_flops_per_device"] = mult * n_act * tokens / meta["n_devices"]
        hlo_flops = rec["roofline"]["flops"]
        rec["useful_flops_ratio"] = (rec["model_flops_per_device"] /
                                     hlo_flops if hlo_flops else 0.0)
    except Exception as e:  # record the failure; the sweep continues
        rec.update({"ok": False, "error": f"{type(e).__name__}: {e}",
                    "traceback": traceback.format_exc()[-4000:]})
    rec["wall_s"] = round(time.time() - t0, 2)
    out.write_text(json.dumps(rec, indent=2, default=str))
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true",
                    help="sweep all arch x shape x {1,2} pods")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--save-hlo", action="store_true")
    ap.add_argument("--opt", default="",
                    help="comma list of perf levers: seqshard,cacheseq,moeshard,headpad,ring")
    ap.add_argument("--out", default=str(OUT_DIR))
    args = ap.parse_args()
    out_dir = Path(args.out)

    if args.all:
        combos = [(a, s, mp)
                  for a in configs.ARCH_NAMES
                  for s in SHAPES
                  for mp in (False, True)]
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        combos = [(args.arch, args.shape, args.multi_pod)]

    n_ok = 0
    for a, s, mp in combos:
        opts = {k: True for k in args.opt.split(",") if k}
        rec = run_combo(a, s, mp, out_dir, force=args.force,
                        save_hlo=args.save_hlo, opts=opts)
        ok = rec.get("ok")
        n_ok += bool(ok)
        tag = "OK " if ok else "FAIL"
        extra = (f"flops={rec['roofline']['flops']:.3g} "
                 f"dom={rec['roofline']['dominant']}" if ok
                 else rec.get("error", ""))
        print(f"[{tag}] {a:22s} {s:12s} {'pod2' if mp else 'pod1'} "
              f"({rec['wall_s']}s) {extra}", flush=True)
    print(f"{n_ok}/{len(combos)} combos OK")


if __name__ == "__main__":
    main()
