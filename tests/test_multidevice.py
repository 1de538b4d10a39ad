"""Multi-device tests run in subprocesses (XLA device count must be set
before jax initializes, so these cannot share the main test process)."""

import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]


def run_py(code: str, devices: int = 8, timeout: int = 600):
    env = {
        # never the accelerator: these children run on virtual CPU devices
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": f"--xla_force_host_platform_device_count={devices}",
        "PYTHONPATH": str(ROOT / "src"),
        "PATH": "/usr/bin:/bin:/usr/local/bin",
        "HOME": "/tmp",
    }
    res = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, timeout=timeout,
                         env=env)
    assert res.returncode == 0, res.stderr[-4000:]
    return res.stdout


def test_microbatch_pipeline_exact():
    """GPipe-style shard_map pipeline == sequential composition."""
    out = run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.pipeline.runner import microbatch_pipeline
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((4,), ("stage",))
        ws = jax.random.normal(jax.random.PRNGKey(0), (4, 16, 16)) * 0.1
        xs = jax.random.normal(jax.random.PRNGKey(1), (6, 2, 16))
        fn = lambda sid, w, x: jnp.tanh(x @ w)
        out = microbatch_pipeline(fn, ws, xs, mesh, axis="stage")
        ref = xs
        for i in range(4):
            ref = jnp.tanh(ref @ ws[i])
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-6)
        print("OK")
    """)
    assert "OK" in out


def test_dryrun_single_combo():
    """The real dry-run path compiles on a small host mesh."""
    out = run_py("""
        import os
        os.environ["XLA_FLAGS"] = \
            "--xla_force_host_platform_device_count=512"
        import tempfile
        from pathlib import Path
        from repro.launch.dryrun import run_combo
        with tempfile.TemporaryDirectory() as d:
            rec = run_combo("llama3.2-1b", "decode_32k", False,
                            Path(d), force=True)
        assert rec["ok"], rec.get("error")
        assert rec["roofline"]["flops"] > 0
        print("OK", rec["roofline"]["dominant"])
    """, devices=512, timeout=900)
    assert "OK" in out


def test_sharded_train_step():
    """train_step runs (not just lowers) on an 8-device host mesh with
    the production sharding rules."""
    out = run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro import configs
        from repro.models.transformer import model as M
        from repro.training.optim import AdamW
        from repro.training.steps import make_train_step
        from repro.launch.sharding import param_pspecs, batch_pspecs
        from repro.launch.mesh import make_mesh, make_test_mesh

        cfg = configs.get("llama3.2-1b").reduced(n_layers=2, d_model=128)
        mesh = make_mesh((2, 4), ("data", "model"))
        params = M.init_params(cfg, jax.random.PRNGKey(0))
        opt = AdamW(lr=1e-3)
        state = opt.init(params)
        toks = jax.random.randint(jax.random.PRNGKey(1), (4, 32), 0,
                                  cfg.vocab_size)
        batch = {"tokens": toks, "labels": toks}
        pspec = param_pspecs(cfg, params, mesh)
        pshard = jax.tree.map(lambda s: NamedSharding(mesh, s), pspec,
                              is_leaf=lambda x: isinstance(x, P))
        params = jax.device_put(params, pshard)
        with mesh:
            step = jax.jit(make_train_step(cfg, opt))
            p2, s2, loss = step(params, state, batch)
        assert np.isfinite(float(loss))
        # matches the unsharded single-device step
        params_cpu = jax.device_get(params)
        step1 = jax.jit(make_train_step(cfg, opt))
        _, _, loss1 = step1(params_cpu, opt.init(params_cpu), batch)
        np.testing.assert_allclose(float(loss), float(loss1), rtol=1e-4)
        print("OK", float(loss))
    """)
    assert "OK" in out


def test_ring_attention_exact():
    """Sequence-parallel ring attention == blockwise reference."""
    out = run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.models.transformer.ring_attention import ring_attention
        from repro.models.transformer.layers import \\
            blockwise_causal_attention
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((2, 4), ("data", "model"))
        for (b, s, k, g, d, w) in [(2, 64, 2, 2, 16, 0),
                                   (1, 128, 1, 4, 32, 0),
                                   (2, 64, 2, 1, 16, 24)]:
            q = jax.random.normal(jax.random.PRNGKey(0), (b, s, k, g, d))
            kk = jax.random.normal(jax.random.PRNGKey(1), (b, s, k, d))
            vv = jax.random.normal(jax.random.PRNGKey(2), (b, s, k, d))
            out = ring_attention(q, kk, vv, mesh, axis="model",
                                 sliding_window=w)
            ref = blockwise_causal_attention(q, kk, vv, sliding_window=w,
                                             q_block=16, kv_block=16)
            np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                       rtol=2e-4, atol=2e-4)
        print("OK")
    """)
    assert "OK" in out
