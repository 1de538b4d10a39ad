"""Pallas kernel sweeps: shapes x dtypes vs the pure-jnp oracles
(interpret mode executes the kernel bodies on CPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.conv2d.ops import (conv2d, conv2d_fused, fallback_count,
                                      reset_fallbacks)
from repro.kernels.conv2d.ref import conv2d_fused_ref, conv2d_ref
from repro.kernels.attention.ops import decode_attention
from repro.kernels.attention.ref import decode_attention_ref
from repro.kernels.ssd.ops import ssd_chunk
from repro.kernels.ssd.ref import ssd_chunk_ref
from repro.models.transformer import layers as L

TOL = {jnp.float32: dict(rtol=2e-5, atol=2e-5),
       jnp.bfloat16: dict(rtol=3e-2, atol=3e-2)}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("shape", [
    (1, 8, 8, 8, 16, 3, 3),
    (2, 12, 10, 16, 32, 1, 1),
    (1, 9, 9, 32, 8, 5, 5),
    (2, 16, 16, 128, 128, 3, 3),
    (1, 10, 8, 8, 16, 7, 1),
    (1, 8, 10, 8, 8, 1, 7),
])
def test_conv2d_sweep(shape, dtype):
    n, h, w, ci, co, kh, kw = shape
    x = jax.random.normal(jax.random.PRNGKey(0), (n, h, w, ci), dtype)
    wt = (jax.random.normal(jax.random.PRNGKey(1), (kh, kw, ci, co),
                            dtype) / np.sqrt(kh * kw * ci)).astype(dtype)
    out = conv2d(x, wt, interpret=True)
    ref = conv2d_ref(x, wt)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **TOL[dtype])


@pytest.mark.parametrize("stride", [(2, 2), (3, 2), 2])
@pytest.mark.parametrize("shape", [
    (1, 9, 9, 8, 16, 3, 3),
    (2, 12, 11, 16, 8, 3, 3),
    (1, 15, 15, 3, 10, 7, 7),    # zoo-style 7x7 stem
    (1, 14, 14, 13, 11, 1, 1),   # 1x1 projection, channel tails
])
def test_conv2d_strided_sweep(shape, stride):
    """Strided convs run the Pallas kernel directly — no fallback."""
    n, h, w, ci, co, kh, kw = shape
    x = jax.random.normal(jax.random.PRNGKey(0), (n, h, w, ci), jnp.float32)
    wt = jax.random.normal(jax.random.PRNGKey(1), (kh, kw, ci, co),
                           jnp.float32) / np.sqrt(kh * kw * ci)
    reset_fallbacks()
    out = conv2d(x, wt, stride=stride, interpret=True)
    st = (stride, stride) if isinstance(stride, int) else stride
    ref = conv2d_ref(x, wt, st)
    assert fallback_count() == 0
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               **TOL[jnp.float32])


@pytest.mark.parametrize("blocks", [(8, 8), (16, 32), (128, 128)])
@pytest.mark.parametrize("shape", [
    (1, 8, 8, 5, 7, 3, 3),       # tails on both axes
    (2, 10, 10, 13, 26, 3, 3),
    (1, 9, 9, 130, 3, 1, 1),     # tail past one 128 block
])
def test_conv2d_channel_tail_blocks(shape, blocks):
    """Non-MXU-aligned channel counts run under any block size: the
    wrapper zero-pads the tail block instead of degrading the tile."""
    n, h, w, ci, co, kh, kw = shape
    bci, bco = blocks
    x = jax.random.normal(jax.random.PRNGKey(0), (n, h, w, ci), jnp.float32)
    wt = jax.random.normal(jax.random.PRNGKey(1), (kh, kw, ci, co),
                           jnp.float32) / np.sqrt(kh * kw * ci)
    out = conv2d(x, wt, block_ci=bci, block_co=bco, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(conv2d_ref(x, wt)),
                               **TOL[jnp.float32])


@pytest.mark.parametrize("relu", [False, True])
@pytest.mark.parametrize("pool", [None, (2, 2)])
@pytest.mark.parametrize("shape", [
    (1, 12, 12, 6, 6, 3, 3, (1, 1)),
    (1, 13, 13, 5, 7, 3, 3, (1, 1)),    # odd conv output + pool floor
    (2, 17, 15, 8, 8, 3, 3, (2, 2)),    # strided conv + pool
])
def test_conv2d_fused_epilogue(shape, relu, pool):
    """Fused bias+relu(+pool) inside the kernel == composed oracle."""
    act = "relu" if relu else "linear"
    n, h, w, ci, co, kh, kw, stride = shape
    x = jax.random.normal(jax.random.PRNGKey(0), (n, h, w, ci), jnp.float32)
    wt = jax.random.normal(jax.random.PRNGKey(1), (kh, kw, ci, co),
                           jnp.float32) / np.sqrt(kh * kw * ci)
    b = jax.random.normal(jax.random.PRNGKey(2), (co,), jnp.float32)
    out = conv2d_fused(x, wt, b, stride=stride, act=act, pool=pool,
                       interpret=True)
    ref = conv2d_fused_ref(x, wt, b, stride=stride, act=act, pool=pool)
    assert out.shape == ref.shape
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               **TOL[jnp.float32])


@pytest.mark.parametrize("shape", [
    (1, 300, 10, 4, 8, 3, 3, (1, 1), None),     # two bands, partial last
    (2, 301, 11, 5, 6, 3, 3, (1, 1), (2, 2)),   # band rounded to pool
    (1, 611, 19, 3, 8, 7, 7, (2, 2), None),     # 7x7/2 stem over bands
    (1, 600, 20, 6, 4, 1, 1, (2, 2), (2, 2)),   # 1x1/2 projection + pool
])
def test_conv2d_row_bands(shape):
    """Outputs taller than one band: every band reads its KH-1 row halo
    and pools on the global pool grid (narrow widths keep it cheap)."""
    from repro.kernels.conv2d.conv2d import _band_rows
    n, h, w, ci, co, kh, kw, stride, pool = shape
    ho, wo = (h - kh) // stride[0] + 1, (w - kw) // stride[1] + 1
    assert ho > _band_rows(ho, wo, pool[0] if pool else 1)
    x = jax.random.normal(jax.random.PRNGKey(0), (n, h, w, ci), jnp.float32)
    wt = jax.random.normal(jax.random.PRNGKey(1), (kh, kw, ci, co),
                           jnp.float32) / np.sqrt(kh * kw * ci)
    b = jax.random.normal(jax.random.PRNGKey(2), (co,), jnp.float32)
    out = conv2d_fused(x, wt, b, stride=stride, act="relu", pool=pool,
                       interpret=True)
    ref = conv2d_fused_ref(x, wt, b, stride=stride, act="relu", pool=pool)
    assert out.shape == ref.shape
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               **TOL[jnp.float32])


def test_conv2d_stride_normalization_and_validation():
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 8, 8, 4))
    wt = jax.random.normal(jax.random.PRNGKey(1), (3, 3, 4, 4)) * 0.1
    np.testing.assert_array_equal(
        np.asarray(conv2d(x, wt, stride=2, interpret=True)),
        np.asarray(conv2d(x, wt, stride=(2, 2), interpret=True)))
    with pytest.raises(ValueError, match="stride"):
        conv2d(x, wt, stride=0, interpret=True)


def test_reset_fallbacks_scopes_accounting_per_run():
    """reset_fallbacks() zeroes the counter AND the warn-once set, so a
    scoped run both counts from zero and re-warns."""
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 2, 2, 4))
    wt = jax.random.normal(jax.random.PRNGKey(1), (3, 3, 4, 4))  # H < KH
    reset_fallbacks()
    with pytest.warns(RuntimeWarning):
        conv2d(x, wt, interpret=True)
    assert fallback_count() == 1
    reset_fallbacks()
    assert fallback_count() == 0
    with pytest.warns(RuntimeWarning):   # warn-once set was cleared too
        conv2d(x, wt, interpret=True)
    assert fallback_count() == 1
    reset_fallbacks()


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("shape", [
    (2, 2, 4, 16, 64, 64),
    (1, 8, 1, 32, 128, 100),
    (2, 1, 8, 64, 256, 7),
    (3, 4, 2, 8, 32, 32),
    (1, 2, 2, 128, 512, 511),
])
def test_decode_attention_sweep(shape, dtype):
    b, k, g, d, s, vl = shape
    q = jax.random.normal(jax.random.PRNGKey(0), (b, k, g, d), dtype)
    kk = jax.random.normal(jax.random.PRNGKey(1), (b, s, k, d), dtype)
    vv = jax.random.normal(jax.random.PRNGKey(2), (b, s, k, d), dtype)
    out = decode_attention(q, kk, vv, jnp.int32(vl), interpret=True)
    ref = decode_attention_ref(q, kk, vv, jnp.int32(vl))
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **TOL[dtype])


@pytest.mark.parametrize("dtype", [jnp.float32])
@pytest.mark.parametrize("shape", [
    (2, 16, 2, 16, 8),
    (1, 64, 4, 32, 16),
    (3, 32, 1, 8, 128),
    (2, 128, 2, 64, 64),
])
def test_ssd_chunk_sweep(shape, dtype):
    bc, q, h, p, n = shape
    x = (jax.random.normal(jax.random.PRNGKey(0), (bc, q, h, p)) * 0.5
         ).astype(dtype)
    dt = jax.nn.softplus(jax.random.normal(jax.random.PRNGKey(1),
                                           (bc, q, h))).astype(dtype)
    A = -jnp.exp(jax.random.normal(jax.random.PRNGKey(2), (h,)) * 0.3
                 ).astype(dtype)
    Bm = (jax.random.normal(jax.random.PRNGKey(3), (bc, q, n)) * 0.3
          ).astype(dtype)
    Cm = (jax.random.normal(jax.random.PRNGKey(4), (bc, q, n)) * 0.3
          ).astype(dtype)
    y, st = ssd_chunk(x, dt, A, Bm, Cm, interpret=True)
    yr, sr = ssd_chunk_ref(x, dt, A, Bm, Cm)
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(yr, np.float32), **TOL[dtype])
    np.testing.assert_allclose(np.asarray(st, np.float32),
                               np.asarray(sr, np.float32), **TOL[dtype])


def test_ssd_kernel_composes_with_interchunk_scan():
    """kernel intra-chunk + jnp inter-chunk == ssd_chunked reference."""
    Bz, Sq, H, P, N, Q = 1, 32, 2, 8, 16, 8
    x = jax.random.normal(jax.random.PRNGKey(0), (Bz, Sq, H, P)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(jax.random.PRNGKey(1),
                                           (Bz, Sq, H)))
    A = -jnp.exp(jax.random.normal(jax.random.PRNGKey(2), (H,)) * 0.3)
    Bm = jax.random.normal(jax.random.PRNGKey(3), (Bz, Sq, N)) * 0.3
    Cm = jax.random.normal(jax.random.PRNGKey(4), (Bz, Sq, N)) * 0.3
    D = jnp.zeros((H,))
    y_ref, h_ref = L.ssd_chunked(x, dt, A, Bm, Cm, D, chunk=Q)

    nc = Sq // Q
    xr = x.reshape(Bz * nc, Q, H, P)
    dtr = dt.reshape(Bz * nc, Q, H)
    Br = Bm.reshape(Bz * nc, Q, N)
    Cr = Cm.reshape(Bz * nc, Q, N)
    y_in, st = ssd_chunk(xr, dtr, A, Br, Cr, interpret=True)
    y_in = y_in.reshape(Bz, nc, Q, H, P)
    st = st.reshape(Bz, nc, H, P, N)
    # inter-chunk recurrence in jnp
    a = (dt * A).reshape(Bz, nc, Q, H)
    cum = jnp.cumsum(a, axis=2)
    cd = jnp.exp(cum[:, :, -1, :])
    h = jnp.zeros((Bz, H, P, N))
    y_tot = []
    for c in range(nc):
        y_inter = jnp.einsum("bqn,bhpn->bqhp", Cr.reshape(
            Bz, nc, Q, N)[:, c], h) * jnp.exp(cum[:, c])[..., None]
        y_tot.append(y_in[:, c] + y_inter)
        h = h * cd[:, c][..., None, None] + st[:, c]
    y = jnp.concatenate(y_tot, axis=1)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(h), np.asarray(h_ref),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("shape", [
    (1, 64, 2, 2, 16, 0),
    (2, 128, 1, 4, 32, 0),
    (1, 256, 2, 1, 64, 0),
    (1, 128, 2, 2, 16, 32),   # sliding window
])
def test_flash_prefill_sweep(shape):
    from repro.kernels.attention.flash_prefill import flash_prefill
    b, s, k, g, d, w = shape
    q = jax.random.normal(jax.random.PRNGKey(0), (b, s, k, g, d))
    kk = jax.random.normal(jax.random.PRNGKey(1), (b, s, k, d))
    vv = jax.random.normal(jax.random.PRNGKey(2), (b, s, k, d))
    out = flash_prefill(q, kk, vv, sliding_window=w, interpret=True)
    ref = L.blockwise_causal_attention(q, kk, vv, sliding_window=w,
                                       q_block=64, kv_block=64)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("shape", [
    (4, 16, 32, 64),     # E, C, D, F
    (8, 128, 64, 128),
    (3, 8, 512, 16),
    (40, 4, 24, 8),      # granite-like expert count
])
def test_moe_gemm_sweep(shape, dtype):
    from repro.kernels.moe_gemm.ops import moe_gemm
    from repro.kernels.moe_gemm.ref import moe_gemm_ref
    e, c, d, f = shape
    x = jax.random.normal(jax.random.PRNGKey(0), (e, c, d), dtype)
    w = (jax.random.normal(jax.random.PRNGKey(1), (e, d, f), dtype)
         / np.sqrt(d)).astype(dtype)
    out = moe_gemm(x, w, interpret=True)
    ref = moe_gemm_ref(x, w)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **TOL[dtype])


def test_conv_kernel_integrates_with_cnn_zoo():
    """The Pallas conv kernel drops into the executable zoo and the
    pipelined stage executor unchanged (system <-> kernel integration).
    The backend is selected explicitly per model/executor — no module
    global."""
    from repro.models.cnn import zoo
    from repro.pipeline.stage import StageExecutor
    m = zoo.vgg16(input_size=(40, 40), scale=0.1, head=False)
    params = m.init(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 40, 40, 3))
    ref = m.forward(params, x)
    out = m.forward(params, x, backend="pallas")
    ex = StageExecutor(m, frozenset(m.graph.layers), [0.5, 0.5],
                       backend="pallas")
    tiled = ex(params, {}, x)
    for k in ref:
        np.testing.assert_allclose(np.asarray(out[k]), np.asarray(ref[k]),
                                   rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(np.asarray(tiled[k]),
                                   np.asarray(ref[k]),
                                   rtol=2e-5, atol=2e-5)
