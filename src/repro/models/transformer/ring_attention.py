"""Ring attention: sequence-parallel causal attention via shard_map.

The §Perf seqshard iteration showed that plain GSPMD sequence sharding
re-gathers K/V inside the q-block scan (3.5e12 B of all-gather per
step).  The correct construction rotates KV shards around the mesh axis
with ``lax.ppermute`` while each device keeps only its local q rows:
per step, one (B, S/m, K, D) block crosses each link — the minimum
possible traffic — and the S x S score tile never exceeds
(S/m) x (S/m) per device.

Causality: with q shard i and kv shard src = (i - r) mod m, global
positions decide the mask; blocks entirely in the future are skipped
cheaply (the mask zeroes them; TPU grids are static so the matmul still
runs — half the ring steps do useful work, as in published ring
attention).
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P


def _block_update(carry, q_blk, k_blk, v_blk, q_pos, k_pos,
                  sliding_window: int):
    """Online-softmax update of (m, l, acc) with one kv block.

    q_blk: (B, Sq, K, G, D); k_blk/v_blk: (B, Sk, K, D);
    q_pos: (Sq,), k_pos: (Sk,) global positions.
    """
    m_, l_, acc = carry
    D = q_blk.shape[-1]
    s = jnp.einsum("bqkgd,bskd->bkgqs", q_blk, k_blk,
                   preferred_element_type=jnp.float32) / math.sqrt(D)
    mask = q_pos[:, None] >= k_pos[None, :]
    if sliding_window:
        mask &= (q_pos[:, None] - k_pos[None, :]) < sliding_window
    s = jnp.where(mask[None, None, None], s, -1e30)
    m_new = jnp.maximum(m_, s.max(axis=-1))
    p = jnp.exp(s - m_new[..., None])
    corr = jnp.exp(m_ - m_new)
    l_new = l_ * corr + p.sum(axis=-1)
    acc_new = acc * corr[..., None] + jnp.einsum(
        "bkgqs,bskd->bkgqd", p.astype(v_blk.dtype), v_blk,
        preferred_element_type=jnp.float32)
    return m_new, l_new, acc_new


def ring_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                   mesh: Mesh | None = None, axis: str = "model",
                   sliding_window: int = 0,
                   axis_size: int | None = None) -> jax.Array:
    """Causal GQA attention with the sequence dim sharded over ``axis``.

    q: (B, S, K, G, D); k/v: (B, S, K, D); S % axis_size == 0.
    ``mesh`` may be None inside jit under an ambient mesh context
    (pass ``axis_size`` then).  Returns (B, S, K, G, D), sharded like q.
    """
    m_size = axis_size if axis_size is not None else mesh.shape[axis]
    B, S, K, G, D = q.shape
    assert S % m_size == 0, (S, m_size)

    def local(q_l, k_l, v_l):
        i = jax.lax.axis_index(axis)
        S_loc = q_l.shape[1]
        q_pos = i * S_loc + jnp.arange(S_loc)

        m0 = jnp.full((B, K, G, S_loc), -jnp.inf, jnp.float32)
        l0 = jnp.zeros((B, K, G, S_loc), jnp.float32)
        a0 = jnp.zeros((B, K, G, S_loc, D), jnp.float32)

        def step(r, carry):
            m_, l_, acc, k_cur, v_cur = carry
            src = (i - r) % m_size
            k_pos = src * S_loc + jnp.arange(S_loc)
            m_, l_, acc = _block_update((m_, l_, acc), q_l, k_cur,
                                        v_cur, q_pos, k_pos,
                                        sliding_window)
            # rotate kv to the next device (i receives from i-1)
            perm = [(j, (j + 1) % m_size) for j in range(m_size)]
            k_nxt = jax.lax.ppermute(k_cur, axis, perm)
            v_nxt = jax.lax.ppermute(v_cur, axis, perm)
            return m_, l_, acc, k_nxt, v_nxt

        m_, l_, acc, _, _ = jax.lax.fori_loop(
            0, m_size, step, (m0, l0, a0, k_l, v_l))
        out = acc / jnp.maximum(l_[..., None], 1e-30)
        return jnp.moveaxis(out, 3, 1).astype(q_l.dtype)  # (B,S_loc,K,G,D)

    spec_q = P(None, axis, None, None, None)
    spec_kv = P(None, axis, None, None)
    kw = {} if mesh is None else {"mesh": mesh}
    fn = shard_map(local, in_specs=(spec_q, spec_kv, spec_kv),
                   out_specs=spec_q, check_vma=False, **kw)
    return fn(q, k, v)
