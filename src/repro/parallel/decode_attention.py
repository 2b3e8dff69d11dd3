"""Explicit sequence-sharded decode attention (flash-decode via shard_map).

The GSPMD-auto path already emits the tree-decode pattern for seq-sharded KV
caches (see attention.kv_cache_specs); this module is the *explicit* version
used by the §Perf hillclimb to control the combine precisely: each shard
computes a partial (max, denom, weighted-sum) over its KV slice, merged with
one tiny psum — collective bytes O(B·H·D) instead of O(S).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def _partial_attn(q, k, v, valid, sm_scale):
    """q [B,KV,G,D]; k/v [B,KV,L,D] (local slice); valid [1,L] bool.
    Returns (m [B,KV,G,1], l [B,KV,G,1], o [B,KV,G,D]) partials."""
    s = jnp.einsum("bkgd,bkld->bkgl", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * sm_scale
    s = jnp.where(valid[:, None, None, :], s, -1e30)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    o = jnp.einsum("bkgl,bkld->bkgd", p, v.astype(jnp.float32))
    return m, l, o


def sharded_decode_attention(q, k_cache, v_cache, index, *, mesh,
                             seq_axis: str = "model", sm_scale: float = 1.0):
    """q [B,H,1,D]; caches [B,KV,S,D] seq-sharded over ``seq_axis``.

    Log-sum-exp merge across shards: given partials (m_i, l_i, o_i),
      M = max_i m_i ;  L = Σ l_i e^{m_i-M} ;  O = Σ o_i e^{m_i-M} / L.
    """
    b, h, _, d = q.shape
    kv = k_cache.shape[1]
    g = h // kv
    qg = q.reshape(b, kv, g, d)
    shard_len = k_cache.shape[2] // mesh.shape[seq_axis]

    def local(qg, k, v, index):
        i = jax.lax.axis_index(seq_axis)
        kpos = i * shard_len + jnp.arange(k.shape[2])[None, :]
        valid = kpos <= index
        m, l, o = _partial_attn(qg, k, v, valid, sm_scale)
        gmax = jax.lax.pmax(m, seq_axis)
        w = jnp.exp(m - gmax)
        lsum = jax.lax.psum(l * w, seq_axis)
        osum = jax.lax.psum(o * w, seq_axis)
        return (osum / jnp.maximum(lsum, 1e-30)).astype(q.dtype)

    fn = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(), P(None, None, seq_axis, None),
                  P(None, None, seq_axis, None), P()),
        out_specs=P(),
        check_vma=False)
    o = fn(qg, k_cache, v_cache, index)
    return o.reshape(b, 1, h * d)
