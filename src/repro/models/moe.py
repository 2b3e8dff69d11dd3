"""Mixture-of-Experts with two dispatch modes (the Cicero tie-in).

Dispatch is *row-grouped*: the batch row is the dispatch group, so every
scatter/gather stays LOCAL to the data shard that owns the row (GSPMD never
sees a scatter across a sharded dim — global scatters made it replicate the
whole dispatch buffer). The only cross-device movement is the resharding of
``xe [B(data), E, cap, D]`` onto experts ``E(model)`` — exactly the canonical
MoE all-to-all.

``einsum`` (baseline): queue position via cumsum-of-one-hot per row.
``streaming`` (Cicero-style): the MoE analogue of §IV-A memory-centric
rendering — (token, k) pairs *sorted by expert id* per row (the single global
reorder; the RIT), giving each expert a contiguous capacity-padded block.
Same per-row capacity semantics ⇒ identical outputs (tested).
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.models import common
from repro.models.common import DP, TP, ninit, shard


def moe_init(key, cfg: ModelConfig, dtype) -> dict:
    d, e = cfg.d_model, cfg.moe_num_experts
    f = cfg.moe_d_ff or cfg.d_ff
    ks = jax.random.split(key, 5)
    p = {
        "router": ninit(ks[0], (d, e), d**-0.5, jnp.float32),
        "wg": ninit(ks[1], (e, d, f), d**-0.5, dtype),
        "wu": ninit(ks[2], (e, d, f), d**-0.5, dtype),
        "wd": ninit(ks[3], (e, f, d), f**-0.5, dtype),
    }
    if cfg.moe_shared_expert:
        from repro.models.ffn import ffn_init
        p["shared"] = ffn_init(ks[4], d, f, dtype)
    return p


def moe_specs(cfg: ModelConfig) -> dict:
    p = {
        "router": P(None, None),
        "wg": P(TP, None, None),  # EP: experts over model axis
        "wu": P(TP, None, None),
        "wd": P(TP, None, None),
    }
    if cfg.moe_shared_expert:
        from repro.models.ffn import ffn_specs
        p["shared"] = ffn_specs()
    return p


def _router(params, x: jnp.ndarray, cfg: ModelConfig
            ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Top-k routing in fp32. x [B,S,D] -> (idx [B,S,k], gate [B,S,k], aux)."""
    logits = x.astype(jnp.float32) @ params["router"]  # [B,S,E]
    gates = jax.nn.softmax(logits, axis=-1)
    gate, idx = jax.lax.top_k(gates, cfg.moe_top_k)
    gate = gate / jnp.clip(gate.sum(-1, keepdims=True), 1e-9)
    e = cfg.moe_num_experts
    density = jnp.zeros((e,)).at[idx.reshape(-1)].add(1.0) / idx.size
    mean_gate = gates.mean((0, 1))
    aux = e * jnp.sum(density * mean_gate)
    return idx, gate.astype(x.dtype), aux


def _row_capacity(cfg: ModelConfig, s: int) -> int:
    cap = int(cfg.capacity_factor * s * cfg.moe_top_k / cfg.moe_num_experts)
    return max(8, -(-cap // 8) * 8)


def _expert_ffn(params, xe: jnp.ndarray) -> jnp.ndarray:
    """xe [B, E, cap, D] -> same, through per-expert SwiGLU (E over model)."""
    h = jax.nn.silu(jnp.einsum("becd,edf->becf", xe, params["wg"]))
    h = h * jnp.einsum("becd,edf->becf", xe, params["wu"])
    return jnp.einsum("becf,efd->becd", h, params["wd"])


def _dispatch_combine(x, idx, gate, cfg, slot_of_pair, keep, params):
    """Shared tail: scatter rows into [B,E,cap,D], expert FFN, gather back.

    slot_of_pair [B, S*k] — flat (e*cap + position) slot per (token, k) pair;
    keep [B, S*k] — False for capacity-dropped pairs.
    """
    b, s, d = x.shape
    k = cfg.moe_top_k
    e = cfg.moe_num_experts
    cap = _row_capacity(cfg, s)
    src_token = jnp.broadcast_to(
        jnp.arange(s * k, dtype=jnp.int32).reshape(s, k) // k,
        (b, s, k)).reshape(b, s * k)

    dump = e * cap
    slots = jnp.where(keep, slot_of_pair, dump)  # [B, S*k]
    flat_gate = gate.reshape(b, s * k)

    def _scatter_local(x_l, slots_l, st_l, n_e):
        """Row-local dispatch scatter into [b_l, n_e*cap, d]."""
        return jax.vmap(
            lambda xr, sl, st: jnp.zeros((n_e * cap + 1, d), xr.dtype)
            .at[sl].set(xr[st], mode="drop"))(x_l, slots_l, st_l)[:, :-1]

    def _combine_local(ye_flat, slots_l, keep_l, gate_l, n_e):
        """ye_flat [b_l, n_e*cap, d] -> weighted per-token sum [b_l, s, d]."""
        contrib = jax.vmap(
            lambda yr, sl: yr[jnp.minimum(sl, n_e * cap - 1)])(ye_flat,
                                                               slots_l)
        contrib = jnp.where(keep_l[..., None], contrib, 0.0)
        out = contrib.astype(jnp.float32) * gate_l[..., None].astype(
            jnp.float32)
        return out.reshape(-1, s, k, d).sum(2)

    mesh = common.current_abstract_mesh()
    sizes = dict(zip(mesh.axis_names, mesh.axis_sizes)) if not mesh.empty \
        else {}
    tp = sizes.get("model", 1)
    dp_size = sizes.get("pod", 1) * sizes.get("data", 1)
    # fully-local EP only where it pays: s > 1 (train/prefill). At decode the
    # dispatch tensors are tiny but the shard_map in_specs would all-gather
    # FSDP-sharded expert weights every step (measured 1.6 GiB/layer on
    # llama4 decode) — the fallback path is strictly better there.
    if tp > 1 and e % tp == 0 and b % dp_size == 0 and s > 1:
        # Fully-local expert parallelism: x is replicated across the model
        # axis, so each model rank scatters ONLY its own experts' tokens and
        # the combine is one small psum([b_l, s, d]) — activations never
        # cross the shard_map boundary. (Returning per-expert buffers
        # replicated-over-model cost 2.3 TiB/step on moonshot; this is the
        # Cicero memory-centric discipline: move the small thing.)
        dp = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
        e_loc = e // tp

        def local_moe(x_l, slots_l, keep_l, st_l, gate_l, wg, wu, wd):
            m = jax.lax.axis_index("model")
            lo = m * e_loc * cap
            mine = (slots_l >= lo) & (slots_l < lo + e_loc * cap) & keep_l
            sl = jnp.where(mine, slots_l - lo, e_loc * cap)
            xe = _scatter_local(x_l, sl, st_l, e_loc)
            xe = xe.reshape(-1, e_loc, cap, d)
            h = jax.nn.silu(jnp.einsum("becd,edf->becf", xe, wg))
            h = h * jnp.einsum("becd,edf->becf", xe, wu)
            ye = jnp.einsum("becf,efd->becd", h, wd)
            part = _combine_local(ye.reshape(-1, e_loc * cap, d), sl, mine,
                                  gate_l, e_loc)
            return jax.lax.psum(part.astype(x_l.dtype), "model")

        out = jax.shard_map(
            local_moe, mesh=mesh,
            in_specs=(P(dp, None, None), P(dp, None), P(dp, None),
                      P(dp, None), P(dp, None),
                      P("model", None, None), P("model", None, None),
                      P("model", None, None)),
            out_specs=P(dp, None, None),
            axis_names=set(dp) | {"model"}, check_vma=False)(
                x, slots, keep, src_token, flat_gate,
                params["wg"], params["wu"], params["wd"])
        out = out.astype(x.dtype)
    else:
        xe = _scatter_local(x, slots, src_token, e).reshape(b, e, cap, d)
        xe = shard(xe, P(DP, TP, None, None))
        ye = _expert_ffn(params, xe).reshape(b, e * cap, d)
        out = _combine_local(ye, slots, keep, flat_gate, e).astype(x.dtype)
    if cfg.moe_shared_expert:
        from repro.models.ffn import ffn
        out = out + ffn(params["shared"], x)
    return out


def moe_einsum(params, x: jnp.ndarray, cfg: ModelConfig
               ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Baseline: queue position = cumsum of one-hot along the row."""
    b, s, d = x.shape
    k = cfg.moe_top_k
    e = cfg.moe_num_experts
    cap = _row_capacity(cfg, s)
    idx, gate, aux = _router(params, x, cfg)

    flat_e = idx.reshape(b, s * k)  # pair order = (token, k)
    onehot = jax.nn.one_hot(flat_e, e, dtype=jnp.int32)  # [B, S*k, E]
    pos = jnp.cumsum(onehot, axis=1) - 1  # queue position per expert
    pos = jnp.take_along_axis(pos, flat_e[..., None], axis=-1)[..., 0]
    keep = pos < cap
    slots = flat_e * cap + jnp.minimum(pos, cap - 1)
    out = _dispatch_combine(x, idx, gate, cfg, slots, keep, params)
    return out, aux


def moe_streaming(params, x: jnp.ndarray, cfg: ModelConfig
                  ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Cicero RIT-style: per-row argsort by expert id → contiguous blocks.

    Avoids the [B, S*k, E] one-hot/cumsum tensor entirely (the reorder is a
    sort, exactly like MVoxel streaming §IV-A); positions fall out of the
    sorted ranks. Output identical to moe_einsum (stable sort keeps queue
    order).
    """
    b, s, d = x.shape
    k = cfg.moe_top_k
    e = cfg.moe_num_experts
    cap = _row_capacity(cfg, s)
    idx, gate, aux = _router(params, x, cfg)

    flat_e = idx.reshape(b, s * k)
    order = jnp.argsort(flat_e, axis=1, stable=True)  # [B, S*k] — the RIT
    sorted_e = jnp.take_along_axis(flat_e, order, axis=1)
    starts = jax.vmap(lambda se: jnp.searchsorted(se, jnp.arange(e)))(sorted_e)
    rank = jnp.arange(s * k)[None, :] - jnp.take_along_axis(
        starts, sorted_e, axis=1)
    keep_sorted = rank < cap
    slot_sorted = sorted_e * cap + jnp.minimum(rank, cap - 1)
    # un-sort the slot assignment back to (token, k) pair order
    inv = jnp.argsort(order, axis=1)
    slots = jnp.take_along_axis(slot_sorted, inv, axis=1)
    keep = jnp.take_along_axis(keep_sorted, inv, axis=1)
    out = _dispatch_combine(x, idx, gate, cfg, slots, keep, params)
    return out, aux


def moe(params, x: jnp.ndarray, cfg: ModelConfig
        ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    if cfg.moe_dispatch == "streaming":
        return moe_streaming(params, x, cfg)
    return moe_einsum(params, x, cfg)
