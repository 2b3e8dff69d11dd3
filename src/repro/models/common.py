"""Shared LM building blocks: norms, init helpers, sharding-spec conventions.

Every ``*_init`` function has a sibling ``*_specs`` returning an identically-
structured tree of ``PartitionSpec`` (tested for treedef equality). Mesh axes:
``pod``/``data`` carry batch (DP), ``model`` carries heads / ffn-hidden /
vocab / experts (TP/EP) — the channel-major discipline: the *feature* axis is
spread across the "banks" (devices).
"""
from __future__ import annotations

from typing import Any, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

PyTree = Any

# logical -> mesh axis names (pod folds into data for DP; see parallel/)
DP = ("pod", "data")
TP = "model"


def dtype_of(name: str):
    return {"bfloat16": jnp.bfloat16, "float32": jnp.float32,
            "float16": jnp.float16}[name]


# --- sharding strategy context (set by launchers before tracing) ---------
# "tp" / "tp+fsdp": activations batch-sharded over (pod,data), features/heads
#                   over model (Megatron).
# "fsdp":           ZeRO-3 for dense models — NO tensor parallelism; the
#                   model axis joins data parallelism (batch over all chips),
#                   params sharded over everything, per-layer all-gathers.
_STRATEGY = "tp"


def set_strategy(name: str) -> None:
    global _STRATEGY
    assert name in ("tp", "tp+fsdp", "fsdp"), name
    _STRATEGY = name


def get_strategy() -> str:
    return _STRATEGY


def _remap_entry(entry):
    """Apply the active strategy to one PartitionSpec entry."""
    if _STRATEGY != "fsdp":
        return entry
    if entry == TP or entry == "model":
        return None  # no tensor parallelism
    if (isinstance(entry, (tuple, list)) and "data" in entry
            and "model" not in entry):
        return tuple(entry) + ("model",)  # model axis joins DP
    return entry


def resolve_spec(spec: P, axis_names) -> P:
    """Strategy remap + drop mesh axes not present in ``axis_names`` (e.g.
    'pod' on a single-pod mesh) so one spec tree serves every mesh."""
    out = []
    for entry in spec:
        entry = _remap_entry(entry)
        if entry is None:
            out.append(None)
        elif isinstance(entry, (tuple, list)):
            kept = tuple(a for a in entry if a in axis_names)
            out.append(kept if kept else None)
        else:
            out.append(entry if entry in axis_names else None)
    return P(*out)


def resolve_tree(tree: PyTree, axis_names) -> PyTree:
    return jax.tree.map(lambda s: resolve_spec(s, axis_names), tree,
                        is_leaf=lambda x: isinstance(x, P))


def guard_spec(spec: P, shape, mesh, strict: bool = False) -> P:
    """resolve_spec + drop placements that cannot help: size-1 dims (e.g. the
    batch axis of a global_batch=1 long-context cell). Non-divisible dims are
    KEPT for internal constraints — GSPMD's padded/uneven tiling is cheaper
    than replication (verified: 24 heads over a 16-way axis compiles) — but
    DROPPED under ``strict`` (jit argument shardings require divisibility)."""
    spec = resolve_spec(spec, mesh.axis_names)
    sizes = dict(zip(mesh.axis_names, getattr(mesh, "axis_sizes", ()) or
                     getattr(mesh, "shape", {}).values()))
    out = []
    for i, entry in enumerate(spec):
        if entry is None or i >= len(shape) or shape[i] <= 1:
            out.append(None)
            continue
        if strict:
            axes = entry if isinstance(entry, tuple) else (entry,)
            extent = 1
            for a in axes:
                extent *= sizes.get(a, 1)
            if extent == 0 or shape[i] % extent != 0:
                out.append(None)
                continue
        out.append(entry)
    return P(*out)


def current_abstract_mesh():
    """The mesh in context, as an AbstractMesh (``.empty`` when none)."""
    return jax.sharding.get_abstract_mesh()


def shard(x: jnp.ndarray, spec: P) -> jnp.ndarray:
    """``with_sharding_constraint`` that no-ops without a mesh in context,
    tolerates meshes missing some logical axes, and drops non-divisible
    placements."""
    mesh = current_abstract_mesh()
    if mesh.empty:
        return x
    return jax.lax.with_sharding_constraint(
        x, guard_spec(spec, x.shape, mesh))


def ninit(key, shape, scale, dtype):
    return (scale * jax.random.normal(key, shape, jnp.float32)).astype(dtype)


# ---------------------------------------------------------------------------
# RMSNorm
# ---------------------------------------------------------------------------


def rmsnorm_init(d: int, dtype) -> dict:
    return {"scale": jnp.ones((d,), dtype)}


def rmsnorm_specs() -> dict:
    return {"scale": P(None)}


def rmsnorm(params: dict, x: jnp.ndarray, eps: float = 1e-5) -> jnp.ndarray:
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    out = x32 * jax.lax.rsqrt(var + eps) * params["scale"].astype(jnp.float32)
    return out.astype(x.dtype)
