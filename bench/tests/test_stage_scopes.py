"""The serving programs' stage scopes, as ``stage_trace.py`` reads them:
the fused tick and the admission prime of a tiny cell (16^3 grid, 16x16
frames), lowered on the CPU, carry every stage the reader knows in
their ``op_name`` metadata, and no scope it does not know. A renamed or
new scope fails here before a chip run reads it as ``unscoped``."""
from __future__ import annotations

import re

import pytest

import stage_trace
from bench_cells import tiny_cell

# op_name components that are JAX's structure, not scopes: nested jits and
# vmaps, control flow, and einsum's named calls
STRUCTURE = re.compile(
    r"^((jit|vmap|pjit)\(.*\)|while|body|cond|branch_\d+_fun|.*->.*)$")


@pytest.fixture(scope="module")
def programs():
    import jax.numpy as jnp

    import run_cell

    cfg = tiny_cell()["config"]
    weights = run_cell.load_arch(cfg).make_weights(cfg, 7)
    engine = run_cell.build_engine(cfg, weights).engine
    s, n, r = cfg["num_slots"], cfg["window"], cfg["res"]
    eye = jnp.stack([jnp.eye(4)] * s)
    rgb, dep = jnp.zeros((s, r, r, 3)), jnp.zeros((s, r, r))
    full = jnp.full((s,), n, jnp.int32)
    tick = engine._tick_jit.lower(
        engine.params, rgb, dep, eye, jnp.stack([eye] * n, axis=1), eye,
        full, jnp.full((s,), engine.hole_cap, jnp.int32),
        jnp.full((s,), cfg["pool_bucket"], jnp.int32), cfg["pool_bucket"])
    prime = engine._prime_select_jit.lower(
        engine.params, eye, jnp.ones((s,), bool), rgb, dep)

    def names(lowered):
        text = lowered.compiler_ir("hlo").as_hlo_module().to_string()
        return set(stage_trace._OP_NAME.findall(text))

    return {"_tick_streaming": names(tick), "_prime_select": names(prime)}


def _scopes(op_name):
    return [c for part in op_name.split(";") for c in part.split("/")[:-1]
            if not STRUCTURE.match(c)]


def test_every_stage_appears(programs):
    found = {stage_trace.stage_of(n) for names in programs.values()
             for n in names}
    assert set(stage_trace.STAGES) <= found
    # the prime renders references: no warp, no dense fallback
    prime = {stage_trace.stage_of(n) for n in programs["_prime_select"]}
    assert {"compact", "rit_build", "gather", "rit_scatter", "rit_fallback",
            "decode", "composite"} <= prime


def test_no_scope_the_reader_does_not_know(programs):
    unknown = {c for names in programs.values() for n in names
               for c in _scopes(n) if c not in stage_trace.STAGES}
    assert not unknown
