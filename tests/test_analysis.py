"""repro.analysis: one deliberately-violating fixture per rule (each must
FIRE with the right span), suppression machinery, the real-repo clean
baseline for the cheap passes, and the jit-cache steady-state probe."""
import dataclasses
import textwrap
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import astlint, jaxpr_pass, pallas_pass
from repro.analysis.findings import Finding, Report, apply_suppressions
from repro.analysis.jitprobe import JitCacheProbe


def _lint(snippet):
    return astlint.lint_source(textwrap.dedent(snippet), "fixture.py")


def _only(findings, rule):
    return [f for f in findings if f.rule == rule]


def _line_of(snippet, needle):
    for i, ln in enumerate(textwrap.dedent(snippet).splitlines(), 1):
        if needle in ln:
            return i
    raise AssertionError(f"{needle!r} not in fixture")


# ---------------------------------------------------------------------------
# AST rules — every rule fires on its violating snippet, right span
# ---------------------------------------------------------------------------


def test_rule_jit_traced_bool_if_fires():
    src = """
    import jax, jax.numpy as jnp

    @jax.jit
    def f(x):
        if jnp.any(x > 0):
            return x
        return -x
    """
    fs = _only(_lint(src), "jit-traced-bool-if")
    assert len(fs) == 1
    assert fs[0].line == _line_of(src, "if jnp.any")


def test_rule_jit_traced_bool_if_ignores_static_branches():
    src = """
    import jax, jax.numpy as jnp

    @jax.jit
    def f(x, key=None):
        if key is None:
            return x
        return x + 1
    """
    assert not _only(_lint(src), "jit-traced-bool-if")


def test_rule_jit_host_sync_fires_on_item_and_np():
    src = """
    import jax
    import numpy as np

    @jax.jit
    def f(x):
        y = x.sum().item()
        return np.asarray(x) + y
    """
    fs = _only(_lint(src), "jit-host-sync")
    assert {f.line for f in fs} == {_line_of(src, ".item()"),
                                    _line_of(src, "np.asarray")}


def test_rule_jit_host_sync_fires_on_scalarized_traced_param():
    src = """
    import jax

    @jax.jit
    def f(x, num_samples):
        return x[: int(num_samples)]
    """
    fs = _only(_lint(src), "jit-host-sync")
    assert len(fs) == 1 and fs[0].line == _line_of(src, "int(num_samples)")
    # static coverage silences it: int() on a static is legitimate
    src_ok = src.replace("@jax.jit",
                         '@functools.partial(jax.jit, '
                         'static_argnames=("num_samples",))')
    assert not _only(_lint("import functools\n" + textwrap.dedent(src_ok)),
                     "jit-host-sync")


def test_rule_jit_missing_static_fires_and_argnums_map_past_self():
    src = """
    import jax

    def f(x, num_seg):
        return x

    g = jax.jit(f)
    """
    fs = _only(_lint(src), "jit-missing-static")
    assert len(fs) == 1 and fs[0].line == _line_of(src, "g = jax.jit(f)")
    assert "num_seg" in fs[0].message
    # bound-method sites drop self when mapping static_argnums (the
    # engine's jax.jit(self._render_windows, static_argnums=(7, 8)) shape)
    src_bound = """
    import jax

    class E:
        def _tick(self, params, x, bucket):
            return x

        def wire(self):
            self._jit = jax.jit(self._tick, static_argnums=(2,))
    """
    assert not _only(_lint(src_bound), "jit-missing-static")
    src_bad = src_bound.replace("static_argnums=(2,)", "static_argnums=(1,)")
    assert len(_only(_lint(src_bad), "jit-missing-static")) == 1


def test_rule_raw_hash_fires_outside_dunder_hash():
    src = """
    def seed_for(scene):
        return hash(scene) % 1000

    class C:
        def __hash__(self):
            return hash(self.name)
    """
    fs = _only(_lint(src), "raw-hash")
    assert len(fs) == 1 and fs[0].line == _line_of(src, "hash(scene)")


def test_rule_mutable_default_frozen_fires():
    src = """
    import dataclasses
    import numpy as np

    @dataclasses.dataclass(frozen=True)
    class Cfg:
        xs: list = dataclasses.field(default=[1, 2])
        arr: object = np.array([1.0])

    @dataclasses.dataclass
    class NotFrozen:
        ys: list = dataclasses.field(default=[3])
    """
    fs = _only(_lint(src), "mutable-default-frozen")
    assert {f.line for f in fs} == {_line_of(src, "xs: list"),
                                    _line_of(src, "arr: object")}


def test_rule_pallas_no_interpret_fires():
    src = """
    from jax.experimental import pallas as pl

    def bad(x):
        return pl.pallas_call(kernel, grid=(1,))(x)
    """
    fs = _only(_lint(src), "pallas-no-interpret")
    assert len(fs) == 1 and fs[0].line == _line_of(src, "pl.pallas_call")
    src_ok = """
    from jax.experimental import pallas as pl
    from repro.kernels.common import resolve_interpret

    def good(x, interpret=None):
        interpret = resolve_interpret(interpret)
        return pl.pallas_call(kernel, grid=(1,), interpret=interpret)(x)
    """
    assert not _only(_lint(src_ok), "pallas-no-interpret")


# ---------------------------------------------------------------------------
# suppression machinery
# ---------------------------------------------------------------------------


def test_justified_suppression_suppresses(tmp_path):
    p = tmp_path / "mod.py"
    p.write_text("x = 1\n"
                 "# lint: disable=raw-hash -- fixture justification\n"
                 "y = hash('k')\n")
    fs = apply_suppressions(
        [Finding("raw-hash", "mod.py", 3, 4, "m")], tmp_path)
    assert fs[0].suppressed and fs[0].justification == "fixture justification"
    rep = Report(findings=fs, rules_run=["raw-hash"])
    assert not rep.active and rep.summary()["suppressed"] == 1


def test_unjustified_suppression_does_not_suppress(tmp_path):
    p = tmp_path / "mod.py"
    p.write_text("y = hash('k')  # lint: disable=raw-hash\n")
    fs = apply_suppressions(
        [Finding("raw-hash", "mod.py", 1, 4, "m")], tmp_path)
    assert not fs[0].suppressed


# ---------------------------------------------------------------------------
# jaxpr rules
# ---------------------------------------------------------------------------


def test_rule_jaxpr_host_transfer_fires_on_callback():
    def leaky(x):
        return jax.pure_callback(
            lambda a: np.asarray(a) * 2.0,
            jax.ShapeDtypeStruct(x.shape, x.dtype), x)

    closed = jax.make_jaxpr(leaky)(jnp.ones(3))
    fs = jaxpr_pass.check_program(closed, "leaky", "p.py", 7)
    hits = _only(fs, "jaxpr-host-transfer")
    assert hits and hits[0].line == 7 and "leaky" in hits[0].message


@pytest.mark.parametrize("operand", ["host_constant", "traced"])
def test_rule_jaxpr_device_put_fires(operand):
    def puts(x):
        # a host array is folded into the program's constants; a traced
        # operand leaves a device_put equation
        if operand == "host_constant":
            return x + jax.device_put(np.ones(3, np.float32))
        return x + jax.device_put(x * 2.0)

    closed = jax.make_jaxpr(puts)(jnp.ones(3))
    assert _only(jaxpr_pass.check_program(closed, "puts", "p.py", 1),
                 "jaxpr-device-put")


def test_rule_jaxpr_device_put_ignores_device_constants():
    table = jnp.ones(3)

    closed = jax.make_jaxpr(lambda x: x * table + 2.0)(jnp.ones(3))
    assert not _only(jaxpr_pass.check_program(closed, "ok", "p.py", 1),
                     "jaxpr-device-put")


def test_rule_jaxpr_dynamic_shape_fires_on_symbolic_dim():
    eqn = SimpleNamespace(
        primitive=SimpleNamespace(name="dummy"), params={},
        invars=[SimpleNamespace(aval=SimpleNamespace(shape=("b", 3)))],
        outvars=[])
    closed = SimpleNamespace(jaxpr=SimpleNamespace(eqns=[eqn]))
    assert _only(jaxpr_pass.check_program(closed, "dyn", "p.py", 1),
                 "jaxpr-dynamic-shape")


def test_rule_recompile_surface_fires_on_fingerprint_collision():
    variants = [{"a": 1}, {"a": 2}]
    fs = jaxpr_pass.check_recompile_surface(
        variants, fingerprint_of=lambda v: "constant",
        trace_of=lambda v: f"program-{v['a']}")
    assert len(_only(fs, "fingerprint-recompile-surface")) == 1
    # honest fingerprints: distinct programs, distinct fingerprints → clean
    assert not jaxpr_pass.check_recompile_surface(
        variants, fingerprint_of=lambda v: f"fp-{v['a']}",
        trace_of=lambda v: f"program-{v['a']}")


def test_rule_fingerprint_field_coverage_fires(monkeypatch):
    from repro.core import config as cfg_mod

    assert jaxpr_pass.check_fingerprint_coverage() == []
    ghost = SimpleNamespace(name="ghost", repr=False)
    monkeypatch.setattr(cfg_mod.dataclasses, "fields",
                        lambda cls: [ghost])
    with pytest.raises(RuntimeError, match="ghost"):
        cfg_mod.verify_fingerprint_coverage()
    assert len(_only(jaxpr_pass.check_fingerprint_coverage(),
                     "fingerprint-field-coverage")) == 1


# ---------------------------------------------------------------------------
# Pallas rules
# ---------------------------------------------------------------------------


def _rec(**kw):
    base = dict(kernel_name="k", path="kern.py", line=5, grid=(4,),
                in_blocks=[], out_blocks=[], scratch_bytes=0)
    base.update(kw)
    return pallas_pass.LaunchRecord(**base)


def test_rule_pallas_block_divisibility_fires():
    rec = _rec(in_blocks=[((3,), (10,), 12)])  # 3 does not divide 10
    fs = pallas_pass.check_launch(rec, "kern.py")
    hits = _only(fs, "pallas-block-divisibility")
    assert len(hits) == 1 and hits[0].line == 5
    assert not pallas_pass.check_launch(
        _rec(in_blocks=[((5,), (10,), 20)]), "kern.py")


def test_rule_pallas_vmem_budget_fires():
    big = pallas_pass.VMEM_BUDGET_BYTES  # one block alone busts ×2 buffer
    rec = _rec(in_blocks=[((1,), (1,), big)])
    assert _only(pallas_pass.check_launch(rec, "kern.py"),
                 "pallas-vmem-budget")


def test_rule_mvoxel_bank_conflict_fires_on_broken_permutation(monkeypatch):
    from repro.core import streaming

    # identity rows masquerading as the interleaved layout: conflicted
    p3 = (streaming.StreamingCfg().mvoxel_edge + 1) ** 3
    monkeypatch.setattr(
        streaming, "layout_row_map",
        lambda cfg: (np.arange(p3, dtype=np.int32), p3))
    fs, _ = pallas_pass.check_layouts()
    assert _only(fs, "mvoxel-bank-conflict")


def test_bank_conflict_recompute_matches_known_factors():
    ident = pallas_pass.recompute_bank_conflict("identity")
    inter = pallas_pass.recompute_bank_conflict("bank_interleaved")
    assert ident["factor"] == 3.0  # recorded, not gated
    assert inter["factor"] == 1.0 and inter["permutation_ok"]
    # independent recompute agrees with the engine's own accounting
    from repro.core import streaming

    assert inter["factor"] == streaming.bank_conflict_factor(
        streaming.StreamingCfg(layout="bank_interleaved"))
    assert ident["factor"] == streaming.bank_conflict_factor(
        streaming.StreamingCfg(layout="identity"))


def test_pallas_spy_captures_real_kernel_geometry():
    from repro.kernels import gather_trilerp

    recs = pallas_pass.record_launches(
        gather_trilerp.gather_trilerp_mvoxels_segmented,
        jax.ShapeDtypeStruct((4, 832, 4), jnp.float32),
        jax.ShapeDtypeStruct((8,), jnp.int32),
        jax.ShapeDtypeStruct((1,), jnp.int32),
        jax.ShapeDtypeStruct((8, 8, 64), jnp.int32),
        jax.ShapeDtypeStruct((8, 8, 64), jnp.float32),
        interpret=True)
    assert len(recs) == 1
    rec = recs[0]
    assert rec.grid == (8,)  # one step per ragged RIT block
    assert rec.in_blocks[0][0] == (1, 832, 4)  # one resident halo block
    assert rec.in_blocks[1][0] == (1, 8, 64)  # scalar keys not counted
    assert not pallas_pass.check_launch(rec, "gather_trilerp.py")


# ---------------------------------------------------------------------------
# repo baseline (cheap passes only — the full run is scripts/lint.sh)
# ---------------------------------------------------------------------------


def test_repo_ast_and_pallas_baseline_clean():
    from pathlib import Path

    from repro.analysis.cli import repo_root, run_repo_analysis

    report, _ = run_repo_analysis(repo_root(Path(__file__).parent),
                                  passes=("ast", "pallas"))
    assert report.active == [], "\n" + report.format()
    assert len(report.rules_run) >= 8


# ---------------------------------------------------------------------------
# jit-cache steady-state probe (the analyzer's cache instrumentation)
# ---------------------------------------------------------------------------


def test_serving_steady_state_zero_recompiles(scene):
    from repro.core import pipeline
    from repro.core.config import RenderConfig
    from repro.nerf import models, rays
    from repro.serve.render_engine import RenderServeEngine, RenderSession

    model, _ = models.make_model("dvgo", grid_res=32, channels=4,
                                 decoder="direct", num_samples=16)
    params = model.init_baked(scene)
    cam = rays.Camera.square(32)
    # pinned pool bucket → the ladder has one rung; every compile happens
    # in the warmup tick and the steady window must add ZERO programs
    cfg = RenderConfig(camera=cam, num_slots=2, window=2, pool_bucket=512)
    serve = RenderServeEngine(model, params, config=cfg)
    trajs = [pipeline.orbit_trajectory(6, step_deg=1.0, phase_deg=20.0 * i)
             for i in range(2)]
    serve.submit([RenderSession(sid=i, poses=list(t))
                  for i, t in enumerate(trajs)])
    assert serve.step()  # warmup tick: compiles the batch program
    probe = JitCacheProbe(serve.engine)
    steady = 0
    while serve.step():
        steady += 1
    serve.finalize()
    assert steady >= 2, "steady window too short to prove anything"
    assert probe.recompiles() == 0, probe.delta()
