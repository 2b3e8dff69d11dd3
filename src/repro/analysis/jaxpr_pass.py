"""jaxpr pass: trace the REAL jitted tick programs and verify their
compile/transfer contracts without executing a single device step.

A tiny (grid_res=16, res=16) streaming engine is constructed and its
serving-path programs — ``_render_windows`` (staged tick),
``_tick_streaming`` (fused steady tick, traced both single-scene and over
multi-scene paged params with a ``scene_of_seg`` steering map) and
``_prime_select`` (admission priming) — are traced with
``jax.make_jaxpr`` on abstract
``ShapeDtypeStruct`` inputs. ``make_jaxpr`` runs the Python trace only:
the resulting jaxpr is exactly the program ``jax.jit`` would compile, and
nothing is dispatched, so the transfer-freedom proof below is static.

Rules:

- ``jaxpr-host-transfer``     any host-callback primitive
                              (``pure_callback``/``io_callback``/
                              ``debug_callback``/infeed/outfeed) inside a
                              tick program — a device-to-host sync on the
                              steady path.
- ``jaxpr-device-put``        explicit ``device_put`` equations,
                              non-scalar host arrays captured as program
                              constants (JAX folds a ``device_put`` of a
                              host array into them; they are uploaded
                              with every compile), or float64
                              ``convert_element_type`` on the steady path
                              (silent placement/precision traffic the
                              engine contract forbids).
- ``jaxpr-dynamic-shape``     every aval in every equation must be a
                              concrete-int ShapedArray — a symbolic or
                              object dim means some input leaks a dynamic
                              shape into the compiled program.
- ``fingerprint-recompile-surface``  across a generated config sweep,
                              two configs whose traced programs differ
                              must have different ``fingerprint()``s —
                              otherwise a compile-affecting field escaped
                              the fingerprint and engine caches can serve
                              a stale program (PR 4's bug class).
- ``fingerprint-field-coverage``  every ``RenderConfig`` field must reach
                              the fingerprint (``repr=True``) or be
                              listed in ``_NON_COMPILE_FIELDS`` (enforced
                              at import time by ``core.config``; rerun
                              here so the CLI reports it as a finding).
"""
from __future__ import annotations

import hashlib
import inspect
from typing import Any, Dict, Iterable, List, Tuple

import jax
import jax.numpy as jnp

from repro.analysis.findings import Finding

ALL_RULES = ("jaxpr-host-transfer", "jaxpr-device-put",
             "jaxpr-dynamic-shape", "fingerprint-recompile-surface",
             "fingerprint-field-coverage")

# the tiny-but-real engine every trace runs against (shapes small enough
# that the whole pass stays inside the lint.sh fast-lane budget)
TINY = dict(scene="lego", res=16, window=2, grid_res=16, channels=4,
            decoder="direct", num_samples=4, backend="streaming",
            pool_holes=True, pallas_interpret=True)

_HOST_PRIMS = ("callback", "infeed", "outfeed")


def _subjaxprs(v) -> Iterable:
    from jax.extend import core

    vals = v if isinstance(v, (list, tuple)) else [v]
    for x in vals:
        if isinstance(x, core.ClosedJaxpr):
            yield x.jaxpr
        elif isinstance(x, core.Jaxpr):
            yield x


def iter_consts(closed) -> Iterable:
    """Every constant captured by a closed jaxpr and by the closed
    sub-jaxprs of its equations (pjit bodies keep their own consts)."""
    from jax.extend import core

    yield from getattr(closed, "consts", ())
    for eqn in iter_eqns(closed.jaxpr):
        for v in eqn.params.values():
            for x in v if isinstance(v, (list, tuple)) else [v]:
                if isinstance(x, core.ClosedJaxpr):
                    yield from x.consts


def iter_eqns(jaxpr) -> Iterable:
    """Every equation in a jaxpr, recursing through pjit/cond/scan/
    pallas_call sub-jaxprs."""
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in _subjaxprs(v):
                yield from iter_eqns(sub)


def jaxpr_hash(closed) -> str:
    """Structural hash of a traced program (pretty-printed jaxpr — var
    names are assigned deterministically by the printer)."""
    return hashlib.sha1(str(closed).encode()).hexdigest()[:16]


def check_program(closed, name: str, path: str, line: int) -> List[Finding]:
    out: List[Finding] = []
    for c in iter_consts(closed):
        if not isinstance(c, jax.Array) and jnp.ndim(c) > 0:
            out.append(Finding(
                "jaxpr-device-put", path, line, 0,
                f"{name}: host array of shape {jnp.shape(c)} captured as a "
                "program constant — it is staged into every compile"))
    for eqn in iter_eqns(closed.jaxpr):
        prim = eqn.primitive.name
        if any(tag in prim for tag in _HOST_PRIMS):
            out.append(Finding(
                "jaxpr-host-transfer", path, line, 0,
                f"{name}: primitive '{prim}' is a host round-trip inside "
                "the traced tick program"))
        if prim == "device_put":
            out.append(Finding(
                "jaxpr-device-put", path, line, 0,
                f"{name}: explicit device_put on the steady path — "
                "placement must be staged outside the tick"))
        if prim == "convert_element_type":
            new = eqn.params.get("new_dtype")
            if new is not None and jnp.dtype(new) == jnp.dtype("float64"):
                out.append(Finding(
                    "jaxpr-device-put", path, line, 0,
                    f"{name}: float64 convert_element_type — a precision "
                    "leak doubling steady-path bytes"))
        for var in list(eqn.invars) + list(eqn.outvars):
            aval = getattr(var, "aval", None)
            shape = getattr(aval, "shape", ())
            if not all(isinstance(d, int) for d in shape):
                out.append(Finding(
                    "jaxpr-dynamic-shape", path, line, 0,
                    f"{name}: non-concrete dim in aval {aval} "
                    f"(primitive '{prim}')"))
    return out


# ---------------------------------------------------------------------------
# tiny-engine construction + the three serving-path traces
# ---------------------------------------------------------------------------


def _sds(shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(tuple(shape), dtype)


def _abstract(tree):
    return jax.tree.map(
        lambda x: (jax.ShapeDtypeStruct(jnp.shape(x), x.dtype)
                   if hasattr(x, "dtype") else x), tree)


def _build_engine(cfg):
    from repro import api
    from repro.core.engine import DeviceSparwEngine

    r = api.make_renderer(cfg)
    return DeviceSparwEngine(r.model, r.params, config=cfg)


def _engine_anchor(method) -> Tuple[str, int]:
    raw = inspect.unwrap(method.__func__ if hasattr(method, "__func__")
                         else method)
    path = inspect.getsourcefile(raw) or "<unknown>"
    return path, inspect.getsourcelines(raw)[1]


def trace_serving_programs(root) -> Tuple[List[Finding], Dict[str, Any]]:
    """Trace the staged tick, the fused steady tick and admission priming
    of a tiny real engine; run every per-program rule on each."""
    from pathlib import Path

    from repro.core.config import RenderConfig

    cfg = RenderConfig(**TINY).resolved()
    eng = _build_engine(cfg)
    eng_f = _build_engine(cfg.replace(fused_tick=True))
    s, n = 1, cfg.window
    h = w = cfg.res
    aparams = _abstract(eng.params)
    i32 = jnp.int32
    bucket, bucket_coarse = eng._current_buckets()

    def rel(p):
        try:
            return Path(p).resolve().relative_to(
                Path(root).resolve()).as_posix()
        except ValueError:
            return p

    programs = {}
    path, line = _engine_anchor(eng._render_windows)
    programs["render_windows"] = (
        jax.make_jaxpr(eng._render_windows, static_argnums=(7, 8))(
            aparams, _sds((s, 4, 4)), _sds((s, n, 4, 4)),
            _sds((s,), i32), _sds((s,), i32), _sds((s,), i32),
            _sds((s,), i32), bucket, bucket_coarse),
        rel(path), line)
    path, line = _engine_anchor(eng_f._tick_streaming)
    programs["render_windows_streaming"] = (
        jax.make_jaxpr(eng_f._tick_streaming, static_argnums=(9,))(
            aparams, _sds((s, h, w, 3)), _sds((s, h, w)), _sds((s, 4, 4)),
            _sds((s, n, 4, 4)), _sds((s, 4, 4)), _sds((s,), i32),
            _sds((s,), i32), _sds((s,), i32), bucket),
        rel(path), line)
    # multi-scene variant: the serve engine pages K scene tables into a
    # stacked device cache and steers segments with a traced scene_of_seg
    # map — the SAME steady tick over those params must also be statically
    # transfer-free (scene churn re-steers values, it never re-stages)
    k = 2
    ms_params = dict(aparams)
    for key in ("table", "mv_table"):
        a = ms_params[key]
        ms_params[key] = _sds((k,) + tuple(a.shape), a.dtype)
    ms_params["scene_of_seg"] = _sds((s,), i32)
    programs["render_windows_streaming_multi_scene"] = (
        jax.make_jaxpr(eng_f._tick_streaming, static_argnums=(9,))(
            ms_params, _sds((s, h, w, 3)), _sds((s, h, w)), _sds((s, 4, 4)),
            _sds((s, n, 4, 4)), _sds((s, 4, 4)), _sds((s,), i32),
            _sds((s,), i32), _sds((s,), i32), bucket),
        rel(path), line)
    path, line = _engine_anchor(eng._prime_select)
    programs["prime_reference_select"] = (
        jax.make_jaxpr(eng._prime_select)(
            aparams, _sds((s, 4, 4)), _sds((s,), jnp.bool_),
            _sds((s, h, w, 3)), _sds((s, h, w))),
        rel(path), line)

    findings: List[Finding] = []
    stats: Dict[str, Any] = {"programs": {}}
    for name, (closed, p, ln) in programs.items():
        fs = check_program(closed, name, p, ln)
        findings.extend(fs)
        stats["programs"][name] = {
            "eqns": sum(1 for _ in iter_eqns(closed.jaxpr)),
            "jaxpr_hash": jaxpr_hash(closed),
            "transfer_free": not any(
                f.rule in ("jaxpr-host-transfer", "jaxpr-device-put")
                for f in fs),
        }
    stats["steady_tick_transfer_free"] = (
        stats["programs"]["render_windows_streaming"]["transfer_free"]
        and stats["programs"]["render_windows_streaming_multi_scene"][
            "transfer_free"])
    return findings, stats


# ---------------------------------------------------------------------------
# fingerprint sweep: traced-program drift must imply fingerprint drift
# ---------------------------------------------------------------------------

# fields swept because each provably reshapes the admission-priming
# program (sample count, frame size, chunking, grid resolution)
SWEEP = (dict(), dict(num_samples=8), dict(res=24), dict(ray_chunk=2048),
         dict(grid_res=24))


def check_recompile_surface(variants, fingerprint_of, trace_of,
                            path: str = "src/repro/core/config.py",
                            line: int = 1) -> List[Finding]:
    """Generic collision check: any two variants with EQUAL fingerprints
    but DIFFERENT traced programs is a recompile-surface escape.
    ``fingerprint_of``/``trace_of`` map a variant to its fingerprint and
    structural program hash (injected so fixture tests can fake them)."""
    by_fp: Dict[str, str] = {}
    out: List[Finding] = []
    for v in variants:
        fp, th = fingerprint_of(v), trace_of(v)
        prev = by_fp.setdefault(fp, th)
        if prev != th:
            out.append(Finding(
                "fingerprint-recompile-surface", path, line, 0,
                f"config variant {v!r} changes the traced program "
                f"(hash {th}) but not the fingerprint ({fp}) — a "
                "compile-affecting field escaped fingerprint()"))
    return out


def sweep_fingerprints(root) -> Tuple[List[Finding], Dict[str, Any]]:
    from repro.core.config import RenderConfig

    def fingerprint_of(overrides):
        return RenderConfig(**{**TINY, **overrides}).fingerprint()

    def trace_of(overrides):
        cfg = RenderConfig(**{**TINY, **overrides}).resolved()
        eng = _build_engine(cfg)
        s = 1
        return jaxpr_hash(jax.make_jaxpr(eng._prime_select)(
            _abstract(eng.params), _sds((s, 4, 4)), _sds((s,), jnp.bool_),
            _sds((s, cfg.res, cfg.res, 3)), _sds((s, cfg.res, cfg.res))))

    import inspect as _i

    from repro.core import config as _cfg_mod
    line = _i.getsourcelines(RenderConfig.fingerprint)[1]
    findings = check_recompile_surface(
        SWEEP, fingerprint_of, trace_of,
        path="src/repro/core/config.py", line=line)
    return findings, {"fingerprint_sweep_variants": len(SWEEP)}


def check_fingerprint_coverage() -> List[Finding]:
    from repro.core import config as cfg_mod

    line = inspect.getsourcelines(cfg_mod.verify_fingerprint_coverage)[1]
    try:
        cfg_mod.verify_fingerprint_coverage()
    except Exception as e:  # noqa: BLE001 — any escape is the finding
        return [Finding("fingerprint-field-coverage",
                        "src/repro/core/config.py", line, 0, str(e))]
    return []


def run(root) -> Tuple[List[Finding], Dict[str, Any]]:
    findings, stats = trace_serving_programs(root)
    f2, s2 = sweep_fingerprints(root)
    findings.extend(f2)
    stats.update(s2)
    findings.extend(check_fingerprint_coverage())
    return findings, stats
