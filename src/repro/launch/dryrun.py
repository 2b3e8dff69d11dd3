import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

# NOTE: the two lines above MUST run before any jax import — jax locks the
# device count on first init. (This also means: no `from __future__` here.)
# CPU-only tool: importing this module overwrites XLA_FLAGS, so no program
# that runs on an accelerator may import it.

_DOC = """Multi-pod dry-run: .lower().compile() every (arch × shape × mesh) cell.

No arrays are ever materialized — parameters, optimizer state, caches and
batches are ShapeDtypeStructs (jax.eval_shape over the real init functions),
so a 400B model "fits" on the CPU container while the compiled artifact is
the real SPMD program the production mesh would run.

Per cell this writes runs/dryrun/<mesh>/<arch>__<shape>.json with
memory_analysis, cost_analysis and the parsed collective schedule — the
roofline table (EXPERIMENTS.md §Roofline) is generated from these files.

Usage:
  python -m repro.launch.dryrun --arch qwen2.5-32b --shape train_4k --mesh single
  python -m repro.launch.dryrun --all [--mesh both] [--jobs 1]
  python -m repro.launch.dryrun --nerf --mesh single
"""

import argparse
import json
import subprocess
import sys
import time
import traceback
from pathlib import Path
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.configs import SHAPES, registry
from repro.configs.base import ModelConfig, ShapeConfig
from repro.launch.mesh import make_production_mesh
from repro.models import lm
from repro.models.common import dtype_of, guard_spec
from repro.optim import adamw_init
from repro.parallel.sharding import apply_strategy, default_strategy
from repro.roofline import analysis
from repro.utils import human_bytes

RUNS = Path(__file__).resolve().parents[3] / "runs" / "dryrun"


# ---------------------------------------------------------------------------
# input specs (ShapeDtypeStruct stand-ins — weak-type-correct, no allocation)
# ---------------------------------------------------------------------------


def sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def batch_specs(cfg: ModelConfig, shape: ShapeConfig, *, train: bool
                ) -> Dict[str, Any]:
    b, s = shape.global_batch, shape.seq_len
    dt = dtype_of(cfg.dtype)
    batch = {"tokens": sds((b, s), jnp.int32)}
    if train:
        batch["targets"] = sds((b, s), jnp.int32)
    if cfg.encoder_layers > 0:
        batch["frame_embeds"] = sds((b, cfg.enc_seq_len, cfg.d_model), dt)
    if cfg.num_image_tokens > 0:
        batch["image_embeds"] = sds((b, cfg.num_image_tokens, cfg.d_model), dt)
    return batch


def _ns_tree(spec_tree, shape_tree, mesh):
    """PartitionSpec tree -> NamedSharding tree, guarded against the mesh."""
    def one(spec, shp):
        return NamedSharding(mesh, guard_spec(spec, shp.shape, mesh,
                                              strict=True))

    return jax.tree.map(one, spec_tree, shape_tree,
                        is_leaf=lambda x: isinstance(x, P))


def _batch_pspec(cfg: ModelConfig, batch, mesh):
    spec = {"tokens": P(("pod", "data"), None)}
    if "targets" in batch:
        spec["targets"] = P(("pod", "data"), None)
    if "frame_embeds" in batch:
        spec["frame_embeds"] = P(("pod", "data"), None, None)
    if "image_embeds" in batch:
        spec["image_embeds"] = P(("pod", "data"), None, None)
    return _ns_tree(spec, batch, mesh)


# ---------------------------------------------------------------------------
# cell builders: (fn, example_args, in_shardings, out_shardings, donate)
# ---------------------------------------------------------------------------


def build_lm_cell(cfg: ModelConfig, shape: ShapeConfig, mesh,
                  moe_dispatch: Optional[str] = None,
                  overrides: Optional[dict] = None):
    if moe_dispatch:
        cfg = cfg.with_(moe_dispatch=moe_dispatch)
    if overrides:
        cfg = cfg.with_(**overrides)
    params_sh = jax.eval_shape(lambda: lm.init_params(cfg, jax.random.key(0)))
    strategy = (cfg.sharding_strategy if cfg.sharding_strategy != "tp"
                or (overrides and "sharding_strategy" in overrides)
                else default_strategy(cfg))
    if strategy == "fsdp" and shape.kind != "train":
        strategy = "tp"  # serving keeps TP/seq-sharded cache layouts
    from repro.models import common as _common
    _common.set_strategy(strategy)
    pspec_tree = apply_strategy(lm.param_specs(cfg), params_sh, strategy)
    pspecs = _ns_tree(pspec_tree, params_sh, mesh)
    repl = NamedSharding(mesh, P())

    if shape.kind == "train":
        opt_sh = jax.eval_shape(adamw_init, params_sh)
        ospecs = {"m": pspecs, "v": pspecs}
        batch = batch_specs(cfg, shape, train=True)
        bspecs = _batch_pspec(cfg, batch, mesh)
        fn = lm.make_train_step(cfg)
        args = (params_sh, opt_sh, batch, sds((), jnp.int32))
        in_sh = (pspecs, ospecs, bspecs, repl)
        out_sh = (pspecs, ospecs, jax.tree.map(lambda _: repl,
                                               {"ce": 0, "aux": 0, "loss": 0,
                                                "lr": 0}))
        return fn, args, in_sh, out_sh, (0, 1)

    if shape.kind == "prefill":
        batch = batch_specs(cfg, shape, train=False)
        bspecs = _batch_pspec(cfg, batch, mesh)
        fn = lm.make_prefill_step(cfg, cache_len=shape.seq_len)
        caches_sh = jax.eval_shape(
            lambda: lm.cache_init(cfg, shape.global_batch, shape.seq_len))
        cspecs = _ns_tree(lm.cache_specs(cfg), caches_sh, mesh)
        logits_sh = sds((shape.global_batch, cfg.vocab_size), jnp.float32)
        lspec = NamedSharding(mesh, guard_spec(P(("pod", "data"), "model"),
                                               logits_sh.shape, mesh,
                                               strict=True))
        args = (params_sh, batch)
        return fn, args, (pspecs, bspecs), (lspec, cspecs), ()

    # decode: one new token against a seq_len KV cache
    shard_seq = shape.seq_len >= (1 << 19)  # long-context cells only
    caches_sh = jax.eval_shape(
        lambda: lm.cache_init(cfg, shape.global_batch, shape.seq_len))
    cspecs = _ns_tree(lm.cache_specs(cfg, shard_seq=shard_seq), caches_sh,
                      mesh)
    fn = lm.make_decode_step(cfg)
    token = sds((shape.global_batch, 1), jnp.int32)
    tok_spec = NamedSharding(mesh, guard_spec(P(("pod", "data"), None),
                                              token.shape, mesh, strict=True))
    logits_sh = sds((shape.global_batch, cfg.vocab_size), jnp.float32)
    lspec = NamedSharding(mesh, guard_spec(P(("pod", "data"), "model"),
                                           logits_sh.shape, mesh,
                                           strict=True))
    repl = NamedSharding(mesh, P())
    args = (params_sh, caches_sh, token, sds((), jnp.int32))
    return fn, args, (pspecs, cspecs, tok_spec, repl), (lspec, cspecs), (1,)


def build_nerf_cell(arch: str, mesh, table_sharding: str = "model",
                    table_dtype=None):
    """render_step for the paper's own models: rays over data, table/model."""
    from repro.configs.cicero_nerf import NERF_CONFIGS
    from repro.nerf import models as nerf_models

    ncfg = NERF_CONFIGS[arch]
    model = nerf_models.NerfModel(ncfg)
    params_sh = jax.eval_shape(lambda: model.init(jax.random.key(0)))
    if table_dtype is not None:
        # store feature tables compactly (bf16 gathers halve HBM traffic);
        # interpolation/decode still run in f32 (einsum promotion)
        params_sh = jax.tree.map(
            lambda l: (jax.ShapeDtypeStruct(l.shape, table_dtype)
                       if l.ndim >= 2 and l.shape[0] >= 4096 else l),
            params_sh)

    def table_spec(path_leaf):
        return P(None)  # resolved per-leaf below

    # shard big tables' leading axis over model (or replicate); decoder repl.
    def spec_for(path, leaf):
        if (table_sharding.startswith("model") and leaf.ndim >= 2
                and leaf.shape[0] >= 4096):
            return P("model", *([None] * (leaf.ndim - 1)))
        return P(*([None] * leaf.ndim))

    flat, treedef = jax.tree.flatten_with_path(params_sh)
    pspec_tree = treedef.unflatten([spec_for(p, l) for p, l in flat])
    pspecs = _ns_tree(pspec_tree, params_sh, mesh)

    n_rays = 800 * 800
    origins = sds((n_rays, 3), jnp.float32)
    dirs = sds((n_rays, 3), jnp.float32)
    rspec = NamedSharding(mesh, guard_spec(P(("pod", "data", "model"),),
                                           (n_rays,), mesh, strict=True))
    rspec3 = NamedSharding(mesh, guard_spec(P(("pod", "data", "model"), None),
                                            (n_rays, 3), mesh, strict=True))

    def render_step(params, o, d):
        return model.render_rays(params, o, d)

    args = (params_sh, origins, dirs)
    return render_step, args, (pspecs, rspec3, rspec3), (rspec3, rspec), ()


# ---------------------------------------------------------------------------
# lower + compile + report
# ---------------------------------------------------------------------------


def run_cell(arch: str, shape_name: str, mesh_name: str,
             moe_dispatch: Optional[str] = None,
             out_path: Optional[Path] = None,
             overrides: Optional[dict] = None,
             nerf_table_sharding: str = "model") -> dict:
    multi = mesh_name == "multi"
    mesh = make_production_mesh(multi_pod=multi)
    is_nerf = arch.startswith("cicero-")
    t0 = time.time()

    if is_nerf:
        fn, args, in_sh, out_sh, donate = build_nerf_cell(
            arch, mesh, table_sharding=nerf_table_sharding,
            table_dtype=jnp.bfloat16 if nerf_table_sharding.endswith("bf16")
            else None)
        mflops = 0.0
        cfg = None
    else:
        cfg = registry.get(arch)
        shape = SHAPES[shape_name]
        if shape_name in cfg.skip_shapes:
            raise SystemExit(f"SKIP {arch}/{shape_name}: needs sub-quadratic "
                             "attention (DESIGN.md §5)")
        fn, args, in_sh, out_sh, donate = build_lm_cell(cfg, shape, mesh,
                                                        moe_dispatch,
                                                        overrides)
        mflops = analysis.model_flops(cfg, shape)

    with jax.set_mesh(mesh):
        jitted = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh,
                         donate_argnums=donate)
        lowered = jitted.lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    report = analysis.from_compiled(
        arch, shape_name if not is_nerf else "render_800", mesh_name,
        mesh.size, compiled, model_flops_global=mflops,
        notes=f"moe_dispatch={moe_dispatch or (cfg.moe_dispatch if cfg else '-')}")
    if cfg is not None:
        axis_sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
        report.hbm_bytes = analysis.analytic_hbm_bytes(
            cfg, SHAPES[shape_name], axis_sizes, report.arg_bytes,
            report.output_bytes, report.alias_bytes)
    d = report.to_dict()
    d.update(lower_s=round(t_lower, 2), compile_s=round(t_compile, 2))

    mem = compiled.memory_analysis()
    print(f"[{arch} × {d['shape']} × {mesh_name}] "
          f"compile={t_compile:.1f}s  "
          f"args/dev={human_bytes(d['arg_bytes'])}  "
          f"temp/dev={human_bytes(d['temp_bytes'])}  "
          f"flops/dev={d['flops']:.3e}  bytes/dev={d['bytes_accessed']:.3e}  "
          f"coll/dev={human_bytes(d['coll_weighted_bytes'])}  "
          f"dominant={d['dominant']}  step={d['step_time_s']*1e3:.2f}ms  "
          f"MFU={d['mfu']*100:.1f}%")
    print("  memory_analysis:", mem)
    print("  cost_analysis keys:", {k: v for k, v in
                                    analysis.cost_analysis_dict(compiled).items()
                                    if k in ("flops", "bytes accessed")})

    if out_path:
        out_path.parent.mkdir(parents=True, exist_ok=True)
        out_path.write_text(json.dumps(d, indent=1))
    return d


def default_out(arch, shape_name, mesh_name, tag="") -> Path:
    return RUNS / mesh_name / f"{arch}__{shape_name}{tag}.json"


def run_all(mesh_names, jobs: int = 1, include_nerf: bool = True,
            skip_existing: bool = True) -> None:
    """Drive every cell in a subprocess (isolation: one bad cell ≠ dead run).
    CPU-only: each child is a JAX process, which on an accelerator host
    would contend with its parent for the chip."""
    cells = []
    for mesh_name in mesh_names:
        for arch, shape_name in registry.runnable_cells():
            cells.append((arch, shape_name, mesh_name))
        if include_nerf:
            for arch in ("cicero-dvgo", "cicero-ngp", "cicero-tensorf"):
                cells.append((arch, "render_800", mesh_name))

    todo = []
    for arch, shape_name, mesh_name in cells:
        out = default_out(arch, shape_name, mesh_name)
        if skip_existing and out.exists():
            continue
        todo.append((arch, shape_name, mesh_name, out))
    print(f"dry-run driver: {len(todo)} cells to go "
          f"({len(cells) - len(todo)} cached)")

    fails = []
    for i, (arch, shape_name, mesh_name, out) in enumerate(todo):
        cmd = [sys.executable, "-m", "repro.launch.dryrun", "--arch", arch,
               "--shape", shape_name, "--mesh", mesh_name, "--out", str(out)]
        print(f"--- [{i+1}/{len(todo)}] {arch} × {shape_name} × {mesh_name}")
        r = subprocess.run(cmd, capture_output=True, text=True)
        sys.stdout.write(r.stdout)
        if r.returncode != 0:
            sys.stdout.write(r.stderr[-2000:])
            fails.append((arch, shape_name, mesh_name))
    print(f"dry-run driver done; {len(fails)} failures: {fails}")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape", default="train_4k")
    ap.add_argument("--mesh", default="single", choices=["single", "multi",
                                                         "both"])
    ap.add_argument("--moe-dispatch", default=None,
                    choices=[None, "einsum", "streaming"])
    ap.add_argument("--out", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--no-skip-existing", action="store_true")
    ap.add_argument("--set", action="append", default=[],
                    help="config override key=value (hillclimbing)")
    ap.add_argument("--nerf-table", default="model",
                    choices=["model", "replicated", "replicated_bf16"])
    args = ap.parse_args()

    overrides = {}
    for kv in args.set:
        k, v = kv.split("=", 1)
        for cast in (int, float):
            try:
                v = cast(v)
                break
            except ValueError:
                continue
        overrides[k] = v

    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    if args.all:
        run_all(meshes, skip_existing=not args.no_skip_existing)
        return
    for mesh_name in meshes:
        out = Path(args.out) if args.out else default_out(
            args.arch, args.shape, mesh_name)
        run_cell(args.arch, args.shape, mesh_name,
                 moe_dispatch=args.moe_dispatch, out_path=out,
                 overrides=overrides or None,
                 nerf_table_sharding=args.nerf_table)


if __name__ == "__main__":
    main()
