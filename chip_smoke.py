"""Chip smoke test: run the renderer's main serving path once on a TPU.

  python chip_smoke.py             # one chip: kernels vs oracles, then the
                                   # fused streaming serve vs the reference serve
  python chip_smoke.py --chips 4   # four chips: the session-sharded staged
                                   # serve vs the same requests unsharded

The one-chip run serves DVGO at the published table width (grid 160, 12
channels, 192 samples per ray) through ``repro.api.make_renderer(cfg).serve``
on ``RenderServeEngine`` with the fused streaming tick, and checks the
frames against the same requests served by the plain XLA reference backend.
Every cut from the paper's setting is printed before the result. The last
line of standard output is one JSON object:

  {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

The script exits non-zero and prints no result when JAX finds no TPU, when
Pallas would run in interpret mode, when the repository's ``src/`` is not
next to it, or when any phase fails.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent

# The smoke's serving shape, chosen from the v5e compile rehearsal
# (tests/test_tpu_compile.py and the fused tick's memory_analysis) with the
# default RIT block width: at 192x192 frames and a 16384-ray pool bucket the
# fused tick takes 5.1 GB of the chip's 15.75 GB. The bucket is 3x the
# largest window hole total (~5.2k rays) so no tick takes the dense
# fallback. ray_chunk sets how many rays one staged chunk (the reference
# prime on admission) sweeps the MVoxel table for; 16384 rather than the
# 4096 default cuts the prime's sweeps from 18 to 5. Frames and the pinned
# pool bucket are the cuts from the paper's setting.
PAPER = dict(res=800, grid_res=160, channels=12, num_samples=192)
SMOKE = dict(res=192, window=4, num_slots=2, sessions=3, frames=8,
             pool_bucket=16384, ray_chunk=16384)
SHARDED = dict(res=192, window=4, num_slots=4, sessions=4, frames=8,
               devices=4, pool_bucket=16384)
PSNR_GATE_DB = 30.0  # the fused-serving parity gate of the render bench
# The reference serve shares the warp, ray and compositing code with the
# fused serve, so parity alone cannot see a geometry fault. On a CPU, where
# that geometry runs in float32, the smoke's requests give a mean hole
# fraction of 0.030; with the warp's rigid transforms at one bfloat16 MXU
# pass a v5e gave 0.173 and sent every frame to the dense fallback. The
# bound sits between the two.
HOLE_FRACTION_MAX = 0.06


def log(msg: str) -> None:
    print(msg, flush=True)


def serve_config(res: int, window: int, num_slots: int, *, grid_res: int,
                 channels: int, num_samples: int, pool_bucket: int,
                 ray_chunk: int, pallas_interpret=None):
    """The fused streaming serving config at the given shape."""
    from repro.core.config import RenderConfig

    return RenderConfig(
        scene="lego", res=res, window=window, num_slots=num_slots,
        backend="streaming", fused_tick=True, pool_holes=True,
        pool_bucket=pool_bucket, ray_chunk=ray_chunk, grid_res=grid_res,
        channels=channels, num_samples=num_samples, decoder="direct",
        pallas_interpret=pallas_interpret).resolved()


def _frames(results):
    """Served frames as one host array ``[sessions, frames, H, W, 3]``."""
    import numpy as np

    return np.stack([np.stack([np.asarray(f) for f in r.frames])
                     for r in results])


def make_requests(sessions: int, frames: int):
    from repro.core import pipeline
    from repro.core.config import RenderRequest

    return [RenderRequest(poses=tuple(pipeline.orbit_trajectory(
        frames, step_deg=1.0, phase_deg=40.0 * i)), sid=i)
        for i in range(sessions)]


class CompileClock:
    """Sums the backend compile seconds JAX reports while it is open."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        self._event = "/jax/core/compile/backend_compile_duration"
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_kw) -> None:
        if event == self._event:
            self.seconds += duration


# ---------------------------------------------------------------------------
# phase 1: each streaming kernel and the fused MLP against its jnp oracle
# ---------------------------------------------------------------------------


def _block_oracle(tables, ids, w):
    """Per-block oracle: ``tables [B, P, C]`` (each block's halo table),
    ``ids``/``w`` ``[B, 8, T]`` → ``[B, C, T]``."""
    import jax

    from repro.nerf import grids

    def one(tbl, i, wt):
        return grids.gather_trilerp_ref(tbl, i.T, wt.T).T

    with jax.default_matmul_precision("highest"):  # exact float32 oracle
        return jax.vmap(one)(tables, ids, w)


def check_kernels(*, num_mv: int, channels: int, block: int = 512,
                  pages: int = 2, hidden: int = 64, samples: int = 4096,
                  interpret=None, seed: int = 0) -> dict:
    """Run each streaming kernel and ``fused_nerf_mlp`` once against its
    plain ``jnp`` oracle; returns the largest error relative to the
    oracle's largest magnitude, per kernel. The ragged sweeps get
    ``2 * num_mv`` blocks of ``block`` columns over random non-decreasing
    keys, the last quarter of them dead; the stacked case keys ``pages``
    pages of MVoxels as the mixed-scene path does."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import gather_trilerp as gt
    from repro.kernels import fused_nerf_mlp, ref
    from repro.kernels import streaming_pipeline as sp
    from repro.nerf import mlp

    p = 729  # (8 + 1)^3 halo rows of an 8^3-vertex MVoxel
    keys = jax.random.split(jax.random.PRNGKey(seed), 8)
    n_blocks = 2 * num_mv
    live = n_blocks - n_blocks // 4

    def ragged(key, num_keys):
        k0, k1, k2 = jax.random.split(key, 3)
        block_key = jnp.sort(jax.random.randint(k0, (n_blocks,), 0,
                                                num_keys, jnp.int32))
        block_key = jnp.where(jnp.arange(n_blocks) < live, block_key,
                              block_key[live - 1])
        shape = (n_blocks, 8, block)
        ids = jax.random.randint(k1, shape, 0, p, jnp.int32)
        w = jax.random.uniform(k2, shape)
        return block_key, ids, w / jnp.sum(w, axis=1, keepdims=True)

    def rel_err(got, want):
        got, want = np.asarray(got), np.asarray(want)
        return float(np.max(np.abs(got - want)) / max(np.max(np.abs(want)),
                                                       1e-30))

    def ragged_err(table, fn, key, num_keys):
        block_key, ids, w = ragged(key, num_keys)
        got = fn(table, block_key, jnp.array([live], jnp.int32), ids, w,
                 interpret=interpret)
        return rel_err(np.asarray(got)[:live],
                       np.asarray(_block_oracle(table[block_key], ids,
                                                w))[:live])

    mv_table = jax.random.normal(keys[0], (num_mv, p, channels))
    stacked = jax.random.normal(keys[1], (pages * num_mv, p, channels))
    errs = {}
    errs["gather_trilerp_mvoxels_segmented"] = ragged_err(
        mv_table, gt.gather_trilerp_mvoxels_segmented, keys[2], num_mv)
    errs["fused_gather_dual"] = ragged_err(mv_table, sp.fused_gather_dual,
                                           keys[3], num_mv)
    errs["fused_gather_dual (stacked pages)"] = ragged_err(
        stacked, sp.fused_gather_dual, keys[7], pages * num_mv)

    dec = mlp.decoder_init(keys[4], mlp.DecoderCfg(
        mode="mlp", in_channels=channels, hidden=hidden))
    feats = jax.random.normal(keys[5], (samples, channels))
    dirs = jax.random.normal(keys[6], (samples, 3))
    direnc = mlp._dir_enc(dirs / jnp.linalg.norm(dirs, axis=-1,
                                                 keepdims=True))
    args = (feats, direnc, dec["w1"], dec["b1"][None, :], dec["w2"],
            dec["b2"][None, :], dec["w_sigma"], dec["w_rgb"],
            dec["b_rgb"][None, :])
    got = fused_nerf_mlp.fused_nerf_mlp(*args, block=512, interpret=interpret)
    with jax.default_matmul_precision("highest"):
        want = ref.nerf_mlp_ref(*args)
    errs["fused_nerf_mlp"] = rel_err(got, want)
    return errs


# ---------------------------------------------------------------------------
# phase 2: the fused streaming serve against the reference serve
# ---------------------------------------------------------------------------


def _memory_analysis(jitted, args) -> dict:
    """Sizes XLA assigned to ``jitted`` compiled at ``args`` (a hit in the
    compile cache when the program already ran)."""
    m = jitted.lower(*args).compile().memory_analysis()
    return {k: int(getattr(m, f"{k}_size_in_bytes")) for k in
            ("argument", "output", "alias", "temp", "generated_code")}


def serve_and_compare(cfg, requests) -> dict:
    """Serve ``requests`` on ``cfg`` (cold, then warm), serve them again on
    the reference backend, and compare the frames. The reference serve
    keeps the default ``ray_chunk``: its XLA gather materializes
    ``[chunk * samples, 8, C]``, which the TPU's tiled layout pads 16x.

    Device memory is read right after the warm fused serve, before the
    reference serve, together with the compiled sizes of the fused tick
    and the admission prime at the arguments the warm serve gave them."""
    import jax
    import numpy as np

    from repro import api
    from repro.core.config import RenderConfig
    from repro.utils import psnr

    clock = CompileClock()
    t0 = time.time()
    renderer = api.make_renderer(cfg)
    jax.block_until_ready(renderer.params["mv_table"])
    setup_s = time.time() - t0

    t0 = time.time()
    results, metrics = renderer.serve(requests)
    jax.block_until_ready([r.frames for r in results])
    cold_s = time.time() - t0
    compile_s = clock.seconds
    engine = renderer.pipeline.serve_engine_for(cfg).engine
    programs = {"fused_tick": "_tick_jit", "prime": "_prime_select_jit"}
    jitted = {name: getattr(engine, attr) for name, attr in programs.items()}
    seen = {}

    def recorder(name):
        def call(*args):
            seen[name] = args
            return jitted[name](*args)
        return call

    for name, attr in programs.items():
        setattr(engine, attr, recorder(name))
    t0 = time.time()
    warm, warm_metrics = renderer.serve(requests)
    jax.block_until_ready([r.frames for r in warm])
    warm_s = time.time() - t0
    recompiles_warm = clock.seconds - compile_s
    for name, attr in programs.items():
        setattr(engine, attr, jitted[name])
    memory = {"device_after_fused_serve": jax.devices()[0].memory_stats()}
    memory.update({name: _memory_analysis(jitted[name], args)
                   for name, args in seen.items()})

    ref_cfg = cfg.replace(backend="reference", fused_tick=False,
                          ray_chunk=RenderConfig.ray_chunk)
    ref_results, _ = api.make_renderer(ref_cfg).serve(requests)

    frames, ref_frames, warm_frames = (_frames(results), _frames(ref_results),
                                       _frames(warm))
    psnrs = [float(psnr(a, b)) for a, b in zip(frames.reshape(
        -1, *frames.shape[2:]), ref_frames.reshape(-1, *frames.shape[2:]))]
    hole = np.asarray([r.stats.hole_fractions for r in results])
    ref_hole = np.asarray([r.stats.hole_fractions for r in ref_results])
    return {
        "frames_shape": list(frames.shape),
        "finite": bool(np.isfinite(frames).all()),
        "setup_s": setup_s,
        "cold_serve_s": cold_s,
        "compile_s": compile_s,
        "warm_serve_s": warm_s,
        "warm_compile_s": recompiles_warm,
        "ticks": warm_metrics["ticks"],
        # the serve loop's own wall clock: it ends with block_until_ready
        # on the last tick's frames
        "tick_s_mean_warm": warm_metrics["wall_s"] / max(warm_metrics["ticks"],
                                                         1),
        "warm_repeatable": bool(np.array_equal(frames, warm_frames)),
        "complete": bool(metrics["complete"]),
        "hole_fraction_mean": float(hole.mean()),
        "dense_fallback_pixels": int(sum(r.stats.fallback_pixels
                                         for r in results)),
        "rit_overflow": warm_metrics["rit_overflow"],
        "slots_occupancy_mean": metrics["slots"]["occupancy_mean"],
        "admission_ticks": metrics["memory"]["admission_ticks"],
        "min_psnr_vs_reference_db": min(psnrs),
        "max_hole_fraction_diff": float(np.max(np.abs(hole - ref_hole))),
        "memory": memory,
    }


def serving_ok(r: dict, expect_shape,
               max_hole_fraction: float = HOLE_FRACTION_MAX) -> bool:
    return (r["finite"] and r["complete"] and r["warm_repeatable"]
            and r["frames_shape"] == list(expect_shape)
            and r["dense_fallback_pixels"] == 0
            and r["hole_fraction_mean"] <= max_hole_fraction
            and r["min_psnr_vs_reference_db"] >= PSNR_GATE_DB)


# ---------------------------------------------------------------------------
# --chips 4: the session-sharded staged serve against the unsharded serve
# ---------------------------------------------------------------------------


def sharded_vs_unsharded(res: int, window: int, num_slots: int,
                         sessions: int, frames: int, devices: int,
                         pool_bucket: int, *, grid_res: int, channels: int,
                         num_samples: int) -> dict:
    """Serve the same requests with ``ShardConfig(num_devices=devices)``
    and on one device (staged tick, reference backend: the sharded layout
    is the XLA program's, which the fused tick does not support yet)."""
    import numpy as np

    from repro import api
    from repro.core.config import RenderConfig, ShardConfig
    from repro.utils import psnr

    cfg = RenderConfig(scene="lego", res=res, window=window,
                       num_slots=num_slots, pool_bucket=pool_bucket,
                       grid_res=grid_res, channels=channels,
                       num_samples=num_samples, decoder="direct").resolved()
    requests = make_requests(sessions, frames)
    base = api.make_renderer(cfg)
    base_res, _ = base.serve(requests)
    shard_cfg = cfg.replace(shard=ShardConfig(num_devices=devices))
    sharded = api.make_renderer(shard_cfg, model=base.model,
                                params=base.params)
    t0 = time.time()
    shard_res, metrics = sharded.serve(requests)
    shard_s = time.time() - t0
    last = sharded.pipeline.serve_engine_for(shard_cfg)._last_result
    shards = [(str(sh.device), str(sh.index[0]))
              for sh in last.frames.addressable_shards]
    a, b = _frames(base_res), _frames(shard_res)
    flat_a, flat_b = (x.reshape(-1, *x.shape[2:]) for x in (a, b))
    return {
        "devices_used": metrics["devices"],
        "frames_shape": list(a.shape),
        "bit_equal": bool(np.array_equal(a, b)),
        "max_abs_diff": float(np.max(np.abs(a - b))),
        "pixels_differing": int(np.any(a != b, axis=-1).sum()),
        "min_psnr_db": min(float(psnr(x, y)) for x, y in zip(flat_a, flat_b)),
        "finite": bool(np.isfinite(b).all()),
        "frame_shards": shards,
        "sharded_serve_s": shard_s,
    }


# The sharded and unsharded serves are two separately compiled programs:
# XLA may fuse and order float32 reductions differently at a different
# per-device batch shape. That moves the last bits of a depth, which can
# flip a z-buffer tie or a hole, and such a pixel is then NeRF-rendered in
# one serve and warped in the other (a difference of ~1e-2). 60 dB admits
# a few hundred such pixels per frame; a session served from the wrong
# shard or slot scores ~20 dB.
SHARDED_PSNR_DB = 60.0


def sharded_ok(r: dict, devices: int) -> bool:
    return (r["finite"] and r["devices_used"] == devices
            and (r["bit_equal"] or r["min_psnr_db"] >= SHARDED_PSNR_DB)
            and len({d for d, _ in r["frame_shards"]}) == devices)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print("chip_smoke: the repository's src/repro is not next to this "
              "script", file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    import jax

    from repro.kernels.common import resolve_interpret
    from repro.utils import enable_compilation_cache

    cache_dir = enable_compilation_cache(ROOT)
    if jax.default_backend() != "tpu":
        print(f"chip_smoke: no TPU (JAX backend is "
              f"{jax.default_backend()!r})", file=sys.stderr)
        return 2
    devices = jax.devices()
    log(f"devices: {devices}")
    log(f"device_kind: {devices[0].device_kind}  count: {len(devices)}")
    log(f"compilation cache: {cache_dir}")
    interpret = resolve_interpret(None)
    log(f"pallas interpret: {str(interpret).lower()}")
    if interpret:
        print("chip_smoke: Pallas resolved to interpret mode",
              file=sys.stderr)
        return 2
    ok = True
    if args.chips == 4:
        if len(devices) < SHARDED["devices"]:
            print(f"chip_smoke: --chips 4 needs 4 devices, "
                  f"{len(devices)} visible", file=sys.stderr)
            return 2
        log(f"sharded phase config: {SHARDED} model: {PAPER} "
            f"(res cut {PAPER['res']} -> {SHARDED['res']})")
        r = sharded_vs_unsharded(**SHARDED, grid_res=PAPER["grid_res"],
                                 channels=PAPER["channels"],
                                 num_samples=PAPER["num_samples"])
        log(f"sharded vs unsharded: {json.dumps(r)}")
        ok = sharded_ok(r, SHARDED["devices"])
        log(f"sharded phase ok: {ok} (min PSNR {SHARDED_PSNR_DB} dB when not "
            f"bit-equal)")
    else:
        errs = check_kernels(num_mv=64, channels=PAPER["channels"])
        log(f"kernels vs oracles (max error / max |oracle|): "
            f"{json.dumps(errs)}")
        kernels_ok = all(e <= 1e-5 for e in errs.values())
        log(f"kernel phase ok: {kernels_ok}")
        cfg = serve_config(SMOKE["res"], SMOKE["window"], SMOKE["num_slots"],
                           grid_res=PAPER["grid_res"],
                           channels=PAPER["channels"],
                           num_samples=PAPER["num_samples"],
                           pool_bucket=SMOKE["pool_bucket"],
                           ray_chunk=SMOKE["ray_chunk"])
        log(f"config: grid_res={cfg.grid_res} channels={cfg.channels} "
            f"num_samples={cfg.num_samples} decoder={cfg.decoder} "
            f"backend={cfg.backend} fused_tick={cfg.fused_tick} "
            f"pool_holes={cfg.pool_holes} slots={cfg.num_slots} "
            f"sessions={SMOKE['sessions']} frames/session={SMOKE['frames']} "
            f"window={cfg.window} stream_capacity={cfg.stream_capacity} "
            f"(samples per RIT block) ray_chunk={cfg.ray_chunk} "
            f"pallas_interpret={cfg.resolved_pallas_interpret()}")
        log(f"cuts from the paper's setting: frames {PAPER['res']}x"
            f"{PAPER['res']} -> {cfg.res}x{cfg.res}; pool bucket adaptive "
            f"-> pinned at {cfg.pool_bucket} hole rays/session/window (one "
            f"tick compile); decoder MLP(64) -> direct (the only decoder a "
            f"baked scene serves); weights: baked analytic lego scene, not "
            f"trained; RIT block width: default (no cut)")
        requests = make_requests(SMOKE["sessions"], SMOKE["frames"])
        r = serve_and_compare(cfg, requests)
        memory = r.pop("memory")
        log(f"serving: {json.dumps(r)}")
        stats = memory.pop("device_after_fused_serve") or {}
        log(f"peak_bytes_in_use (after the fused serves, before the "
            f"reference serve): {stats.get('peak_bytes_in_use')} of "
            f"{stats.get('bytes_limit')}")
        for name, sizes in memory.items():
            log(f"compiled {name} program bytes: {json.dumps(sizes)}")
        rit = dict(r["rit_overflow"] or {})
        pad = rit.pop("pad", None)
        for stage, v in rit.items():
            log(f"rit overflow share ({stage} stage): "
                f"{v['overflow_share']:.6f} ({v['spilled_samples']} of "
                f"{v['samples']} samples spilled past the RIT)")
        if pad is not None:
            log(f"rit pad share: {pad['pad_share']:.6f} ({pad['pad_columns']}"
                f" of {pad['columns']} columns of the live RIT blocks)")
        log(f"parity: min PSNR vs reference serve "
            f"{r['min_psnr_vs_reference_db']:.3f} dB (gate "
            f">= {PSNR_GATE_DB}); max hole-fraction diff "
            f"{r['max_hole_fraction_diff']:.6f}")
        log(f"geometry: mean hole fraction {r['hole_fraction_mean']:.6f} "
            f"(gate <= {HOLE_FRACTION_MAX}); dense-fallback pixels "
            f"{r['dense_fallback_pixels']} (gate 0)")
        shape = (SMOKE["sessions"], SMOKE["frames"], cfg.res, cfg.res, 3)
        serve_ok = serving_ok(r, shape)
        log(f"serving phase ok: {serve_ok}")
        ok = kernels_ok and serve_ok
    if not ok:
        print("chip_smoke: a phase failed", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
