"""NeRF models: grid representation + decoder + volume renderer.

``NerfModel`` implements the paper's three-stage pipeline. Two execution
backends (``NerfConfig.backend``):

* ``"reference"`` — pixel-centric gather + plain-jnp decoder (the baseline
  order the paper starts from).
* ``"streaming"`` — memory-centric order through the Pallas kernels:
  ``kernels.ops.gather_features_streaming`` (MVoxel-resident GU gather) and
  ``kernels.ops.nerf_mlp`` (fused decoder). Must produce images matching the
  reference backend (tested); only the memory/work schedule changes. The
  MVoxel halo re-layout of the feature table is built once per params via
  :meth:`NerfModel.prepare_streaming` and travels inside ``params`` so the
  per-frame hot path never rebuilds it. Non-dense representations (hash /
  factorized) keep the reference path — the paper's NGP-level fallback.

An ``oracle`` model renders the analytic scene directly (exact depth,
view-dependent radiance) and is used for warp-threshold experiments.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.nerf import grids, mlp, rays, scenes, volrend


@dataclass(frozen=True)
class NerfConfig:
    kind: str  # dvgo | ngp | tensorf | oracle
    grid_res: int = 64
    channels: int = 8
    hash_levels: int = 8
    hash_table_size: int = 2**14
    hash_base_res: int = 16
    hash_max_res: int = 256
    tensorf_rank: int = 8
    decoder: str = "mlp"  # mlp | rgbnet | direct
    mlp_hidden: int = 64  # the decoder's hidden width (rgbnet: 128)
    # rgbnet (DVGO's fine stage; channel 0 is the raw density, the rest
    # k0): view-encoding frequencies, and the alpha of one voxel step at
    # raw density 0
    viewbase_pe: int = 4
    alpha_init: float = 1e-2
    num_samples: int = 64
    near: float = 0.5
    far: float = 6.0
    white_bkgd: bool = True
    backend: str = "reference"  # reference | streaming (Pallas hot path)
    stream_mvoxel_edge: int = 8  # paper: 8^3-point MVoxels
    stream_capacity: int = 512  # samples per ragged RIT block
    # physical row order of the MVoxel halo blocks: "identity" keeps raw
    # (x,y,z) raster order (the parity control); "bank_interleaved" round-
    # robins halo points across SRAM banks so a voxel's 8 corners never
    # collide (paper §IV-C). Bit-identical outputs by construction.
    mvoxel_layout: str = "identity"
    pallas_interpret: Optional[bool] = None  # None = auto (interpret on CPU)

    @property
    def dense_cfg(self) -> grids.DenseGridCfg:
        return grids.DenseGridCfg(res=self.grid_res, channels=self.channels)

    @property
    def hash_cfg(self) -> grids.HashGridCfg:
        return grids.HashGridCfg(
            num_levels=self.hash_levels,
            base_res=self.hash_base_res,
            max_res=self.hash_max_res,
            table_size=self.hash_table_size,
            channels=2,
        )

    @property
    def tensorf_cfg(self) -> grids.TensoRFCfg:
        return grids.TensoRFCfg(res=self.grid_res, rank=self.tensorf_rank,
                                channels=self.channels)

    @property
    def feat_channels(self) -> int:
        if self.kind == "ngp":
            return self.hash_cfg.out_channels
        return self.channels

    @property
    def decoder_cfg(self) -> mlp.DecoderCfg:
        return mlp.DecoderCfg(mode=self.decoder, in_channels=self.feat_channels,
                              hidden=self.mlp_hidden,
                              viewbase_pe=self.viewbase_pe,
                              alpha_init=self.alpha_init,
                              voxel_size=2.0 / (self.grid_res - 1))

    def feature_table_bytes(self) -> int:
        """Model size (the paper's Fig. 2 x-axis): feature vectors only."""
        if self.kind == "dvgo":
            return self.grid_res**3 * self.channels * 4
        if self.kind == "ngp":
            return self.hash_levels * self.hash_table_size * 2 * 4
        if self.kind == "tensorf":
            return (3 * self.grid_res**2 * self.tensorf_rank + 3 * self.grid_res * self.tensorf_rank) * 4
        return 0


class NerfModel:
    def __init__(self, cfg: NerfConfig, scene: Optional[scenes.Scene] = None):
        self.cfg = cfg
        self.scene = scene
        self._render_rays_jit: Optional[callable] = None
        self._render_rays_flat_jit: Optional[callable] = None
        # feature-table identity → prebuilt MVoxel halo table. An LRU (not
        # a single slot): one model serving alternating scenes (A, B, A,
        # B, ...) must rebuild ZERO tables once both are resident — the
        # single-slot cache silently thrashed on exactly that pattern.
        # Keys hold the table object, so an `is` hit can never alias a
        # recycled id.
        from repro.core.scene_cache import SceneCache as _SceneCache

        self._mv_table_cache = _SceneCache(max_entries=8)

    # ------------------------------------------------------------------
    def init(self, key: jax.Array) -> dict:
        c = self.cfg
        kg, kd = jax.random.split(key)
        if c.kind == "dvgo":
            params = grids.dense_init(kg, c.dense_cfg)
        elif c.kind == "ngp":
            params = grids.hash_init(kg, c.hash_cfg)
        elif c.kind == "tensorf":
            params = grids.tensorf_init(kg, c.tensorf_cfg)
        elif c.kind == "oracle":
            params = {}
        else:
            raise ValueError(c.kind)
        params["decoder"] = mlp.decoder_init(kd, c.decoder_cfg)
        return params

    def init_baked(self, scene: scenes.Scene) -> dict:
        """Dense grid baked from the analytic scene; decoder = direct."""
        assert self.cfg.kind == "dvgo" and self.cfg.decoder == "direct"
        table = scenes.bake_dense_table(scene, self.cfg.grid_res, self.cfg.channels)
        return {"table": table, "decoder": {}}

    # ------------------------------------------------------------------
    @property
    def streaming_cfg(self):
        """StreamingCfg matching this model's dense grid (backend='streaming')."""
        from repro.core import streaming as _streaming

        c = self.cfg
        return _streaming.StreamingCfg(grid_res=c.grid_res,
                                       mvoxel_edge=c.stream_mvoxel_edge,
                                       capacity=c.stream_capacity,
                                       layout=c.mvoxel_layout)

    def prepare_streaming(self, params: dict) -> dict:
        """Attach the prebuilt MVoxel halo table for the streaming backend.

        The re-layout is cached per params (keyed on the feature table's
        identity) so it is built exactly once and hoisted out of every frame
        loop; it travels inside ``params`` as ``"mv_table"`` so jitted render
        functions receive it as a plain input. No-op for other backends/kinds.
        """
        if self.cfg.backend != "streaming" or self.cfg.kind != "dvgo":
            return params
        from repro.core import streaming as _streaming

        scfg = self.streaming_cfg
        if "mv_table" in params:
            if params["mv_table"].ndim == 4:
                # stacked multi-scene resident set [K, num_mv, P, C] — the
                # serve engine's SceneCache built and owns these pages
                return params
            if params["mv_table"].shape[1] == scfg.halo_rows:
                return params
            # staged under a different mvoxel_layout (row count differs) —
            # a stale table would make every layout-remapped id miss;
            # rebuild from the raw feature table instead of trusting it
            params = {k: v for k, v in params.items() if k != "mv_table"}
        from repro.core.scene_cache import ParamsToken as _Token

        table = params["table"]
        # keyed on (table identity, streaming geometry): a layout change
        # (halo row count differs) must rebuild, never serve a stale shape
        mv_table = self._mv_table_cache.get_or_build(
            (_Token(table), scfg),
            lambda: ((built := _streaming.build_mvoxel_table(
                table, scfg)), built.nbytes))
        return {**params, "mv_table": mv_table}

    def query_features(self, params: dict, points: jnp.ndarray,
                       backend: Optional[str] = None,
                       seg: Optional[jnp.ndarray] = None,
                       num_seg: int = 1) -> jnp.ndarray:
        """``seg``/``num_seg`` carry the flat ray-batch core's segment axis
        (one segment per serving session): the streaming gather drops
        chunk padding (``seg >= num_seg``) from its ragged RIT, and each
        sample's output depends on that sample alone. Ignored by reference
        paths (their gathers are per-sample — segment-oblivious by
        construction).

        Mixed-scene serving rides the same call: when ``params`` carry the
        stacked resident set (``table`` ``[K, res^3, C]`` + ``mv_table``
        ``[K, num_mv, P, C]`` + traced ``scene_of_seg`` ``[num_seg]``),
        each segment gathers from its own scene's rows."""
        c = self.cfg
        backend = backend or c.backend
        if backend == "streaming" and c.kind == "dvgo":
            from repro.kernels import ops

            scene_of_seg = params.get("scene_of_seg")
            if scene_of_seg is not None and seg is None:
                raise ValueError(
                    "multi-scene params (scene_of_seg present) need the "
                    "segment axis: render through the flat ray-batch core")
            return ops.gather_features_streaming(
                params["table"], points, self.streaming_cfg,
                mv_table=params.get("mv_table"), seg=seg, num_seg=num_seg,
                scene_of_seg=scene_of_seg, interpret=c.pallas_interpret)
        # hash / factorized representations have no dense vertex walk — they
        # stay on the reference path (the paper's NGP level-fallback)
        if c.kind == "dvgo":
            return grids.dense_query(params, points, c.dense_cfg)
        if c.kind == "ngp":
            return grids.hash_query(params, points, c.hash_cfg)
        if c.kind == "tensorf":
            return grids.tensorf_query(params, points, c.tensorf_cfg)
        raise ValueError(c.kind)

    def query_field(self, params: dict, points: jnp.ndarray, dirs: jnp.ndarray,
                    backend: Optional[str] = None,
                    seg: Optional[jnp.ndarray] = None, num_seg: int = 1
                    ) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """(sigma [S], rgb [S,3]) at sample points."""
        if self.cfg.kind == "oracle":
            assert self.scene is not None
            return scenes.scene_density(self.scene, points), scenes.scene_radiance(
                self.scene, points, dirs)
        backend = backend or self.cfg.backend
        feats = self.query_features(params, points, backend=backend,
                                    seg=seg, num_seg=num_seg)
        return self.decode_features(params, feats, dirs, backend=backend)

    @jax.named_scope("decode")
    def decode_features(self, params: dict, feats: jnp.ndarray,
                        dirs: jnp.ndarray, backend: Optional[str] = None,
                        tick: bool = False
                        ) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """Decoder tail of :meth:`query_field` — gathered features →
        (sigma, rgb). Split out so the unified streaming tick
        (``raybatch.render_tick_streaming``) can run its ONE fused gather
        and still share the exact decoder path with the staged pipeline.
        ``tick`` marks the fused tick's call, whose decoder kernel runs
        under its own name (``fused_nerf_mlp``; staged renders run
        ``staged_nerf_mlp``)."""
        backend = backend or self.cfg.backend
        if backend == "streaming" and self.cfg.decoder in ("mlp", "rgbnet"):
            from repro.kernels import ops

            if self.cfg.decoder == "rgbnet":
                return ops.dvgo_rgbnet(feats, dirs, params["decoder"],
                                       self.cfg.decoder_cfg, tick=tick,
                                       interpret=self.cfg.pallas_interpret)
            return ops.nerf_mlp(feats, mlp._dir_enc(dirs), params["decoder"],
                                tick=tick,
                                interpret=self.cfg.pallas_interpret)
        return mlp.decode(params["decoder"], feats, dirs, self.cfg.decoder_cfg)

    # ------------------------------------------------------------------
    def render_rays(self, params: dict, origins: jnp.ndarray, dirs: jnp.ndarray,
                    key: Optional[jax.Array] = None,
                    seg: Optional[jnp.ndarray] = None, num_seg: int = 1,
                    num_samples: Optional[int] = None
                    ) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """Pixel-centric rendering. Returns (color [R,3], depth [R]).

        ``seg`` ([R] int32) + static ``num_seg`` tag each ray with its
        owning session for the flat ray-batch core — per-ray math is
        segment-oblivious, only the streaming gather's RIT bucketing uses
        them (see :meth:`query_features`). Static ``num_samples``
        overrides the config's per-ray sample budget — the adaptive
        (ASDR-style) coarse sub-pool renders low-disagreement hole rays
        at ``num_samples // coarse_factor``.
        """
        c = self.cfg
        ns = int(num_samples) if num_samples is not None else c.num_samples
        pts, t_vals = rays.sample_along_rays(origins, dirs, c.near, c.far,
                                             ns, key)
        flat_pts = pts.reshape(-1, 3)
        flat_dirs = jnp.repeat(dirs, ns, axis=0)
        sample_seg = (jnp.repeat(seg, ns)
                      if seg is not None else None)
        sigma, rgb = self.query_field(params, flat_pts, flat_dirs,
                                      seg=sample_seg, num_seg=num_seg)
        sigma = sigma.reshape(-1, ns)
        rgb = rgb.reshape(-1, ns, 3)
        color, depth, _ = volrend.composite(sigma, rgb, t_vals, c.far, c.white_bkgd)
        return color, depth

    def render_rays_flat(self, params: dict, origins: jnp.ndarray,
                         dirs: jnp.ndarray,
                         seg: Optional[jnp.ndarray] = None, num_seg: int = 1,
                         num_samples: Optional[int] = None
                         ) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """Flat ray-batch rendering: rays from any number of sessions run
        as ONE fused call (this replaced the vmapped ``render_rays_batch``
        internals — the Pallas kernels see one large contiguous batch
        instead of S small per-session programs). Per-ray outputs are
        independent of how rays are batched, so each session's rows match
        its exclusive render bit-for-bit."""
        return self.render_rays(params, origins.reshape(-1, 3),
                                dirs.reshape(-1, 3), seg=seg, num_seg=num_seg,
                                num_samples=num_samples)

    @property
    def render_rays_jit(self):
        """Jitted ``render_rays``, created once per model (not per call) so
        XLA's compile cache is shared by every renderer using this model."""
        if self._render_rays_jit is None:
            # num_seg/num_samples shape the program (RIT slot count,
            # samples per ray): traced they would crash int()/reshape at
            # first non-default use — same statics as render_rays_flat_jit
            self._render_rays_jit = jax.jit(
                self.render_rays, static_argnames=("num_seg", "num_samples"))
        return self._render_rays_jit

    @property
    def render_rays_flat_jit(self):
        """Jitted :meth:`render_rays_flat` (the flat ray-batch core's fused
        entry), created once per model so XLA's compile cache is shared by
        every caller. ``num_seg``/``num_samples`` are static (they set
        batch shapes); re-traces only per distinct value."""
        if self._render_rays_flat_jit is None:
            self._render_rays_flat_jit = jax.jit(
                self.render_rays_flat,
                static_argnames=("num_seg", "num_samples"))
        return self._render_rays_flat_jit

    def render_image(self, params: dict, cam: rays.Camera, c2w: jnp.ndarray,
                     chunk: int = 1 << 14) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """Full-frame render (chunked over rays to bound memory)."""
        o, d = rays.generate_rays(cam, c2w)
        n = o.shape[0]
        colors, depths = [], []
        render = self.render_rays_jit
        for i in range(0, n, chunk):
            col, dep = render(params, o[i : i + chunk], d[i : i + chunk])
            colors.append(col)
            depths.append(dep)
        color = jnp.concatenate(colors).reshape(cam.height, cam.width, 3)
        depth = jnp.concatenate(depths).reshape(cam.height, cam.width)
        return color, depth

    def render_image_batch(self, params: dict, cam: rays.Camera,
                           c2ws: jnp.ndarray, chunk: int = 1 << 14
                           ) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """Full-frame renders for a pose batch [S,4,4] ->
        ([S,H,W,3], [S,H,W]), chunked over rays with the session axis kept
        on-device — every chunk is ONE fused flat call over all S sessions'
        rays (session-major, segment-tagged) via
        :attr:`render_rays_flat_jit`."""
        o, d = rays.generate_rays_batch(cam, c2ws)  # [S,HW,3]
        s, n = o.shape[0], o.shape[1]
        render = self.render_rays_flat_jit
        colors, depths = [], []
        for i in range(0, n, chunk):
            width = o[:, i:i + chunk].shape[1]
            seg = jnp.repeat(jnp.arange(s, dtype=jnp.int32), width)
            col, dep = render(params, o[:, i:i + chunk], d[:, i:i + chunk],
                              seg=seg, num_seg=s)
            colors.append(col.reshape(s, width, 3))
            depths.append(dep.reshape(s, width))
        color = jnp.concatenate(colors, axis=1).reshape(
            s, cam.height, cam.width, 3)
        depth = jnp.concatenate(depths, axis=1).reshape(
            s, cam.height, cam.width)
        return color, depth


def make_model(kind: str, scene: Optional[scenes.Scene] = None, **kw) -> Tuple[NerfModel, NerfConfig]:
    cfg = NerfConfig(kind=kind, **kw)
    return NerfModel(cfg, scene=scene), cfg
