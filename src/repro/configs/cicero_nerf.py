"""The paper's own three NeRF model configs (DVGO / Instant-NGP / TensoRF)
as framework-selectable architectures, plus the SPARW pipeline defaults.

These are what the Cicero benchmarks instantiate; the dry-run also lowers a
distributed ``render_step`` for them (rays tile-parallel over ``data``, the
feature table replicated or sharded over ``model``).
"""
from dataclasses import dataclass

from repro.nerf.models import NerfConfig


@dataclass(frozen=True)
class CiceroPipelineCfg:
    window: int = 16  # warping window (Fig. 22 sweeps 1..31)
    phi_deg: float | None = None  # warp-angle threshold (Fig. 26: 1..16 deg)
    mvoxel_edge: int = 8  # 8^3-point MVoxels (paper §V)
    rit_capacity: int = 512


# full-scale configs (dry-run / cost-model scale: 800x800 frames, 192 samples)
# DVGO's fine-grid widths (160^3 vertices, 12 feature channels) decoded by the
# repository's own MLP(64): a density head on the hidden layer and a 9-wide
# quadratic direction encoding. DVGO publishes no such decoder.
DVGO = NerfConfig(kind="dvgo", grid_res=160, channels=12, decoder="mlp",
                  mlp_hidden=64, num_samples=192)
# DVGO's published fine stage (arXiv 2111.11215, configs/default.py
# fine_model_and_render): one table of the 160^3 raw density grid and the
# 12-channel k0 grid, the density post-activated with alpha_init 1e-2, and
# rgbnet 39 -> 128 -> 128 -> 3 over k0 and a 4-frequency view encoding
DVGO_FINE = NerfConfig(kind="dvgo", grid_res=160, channels=13,
                       decoder="rgbnet", mlp_hidden=128, viewbase_pe=4,
                       alpha_init=1e-2, num_samples=192)
NGP = NerfConfig(kind="ngp", hash_levels=8, hash_table_size=2**19,
                 hash_base_res=16, hash_max_res=1024, decoder="mlp",
                 mlp_hidden=64, num_samples=192)
TENSORF = NerfConfig(kind="tensorf", grid_res=300, tensorf_rank=48,
                     channels=27, decoder="mlp", mlp_hidden=64,
                     num_samples=192)

# bench-scale configs (CPU-measurable quality experiments)
DVGO_BENCH = NerfConfig(kind="dvgo", grid_res=64, channels=4,
                        decoder="direct", num_samples=64)
NGP_BENCH = NerfConfig(kind="ngp", hash_levels=6, hash_table_size=2**14,
                       hash_base_res=8, hash_max_res=128, decoder="mlp",
                       mlp_hidden=32, num_samples=64)
TENSORF_BENCH = NerfConfig(kind="tensorf", grid_res=64, tensorf_rank=8,
                           channels=8, decoder="mlp", mlp_hidden=32,
                           num_samples=64)

NERF_CONFIGS = {
    "cicero-dvgo": DVGO,
    "cicero-dvgo-fine": DVGO_FINE,
    "cicero-ngp": NGP,
    "cicero-tensorf": TENSORF,
}
NERF_BENCH_CONFIGS = {
    "cicero-dvgo": DVGO_BENCH,
    "cicero-ngp": NGP_BENCH,
    "cicero-tensorf": TENSORF_BENCH,
}
