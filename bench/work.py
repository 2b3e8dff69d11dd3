"""Operations and HBM bytes that a serving tick, and each kernel, must do.

Counted from the live samples the algorithm renders, never from how a
program implements them: hole rays and reference rays times the samples
per ray, one sweep of the MVoxel table, and the frames read and written.
Padded RIT slots, pool padding, idle slots and the overflow fallback's
duplicated gathers do no required work, so a share computed from these
counts reads the same whatever implements the tick.
"""
from __future__ import annotations

F32 = 4
CORNERS = 8
DIR_ENC = 9  # view-direction encoding width of the MLP decoder


def mvoxel_table_bytes(cfg: dict) -> int:
    """One sweep of the MVoxel halo table: every (edge+1)^3 block once."""
    per_edge = -(-cfg["grid_res"] // cfg["mvoxel_edge"])
    halo = (cfg["mvoxel_edge"] + 1) ** 3
    return per_edge ** 3 * halo * cfg["channels"] * F32


def gather_work(cfg: dict, samples: int) -> dict:
    """Trilinear gather of ``samples`` samples from the MVoxel table: a
    weighted sum of 8 corner rows per sample; corner ids and weights in,
    features out, and one sweep of the table."""
    c = cfg["channels"]
    return {"flops": samples * CORNERS * c * 2,
            "bytes": (mvoxel_table_bytes(cfg)
                      + samples * (CORNERS * F32 * 2 + c * F32))}


def decoder_flops_per_sample(cfg: dict) -> int:
    if cfg["decoder"] != "mlp":
        return 4  # sigma clamp and three colour clips
    c, h = cfg["channels"], cfg["mlp_hidden"]
    return 2 * (c * h + h * h + h + (h + DIR_ENC) * 3)


def mlp_work(cfg: dict, samples: int) -> dict:
    """The MLP decoder over ``samples`` samples: features and the
    direction encoding in, (sigma, rgb) out, weights once."""
    c, h = cfg["channels"], cfg["mlp_hidden"]
    weights = (c * h + h + h * h + h + h + (h + DIR_ENC) * 3 + 3) * F32
    return {"flops": samples * decoder_flops_per_sample(cfg),
            "bytes": samples * (c + DIR_ENC + 4) * F32 + weights}


# per-sample compositing: delta, exp, alpha, transmittance, weight, and
# the weighted colour and depth sums
COMPOSITE_FLOPS_PER_SAMPLE = 16
# per-pixel warp: unprojection, two 3x3 transforms, projection, rounding,
# z-test and the colour select
WARP_FLOPS_PER_PIXEL = 60


def tick_work(cfg: dict, hole_rays: int, ref_rays: int,
              target_frames: int, warped_refs: int) -> dict:
    """Required work of one tick (or one admission prime, with
    ``hole_rays = target_frames = warped_refs = 0``): render ``hole_rays +
    ref_rays`` rays and warp ``warped_refs`` reference frames into
    ``target_frames`` frames. Bytes: one table sweep, the reference frames
    read (colour and depth), the target frames written and the new
    reference frames written (colour and depth)."""
    hw = cfg["res"] ** 2
    ns = cfg["num_samples"]
    samples = (hole_rays + ref_rays) * ns
    flops = (samples * (CORNERS * cfg["channels"] * 2
                        + decoder_flops_per_sample(cfg)
                        + COMPOSITE_FLOPS_PER_SAMPLE)
             + target_frames * hw * WARP_FLOPS_PER_PIXEL)
    nbytes = (mvoxel_table_bytes(cfg)
              + warped_refs * hw * 4 * F32
              + target_frames * hw * 3 * F32
              + ref_rays * 4 * F32)
    return {"flops": flops, "bytes": nbytes}
