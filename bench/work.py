"""Operations and HBM bytes that a serving tick must do.

Counted from the live samples the algorithm renders, never from how a
program implements them: hole rays and reference rays times the samples
per ray, one sweep of the model's feature table, and the frames read and
written. Padded RIT slots, pool padding, idle slots and the overflow
fallback's duplicated gathers do no required work, so a share computed
from these counts reads the same whatever implements the tick. What the
feature gather and the decoder cost per sample, and what a table sweep
reads, is the architecture's (``bench/arch/<arch>.py``).
"""
from __future__ import annotations

F32 = 4

# per-sample compositing: delta, exp, alpha, transmittance, weight, and
# the weighted colour and depth sums
COMPOSITE_FLOPS_PER_SAMPLE = 16
# per-pixel warp: unprojection, two 3x3 transforms, projection, rounding,
# z-test and the colour select
WARP_FLOPS_PER_PIXEL = 60


def tick_work(arch, cfg: dict, hole_rays: int, ref_rays: int,
              target_frames: int, warped_refs: int) -> dict:
    """Required work of one tick (or one admission prime, with
    ``hole_rays = target_frames = warped_refs = 0``) of a model of the
    architecture ``arch``: render ``hole_rays + ref_rays`` rays and warp
    ``warped_refs`` reference frames into ``target_frames`` frames. Bytes:
    one table sweep, the reference frames read (colour and depth), the
    target frames written and the new reference frames written (colour and
    depth)."""
    hw = cfg["res"] ** 2
    samples = (hole_rays + ref_rays) * cfg["num_samples"]
    flops = (arch.gather_work(cfg, samples)["flops"]
             + arch.decoder_work(cfg, samples)["flops"]
             + samples * COMPOSITE_FLOPS_PER_SAMPLE
             + target_frames * hw * WARP_FLOPS_PER_PIXEL)
    nbytes = (arch.table_sweep_bytes(cfg)
              + warped_refs * hw * 4 * F32
              + target_frames * hw * 3 * F32
              + ref_rays * 4 * F32)
    return {"flops": flops, "bytes": nbytes}
