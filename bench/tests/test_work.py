"""Required operations and bytes, against counts worked by hand at a tiny
shape: a 16^3 grid of 12 channels in 8^3 MVoxels (2 per edge, 8 in all,
9^3 = 729 halo rows each), 16 samples per ray, 4x4 frames; and against
the counts that the harness gave before the architecture's terms moved
to ``bench/arch/``, for every configuration at its own size and at its
architecture's ``TINY``."""
from __future__ import annotations

import json

import pytest

import peaks
import work
from bench_cells import CONFIG_FILES, ROOT

CFG = {"arch": "dvgo_dense", "grid_res": 16, "mvoxel_edge": 8,
       "channels": 12, "num_samples": 16, "res": 4, "mlp_hidden": 64,
       "decoder": "mlp"}


@pytest.fixture(scope="module")
def arch():
    import run_cell

    return run_cell.load_arch(CFG)


def test_table_sweep(arch):
    assert arch.table_sweep_bytes(CFG) == 8 * 729 * 12 * 4 == 279936


def test_gather(arch):
    w = arch.gather_work(CFG, 10)
    assert w["flops"] == 10 * 8 * 12 * 2 == 1920
    # ids and weights (8 + 8 words) in, 12 features out, per sample
    assert w["bytes"] == 279936 + 10 * (16 * 4 + 12 * 4) == 281056


def test_mlp(arch):
    # 12x64 + 64x64 + 64x1 + 73x3 multiply-adds per sample
    assert arch.decoder_work(CFG, 1)["flops"] == 2 * 5147 == 10294
    w = arch.decoder_work(CFG, 10)
    assert w["flops"] == 102940
    weights = (768 + 64 + 4096 + 64 + 64 + 219 + 3) * 4
    assert w["bytes"] == 10 * (12 + 9 + 4) * 4 + weights


def test_tick(arch):
    # 2 hole rays and 16 reference rays of 16 samples; 1 reference frame
    # warped into 4 target frames of 16 pixels
    w = work.tick_work(arch, CFG, hole_rays=2, ref_rays=16, target_frames=4,
                       warped_refs=1)
    per_sample = 8 * 12 * 2 + 10294 + 16
    assert w["flops"] == 18 * 16 * per_sample + 4 * 16 * 60
    assert w["bytes"] == 279936 + 16 * 16 + 4 * 16 * 12 + 16 * 16


def test_direct_decoder_is_cheap(arch):
    assert arch.decoder_work(dict(CFG, decoder="direct"), 1)["flops"] == 4


# (hole_rays, ref_rays, target_frames, warped_refs) -> (flops, bytes) of
# tick_work, and samples -> (flops, bytes) of the gather and the decoder,
# as the harness counted them before the move: a full preview tick, an
# admission prime of two slots, and a tick of one slot.
GOLDEN = {
    ("cicero-dvgo-baked", "full"): {
        "tick_work": [
            ((32768, 73728, 8, 2), (4352507904, 285834240)),
            ((0, 73728, 0, 0), (3001024512, 281115648)),
            ((1234, 36864, 4, 1), (1559588352, 282885120)),
        ],
        "gather_work": [
            (20447232, (3925868544, 2570025984)),
            (14155776, (2717908992, 1865382912)),
            (7314816, (1404444672, 1099195392)),
        ],
        "mlp_work": [
            (20447232, (81788928, 2044744312)),
            (14155776, (56623104, 1415598712)),
            (7314816, (29259264, 731502712)),
        ],
    },
    ("cicero-dvgo-baked", "tiny"): {
        "tick_work": [
            ((512, 512, 8, 2), (3596288, 320896)),
            ((0, 512, 0, 0), (1736704, 288128)),
            ((1234, 256, 4, 1), (5115520, 300416)),
        ],
        "gather_work": [
            (16384, (3145728, 2114944)),
            (8192, (1572864, 1197440)),
            (23840, (4577280, 2950016)),
        ],
        "mlp_work": [
            (16384, (65536, 1659512)),
            (8192, (32768, 840312)),
            (23840, (95360, 2405112)),
        ],
    },
    ("cicero-dvgo", "full"): {
        "tick_work": [
            ((32768, 73728, 8, 2), (214754525184, 285834240)),
            ((0, 73728, 0, 0), (148663959552, 281115648)),
            ((1234, 36864, 4, 1), (76829044992, 282885120)),
        ],
        "gather_work": [
            (20447232, (3925868544, 2570025984)),
            (14155776, (2717908992, 1865382912)),
            (7314816, (1404444672, 1099195392)),
        ],
        "mlp_work": [
            (20447232, (210483806208, 2044744312)),
            (14155776, (145719558144, 1415598712)),
            (7314816, (75298715904, 731502712)),
        ],
    },
    ("cicero-dvgo", "tiny"): {
        "tick_work": [
            ((512, 512, 8, 2), (172187648, 320896)),
            ((0, 512, 0, 0), (86032384, 288128)),
            ((1234, 256, 4, 1), (250429120, 300416)),
        ],
        "gather_work": [
            (16384, (3145728, 2114944)),
            (8192, (1572864, 1197440)),
            (23840, (4577280, 2950016)),
        ],
        "mlp_work": [
            (16384, (168656896, 1659512)),
            (8192, (84328448, 840312)),
            (23840, (245408960, 2405112)),
        ],
    },
}


@pytest.mark.parametrize("config,size", sorted(GOLDEN),
                         ids=[f"{c}-{s}" for c, s in sorted(GOLDEN)])
def test_counts_are_the_parents(config, size):
    import run_cell

    cfg = json.loads((ROOT / CONFIG_FILES[config]).read_text())
    arch = run_cell.load_arch(cfg)
    if size == "tiny":
        cfg.update(arch.TINY)
    want = GOLDEN[(config, size)]

    def pair(w):
        return (w["flops"], w["bytes"])

    for args, counts in want["tick_work"]:
        assert pair(work.tick_work(arch, cfg, *args)) == counts, args
    for samples, counts in want["gather_work"]:
        assert pair(arch.gather_work(cfg, samples)) == counts, samples
    for samples, counts in want["mlp_work"]:
        assert pair(arch.decoder_work(cfg, samples)) == counts, samples


def test_roofline_bound():
    p = peaks.peaks_for("TPU v5 lite")
    t, bound = peaks.roofline_s(197e12, 1.0, p)
    assert (t, bound) == (pytest.approx(1.0), "compute")
    t, bound = peaks.roofline_s(1.0, 819e9, p)
    assert (t, bound) == (pytest.approx(1.0), "memory")
    with pytest.raises(KeyError):
        peaks.peaks_for("cpu")
