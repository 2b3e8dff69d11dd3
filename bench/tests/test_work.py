"""Required operations and bytes, against counts worked by hand at a tiny
shape: a 16^3 grid of 12 channels in 8^3 MVoxels (2 per edge, 8 in all,
9^3 = 729 halo rows each), 16 samples per ray, 4x4 frames."""
from __future__ import annotations

import pytest

import peaks
import work

CFG = {"grid_res": 16, "mvoxel_edge": 8, "channels": 12, "num_samples": 16,
       "res": 4, "mlp_hidden": 64, "decoder": "mlp"}


def test_table_sweep():
    assert work.mvoxel_table_bytes(CFG) == 8 * 729 * 12 * 4 == 279936


def test_gather():
    w = work.gather_work(CFG, 10)
    assert w["flops"] == 10 * 8 * 12 * 2 == 1920
    # ids and weights (8 + 8 words) in, 12 features out, per sample
    assert w["bytes"] == 279936 + 10 * (16 * 4 + 12 * 4) == 281056


def test_mlp():
    # 12x64 + 64x64 + 64x1 + 73x3 multiply-adds per sample
    assert work.decoder_flops_per_sample(CFG) == 2 * 5147 == 10294
    w = work.mlp_work(CFG, 10)
    assert w["flops"] == 102940
    weights = (768 + 64 + 4096 + 64 + 64 + 219 + 3) * 4
    assert w["bytes"] == 10 * (12 + 9 + 4) * 4 + weights


def test_tick():
    # 2 hole rays and 16 reference rays of 16 samples; 1 reference frame
    # warped into 4 target frames of 16 pixels
    w = work.tick_work(CFG, hole_rays=2, ref_rays=16, target_frames=4,
                       warped_refs=1)
    per_sample = 8 * 12 * 2 + 10294 + 16
    assert w["flops"] == 18 * 16 * per_sample + 4 * 16 * 60
    assert w["bytes"] == 279936 + 16 * 16 + 4 * 16 * 12 + 16 * 16


def test_direct_decoder_is_cheap():
    assert work.decoder_flops_per_sample(dict(CFG, decoder="direct")) == 4


def test_roofline_bound():
    p = peaks.peaks_for("TPU v5 lite")
    t, bound = peaks.roofline_s(197e12, 1.0, p)
    assert (t, bound) == (pytest.approx(1.0), "compute")
    t, bound = peaks.roofline_s(1.0, 819e9, p)
    assert (t, bound) == (pytest.approx(1.0), "memory")
    with pytest.raises(KeyError):
        peaks.peaks_for("cpu")
