"""The traffic generator: deterministic per seed, and every seed serves
the same session lengths in the same order, and each session's orbit in
the same narrow stratum."""
from __future__ import annotations

import numpy as np
import pytest

import traffic

SEEDS = [0, 7, 2**31 + 12345, 2**40 + 3]
MIXES = sorted(p.stem for p in traffic.TRAFFIC_DIR.glob("*.json"))


@pytest.mark.parametrize("mix", MIXES)
@pytest.mark.parametrize("seed", SEEDS)
def test_same_seed_same_sessions(mix, seed):
    m = traffic.load(mix)
    a, b = traffic.sessions(m, 4, seed), traffic.sessions(m, 4, seed)
    assert a == b
    for sa, sb in zip(a[:3], b[:3]):
        assert np.array_equal(traffic.orbit_poses(sa, m["motion"]),
                              traffic.orbit_poses(sb, m["motion"]))


@pytest.mark.parametrize("mix", MIXES)
def test_seeds_share_sizes_and_speeds(mix):
    m = traffic.load(mix)
    base = traffic.sessions(m, 4, SEEDS[0])
    n = len(base)
    strata = {
        "height": (3, *m["motion"]["height"]),
        "phase_deg": (5, 0.0, 360.0),
        "step_deg": (7, *m["motion"]["step_deg"]),
    }
    for seed in SEEDS:
        flat = traffic.sessions(m, 4, seed)
        # the same lengths in the same order for every seed
        assert [x.frames for x in flat] == [x.frames for x in base]
        # every other quantity in the same stratum of 1/n of its range
        for key, (b, lo, hi) in strata.items():
            at = lo + (hi - lo) * traffic.halton(n, b)
            got = np.array([getattr(x, key) for x in flat])
            assert np.all(got >= at - 1e-9), key
            assert np.all(got <= at + (hi - lo) / n + 1e-9), key
    assert traffic.sessions(m, 4, 1) != traffic.sessions(m, 4, 2)


def test_stratified_lengths_come_in_one_order_for_every_seed():
    m = traffic.load("churn")
    orders = [[x.frames for x in traffic.sessions(m, 4, seed)]
              for seed in SEEDS]
    assert all(o == orders[0] for o in orders[1:])
    # every prefix of 2^k sessions spreads over the pool's quantiles: the
    # first 8 are six sessions of 1 window, one of 2 and one of 4
    assert sorted(orders[0][:8]) == [4] * 6 + [8, 16]
    # base 2 visits every one of 2^k strata once in each 2^k points
    assert sorted((traffic.halton(64, 2) * 64).astype(int)) == list(range(64))


def test_churn_lengths_are_whole_windows_of_pareto():
    m = traffic.load("churn")
    pool = traffic._length_pool(m["session_frames"], 4)
    assert set(pool) <= {4, 8, 12, 16}
    # 1 + floor(Lomax(1.5)) at the pool's quantiles: P(X < 1) = 1 - 2^-1.5
    assert np.mean(pool == 4) == pytest.approx(1 - 2 ** -1.5, abs=1 / 64)


def test_orbit_poses_are_rigid_and_look_at_the_origin():
    m = traffic.load("steady")
    spec = traffic.sessions(m, 4, 3)[0]
    spec.frames = 5
    poses = traffic.orbit_poses(spec, m["motion"]).astype(np.float64)
    for p in poses:
        r = p[:3, :3]
        assert np.allclose(r.T @ r, np.eye(3), atol=1e-5)
        fwd = -p[:3, 3] / np.linalg.norm(p[:3, 3])
        assert np.allclose(r[:, 2], fwd, atol=1e-5)
