"""The one generator behind every traffic mix in ``bench/traffic/``.

A mix is a JSON file of parameters. Today's mixes are closed loops: a
fixed population of viewers, each wearing a headset that waits for its
frames. A viewer watches one session at a time; its next session arrives
when the last frame of the previous one reaches it. A session is an orbit
around the scene with its own start phase, elevation and angular step.

Everything is drawn from the run's seed, and every seed does the same
work. A window sees only the first few sessions, so each quantity comes
in one fixed low-discrepancy order over its distribution's quantiles:
session ``k`` takes the ``k``-th point of a Halton sequence (base 2 for
its length, 3 for its elevation, 5 for its start phase, 7 for its
angular step), and every prefix of the sessions already spans each
distribution. Lengths are whole windows and take their point exactly;
the seed moves each session's elevation, phase and step within a stratum
of ``1/n`` of the range (``n`` the sessions drawn), so that seeds serve
different frames of nearly the same work.
"""
from __future__ import annotations

import json
from dataclasses import dataclass
from pathlib import Path
from typing import List

import numpy as np

TRAFFIC_DIR = Path(__file__).resolve().parent / "traffic"
# far more sessions than a window serves, so that none runs out
SESSIONS_PER_VIEWER = 32


def load(name: str, directory: Path = TRAFFIC_DIR) -> dict:
    return json.loads((directory / f"{name}.json").read_text())


@dataclass
class SessionSpec:
    frames: int
    phase_deg: float
    height: float
    step_deg: float


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def radical_inverse(i: int, base: int) -> float:
    """``i``'s digits in ``base`` mirrored about the radix point: the
    ``i``-th point of the van der Corput sequence in that base."""
    out, f = 0.0, 1.0 / base
    while i:
        i, digit = divmod(i, base)
        out += f * digit
        f /= base
    return out


def halton(n: int, base: int) -> np.ndarray:
    """The first ``n`` points of the van der Corput sequence in ``base``."""
    return np.array([radical_inverse(i, base) for i in range(n)])


def _length_pool(spec: dict, window: int) -> np.ndarray:
    """Session lengths in frames, one per pool entry."""
    if spec["kind"] == "fixed":
        return np.array([spec["frames"]])
    if spec["kind"] == "pareto_windows":
        # numpy's ``pareto`` draws the Lomax form: (1 - U)^(-1/a) - 1
        lomax = (1.0 - _quantiles(spec["pool"])) ** (-1.0 / spec["alpha"]) - 1.0
        wins = np.clip(1 + np.floor(lomax), 1, spec["max_windows"])
        return wins.astype(int) * window
    raise ValueError(f"unknown session_frames kind {spec['kind']!r}")


def sessions(mix: dict, window: int, seed: int) -> List[SessionSpec]:
    """The sessions of the mix and the seed, in the order they arrive:
    whichever viewer asks next is given the next one."""
    rng = np.random.default_rng(seed)
    total = mix["viewers"] * SESSIONS_PER_VIEWER
    pool = _length_pool(mix["session_frames"], window)
    lengths = pool[(halton(total, 2) * len(pool)).astype(int)]

    def spread(base: int, lo: float, hi: float) -> np.ndarray:
        u = halton(total, base) + rng.uniform(0.0, 1.0 / total, size=total)
        return lo + (hi - lo) * u

    m = mix["motion"]
    heights = spread(3, *m["height"])
    phases = spread(5, 0.0, 360.0)
    steps = spread(7, *m["step_deg"])
    return [SessionSpec(frames=int(lengths[i]), phase_deg=float(phases[i]),
                        height=float(heights[i]), step_deg=float(steps[i]))
            for i in range(total)]


def _look_at(eye: np.ndarray) -> np.ndarray:
    """Camera-to-world pose at ``eye`` looking at the origin, y up
    (camera axes: x right, y down, z forward)."""
    fwd = -eye / (np.linalg.norm(eye) + 1e-9)
    right = np.cross(fwd, np.array([0.0, 1.0, 0.0]))
    right /= np.linalg.norm(right) + 1e-9
    down = np.cross(fwd, right)
    pose = np.eye(4)
    pose[:3, 0], pose[:3, 1], pose[:3, 2], pose[:3, 3] = right, down, fwd, eye
    return pose


def orbit_poses(spec: SessionSpec, motion: dict) -> np.ndarray:
    """``[frames, 4, 4]`` float32 poses of the session's orbit."""
    t = np.deg2rad(spec.phase_deg + spec.step_deg * np.arange(spec.frames))
    eye = np.stack([motion["radius"] * np.cos(t),
                    spec.height + motion["wobble"] * np.sin(3.0 * t),
                    motion["radius"] * np.sin(t)], axis=-1)
    return np.stack([_look_at(e) for e in eye]).astype(np.float32)
