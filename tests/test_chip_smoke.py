"""chip_smoke.py's phases at a tiny size on the CPU, and its refusals.

The script itself refuses to run without a TPU; here its phase functions
run with interpret-mode Pallas to check control flow, counts and parity.
"""
import importlib.util
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import jax
import pytest

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_kernel_phase_matches_oracles(smoke):
    errs = smoke.check_kernels(num_mv=8, channels=12, block=128,
                               samples=1024, interpret=True)
    assert set(errs) == {
        "gather_trilerp_mvoxels_segmented", "fused_gather_dual",
        "fused_gather_dual (stacked pages)", "fused_nerf_mlp"}
    assert all(e <= 1e-5 for e in errs.values()), errs


# At 32x32 frames the tiny serve's mean hole fraction is 0.0023 with
# float32 geometry and 0.0100 with the warp's transforms at one bfloat16
# pass; the bound sits between them, as HOLE_FRACTION_MAX does at the
# smoke's own size.
TINY_HOLE_FRACTION_MAX = 0.005


def _tiny_serve(smoke):
    cfg = smoke.serve_config(32, 2, 2, grid_res=16, channels=12,
                             num_samples=8, pool_bucket=128, ray_chunk=128,
                             pallas_interpret=True)
    return smoke.serve_and_compare(cfg, smoke.make_requests(3, 4))


def test_serving_phase_queues_and_matches_reference(smoke):
    r = _tiny_serve(smoke)
    assert smoke.serving_ok(r, (3, 4, 32, 32, 3), TINY_HOLE_FRACTION_MAX), r
    # 3 sessions over 2 slots: the third waits, then a slot is reused
    assert r["admission_ticks"] == 2 and r["ticks"] == 4
    assert r["warm_compile_s"] == 0.0
    ref = r["rit_overflow"]["ref"]
    assert ref["samples"] == 4 * 2 * 32 * 32 * 8  # ticks x slots x HW x ns
    # the ragged RIT spills nothing; its padding is counted
    assert ref["overflow_share"] == 0.0
    pad = r["rit_overflow"]["pad"]
    assert 0 <= pad["pad_columns"] < pad["columns"]
    assert pad["pad_share"] == pad["pad_columns"] / pad["columns"]
    assert set(r["memory"]) == {"device_after_fused_serve", "fused_tick",
                                "prime"}
    assert r["memory"]["fused_tick"]["argument"] > 0


class _OneBf16PassMatmul:
    """``jax.numpy`` with ``matmul`` as a TPU runs a float32 matmul at its
    default precision: one pass over bfloat16-rounded operands."""

    def __getattr__(self, name):
        return getattr(jax.numpy, name)

    @staticmethod
    def matmul(a, b, precision=None):
        jnp = jax.numpy
        return jnp.matmul(jnp.asarray(a).astype(jnp.bfloat16),
                          jnp.asarray(b).astype(jnp.bfloat16),
                          preferred_element_type=jnp.float32)


def test_serving_gate_fails_on_reduced_precision_warp(smoke, monkeypatch):
    from repro.core import sparw

    monkeypatch.setattr(sparw, "jnp", _OneBf16PassMatmul())
    r = _tiny_serve(smoke)
    # both serves share the faulty warp, so parity still passes ...
    assert r["min_psnr_vs_reference_db"] >= smoke.PSNR_GATE_DB
    # ... and the geometry gate is what fails
    assert r["hole_fraction_mean"] > TINY_HOLE_FRACTION_MAX
    assert not smoke.serving_ok(r, (3, 4, 32, 32, 3), TINY_HOLE_FRACTION_MAX)


def _run_script(path: Path, tmp_path: Path, *args) -> subprocess.CompletedProcess:
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    return subprocess.run([sys.executable, str(path), *args],
                          capture_output=True, text=True, env=env,
                          cwd=str(path.parent), timeout=300)


def test_script_refuses_without_tpu(tmp_path):
    r = _run_script(ROOT / "chip_smoke.py", tmp_path)
    assert r.returncode != 0
    assert "no TPU" in r.stderr
    assert '"ok"' not in r.stdout


def test_script_refuses_outside_the_repo(tmp_path):
    alone = tmp_path / "alone"
    alone.mkdir()
    shutil.copy(ROOT / "chip_smoke.py", alone)
    r = _run_script(alone / "chip_smoke.py", tmp_path)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout


def test_sharded_phase_on_forced_host_devices(smoke, tmp_path):
    code = (
        "import json, sys; sys.path[:0] = [sys.argv[1], sys.argv[1] + '/src']\n"
        "import chip_smoke as s\n"
        "r = s.sharded_vs_unsharded(16, 2, 4, 4, 4, 4, 64, grid_res=16,\n"
        "                           channels=12, num_samples=8)\n"
        "print(json.dumps(r))\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    r = subprocess.run([sys.executable, "-c", code, str(ROOT)],
                       capture_output=True, text=True, env=env,
                       cwd=str(tmp_path), timeout=600)
    assert r.returncode == 0, r.stderr[-2000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert smoke.sharded_ok(out, 4), out


def test_compilation_cache_dir(monkeypatch, tmp_path):
    from repro.utils import enable_compilation_cache

    prev = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "env"))
        assert enable_compilation_cache(tmp_path) == str(tmp_path / "env")
        assert jax.config.jax_compilation_cache_dir == prev
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        got = enable_compilation_cache(tmp_path)
        assert got == str(tmp_path.resolve() / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == got
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)
