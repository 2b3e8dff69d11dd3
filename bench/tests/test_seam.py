"""A configuration joins the benchmark as new files only: a configuration
file that names its architecture, the cell's limits and mix, and entries
in ``BENCHMARK.json``; no file the harness already has is edited. And the
architecture modules' reference side (weights and field) imports nothing
of the program."""
from __future__ import annotations

import copy
import json
import os
import shutil
import subprocess
import sys

import pytest

from bench_cells import BENCH, BENCHMARK, CELL, CONFIG_FILES, ROOT, shrink

NEW = "seam-dvgo"


def _checkout(tmp_path, cfg: dict) -> dict:
    """New files only, under ``tmp_path``: the configuration ``cfg`` as
    ``NEW``, the limits and mix of its preview cell, and ``BENCHMARK.json``
    with its entries added. Returns that benchmark."""
    data = tmp_path / "bench"
    for d in ("configs", "limits", "traffic"):
        (data / d).mkdir(parents=True)
    (data / "configs" / f"{NEW}.json").write_text(
        json.dumps(dict(cfg, name=NEW)))
    shutil.copy(BENCH / "limits" / f"{CELL}.json",
                data / "limits" / f"{NEW}.preview.json")
    shutil.copy(BENCH / "traffic" / "preview.json",
                data / "traffic" / "preview.json")
    benchmark = copy.deepcopy(BENCHMARK)
    benchmark["configs"].append(
        {"name": NEW, "source": "a copy of cicero-dvgo-baked",
         "file": f"bench/configs/{NEW}.json", "reduced": [],
         "why": "joins through new files only"})
    benchmark["workloads"].append(
        {"name": f"{NEW}.preview", "config": NEW, "traffic": "preview",
         "chips": 1, "why": "joins through new files only"})
    return benchmark


def _config() -> dict:
    return json.loads((BENCH / "configs" / "cicero-dvgo-baked.json")
                      .read_text())


def test_a_configuration_joins_through_new_files_only(tmp_path):
    import run_cell

    benchmark = _checkout(tmp_path, _config())
    cell = run_cell.load_cell(f"{NEW}.preview", benchmark, root=tmp_path)
    assert cell["config"]["name"] == NEW
    r = run_cell.run(shrink(cell), 2**31 + 17, 2.0, trace=False,
                     require_chip=False)
    assert r["correct"] is True, r["checks"]
    assert r["attempted"] > 0 and r["failed"] == 0


@pytest.mark.parametrize("arch,error", [(None, ValueError),
                                        ("no_such_arch", FileNotFoundError),
                                        ("../weights", ValueError)])
def test_a_configuration_without_its_module_is_refused(tmp_path, arch,
                                                       error):
    import run_cell

    cfg = _config()
    del cfg["arch"]
    if arch is not None:
        cfg["arch"] = arch
    benchmark = _checkout(tmp_path, cfg)
    with pytest.raises(error):
        run_cell.load_cell(f"{NEW}.preview", benchmark, root=tmp_path)


# Run in a fresh process in which any import of the program fails: load
# each architecture module as the harness does, make its weights at its
# TINY size and evaluate its field at both precisions.
CHILD = r"""
import importlib.abc, json, sys


class Block(importlib.abc.MetaPathFinder):
    def find_spec(self, name, path, target=None):
        if name == "repro" or name.startswith("repro."):
            raise ImportError(f"{name} is the program under test")


sys.meta_path.insert(0, Block())
sys.path.insert(0, sys.argv[1])
import jax.numpy as jnp
import numpy as np

import run_cell

try:
    import repro  # noqa: F401
    sys.exit("the program was importable")
except ImportError:
    pass
for cfg in json.loads(sys.argv[2]):
    arch = run_cell.load_arch(cfg)
    cfg.update(arch.TINY)
    weights = arch.make_weights(cfg, 2**33 + 1)
    pts = np.random.default_rng(0).uniform(-1, 1, (64, 3)).astype("float32")
    dirs = pts / np.linalg.norm(pts, axis=-1, keepdims=True)
    for precision in ("highest", "high"):
        sigma, rgb = arch.field(weights, jnp.asarray(pts), jnp.asarray(dirs),
                                arch.reference_key(cfg), precision)
        assert sigma.shape == (64,) and rgb.shape == (64, 3), cfg["name"]
        assert bool(jnp.isfinite(sigma).all() & jnp.isfinite(rgb).all())
        assert float(sigma.max()) > 0, cfg["name"]
    print(cfg["arch"], cfg["name"])
"""


def test_the_reference_side_imports_nothing_of_the_program():
    files = set(CONFIG_FILES.values()) | {
        str(p.relative_to(ROOT)) for p in (BENCH / "configs").glob("*.json")}
    configs = [json.loads((ROOT / f).read_text()) for f in sorted(files)]
    archs = {p.stem for p in (BENCH / "arch").glob("*.py")}
    assert archs and archs <= {c["arch"] for c in configs}, \
        "an architecture module that no configuration uses"
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "-c", CHILD, str(BENCH),
                        json.dumps(configs)], env=env, capture_output=True,
                       text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-4000:]
    served = {tuple(line.split()) for line in p.stdout.splitlines()}
    assert served == {(c["arch"], c["name"]) for c in configs}
