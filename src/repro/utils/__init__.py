"""Small shared utilities: tree math, metrics, deterministic RNG streams."""
from __future__ import annotations

import functools
import os
from pathlib import Path
from typing import Any, Callable, Iterable

import jax
import jax.numpy as jnp
import numpy as np

PyTree = Any


def tree_bytes(tree: PyTree) -> int:
    """Total bytes of all array leaves (works on ShapeDtypeStruct too)."""
    leaves = jax.tree_util.tree_leaves(tree)
    return int(sum(np.prod(l.shape) * np.dtype(l.dtype).itemsize for l in leaves))


def tree_count(tree: PyTree) -> int:
    """Total number of scalar elements across all leaves."""
    leaves = jax.tree_util.tree_leaves(tree)
    return int(sum(np.prod(l.shape) for l in leaves))


def tree_finite(tree: PyTree) -> bool:
    """True iff every leaf is fully finite (no NaN/Inf)."""
    leaves = jax.tree_util.tree_leaves(tree)
    return all(bool(jnp.all(jnp.isfinite(l.astype(jnp.float32)))) for l in leaves)


def psnr(img: jnp.ndarray, ref: jnp.ndarray, data_range: float = 1.0) -> jnp.ndarray:
    """Peak signal-to-noise ratio in dB (paper's quality metric)."""
    mse = jnp.mean((img.astype(jnp.float32) - ref.astype(jnp.float32)) ** 2)
    mse = jnp.maximum(mse, 1e-12)
    return 10.0 * jnp.log10(data_range**2 / mse)


def fold_rng(key: jax.Array, *names: str) -> jax.Array:
    """Deterministically derive a sub-key from string names (stable across
    processes — str hash() is randomized by PYTHONHASHSEED)."""
    import zlib

    for name in names:
        key = jax.random.fold_in(key, zlib.crc32(name.encode("utf-8")))
    return key


def named_keys(key: jax.Array, names: Iterable[str]) -> dict[str, jax.Array]:
    return {n: fold_rng(key, n) for n in names}


def round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def cdiv(a: int, b: int) -> int:
    return (a + b - 1) // b


def human_bytes(n: float) -> str:
    for unit in ("B", "KiB", "MiB", "GiB", "TiB"):
        if abs(n) < 1024.0:
            return f"{n:.2f} {unit}"
        n /= 1024.0
    return f"{n:.2f} PiB"


def human_count(n: float) -> str:
    for unit in ("", "K", "M", "B", "T"):
        if abs(n) < 1000.0:
            return f"{n:.2f}{unit}"
        n /= 1000.0
    return f"{n:.2f}Q"


def chunked(seq, size):
    for i in range(0, len(seq), size):
        yield seq[i : i + size]


def enable_compilation_cache(checkout: Path) -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is honored as JAX reads it and
    nothing is set in code. Otherwise the cache lives at the fixed
    ``<checkout>/.jax_cache`` (gitignored): the directory is part of the
    cache key, so it must not move between runs. Call it from a program's
    ``main()``, never at import.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(Path(checkout).resolve() / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def jit_with_name(fn: Callable, name: str, **jit_kwargs) -> Callable:
    wrapped = functools.wraps(fn)(jax.jit(fn, **jit_kwargs))
    wrapped.__name__ = name
    return wrapped
