"""Small shared utilities (no jax imports at module scope beyond jax itself)."""
from __future__ import annotations

import functools
import math
import os
import time
from pathlib import Path
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np


# the checkout that holds src/repro: per-checkout caches live under it
CHECKOUT_ROOT = Path(__file__).resolve().parents[2]


def use_compile_cache() -> str:
    """Give JAX's persistent compilation cache a fixed directory and return
    it. Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing is set here; otherwise the cache goes to ``.jax_cache`` at the
    checkout root (gitignored) — a fixed path, since the directory is part
    of what a later run must find again. Every entry point calls this
    before its first compile."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(CHECKOUT_ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def round_up(x: int, m: int) -> int:
    """Smallest multiple of m that is >= x."""
    return ((x + m - 1) // m) * m


def cdiv(a: int, b: int) -> int:
    return (a + b - 1) // b


def tree_bytes(tree: Any) -> int:
    """Total bytes of a pytree of arrays / ShapeDtypeStructs."""
    leaves = jax.tree_util.tree_leaves(tree)
    return sum(int(np.prod(l.shape)) * jnp.dtype(l.dtype).itemsize for l in leaves)


def tree_params(tree: Any) -> int:
    """Total element count of a pytree."""
    leaves = jax.tree_util.tree_leaves(tree)
    return sum(int(np.prod(l.shape)) for l in leaves)


def fmt_bytes(n: float) -> str:
    for unit in ("B", "KiB", "MiB", "GiB", "TiB", "PiB"):
        if abs(n) < 1024.0:
            return f"{n:.2f}{unit}"
        n /= 1024.0
    return f"{n:.2f}EiB"


def fmt_count(n: float) -> str:
    for unit in ("", "K", "M", "B", "T"):
        if abs(n) < 1000.0:
            return f"{n:.2f}{unit}"
        n /= 1000.0
    return f"{n:.2f}Q"


class Timer:
    """Context-manager wall timer: with Timer() as t: ...; t.s"""

    def __enter__(self):
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.s = time.perf_counter() - self.t0
        return False


def timeit_median(fn, *args, warmup: int = 1, iters: int = 3) -> float:
    """Median wall time of fn(*args) with block_until_ready on the output."""
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts))


def cast_tree(tree: Any, dtype) -> Any:
    """Cast all floating leaves of a pytree to dtype."""
    def _cast(x):
        if jnp.issubdtype(x.dtype, jnp.floating):
            return x.astype(dtype)
        return x
    return jax.tree_util.tree_map(_cast, tree)
