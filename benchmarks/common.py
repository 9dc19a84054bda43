"""Shared benchmark plumbing: timing + CSV emission, compile cache."""

from __future__ import annotations

import os
import pathlib
import time
from typing import Callable, Dict, Iterable, List, Tuple

#: the checkout root (this file lives in ``<root>/benchmarks``)
ROOT = pathlib.Path(__file__).resolve().parent.parent


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing else is set.  Otherwise the cache goes to the fixed
    ``<checkout>/.jax_cache/`` (git-ignored): the directory is part of
    what a later process must find again, so it never carries a
    temporary name, a pid or a time.  Call it from an entry point's
    ``main()`` before the first compile, never at import."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    path = str(ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


class Bench:
    """Collects (name, us_per_call, derived) rows for benchmarks.run."""

    def __init__(self):
        self.rows: List[Tuple[str, float, str]] = []

    def timeit(self, name: str, fn: Callable[[], Dict], derived_keys=()):
        t0 = time.perf_counter()
        out = fn() or {}
        us = (time.perf_counter() - t0) * 1e6
        derived = ";".join(f"{k}={out[k]:.4g}" if isinstance(out[k], float)
                           else f"{k}={out[k]}"
                           for k in derived_keys if k in out)
        self.rows.append((name, us, derived))
        return out

    def add(self, name: str, us: float, derived: str = ""):
        self.rows.append((name, us, derived))

    def emit(self) -> None:
        for name, us, derived in self.rows:
            print(f"{name},{us:.1f},{derived}")
