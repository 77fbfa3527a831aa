"""Shared small utilities: pytree path flattening, sizes, hashing, logging."""

from __future__ import annotations

import hashlib
import logging
import math
import os
import time
from pathlib import Path
from typing import Any, Iterable

import jax
import numpy as np

logger = logging.getLogger("repro")
if not logger.handlers:  # configure once; launchers may reconfigure
    _h = logging.StreamHandler()
    _h.setFormatter(logging.Formatter("%(asctime)s %(name)s %(levelname)s %(message)s"))
    logger.addHandler(_h)
    logger.setLevel(os.environ.get("REPRO_LOGLEVEL", "INFO"))


# the checkout root (src/repro/utils.py -> ../..)
REPO_ROOT = Path(__file__).resolve().parents[2]


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    ``$JAX_COMPILATION_CACHE_DIR``, when set, is read by JAX itself and left
    alone. Otherwise the cache lives at the fixed ``<checkout>/.jax_cache``,
    never a temp dir or a name with a pid or a time in it, so the next run
    finds what this one wrote. Call before the first compile.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(REPO_ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


# ---------------------------------------------------------------------------
# pytree <-> flat dict keyed by "/"-joined path strings
# ---------------------------------------------------------------------------

def _key_str(k: Any) -> str:
    if isinstance(k, jax.tree_util.DictKey):
        return str(k.key)
    if isinstance(k, jax.tree_util.SequenceKey):
        return str(k.idx)
    if isinstance(k, jax.tree_util.GetAttrKey):
        return str(k.name)
    if isinstance(k, jax.tree_util.FlattenedIndexKey):
        return str(k.key)
    return str(k)


def flatten_with_paths(tree: Any, is_leaf=None) -> tuple[dict[str, Any], Any]:
    """Flatten ``tree`` to ``{path: leaf}`` plus the treedef for unflattening."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(tree, is_leaf=is_leaf)
    flat = {}
    for path, leaf in leaves:
        key = "/".join(_key_str(k) for k in path) or "."
        if key in flat:
            raise ValueError(f"duplicate flattened key {key!r}")
        flat[key] = leaf
    return flat, treedef


def unflatten_from_paths(treedef: Any, flat: dict[str, Any]) -> Any:
    """Inverse of :func:`flatten_with_paths` (keys must match treedef order)."""
    # tree_flatten_with_path ordering is deterministic; rebuild in that order.
    dummy = jax.tree_util.tree_unflatten(treedef, list(range(treedef.num_leaves)))
    leaves, _ = jax.tree_util.tree_flatten_with_path(dummy)
    ordered = []
    for path, idx in leaves:
        key = "/".join(_key_str(k) for k in path) or "."
        if key not in flat:
            raise KeyError(f"missing leaf {key!r} during unflatten")
        ordered.append(flat[key])
    return jax.tree_util.tree_unflatten(treedef, ordered)


# ---------------------------------------------------------------------------
# sizes / formatting
# ---------------------------------------------------------------------------

def nbytes_of(x: Any) -> int:
    return int(np.prod(x.shape)) * np.dtype(x.dtype).itemsize if hasattr(x, "shape") else 0


def tree_nbytes(tree: Any) -> int:
    return sum(nbytes_of(l) for l in jax.tree_util.tree_leaves(tree))


def human_bytes(n: float) -> str:
    for unit in ("B", "KiB", "MiB", "GiB", "TiB"):
        if abs(n) < 1024.0 or unit == "TiB":
            return f"{n:.2f}{unit}"
        n /= 1024.0
    return f"{n:.2f}TiB"


def ceil_div(a: int, b: int) -> int:
    return -(-a // b)


def round_up(a: int, b: int) -> int:
    return ceil_div(a, b) * b


# ---------------------------------------------------------------------------
# hashing (content ids for delta checkpoints)
# ---------------------------------------------------------------------------

def content_hash(buf: bytes | memoryview) -> str:
    return hashlib.blake2b(buf, digest_size=16).hexdigest()


def crc32_of(buf: bytes | memoryview) -> int:
    import zlib

    return zlib.crc32(buf) & 0xFFFFFFFF


class StepTimer:
    """Wall-clock timer with named laps (used by benchmarks)."""

    def __init__(self) -> None:
        self.t0 = time.perf_counter()
        self.laps: list[tuple[str, float]] = []

    def lap(self, name: str) -> float:
        t = time.perf_counter()
        dt = t - self.t0
        self.laps.append((name, dt))
        self.t0 = t
        return dt


def prod(xs: Iterable[int]) -> int:
    return math.prod(xs)
