"""Incremental (delta) CMIs — paper §Q3.

"Another solution is to save the CMIs incrementally by saving only deltas of
each consecutive checkpoint."

Two cooperating pieces:

* :class:`DeltaTracker` — decides, per job, which published CMI the next one
  should delta against. Chains are capped (``full_every``) so restores never
  replay long chains and GC can reclaim ancestors.
* :func:`device_changed_hints` — runs the `kernels/delta_encode` Pallas
  kernel over (previous, current) device trees to produce per-chunk "changed"
  bitmaps *on device*, so unchanged blocks are never copied to host at all
  (beyond the paper: their delta proposal still hashed on the host).

The chunk grid here must match the serializer's (axis-0 row blocks of
``chunk_bytes``) — both call :func:`repro.checkpoint.serializer._chunk_rows`.
The grid is independent of ``SaveOptions.writers``: striping only decides
which ``data-*.bin`` a written chunk lands in, and the serializer's
round-robin placement is deterministic in enumeration order, so hint bitmap
indices stay aligned with the chunk table no matter how many writers ran.
A delta chunk may therefore reference a parent chunk living in any of the
parent's shard files (``ChunkEntry.file`` + ``ref`` resolve it).

The same grid also keys the *streaming* delta path: a repeated
``dhp.hop(..., changed_hint=device_changed_hints(prev, cur))`` to a
process-backed node sends only the chunks whose bitmap bit (or content
hash) changed since the destination's cached baseline — the shared chunk
engine (``serializer.iter_state_chunks``) walks the identical enumeration
order whether the consumer is a data file or a socket, so one bitmap serves
disk deltas and wire deltas alike.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any

import jax
import numpy as np

from repro.checkpoint.serializer import _chunk_rows, _norm_index
from repro.utils import flatten_with_paths, logger


@dataclass
class DeltaPolicy:
    enabled: bool = True
    full_every: int = 8  # emit a full (chain-resetting) CMI every N publishes
    keep_last: int = 2  # CMIs retained by job-store GC (plus chain ancestors)


class DeltaTracker:
    def __init__(self, policy: DeltaPolicy):
        self.policy = policy
        self._last: dict[str, str] = {}  # job_id -> last published CMI name
        self._chain_len: dict[str, int] = {}

    def parent_for(self, job_id: str, jobstore) -> str | None:
        if not self.policy.enabled:
            return None
        last = self._last.get(job_id)
        if last is None:
            return None
        if self._chain_len.get(job_id, 0) >= self.policy.full_every - 1:
            logger.debug("delta chain for job %s reset (full_every)", job_id)
            return None
        # parent must still exist (GC keeps chain ancestors of kept CMIs,
        # but a restart may reference a since-GC'd name)
        from repro.checkpoint.atomic import is_committed

        if not is_committed(jobstore.cmi_root(job_id) / last):
            return None
        return last

    def record_published(self, job_id: str, name: str) -> None:
        prev = self._last.get(job_id)
        self._last[job_id] = name
        self._chain_len[job_id] = 0 if prev is None else (
            0 if self._chain_len.get(job_id, 0) >= self.policy.full_every - 1
            else self._chain_len.get(job_id, 0) + 1
        )


# ---------------------------------------------------------------------------
# on-device change detection
# ---------------------------------------------------------------------------


def _changed_blocks_fn():
    """Pallas kernel on TPU; the (mathematically identical) jnp oracle
    elsewhere — interpret-mode Pallas over GB-scale states would put a
    python-loop on the publish path. Kernel↔oracle equality is enforced by
    tests/test_kernels.py."""
    from repro.kernels.common import use_interpret
    from repro.kernels.delta_encode.ops import changed_blocks
    from repro.kernels.delta_encode.ref import changed_blocks_ref

    if use_interpret():
        return changed_blocks_ref
    return changed_blocks


def device_changed_hints(
    prev_tree: Any, new_tree: Any, *, chunk_bytes: int = 16 << 20
) -> dict[str, np.ndarray]:
    """Per-array per-chunk "changed" bitmaps computed on device.

    Works shard-by-shard so only shard-local comparisons run (no gather);
    shard bitmaps concatenate in the serializer's sorted-shard order. Arrays
    whose shapes/shardings differ between trees are marked fully changed.
    """
    changed_fn = _changed_blocks_fn()
    prev_flat, _ = flatten_with_paths(prev_tree)
    new_flat, _ = flatten_with_paths(new_tree)
    hints: dict[str, np.ndarray] = {}
    for path, new_leaf in new_flat.items():
        if not isinstance(new_leaf, (jax.Array, np.ndarray)):
            continue
        prev_leaf = prev_flat.get(path)
        if (
            prev_leaf is None
            or tuple(prev_leaf.shape) != tuple(new_leaf.shape)
            or np.dtype(prev_leaf.dtype) != np.dtype(new_leaf.dtype)
        ):
            continue  # no hint -> serializer hashes (and likely rewrites)
        itemsize = np.dtype(new_leaf.dtype).itemsize
        if isinstance(new_leaf, jax.Array) and isinstance(prev_leaf, jax.Array):
            shape = tuple(new_leaf.shape)
            new_shards = {_norm_index(s.index, shape): s.data for s in new_leaf.addressable_shards}
            prev_shards = {_norm_index(s.index, shape): s.data for s in prev_leaf.addressable_shards}
            if set(new_shards) != set(prev_shards):
                continue
            bits = []
            for key in sorted(new_shards):
                rows = _chunk_rows(tuple(new_shards[key].shape), itemsize, chunk_bytes)
                bits.append(np.asarray(changed_fn(prev_shards[key], new_shards[key], rows)))
            hints[path] = np.concatenate(bits) if bits else np.zeros(0, bool)
        else:
            rows = _chunk_rows(tuple(new_leaf.shape), itemsize, chunk_bytes)
            hints[path] = np.asarray(changed_fn(prev_leaf, new_leaf, rows))
    return hints
