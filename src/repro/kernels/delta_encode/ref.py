"""Pure-jnp oracle for delta_encode: per-chunk changed bitmap."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.common import bitcast_to_int
from repro.utils import ceil_div


def to_blocks(x: jax.Array, rows: int) -> jax.Array:
    """Reshape to (nblocks, block_elems): axis-0 row blocks of ``rows`` rows.

    Matches the serializer chunk grid (`_chunk_rows`): block i covers rows
    [i*rows, (i+1)*rows). Trailing partial blocks are zero-padded — both
    operands get identical padding so it never flags a change.
    """
    x = bitcast_to_int(x)
    if x.ndim == 0:
        x = x[None]
    x2 = x.reshape(x.shape[0], -1) if x.ndim > 1 else x[:, None]
    n0 = x2.shape[0]
    nb = max(1, ceil_div(n0, rows))
    pad = nb * rows - n0
    x2 = jnp.pad(x2, ((0, pad), (0, 0)))
    return x2.reshape(nb, rows * x2.shape[1])


def changed_blocks_ref(old: jax.Array, new: jax.Array, rows: int) -> jax.Array:
    """bool[nblocks]: does chunk i differ bitwise between old and new?"""
    if tuple(old.shape) != tuple(new.shape):
        raise ValueError(f"shape mismatch {old.shape} vs {new.shape}")
    if np.dtype(old.dtype) != np.dtype(new.dtype):
        raise ValueError(f"dtype mismatch {old.dtype} vs {new.dtype}")
    ob = to_blocks(old, rows)
    nb = to_blocks(new, rows)
    return jnp.any(ob != nb, axis=1)
