"""Public wrapper for the delta_encode kernel: shaping and dispatch."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.common import bitcast_to_int, use_interpret
from repro.kernels.delta_encode.delta_encode import delta_encode_rows
from repro.utils import ceil_div


def _rows_view(x: jax.Array) -> jax.Array:
    """(rows, cols) bitwise int view that keeps the minor dim (a free reshape)."""
    x = bitcast_to_int(x)
    return x.reshape(-1, x.shape[-1]) if x.ndim >= 2 else x.reshape(-1, 1)


def changed_blocks(old: jax.Array, new: jax.Array, rows: int, *, interpret: bool | None = None) -> jax.Array:
    """bool[nblocks] — chunk grid matches repro.checkpoint._chunk_rows."""
    if tuple(old.shape) != tuple(new.shape):
        raise ValueError(f"shape mismatch {old.shape} vs {new.shape}")
    if np.dtype(old.dtype) != np.dtype(new.dtype):
        raise ValueError(f"dtype mismatch {old.dtype} vs {new.dtype}")
    if interpret is None:
        interpret = use_interpret()
    n0 = old.shape[0] if old.ndim else 1
    if old.size == 0:
        return jnp.zeros(max(1, ceil_div(n0, rows)), bool)
    ov, nv = _rows_view(jnp.asarray(old)), _rows_view(jnp.asarray(new))
    per_row = delta_encode_rows(ov, nv, interpret=interpret)[:, 0]
    # view rows per axis-0 chunk: the chunk grid cuts axis 0 every ``rows``
    group = rows * (ov.shape[0] // n0)
    nblocks = max(1, ceil_div(n0, rows))
    per_row = jnp.pad(per_row, (0, nblocks * group - per_row.shape[0]))
    return jnp.any(per_row.reshape(nblocks, group) != 0, axis=1)
