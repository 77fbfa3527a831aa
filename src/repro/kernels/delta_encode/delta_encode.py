"""Pallas kernel: per-chunk changed-bitmap for incremental CMIs (paper §Q3).

Workload: two equal-shaped arrays (previous and current value of one shard),
logically split into the serializer's axis-0 chunk grid. Output: one flag per
chunk — "did any byte change?". This is purely memory-bound (2 reads, ~0
writes), so the kernel's job is a single fused pass at HBM bandwidth; doing
it with a host hash costs a device→host copy of *everything* first, which is
exactly the overhead the paper measured as dominating (§4: "the cost of disk
I/O and network transfer of CMIs overshadows the cost of numerical
computation").

Tiling: ops.py hands over a (rows, cols) signed-int view that keeps the leaf's
minor dim (merging only major dims, so the view is free in the TPU's tiled
layout — flattening each chunk into one long row instead forces a relayout
that took the compiler over a minute at vocab×d_model). Each program reads a
(tile_r, cols) block of both operands and writes one "any difference" flag
per row; ops.py folds row flags into chunk flags. The last row block may be
ragged: its out-of-range rows produce flags that ops.py slices away.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BLOCK_BYTES = 1 << 20  # per operand block; 2 operands x 2 buffers = 4 MiB VMEM
ROW_ALIGN = 32  # sublane multiple for every packed width (8-bit: 32, 16-bit: 16, 32-bit: 8)


def _kernel(old_ref, new_ref, out_ref):
    # widen packed (8/16-bit) words and xor in 32 bits: Mosaic has no narrow
    # compare, and ``old != new`` on widened words folds back into one
    diff = (old_ref[...].astype(jnp.int32) ^ new_ref[...].astype(jnp.int32)) != 0
    out_ref[...] = jnp.max(diff.astype(jnp.int32), axis=1, keepdims=True)


def _row_tile(n_rows: int, n_cols: int, itemsize: int) -> int:
    """Rows per program: ~BLOCK_BYTES per operand, aligned, at most n_rows."""
    tile = max(ROW_ALIGN, BLOCK_BYTES // max(1, n_cols * itemsize) // ROW_ALIGN * ROW_ALIGN)
    return n_rows if n_rows <= tile else tile


@functools.partial(jax.jit, static_argnames=("interpret",))
def delta_encode_rows(old: jax.Array, new: jax.Array, *, interpret: bool = True):
    """(rows, cols) signed-int pair -> int32[rows, 1] per-row changed flags."""
    n_rows, n_cols = old.shape
    tile_r = _row_tile(n_rows, n_cols, old.dtype.itemsize)
    return pl.pallas_call(
        _kernel,
        grid=(pl.cdiv(n_rows, tile_r),),
        in_specs=[
            pl.BlockSpec((tile_r, n_cols), lambda i: (i, 0)),
            pl.BlockSpec((tile_r, n_cols), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((tile_r, 1), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n_rows, 1), jnp.int32),
        interpret=interpret,
    )(old, new)
