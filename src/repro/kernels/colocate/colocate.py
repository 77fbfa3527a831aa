"""Pallas kernel: blocked angular nearest-neighbor match (VIIRS -> CrIS).

The paper's application hot-spot ("match VIIRS to CrIS", Fig. 7 line 13):
for each of N VIIRS view vectors find the CrIS line-of-sight with maximal
cosine. N ~ millions, M ~ thousands; the naive N×M score matrix is hundreds
of GiB, so it must be blocked. On TPU the dot is MXU work (K padded 3→8) and
the running (best, argbest) merge is VPU work over VMEM-resident
accumulators.

Grid: (N/TILE_N, M/TILE_M), M minor. The two output blocks — best cosine and
best index, both (TILE_N, 1) — are revisited across the M sweep (index map
ignores j), so the merge state never leaves VMEM. The M padding columns are
masked with -inf via an iota test against the true M (static).

VMEM per program ≈ TILE_N·K + K·TILE_M + TILE_N·TILE_M floats ≈ 1.1 MiB.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

TILE_N = 512
TILE_M = 512
K_PAD = 8  # 3 coords zero-padded; zeros contribute nothing to the dot

NEG_INF = float("-inf")


def _kernel(m_true: int, u_ref, los_ref, idx_ref, cos_ref):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        cos_ref[...] = jnp.full_like(cos_ref, NEG_INF)
        idx_ref[...] = jnp.zeros_like(idx_ref)

    # scores: (TILE_N, TILE_M) = (TILE_N, K) @ (K, TILE_M)
    scores = jax.lax.dot_general(
        u_ref[...],
        los_ref[...],
        dimension_numbers=(((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,  # full f32: argmax of near-ties
        preferred_element_type=jnp.float32,
    )
    col = j * TILE_M + jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
    scores = jnp.where(col < m_true, scores, NEG_INF)

    local_best = jnp.max(scores, axis=1, keepdims=True)  # (TILE_N, 1)
    local_arg = jnp.argmax(scores, axis=1).astype(jnp.int32)[:, None] + j * TILE_M

    better = local_best > cos_ref[...]
    cos_ref[...] = jnp.where(better, local_best, cos_ref[...])
    idx_ref[...] = jnp.where(better, local_arg, idx_ref[...])


@functools.partial(jax.jit, static_argnames=("m_true", "interpret"))
def colocate_kernel(u_pad: jax.Array, los_pad: jax.Array, *, m_true: int, interpret: bool = True):
    """u_pad (N_pad, K_PAD) f32, los_pad (M_pad, K_PAD) f32 -> (idx, cos)."""
    n_pad, _ = u_pad.shape
    m_pad, _ = los_pad.shape
    assert n_pad % TILE_N == 0 and m_pad % TILE_M == 0
    grid = (n_pad // TILE_N, m_pad // TILE_M)
    return pl.pallas_call(
        functools.partial(_kernel, m_true),
        grid=grid,
        in_specs=[
            pl.BlockSpec((TILE_N, K_PAD), lambda i, j: (i, 0)),
            pl.BlockSpec((TILE_M, K_PAD), lambda i, j: (j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((TILE_N, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((TILE_N, 1), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n_pad, 1), jnp.int32),
            jax.ShapeDtypeStruct((n_pad, 1), jnp.float32),
        ],
        interpret=interpret,
    )(u_pad, los_pad)
