"""In-mesh pipeline parallelism — the Mobile Pipeline (paper ref [7]) on a
device axis.

The NavP view: a microbatch is a traveler whose itinerary visits every
pipeline stage; `jax.lax.ppermute` is the hop. GPipe schedule inside one
``shard_map``: each device along the ``stage`` axis holds one stage's
parameters (stacked params sharded on their leading dim); at tick *t* device
*s* processes microbatch *t − s* and permutes its activation to *s + 1*.
Bubble fraction = (S−1)/(M+S−1), the usual GPipe cost.

This is the layer-level counterpart of ``repro.core.itinerary.MobilePipeline``
(which schedules whole jobs across nodes); see tests/test_pipeline.py for the
equivalence proof against a sequential stack.
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def pipeline_forward(
    stage_fn: Callable[[Any, jax.Array], jax.Array],
    stacked_params: Any,  # leaves with leading dim S = n_stages
    x: jax.Array,  # (M, mb, ...) microbatched input
    mesh: Mesh,
    axis: str = "model",
) -> jax.Array:
    """Run x through S chained stages pipelined over mesh axis ``axis``.

    ``stage_fn(params_for_one_stage, activation) -> activation`` must be
    shape-preserving (residual-block style, like the transformer stacks).
    Returns (M, mb, ...) outputs after all S stages.
    """
    n_stages = dict(mesh.shape)[axis]
    m = x.shape[0]
    first = jax.tree_util.tree_leaves(stacked_params)[0]
    if first.shape[0] != n_stages:
        raise ValueError(f"stacked params leading dim {first.shape[0]} != stages {n_stages}")

    p_specs = jax.tree_util.tree_map(
        lambda l: P(axis, *([None] * (l.ndim - 1))), stacked_params
    )

    def body(params_local, x_all):
        # params_local: leaves (1, ...) — this device's stage
        # x_all: (M, mb, ...) replicated input queue
        sidx = jax.lax.axis_index(axis)
        pl = jax.tree_util.tree_map(lambda l: l[0], params_local)
        mb_shape = x_all.shape[1:]
        buf = jnp.zeros(mb_shape, x_all.dtype)  # activation in flight here
        out = jnp.zeros_like(x_all)

        def tick(carry, t):
            buf, out = carry
            # stage 0 ingests microbatch t (if any); others use what arrived
            take = jnp.clip(t, 0, m - 1)
            inject = jax.lax.dynamic_index_in_dim(x_all, take, 0, keepdims=False)
            cur = jnp.where(sidx == 0, jnp.where(t < m, inject, buf), buf)
            y = stage_fn(pl, cur)
            # last stage emits microbatch t - (S-1)
            emit_idx = jnp.clip(t - (n_stages - 1), 0, m - 1)
            emit = jnp.logical_and(sidx == n_stages - 1, t - (n_stages - 1) >= 0)
            out = jax.lax.cond(
                emit,
                lambda o: jax.lax.dynamic_update_index_in_dim(o, y, emit_idx, 0),
                lambda o: o,
                out,
            )
            # hop to the next stage (ring; stage S-1 -> 0 carries garbage)
            nxt = jax.lax.ppermute(
                y, axis, perm=[(i, (i + 1) % n_stages) for i in range(n_stages)]
            )
            return (nxt, out), None

        (buf, out), _ = jax.lax.scan(tick, (buf, out), jnp.arange(m + n_stages - 1))
        # only the last stage's `out` is non-zero; psum broadcasts it
        return jax.lax.psum(out, axis)

    fn = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(p_specs, P()),
        out_specs=P(),
        check_vma=False,
    )
    return fn(stacked_params, x)


def stage_shardings(stacked_params: Any, mesh: Mesh, axis: str = "model") -> Any:
    return jax.tree_util.tree_map(
        lambda l: NamedSharding(mesh, P(axis, *([None] * (l.ndim - 1)))), stacked_params
    )
