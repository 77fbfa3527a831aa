"""jit'd step builders: init / train / prefill / decode with explicit shardings.

These are the functions the launcher and the multi-pod dry-run lower. Each
builder returns ``(fn, in_shardings, out_shardings)`` so callers can either
``jax.jit(fn, in_shardings=..., out_shardings=...)`` for real execution or
``.lower(...).compile()`` against ShapeDtypeStructs for the dry-run.

Training state layout (a plain dict — CMI-serializable):

    {"params": ..., "opt": {mu, nu, master, count}, "step": i32[],
     "rng": u32[2], "data": {"data_step": i32[], "seed": i32[]}}
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ArchConfig, InputShape
from repro.distributed.sharding import (
    CACHE_RULES,
    DEFAULT_RULES,
    OPT_RULES,
    batch_axes,
    data_pspec,
    tree_shardings,
)
from repro.models.model import Model, input_specs
from repro.optim.adamw import AdamWConfig, adamw_update, init_opt_state, opt_axes
from repro.optim.schedules import warmup_cosine


def _repl(mesh: Mesh):
    return NamedSharding(mesh, P())


@functools.lru_cache(maxsize=None)
def model_axes_for(cfg: ArchConfig) -> Any:
    """Logical-axes tree for ``cfg``'s params, derived without allocation.

    ``Model.init`` builds the axes tree as static python data during tracing,
    so running it under ``eval_shape`` and capturing the side output costs
    nothing device-side.
    """
    box = {}

    def f(k):
        p, a = Model(cfg).init(k)
        box["axes"] = a
        return p

    box["struct"] = jax.eval_shape(f, jax.random.PRNGKey(0))
    return box["axes"], box["struct"]


def cache_axes(cfg: ArchConfig) -> Any:
    """Logical axes for the decode cache tree (mirrors Model.cache_struct)."""
    from repro.models import transformer as tf

    kvax = ("layers", "batch", "seq", "kv_heads", "head_dim")
    if cfg.encdec:
        return {"k": kvax, "v": kvax, "xk": kvax, "xv": kvax}
    out = {}
    for gname, n, mixer, ffn in tf.block_groups(cfg):
        if mixer == "gqa":
            out[gname] = {"k": kvax, "v": kvax}
        elif mixer == "mla":
            out[gname] = {
                "ckv": ("layers", "batch", "seq", None),
                "kr": ("layers", "batch", "seq", None),
            }
        elif mixer == "hybrid":
            out[gname] = {
                "attn": {"k": kvax, "v": kvax},
                "ssd": ("layers", "batch", "heads", None, "head_dim"),
            }
        elif mixer == "mlstm":
            out[gname] = {"mlstm": ("layers", "batch", "heads", "head_dim", None)}
    return out


def batch_shardings(batch_struct: Any, mesh: Mesh) -> Any:
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(
            mesh, data_pspec(mesh, len(s.shape), s.shape[0] if s.shape else None)
        ),
        batch_struct,
    )


def state_shardings(model_axes: Any, state_struct: Any, mesh: Mesh) -> Any:
    """Shardings for the full train-state tree."""
    params_sh = tree_shardings(model_axes, state_struct["params"], mesh, DEFAULT_RULES)
    opt_sh = tree_shardings(opt_axes(model_axes), state_struct["opt"], mesh, OPT_RULES)
    return {
        "params": params_sh,
        "opt": opt_sh,
        "step": _repl(mesh),
        "rng": _repl(mesh),
        "data": {"data_step": _repl(mesh), "seed": _repl(mesh)},
    }


def state_struct_for(cfg: ArchConfig, opt_cfg: AdamWConfig) -> Any:
    """ShapeDtypeStruct tree of the train state (no allocation)."""
    _, params = model_axes_for(cfg)
    opt = jax.eval_shape(lambda p: init_opt_state(p, opt_cfg), params)
    i32 = jax.ShapeDtypeStruct((), jnp.int32)
    return {
        "params": params,
        "opt": opt,
        "step": i32,
        "rng": jax.ShapeDtypeStruct((2,), jnp.uint32),
        "data": {"data_step": i32, "seed": i32},
    }


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------


def make_init_fn(cfg: ArchConfig, mesh: Mesh, opt_cfg: AdamWConfig, seed: int = 0):
    """Returns a jit'd () -> state with sharded outputs."""
    model = Model(cfg)

    def init_fn():
        params, _ = model.init(jax.random.PRNGKey(seed))
        opt = init_opt_state(params, opt_cfg)
        return {
            "params": params,
            "opt": opt,
            "step": jnp.zeros((), jnp.int32),
            "rng": jnp.asarray([0, seed + 1], jnp.uint32),
            "data": {"data_step": jnp.zeros((), jnp.int32), "seed": jnp.asarray(seed, jnp.int32)},
        }

    model_axes, _ = model_axes_for(cfg)
    struct = state_struct_for(cfg, opt_cfg)
    out_sh = state_shardings(model_axes, struct, mesh)
    return jax.jit(init_fn, out_shardings=out_sh), out_sh


# ---------------------------------------------------------------------------
# train step
# ---------------------------------------------------------------------------


def make_train_step(
    cfg: ArchConfig,
    mesh: Mesh,
    opt_cfg: AdamWConfig,
    *,
    peak_lr: float = 3e-4,
    warmup: int = 100,
    total_steps: int = 10_000,
    seq_shard: bool = False,
):
    """Returns (train_step, state_shardings, batch_shardings).

    ``train_step(state, batch) -> (state, metrics)``; donate state.

    Perf knob (EXPERIMENTS.md §Perf):
      seq_shard     — sequence-parallel residual stream: activations between
                      layers carry P(batch, "model", None); GSPMD inserts the
                      Megatron-SP all-gather/reduce-scatter transitions.
    """
    from repro.distributed.ctx import sharding_context

    model = Model(cfg)
    bax = batch_axes(mesh)
    bspec = tuple(bax) if len(bax) > 1 else (bax[0] if bax else None)
    constraints = {}
    if seq_shard:
        constraints["resid"] = NamedSharding(mesh, P(bspec, "model", None))
    def train_step(state, batch):
        with sharding_context(constraints):
            def loss_fn(p):
                return model.loss(p, batch)

            # NOTE gradient compression: params are bf16, so grads and their
            # data-parallel all-reduce are already bf16 on the wire (verified
            # in the dry-run HLO); fp32 precision lives only in the sharded
            # master copy. No extra cast needed — see EXPERIMENTS.md §Perf.
            loss, grads = jax.value_and_grad(loss_fn)(state["params"])
        lr = warmup_cosine(state["step"], peak_lr=peak_lr, warmup=warmup, total=total_steps)
        new_params, new_opt, om = adamw_update(grads, state["opt"], state["params"], lr, opt_cfg)
        new_state = {
            "params": new_params,
            "opt": new_opt,
            "step": state["step"] + 1,
            "rng": state["rng"],
            "data": {
                "data_step": state["data"]["data_step"] + 1,
                "seed": state["data"]["seed"],
            },
        }
        metrics = {"loss": loss, "lr": lr, **om}
        return new_state, metrics

    model_axes, _ = model_axes_for(cfg)
    struct = state_struct_for(cfg, opt_cfg)
    st_sh = state_shardings(model_axes, struct, mesh)
    metrics_sh = {"loss": _repl(mesh), "lr": _repl(mesh), "grad_norm": _repl(mesh)}
    return train_step, st_sh, metrics_sh


# ---------------------------------------------------------------------------
# serve steps
# ---------------------------------------------------------------------------


def make_prefill_step(cfg: ArchConfig, mesh: Mesh, shape: InputShape):
    model = Model(cfg)
    s_max = shape.seq_len + cfg.vision_prefix

    def prefill_step(params, batch):
        logits, caches = model.prefill(params, batch, s_max)
        return logits, caches

    model_axes, params_struct = model_axes_for(cfg)
    p_sh = tree_shardings(model_axes, params_struct, mesh, DEFAULT_RULES)
    cache_struct = model.cache_struct(shape.global_batch, s_max)
    c_sh = tree_shardings(cache_axes(cfg), cache_struct, mesh, CACHE_RULES)
    out_sh = (NamedSharding(mesh, data_pspec(mesh, 2)), c_sh)
    return prefill_step, p_sh, out_sh


def make_decode_step(cfg: ArchConfig, mesh: Mesh, shape: InputShape):
    """One-token serve step over a seq_len-deep cache (the assigned decode
    shapes). Returns (fn, params_sh, cache_sh)."""
    model = Model(cfg)

    def decode_step(params, caches, tokens, pos):
        logits, new_caches = model.decode(params, caches, tokens, pos)
        return logits, new_caches

    model_axes, params_struct = model_axes_for(cfg)
    p_sh = tree_shardings(model_axes, params_struct, mesh, DEFAULT_RULES)
    cache_struct = model.cache_struct(shape.global_batch, shape.seq_len)
    c_sh = tree_shardings(cache_axes(cfg), cache_struct, mesh, CACHE_RULES)
    return decode_step, p_sh, c_sh
