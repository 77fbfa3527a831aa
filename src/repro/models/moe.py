"""Mixture-of-Experts FFN: routing over every expert, the held experts' part
of the result, no token dropped.

An expert layer is told which experts it holds: ``cfg.ep_size`` ranks share
each layer's ``n_experts``, and this one (``cfg.ep_rank``) holds the
``cfg.held_experts`` ids ``[ep_rank * held, (ep_rank + 1) * held)``; at
``ep_size`` 1 that is every expert. Every token is routed over all
``n_experts``, and the layer computes only its held experts' part of the
result, plus the shared experts. Tokens routed to experts held elsewhere add
nothing here: the exchange that would bring the other ranks' parts is not
part of this layer.

Two ways to compute the held part, one result:

  ``moe_ffn`` (train, prefill) — the token-expert rows of held experts,
      sorted by expert, feed one grouped product (``jax.lax.ragged_dot``), so
      every row is computed and none is dropped, however unevenly the tokens
      route. Rows of experts held elsewhere sort last and are left out.
  ``moe_decode`` (one token a sequence) — a loop over the held experts that
      the tokens routed to, each read from the layer-stacked weights by
      index, so a step reads the weights of those experts and no others.

Both also return a count: ``moe_ffn`` the token-expert rows its held experts
computed, ``moe_decode`` the experts it read.

Routing flavours:
  softmax  — top-k of softmax(logits), gates renormalised over the k chosen
  sigmoid  — DeepSeek-V3 ``noaux_tc`` (one group): selection by sigmoid
             score + a static correction bias, gates = the chosen experts'
             unbiased scores, normalised, times ``routed_scale`` (the online
             bias controller is a training-loop detail, omitted).
The router's logits are float32 products at full precision.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models.layers import init_dense, pdtype, swiglu

EXPERT_WEIGHTS = ("wg", "wu", "wd")


def init_moe(key, cfg: ArchConfig, n_layers: int):
    e, x_, f = cfg.d_model, cfg.n_experts, cfg.resolved_moe_d_ff
    held = cfg.held_experts
    dt = pdtype(cfg)
    ks = jax.random.split(key, 8)
    p, a = {}, {}
    p["w_router"], a["w_router"] = init_dense(ks[0], (n_layers, e, x_), ("layers", "embed", None), jnp.float32)
    if cfg.router_type == "sigmoid":
        p["router_bias"] = jnp.zeros((n_layers, x_), jnp.float32)
        a["router_bias"] = ("layers", None)
    p["wg"], a["wg"] = init_dense(ks[1], (n_layers, held, e, f), ("layers", "experts", "embed", "moe_mlp"), dt)
    p["wu"], a["wu"] = init_dense(ks[2], (n_layers, held, e, f), ("layers", "experts", "embed", "moe_mlp"), dt)
    p["wd"], a["wd"] = init_dense(ks[3], (n_layers, held, f, e), ("layers", "experts", "moe_mlp", "embed"), dt)
    if cfg.n_shared_experts:
        fs = f * cfg.n_shared_experts
        p["ws_g"], a["ws_g"] = init_dense(ks[4], (n_layers, e, fs), ("layers", "embed", "mlp"), dt)
        p["ws_u"], a["ws_u"] = init_dense(ks[5], (n_layers, e, fs), ("layers", "embed", "mlp"), dt)
        p["ws_d"], a["ws_d"] = init_dense(ks[6], (n_layers, fs, e), ("layers", "mlp", "embed"), dt)
    return p, a


def route(x2: jax.Array, p: dict, cfg: ArchConfig):
    """x2 (T, E) -> (gates (T,k) f32, idx (T,k) i32), over all ``n_experts``."""
    k = cfg.top_k
    logits = jnp.einsum("te,ex->tx", x2.astype(jnp.float32), p["w_router"],
                        precision=jax.lax.Precision.HIGHEST)
    if cfg.router_type == "sigmoid":
        scores = jax.nn.sigmoid(logits)
        sel = scores + p["router_bias"]
        _, idx = jax.lax.top_k(sel, k)
        g = jnp.take_along_axis(scores, idx, axis=-1)
        gates = g / jnp.maximum(jnp.sum(g, axis=-1, keepdims=True), 1e-9)
    else:
        _, idx = jax.lax.top_k(logits, k)
        g = jnp.take_along_axis(logits, idx, axis=-1)
        gates = jax.nn.softmax(g, axis=-1)
    if cfg.routed_scale != 1.0:
        gates = gates * cfg.routed_scale
    return gates.astype(jnp.float32), idx.astype(jnp.int32)


def _held(idx: jax.Array, cfg: ArchConfig):
    """Global expert ids -> (local id, held here?)."""
    local = idx - cfg.ep_rank * cfg.held_experts
    return local, (local >= 0) & (local < cfg.held_experts)


def _shared(p: dict, x: jax.Array, cfg: ArchConfig) -> jax.Array:
    return swiglu(x, p["ws_g"], p["ws_u"], p["ws_d"]) if cfg.n_shared_experts else 0.0


def moe_ffn(p: dict, x: jax.Array, cfg: ArchConfig):
    """x: (B, S, E) -> (held part + shared experts (B, S, E), rows computed).

    Dropless: the ``T * k`` token-expert rows are sorted by held expert
    (rows of experts held elsewhere last), and the grouped product runs over
    the held groups; their sizes are whatever the routing gives."""
    b, s, e = x.shape
    x2 = x.reshape(b * s, e)
    gates, idx = route(x2, p, cfg)
    local, held = _held(idx.reshape(-1), cfg)
    n_held = cfg.held_experts
    key = jnp.where(held, local, n_held)
    order = jnp.argsort(key, stable=True)
    rows = order // cfg.top_k  # the token of each sorted row
    sizes = jnp.bincount(key, length=n_held + 1)[:n_held].astype(jnp.int32)
    xs = x2[rows]
    hg = jax.lax.ragged_dot(xs, p["wg"], sizes)
    hu = jax.lax.ragged_dot(xs, p["wu"], sizes)
    y = jax.lax.ragged_dot(jax.nn.silu(hg) * hu, p["wd"], sizes)
    keep = held[order]
    w = jnp.where(keep, gates.reshape(-1)[order], 0.0)
    y = jnp.where(keep[:, None], y.astype(jnp.float32) * w[:, None], 0.0)
    out = jnp.zeros((b * s, e), jnp.float32).at[rows].add(y)
    from repro.distributed.ctx import constrain

    out = constrain(out.astype(x.dtype).reshape(b, s, e), "resid")
    return out + _shared(p, x, cfg), jnp.sum(held.astype(jnp.int32))


def moe_decode(p: dict, x: jax.Array, cfg: ArchConfig, experts: dict, layer: jax.Array):
    """One token a sequence: x (B, 1, E) -> (held part + shared (B, 1, E), experts read).

    ``p`` holds the layer's router and shared experts; ``experts`` the
    routed experts' weights of every layer of the group, stacked
    (``EXPERT_WEIGHTS``, each (L, held, ...)), and ``layer`` this layer's
    index in them. The loop runs once for each held expert that some token
    routed to, and reads only that expert's weights."""
    b, s, e = x.shape
    x2 = x.reshape(b * s, e)
    t = b * s
    gates, idx = route(x2, p, cfg)
    local, held = _held(idx, cfg)
    slot = jnp.where(held, local, 0)
    n_held = cfg.held_experts
    # each token's gate on each held expert (0 where it did not route there)
    w = jnp.zeros((t, n_held), jnp.float32).at[jnp.arange(t)[:, None], slot].add(
        jnp.where(held, gates, 0.0))
    hit = jnp.zeros((n_held,), jnp.int32).at[slot].max(held.astype(jnp.int32))
    n_read = jnp.sum(hit)
    ids = jnp.argsort(1 - hit, stable=True)  # the experts hit first

    def body(i, acc):
        j = ids[i]
        wg, wu, wd = (experts[n][layer, j] for n in EXPERT_WEIGHTS)
        y = swiglu(x2, wg, wu, wd).astype(jnp.float32)
        return acc + y * jax.lax.dynamic_index_in_dim(w, j, axis=1)

    out = jax.lax.fori_loop(0, n_read, body, jnp.zeros((t, e), jnp.float32))
    return out.astype(x.dtype).reshape(b, s, e) + _shared(p, x, cfg), n_read
