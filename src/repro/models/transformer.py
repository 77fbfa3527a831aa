"""Decoder-only transformer assembly: layer groups, scan, remat, caches.

Layers with identical structure are stacked on a leading ``L`` axis and run
under one ``lax.scan`` (compact HLO at 60-80 layers, fast multi-pod
compiles). Architectures whose stack is non-uniform (deepseek-v3: 3 dense
then 58 MoE layers) split into *groups*, each its own stacked scan —
``block_groups(cfg)`` derives the grouping deterministically from config.

Mixer kinds: gqa | mla | hybrid (attn ‖ SSD, Hymba) | mlstm (xLSTM).
FFN kinds:   dense (SwiGLU) | moe | none (mLSTM blocks own their FFN).
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models import attention as attn
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod
from repro.models.layers import init_dense, init_embedding, pdtype, rmsnorm


def block_groups(cfg: ArchConfig) -> list[tuple[str, int, str, str]]:
    """[(group_name, n_layers, mixer_kind, ffn_kind)]"""
    if cfg.mla:
        mixer = "mla"
    elif cfg.ssm:
        mixer = "hybrid"
    elif cfg.mlstm:
        mixer = "mlstm"
    else:
        mixer = "gqa"
    ffn = "moe" if cfg.moe else ("dense" if cfg.d_ff > 0 else "none")
    if cfg.moe and cfg.first_dense_layers > 0:
        return [
            ("g0", cfg.first_dense_layers, mixer, "dense"),
            ("g1", cfg.n_layers - cfg.first_dense_layers, mixer, "moe"),
        ]
    return [("g0", cfg.n_layers, mixer, ffn)]


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------


def _init_ffn(key, cfg: ArchConfig, n_layers: int, kind: str):
    dt = pdtype(cfg)
    e = cfg.d_model
    if kind == "dense":
        ks = jax.random.split(key, 3)
        p, a = {}, {}
        p["wg"], a["wg"] = init_dense(ks[0], (n_layers, e, cfg.d_ff), ("layers", "embed", "mlp"), dt)
        p["wu"], a["wu"] = init_dense(ks[1], (n_layers, e, cfg.d_ff), ("layers", "embed", "mlp"), dt)
        p["wd"], a["wd"] = init_dense(ks[2], (n_layers, cfg.d_ff, e), ("layers", "mlp", "embed"), dt)
        return p, a
    if kind == "moe":
        return moe_mod.init_moe(key, cfg, n_layers)
    return {}, {}


def _init_mixer(key, cfg: ArchConfig, n_layers: int, kind: str):
    if kind == "gqa":
        return {"attn": dict(zip(("p", "a"), attn.init_gqa(key, cfg, n_layers)))}
    if kind == "mla":
        return {"attn": dict(zip(("p", "a"), attn.init_mla(key, cfg, n_layers)))}
    if kind == "hybrid":
        k1, k2 = jax.random.split(key)
        return {
            "attn": dict(zip(("p", "a"), attn.init_gqa(k1, cfg, n_layers))),
            "ssd": dict(zip(("p", "a"), ssm_mod.init_ssd(k2, cfg, n_layers))),
        }
    if kind == "mlstm":
        return {"mlstm": dict(zip(("p", "a"), ssm_mod.init_mlstm(key, cfg, n_layers)))}
    raise ValueError(kind)


def init_lm(key, cfg: ArchConfig):
    """Returns (params, axes) — parallel trees."""
    dt = pdtype(cfg)
    keys = jax.random.split(key, 4 + len(block_groups(cfg)))
    params: dict[str, Any] = {}
    axes: dict[str, Any] = {}
    params["embed"], axes["embed"] = init_embedding(keys[0], cfg)
    if not cfg.tie_embeddings:
        params["unembed"], axes["unembed"] = init_embedding(keys[1], cfg)
    params["final_norm"] = jnp.ones((cfg.d_model,), dt)
    axes["final_norm"] = ("embed",)
    params["blocks"], axes["blocks"] = {}, {}
    for i, (gname, n, mixer, ffn) in enumerate(block_groups(cfg)):
        gk = jax.random.split(keys[3 + i], 3)
        bp: dict[str, Any] = {"ln1": jnp.ones((n, cfg.d_model), dt)}
        ba: dict[str, Any] = {"ln1": ("layers", "embed")}
        mix = _init_mixer(gk[0], cfg, n, mixer)
        for name, pa in mix.items():
            bp[name], ba[name] = pa["p"], pa["a"]
        if ffn != "none":
            bp["ln2"] = jnp.ones((n, cfg.d_model), dt)
            ba["ln2"] = ("layers", "embed")
            fp, fa = _init_ffn(gk[1], cfg, n, ffn)
            bp["ffn"], ba["ffn"] = fp, fa
        params["blocks"][gname] = bp
        axes["blocks"][gname] = ba
    return params, axes


# ---------------------------------------------------------------------------
# block apply (single layer; params without the L axis)
# ---------------------------------------------------------------------------


def _mixer_train(pl, x, cfg: ArchConfig, mixer: str):
    if mixer == "gqa":
        return attn.gqa_train(pl["attn"], x, cfg)
    if mixer == "mla":
        return attn.mla_train(pl["attn"], x, cfg)
    if mixer == "hybrid":
        ya = attn.gqa_train(pl["attn"], x, cfg)
        ys = ssm_mod.ssd_train(pl["ssd"], x, cfg)
        return (ya + ys) * 0.5
    if mixer == "mlstm":
        return ssm_mod.mlstm_train(pl["mlstm"], x, cfg)
    raise ValueError(mixer)


def _ffn_apply(pl, x, cfg: ArchConfig, ffn: str, experts=None):
    """The block's FFN: (output, counts). A MoE layer counts the token-expert
    rows its held experts computed (``expert_rows``) or, given the group's
    stacked experts and its layer index (``experts``, decode), the experts it
    read (``experts_read``); a dense one counts nothing."""
    if ffn == "dense":
        from repro.models.layers import swiglu

        return swiglu(x, pl["ffn"]["wg"], pl["ffn"]["wu"], pl["ffn"]["wd"]), {}
    if ffn == "moe" and experts is None:
        y, rows = moe_mod.moe_ffn(pl["ffn"], x, cfg)
        return y, {"expert_rows": rows}
    if ffn == "moe":
        y, n_read = moe_mod.moe_decode(pl["ffn"], x, cfg, *experts)
        return y, {"experts_read": n_read}
    raise ValueError(ffn)


def block_train(pl, x, cfg: ArchConfig, mixer: str, ffn: str):
    h = x + _mixer_train(pl, rmsnorm(x, pl["ln1"], cfg.norm_eps), cfg, mixer)
    if ffn != "none":
        h = h + _ffn_apply(pl, rmsnorm(h, pl["ln2"], cfg.norm_eps), cfg, ffn)[0]
    return h


def block_prefill(pl, x, cfg, mixer, ffn, s_max):
    """Like block_train but also returns this layer's decode cache and the
    FFN's counts."""
    xin = rmsnorm(x, pl["ln1"], cfg.norm_eps)
    if mixer == "gqa":
        y = attn.gqa_train(pl["attn"], xin, cfg)
        cache = attn.gqa_prefill_cache(pl["attn"], xin, cfg, s_max)
    elif mixer == "mla":
        y = attn.mla_train(pl["attn"], xin, cfg)
        cache = attn.mla_prefill_cache(pl["attn"], xin, cfg, s_max)
    elif mixer == "hybrid":
        ya = attn.gqa_train(pl["attn"], xin, cfg)
        cache = attn.gqa_prefill_cache(pl["attn"], xin, cfg, s_max)
        xs = jnp.einsum("bse,ehd->bshd", xin, pl["ssd"]["wx"])
        bb = jnp.einsum("bse,ehn->bshn", xin, pl["ssd"]["wB"])
        dt_, log_a = ssm_mod._ssd_gates(pl["ssd"], xin)
        cc = jnp.einsum("bse,ehn->bshn", xin, pl["ssd"]["wC"])
        v = xs * dt_[..., None].astype(xs.dtype)
        ys_f, sstate = ssm_mod.chunked_linear_recurrence(cc, bb, v, log_a, chunk=cfg.chunk)
        ys_f = ys_f + xs.astype(jnp.float32) * pl["ssd"]["D"][None, None, :, None]
        ys = jnp.einsum("bshd,hde->bse", ys_f.astype(x.dtype), pl["ssd"]["wo"])
        y = (ya + ys) * 0.5
        cache = {"attn": cache, "ssd": sstate}
    elif mixer == "mlstm":
        b = x.shape[0]
        q, k, v, i_g, log_f, og = ssm_mod._mlstm_qkvg(pl["mlstm"], xin, cfg)
        k_eff = k.astype(jnp.float32) * i_g[..., None]
        v_aug = jnp.concatenate(
            [v.astype(jnp.float32), jnp.ones(v.shape[:-1] + (1,), jnp.float32)], axis=-1
        )
        y_aug, mstate = ssm_mod.chunked_linear_recurrence(q, k_eff, v_aug, log_f, chunk=cfg.chunk)
        yv = y_aug[..., :-1] / jnp.maximum(jnp.abs(y_aug[..., -1:]), 1.0)
        y = ssm_mod._mlstm_out(pl["mlstm"], yv, og, x.dtype, cfg, cfg.norm_eps)
        cache = {"mlstm": mstate}
    else:
        raise ValueError(mixer)
    h = x + y
    counts = {}
    if ffn != "none":
        y, counts = _ffn_apply(pl, rmsnorm(h, pl["ln2"], cfg.norm_eps), cfg, ffn)
        h = h + y
    return h, cache, counts


def block_decode(pl, x, cache, pos, cfg, mixer, ffn, experts=None):
    """One decode step of a layer: (hidden, cache, the FFN's counts).
    ``experts`` as ``_ffn_apply`` takes it, for a MoE layer."""
    xin = rmsnorm(x, pl["ln1"], cfg.norm_eps)
    if mixer == "gqa":
        y, cache = attn.gqa_decode(pl["attn"], xin, cache, pos, cfg)
    elif mixer == "mla":
        y, cache = attn.mla_decode(pl["attn"], xin, cache, pos, cfg)
    elif mixer == "hybrid":
        ya, ac = attn.gqa_decode(pl["attn"], xin, cache["attn"], pos, cfg)
        ys, sc = ssm_mod.ssd_decode(pl["ssd"], xin, cache["ssd"], cfg)
        y = (ya + ys) * 0.5
        cache = {"attn": ac, "ssd": sc}
    elif mixer == "mlstm":
        y, mc = ssm_mod.mlstm_decode(pl["mlstm"], xin, cache["mlstm"], cfg)
        cache = {"mlstm": mc}
    else:
        raise ValueError(mixer)
    h = x + y
    counts = {}
    if ffn != "none":
        y, counts = _ffn_apply(pl, rmsnorm(h, pl["ln2"], cfg.norm_eps), cfg, ffn, experts)
        h = h + y
    return h, cache, counts


# ---------------------------------------------------------------------------
# stacks: scan over layers, per group
# ---------------------------------------------------------------------------

_REMAT_POLICIES = {
    "nothing": jax.checkpoint_policies.nothing_saveable,
    "dots": jax.checkpoint_policies.checkpoint_dots,
    "full": jax.checkpoint_policies.everything_saveable,
}


def _maybe_remat(fn, cfg: ArchConfig):
    return jax.checkpoint(fn, policy=_REMAT_POLICIES[cfg.remat], prevent_cse=False)


def forward_train(params, x, cfg: ArchConfig):
    """x: (B,S,E) embedded inputs -> final hidden (B,S,E)."""
    from repro.distributed.ctx import constrain

    x = constrain(x, "resid")
    for gname, n, mixer, ffn in block_groups(cfg):
        gp = params["blocks"][gname]

        def body(h, pl, mixer=mixer, ffn=ffn):
            h = block_train(pl, h, cfg, mixer, ffn)
            return constrain(h, "resid"), None

        x, _ = jax.lax.scan(_maybe_remat(body, cfg), x, gp)
    return rmsnorm(x, params["final_norm"], cfg.norm_eps)


def _sum_counts(total: dict, per_layer: dict) -> None:
    for k, v in per_layer.items():
        total[k] = total.get(k, 0) + jnp.sum(v)


def forward_prefill(params, x, cfg: ArchConfig, s_max: int):
    """Returns (final hidden, caches, counts) — caches stacked per group,
    the FFNs' counts summed over layers."""
    caches, counts = {}, {}
    for gname, n, mixer, ffn in block_groups(cfg):
        gp = params["blocks"][gname]

        def body(h, pl, mixer=mixer, ffn=ffn):
            h2, cache, c = block_prefill(pl, h, cfg, mixer, ffn, s_max)
            return h2, (cache, c)

        x, (gcache, gcounts) = jax.lax.scan(body, x, gp)
        caches[gname] = gcache
        _sum_counts(counts, gcounts)
    return rmsnorm(x, params["final_norm"], cfg.norm_eps), caches, counts


def forward_decode(params, x, caches, pos, cfg: ArchConfig):
    """x: (B,1,E). Returns (final hidden (B,1,E), new caches, counts).

    A MoE group's routed experts stay out of the scan's per-layer slices:
    each layer indexes the group's stacked weights for the experts its
    tokens routed to (``moe.moe_decode``), so a step reads no others."""
    new_caches, counts = {}, {}
    for gname, n, mixer, ffn in block_groups(cfg):
        gp = params["blocks"][gname]
        xs, stacked = (gp, caches[gname]), None
        if ffn == "moe":
            stacked = {k: gp["ffn"][k] for k in moe_mod.EXPERT_WEIGHTS}
            rest = {k: v for k, v in gp["ffn"].items() if k not in stacked}
            xs = ({**gp, "ffn": rest}, caches[gname], jnp.arange(n))

        def body(h, xs, mixer=mixer, ffn=ffn, stacked=stacked):
            experts = None if stacked is None else (stacked, xs[2])
            h2, cache2, c = block_decode(xs[0], h, xs[1], pos, cfg, mixer, ffn, experts)
            return h2, (cache2, c)

        x, (gcache, gcounts) = jax.lax.scan(body, x, xs)
        new_caches[gname] = gcache
        _sum_counts(counts, gcounts)
    return rmsnorm(x, params["final_norm"], cfg.norm_eps), new_caches, counts
