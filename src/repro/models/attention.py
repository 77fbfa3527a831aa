"""Attention: GQA (blockwise/windowed) and MLA (latent KV, absorbed decode).

Layouts: activations (B, S, E); attention internals grouped for GQA as
(B, S, KV, G, Dh) with G = n_heads // n_kv_heads so k/v are never physically
repeated. Prefill/train uses a q-block scan (memory-efficient attention):
the (qb × T) score tile is the only S²-shaped transient, so 32k prefill
never materialises S×S. The Pallas flash kernel
(`repro.kernels.flash_attention`) computes the same math with VMEM tiling +
causal block skip on TPU; tests assert they agree.

KV caches:
  full:    {"k": (B, S_max, KV, Dh), "v": ...}             decode_32k
  window:  same with S_max = window (rolling slots, pos%W)  long_500k hybrid
  MLA:     {"ckv": (B, S_max, KVr), "kr": (B, S_max, Rr)}   compressed latent
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.models.layers import apply_rope, init_dense, pdtype, rmsnorm

NEG = -1e30


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------


def init_gqa(key, cfg: ArchConfig, n_layers: int, *, cross: bool = False):
    e, h, kv = cfg.d_model, cfg.n_heads, cfg.n_kv_heads
    dh = cfg.resolved_head_dim
    dt = pdtype(cfg)
    ks = jax.random.split(key, 8)
    p, a = {}, {}
    p["wq"], a["wq"] = init_dense(ks[0], (n_layers, e, h, dh), ("layers", "embed", "heads", "head_dim"), dt)
    p["wk"], a["wk"] = init_dense(ks[1], (n_layers, e, kv, dh), ("layers", "embed", "kv_heads", "head_dim"), dt)
    p["wv"], a["wv"] = init_dense(ks[2], (n_layers, e, kv, dh), ("layers", "embed", "kv_heads", "head_dim"), dt)
    p["wo"], a["wo"] = init_dense(ks[3], (n_layers, h, dh, e), ("layers", "heads", "head_dim", "embed"), dt)
    if cfg.attn_bias:
        p["bq"] = jnp.zeros((n_layers, h, dh), dt); a["bq"] = ("layers", "heads", "head_dim")
        p["bk"] = jnp.zeros((n_layers, kv, dh), dt); a["bk"] = ("layers", "kv_heads", "head_dim")
        p["bv"] = jnp.zeros((n_layers, kv, dh), dt); a["bv"] = ("layers", "kv_heads", "head_dim")
        p["bo"] = jnp.zeros((n_layers, e), dt); a["bo"] = ("layers", "embed")
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((n_layers, dh), dt); a["q_norm"] = ("layers", "head_dim")
        p["k_norm"] = jnp.ones((n_layers, dh), dt); a["k_norm"] = ("layers", "head_dim")
    return p, a


def init_mla(key, cfg: ArchConfig, n_layers: int):
    e, h = cfg.d_model, cfg.n_heads
    qr, kvr = cfg.q_lora_rank, cfg.kv_lora_rank
    nd, rd, vd = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    dt = pdtype(cfg)
    ks = jax.random.split(key, 6)
    p, a = {}, {}
    if qr:
        p["wq_a"], a["wq_a"] = init_dense(ks[0], (n_layers, e, qr), ("layers", "embed", "q_lora"), dt)
        p["q_ln"] = jnp.ones((n_layers, qr), dt); a["q_ln"] = ("layers", "q_lora")
        p["wq_b"], a["wq_b"] = init_dense(ks[1], (n_layers, qr, h, nd + rd), ("layers", "q_lora", "heads", "head_dim"), dt)
    else:  # no query LoRA (q_lora_rank 0): a plain x Wq
        p["wq"], a["wq"] = init_dense(ks[0], (n_layers, e, h, nd + rd), ("layers", "embed", "heads", "head_dim"), dt)
    p["wkv_a"], a["wkv_a"] = init_dense(ks[2], (n_layers, e, kvr + rd), ("layers", "embed", None), dt)
    p["kv_ln"] = jnp.ones((n_layers, kvr), dt); a["kv_ln"] = ("layers", None)
    p["wkv_b"], a["wkv_b"] = init_dense(ks[3], (n_layers, kvr, h, nd + vd), ("layers", None, "heads", "head_dim"), dt)
    p["wo"], a["wo"] = init_dense(ks[4], (n_layers, h, vd, e), ("layers", "heads", "head_dim", "embed"), dt)
    return p, a


# ---------------------------------------------------------------------------
# blockwise grouped attention (train / prefill)
# ---------------------------------------------------------------------------


def _grouped_scores(qb, k):  # (B,qb,KV,G,D),(B,T,KV,D) -> (B,KV,G,qb,T) f32
    return jnp.einsum("bqkgd,btkd->bkgqt", qb, k, preferred_element_type=jnp.float32)


def _grouped_out(probs, v):  # (B,KV,G,qb,T),(B,T,KV,D) -> (B,qb,KV,G,D)
    return jnp.einsum("bkgqt,btkd->bqkgd", probs.astype(v.dtype), v)


def blockwise_attention(
    q: jax.Array,  # (B, S, KV, G, D)
    k: jax.Array,  # (B, T, KV, D)
    v: jax.Array,  # (B, T, KV, D)
    *,
    causal: bool = True,
    window: int = 0,
    q_block: int = 1024,
) -> jax.Array:
    b, s, kv, g, d = q.shape
    dv = v.shape[-1]  # output feature dim (MLA: v_head_dim != qk dim)
    t = k.shape[1]
    scale = 1.0 / np.sqrt(d)
    q_block = min(q_block, s)
    nq = -(-s // q_block)
    pad = nq * q_block - s
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0), (0, 0)))
    qs = q.reshape(b, nq, q_block, kv, g, d).transpose(1, 0, 2, 3, 4, 5)

    if window and window > 0:
        # sliding window: slice [q0 - W + 1, q0 + qb) of k/v per block
        w = window
        span = w - 1 + q_block
        kp = jnp.pad(k, ((0, 0), (w - 1, 0), (0, 0), (0, 0)))  # left-pad
        vp = jnp.pad(v, ((0, 0), (w - 1, 0), (0, 0), (0, 0)))

        def body(qi, qb_):
            q0 = qi * q_block
            kw = jax.lax.dynamic_slice_in_dim(kp, q0, span, axis=1)
            vw = jax.lax.dynamic_slice_in_dim(vp, q0, span, axis=1)
            qpos = q0 + jnp.arange(q_block)
            kpos = q0 - (w - 1) + jnp.arange(span)  # absolute (may be <0 = pad)
            mask = (kpos[None, :] <= qpos[:, None]) & (kpos[None, :] > qpos[:, None] - w)
            mask &= kpos[None, :] >= 0
            sc = _grouped_scores(qb_, kw) * scale
            sc = jnp.where(mask[None, None, None], sc, NEG)
            probs = jax.nn.softmax(sc, axis=-1)
            return _grouped_out(probs, vw)

        # checkpoint the per-block body: bwd re-forms each (qb × span) score
        # tile instead of saving all of them (keeps bwd memory = one tile)
        body = jax.checkpoint(body, prevent_cse=False)
        out = jax.lax.map(lambda xs: body(xs[0], xs[1]), (jnp.arange(nq), qs))
    else:

        def body(qi, qb_):
            sc = _grouped_scores(qb_, k) * scale  # (B,KV,G,qb,T)
            if causal:
                qpos = qi * q_block + jnp.arange(q_block)
                mask = jnp.arange(t)[None, :] <= qpos[:, None]
                sc = jnp.where(mask[None, None, None], sc, NEG)
            probs = jax.nn.softmax(sc, axis=-1)
            return _grouped_out(probs, v)

        body = jax.checkpoint(body, prevent_cse=False)
        out = jax.lax.map(lambda xs: body(xs[0], xs[1]), (jnp.arange(nq), qs))

    out = out.transpose(1, 0, 2, 3, 4, 5).reshape(b, nq * q_block, kv, g, dv)
    return out[:, :s]


# ---------------------------------------------------------------------------
# GQA layer apply
# ---------------------------------------------------------------------------


def _proj_qkv(p, x, cfg: ArchConfig):
    q = jnp.einsum("bse,ehd->bshd", x, p["wq"])
    k = jnp.einsum("bse,ehd->bshd", x, p["wk"])
    v = jnp.einsum("bse,ehd->bshd", x, p["wv"])
    if cfg.attn_bias:
        q = q + p["bq"]; k = k + p["bk"]; v = v + p["bv"]
    if cfg.qk_norm:
        q = rmsnorm(q, p["q_norm"], cfg.norm_eps)
        k = rmsnorm(k, p["k_norm"], cfg.norm_eps)
    return q, k, v


def gqa_train(p, x, cfg: ArchConfig, *, causal: bool = True, use_rope: bool = True,
              positions: jax.Array | None = None, kv_source: jax.Array | None = None):
    """Train/prefill attention (optionally cross: kv from ``kv_source``)."""
    b, s, e = x.shape
    kv_n, g = cfg.n_kv_heads, cfg.n_heads // cfg.n_kv_heads
    dh = cfg.resolved_head_dim
    src = x if kv_source is None else kv_source
    q = jnp.einsum("bse,ehd->bshd", x, p["wq"])
    k = jnp.einsum("bse,ehd->bshd", src, p["wk"])
    v = jnp.einsum("bse,ehd->bshd", src, p["wv"])
    if cfg.attn_bias:
        q = q + p["bq"]; k = k + p["bk"]; v = v + p["bv"]
    if cfg.qk_norm:
        q = rmsnorm(q, p["q_norm"], cfg.norm_eps)
        k = rmsnorm(k, p["k_norm"], cfg.norm_eps)
    if use_rope:
        pos_q = positions if positions is not None else jnp.arange(s)
        q = apply_rope(q, pos_q, cfg.rope_theta)
        k = apply_rope(k, jnp.arange(k.shape[1]), cfg.rope_theta)
    qg = q.reshape(b, s, kv_n, g, dh)
    out = blockwise_attention(
        qg, k, v, causal=causal, window=cfg.window, q_block=cfg.attn_q_block
    )
    out = out.reshape(b, s, cfg.n_heads, dh)
    y = jnp.einsum("bshd,hde->bse", out, p["wo"])
    if cfg.attn_bias:
        y = y + p["bo"]
    return y


def gqa_prefill_cache(p, x, cfg: ArchConfig, s_max: int, *, use_rope: bool = True):
    """Build the decode cache from a prefill pass (k/v padded to s_max)."""
    b, s, _ = x.shape
    q, k, v = _proj_qkv(p, x, cfg)
    if use_rope:
        pos = jnp.arange(s)
        k = apply_rope(k, pos, cfg.rope_theta)
    if cfg.window and cfg.window > 0:
        s_max = min(s_max, cfg.window)
        # rolling layout: slot = pos % W of the last W positions
        last = k.shape[1]
        take = min(last, s_max)
        ks_, vs_ = k[:, -take:], v[:, -take:]
        pos0 = jnp.arange(s - take, s)
        slots = pos0 % s_max
        kc = jnp.zeros((b, s_max) + k.shape[2:], k.dtype).at[:, slots].set(ks_)
        vc = jnp.zeros((b, s_max) + v.shape[2:], v.dtype).at[:, slots].set(vs_)
        return {"k": kc, "v": vc}
    kc = jnp.zeros((b, s_max) + k.shape[2:], k.dtype).at[:, :s].set(k)
    vc = jnp.zeros((b, s_max) + v.shape[2:], v.dtype).at[:, :s].set(v)
    return {"k": kc, "v": vc}


def gqa_decode(p, x, cache: dict, pos, cfg: ArchConfig, *, use_rope: bool = True):
    """One-token decode: update cache at ``pos``, attend over it.

    ``pos`` is a traced scalar (current absolute position). Window caches use
    rolling slots (pos % W); softmax permutation-invariance makes slot order
    irrelevant.
    """
    b, s1, e = x.shape  # s1 == 1
    kv_n, g = cfg.n_kv_heads, cfg.n_heads // cfg.n_kv_heads
    dh = cfg.resolved_head_dim
    q, k, v = _proj_qkv(p, x, cfg)
    if use_rope:
        posv = jnp.full((s1,), pos)
        q = apply_rope(q, posv, cfg.rope_theta)
        k = apply_rope(k, posv, cfg.rope_theta)
    s_max = cache["k"].shape[1]
    windowed = bool(cfg.window) and cfg.window > 0
    slot = (pos % s_max) if windowed else pos
    kc = jax.lax.dynamic_update_slice_in_dim(cache["k"], k, slot, axis=1)
    vc = jax.lax.dynamic_update_slice_in_dim(cache["v"], v, slot, axis=1)
    qg = q.reshape(b, s1, kv_n, g, dh)
    sc = _grouped_scores(qg, kc) / np.sqrt(dh)  # (B,KV,G,1,s_max)
    idx = jnp.arange(s_max)
    valid = (idx <= pos) if not windowed else ((idx <= pos) | (pos >= s_max))
    sc = jnp.where(valid[None, None, None, None, :], sc, NEG)
    probs = jax.nn.softmax(sc, axis=-1)
    out = _grouped_out(probs, vc).reshape(b, s1, cfg.n_heads, dh)
    y = jnp.einsum("bshd,hde->bse", out, p["wo"])
    if cfg.attn_bias:
        y = y + p["bo"]
    return y, {"k": kc, "v": vc}


# ---------------------------------------------------------------------------
# MLA (deepseek): expanded train/prefill, absorbed decode
# ---------------------------------------------------------------------------


def _mla_qkv(p, x, cfg: ArchConfig, positions):
    nd, rd = cfg.qk_nope_dim, cfg.qk_rope_dim
    if cfg.q_lora_rank:
        cq = rmsnorm(jnp.einsum("bse,eq->bsq", x, p["wq_a"]), p["q_ln"], cfg.norm_eps)
        q = jnp.einsum("bsq,qhd->bshd", cq, p["wq_b"])  # (B,S,H,nd+rd)
    else:
        q = jnp.einsum("bse,ehd->bshd", x, p["wq"])
    q_nope, q_rope = q[..., :nd], q[..., nd:]
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta)
    ckv_full = jnp.einsum("bse,ek->bsk", x, p["wkv_a"])
    ckv = rmsnorm(ckv_full[..., : cfg.kv_lora_rank], p["kv_ln"], cfg.norm_eps)
    k_rope = ckv_full[..., cfg.kv_lora_rank :][:, :, None, :]  # (B,S,1,rd)
    k_rope = apply_rope(k_rope, positions, cfg.rope_theta)[:, :, 0]
    return q_nope, q_rope, ckv, k_rope


def mla_train(p, x, cfg: ArchConfig, *, causal: bool = True):
    b, s, _ = x.shape
    nd, rd, vd = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    pos = jnp.arange(s)
    q_nope, q_rope, ckv, k_rope = _mla_qkv(p, x, cfg, pos)
    kvx = jnp.einsum("bsk,khd->bshd", ckv, p["wkv_b"])  # (B,S,H,nd+vd)
    k_nope, v = kvx[..., :nd], kvx[..., nd:]
    k = jnp.concatenate([k_nope, jnp.broadcast_to(k_rope[:, :, None, :], (b, s, cfg.n_heads, rd))], axis=-1)
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    # MHA == GQA with KV == H, G == 1
    qg = q.reshape(b, s, cfg.n_heads, 1, nd + rd)
    out = blockwise_attention(qg, k, v, causal=causal, q_block=cfg.attn_q_block)
    out = out.reshape(b, s, cfg.n_heads, vd)
    return jnp.einsum("bshd,hde->bse", out, p["wo"])


def mla_prefill_cache(p, x, cfg: ArchConfig, s_max: int):
    b, s, _ = x.shape
    pos = jnp.arange(s)
    _, _, ckv, k_rope = _mla_qkv(p, x, cfg, pos)
    ckv_c = jnp.zeros((b, s_max, cfg.kv_lora_rank), ckv.dtype).at[:, :s].set(ckv)
    kr_c = jnp.zeros((b, s_max, cfg.qk_rope_dim), k_rope.dtype).at[:, :s].set(k_rope)
    return {"ckv": ckv_c, "kr": kr_c}


def mla_decode(p, x, cache: dict, pos, cfg: ArchConfig):
    """Absorbed decode: scores/output computed in the latent space, so the
    per-step cost is O(S·(KVr+Rr)) per head-group instead of O(S·H·Dh)."""
    b, s1, _ = x.shape
    nd, rd, vd = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    posv = jnp.full((s1,), pos)
    q_nope, q_rope, ckv_new, kr_new = _mla_qkv(p, x, cfg, posv)
    ckv = jax.lax.dynamic_update_slice_in_dim(cache["ckv"], ckv_new, pos, axis=1)
    kr = jax.lax.dynamic_update_slice_in_dim(cache["kr"], kr_new, pos, axis=1)
    wkv_k = p["wkv_b"][..., :nd]  # (KVr, H, nd)
    wkv_v = p["wkv_b"][..., nd:]  # (KVr, H, vd)
    q_lat = jnp.einsum("bqhn,khn->bqhk", q_nope, wkv_k)  # absorb k-expansion
    sc = jnp.einsum("bqhk,bsk->bhqs", q_lat, ckv, preferred_element_type=jnp.float32)
    sc = sc + jnp.einsum("bqhr,bsr->bhqs", q_rope, kr, preferred_element_type=jnp.float32)
    sc = sc / np.sqrt(nd + rd)
    s_max = ckv.shape[1]
    valid = jnp.arange(s_max) <= pos
    sc = jnp.where(valid[None, None, None, :], sc, NEG)
    probs = jax.nn.softmax(sc, axis=-1)
    o_lat = jnp.einsum("bhqs,bsk->bqhk", probs.astype(ckv.dtype), ckv)
    out = jnp.einsum("bqhk,khv->bqhv", o_lat, wkv_v)
    y = jnp.einsum("bqhv,hve->bqe", out, p["wo"])
    return y, {"ckv": ckv, "kr": kr}
