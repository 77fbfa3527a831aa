"""Model facade: one uniform API over all 10 architectures.

    model = Model(cfg)
    params, axes = model.init(key)
    loss = model.loss(params, batch)                      # train shapes
    hidden, caches = model.prefill(params, batch, s_max)   # prefill shapes
    logits, caches = model.decode(params, caches, tok, pos)# decode shapes
    (..., counts) = model.prefill/decode(..., counts=True)  # + the FFNs' counts
    caches = model.init_cache(batch, s_ctx)                # zeros / specs

``input_specs(cfg, shape)`` produces the ShapeDtypeStruct stand-ins the
multi-pod dry-run lowers against (no allocation).
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig, InputShape
from repro.models import encdec as encdec_mod
from repro.models import ssm as ssm_mod
from repro.models import transformer as tf
from repro.models.layers import embed, pdtype, softmax_xent_chunked, unembed_logits


class Model:
    def __init__(self, cfg: ArchConfig):
        self.cfg = cfg

    # -- init ---------------------------------------------------------------
    def init(self, key) -> tuple[Any, Any]:
        if self.cfg.encdec:
            return encdec_mod.init_encdec(key, self.cfg)
        return tf.init_lm(key, self.cfg)

    def _unembed(self, params):
        return params["embed"] if self.cfg.tie_embeddings else params["unembed"]

    def _embed_inputs(self, params, batch) -> tuple[jax.Array, jax.Array]:
        """Returns (x (B, S_tot, E), labels (B, S_tot))."""
        cfg = self.cfg
        x = embed(batch["tokens"], params["embed"]).astype(pdtype(cfg))
        labels = batch["labels"]
        if cfg.vision_prefix:
            vis = batch["vis_embeds"].astype(x.dtype)  # (B, P, E) stub frontend
            x = jnp.concatenate([vis, x], axis=1)
            ignore = jnp.full(vis.shape[:2], -1, labels.dtype)
            labels = jnp.concatenate([ignore, labels], axis=1)
        return x, labels

    # -- train --------------------------------------------------------------
    def loss(self, params, batch) -> jax.Array:
        cfg = self.cfg
        if cfg.encdec:
            enc_out = encdec_mod.encode(params, batch["enc_frames"].astype(pdtype(cfg)), cfg)
            h = encdec_mod.decode_train(params, batch["tokens"], enc_out, cfg)
            return softmax_xent_chunked(h, self._unembed(params), batch["labels"], cfg.loss_chunk)
        x, labels = self._embed_inputs(params, batch)
        h = tf.forward_train(params, x, cfg)
        return softmax_xent_chunked(h, self._unembed(params), labels, cfg.loss_chunk)

    # -- serve --------------------------------------------------------------
    def prefill(self, params, batch, s_max: int, *, counts: bool = False):
        """Returns (last-position logits (B, V), caches), and with ``counts``
        the FFNs' counts summed over layers (a MoE model's ``expert_rows``,
        the token-expert rows its held experts computed; ``{}`` otherwise)."""
        cfg = self.cfg
        c = {}
        if cfg.encdec:
            enc_out = encdec_mod.encode(params, batch["enc_frames"].astype(pdtype(cfg)), cfg)
            h, caches = encdec_mod.prefill(params, batch["tokens"], enc_out, cfg, s_max)
        else:
            x = embed(batch["tokens"], params["embed"]).astype(pdtype(cfg))
            if cfg.vision_prefix:
                x = jnp.concatenate([batch["vis_embeds"].astype(x.dtype), x], axis=1)
            h, caches, c = tf.forward_prefill(params, x, cfg, s_max)
        logits = unembed_logits(h[:, -1], self._unembed(params))
        return (logits, caches, c) if counts else (logits, caches)

    def decode(self, params, caches, tokens, pos, *, counts: bool = False):
        """One decode step. tokens (B,1) int32; pos scalar int32 (absolute).
        With ``counts``, also the FFNs' counts summed over layers (a MoE
        model's ``experts_read``, the routed experts whose weights the step
        read; ``{}`` otherwise)."""
        cfg = self.cfg
        c = {}
        x = embed(tokens, params["embed"]).astype(pdtype(cfg))
        if cfg.encdec:
            x = x + jnp.take(params["pos_dec"], jnp.full((1,), pos), axis=0)[None, 0]
            h, caches = encdec_mod.decode_step(params, x, caches, pos, cfg)
        else:
            h, caches, c = tf.forward_decode(params, x, caches, pos, cfg)
        logits = unembed_logits(h, self._unembed(params))  # (B, 1, V)
        return (logits, caches, c) if counts else (logits, caches)

    # -- caches ---------------------------------------------------------------
    def cache_struct(self, batch: int, s_ctx: int) -> Any:
        """ShapeDtypeStruct tree for the decode caches (also used to zero-init)."""
        cfg = self.cfg
        dt = pdtype(cfg)
        kv, dh = cfg.n_kv_heads, cfg.resolved_head_dim
        s_kv = min(s_ctx, cfg.window) if cfg.window else s_ctx

        def sds(shape, dtype):
            return jax.ShapeDtypeStruct(shape, dtype)

        if cfg.encdec:
            l = cfg.n_layers
            return {
                "k": sds((l, batch, s_kv, kv, dh), dt),
                "v": sds((l, batch, s_kv, kv, dh), dt),
                "xk": sds((l, batch, cfg.enc_seq, kv, dh), dt),
                "xv": sds((l, batch, cfg.enc_seq, kv, dh), dt),
            }
        out = {}
        for gname, n, mixer, ffn in tf.block_groups(cfg):
            if mixer == "gqa":
                c = {"k": sds((n, batch, s_kv, kv, dh), dt), "v": sds((n, batch, s_kv, kv, dh), dt)}
            elif mixer == "mla":
                c = {
                    "ckv": sds((n, batch, s_ctx, cfg.kv_lora_rank), dt),
                    "kr": sds((n, batch, s_ctx, cfg.qk_rope_dim), dt),
                }
            elif mixer == "hybrid":
                c = {
                    "attn": {
                        "k": sds((n, batch, s_kv, kv, dh), dt),
                        "v": sds((n, batch, s_kv, kv, dh), dt),
                    },
                    "ssd": sds((n, batch, cfg.n_heads, cfg.ssm_state, dh), jnp.float32),
                }
            elif mixer == "mlstm":
                c = {"mlstm": sds((n, batch, cfg.n_heads, dh, dh + 1), jnp.float32)}
            else:
                raise ValueError(mixer)
            out[gname] = c
        return out

    def init_cache(self, batch: int, s_ctx: int) -> Any:
        return jax.tree_util.tree_map(
            lambda s: jnp.zeros(s.shape, s.dtype), self.cache_struct(batch, s_ctx)
        )


# ---------------------------------------------------------------------------
# input specs for the dry-run (ShapeDtypeStruct stand-ins, no allocation)
# ---------------------------------------------------------------------------


def input_specs(cfg: ArchConfig, shape: InputShape) -> dict[str, Any]:
    """Model inputs for (cfg, shape) as ShapeDtypeStructs.

    train:   tokens/labels (B, S) [+ modality stubs]
    prefill: tokens (B, S) [+ modality stubs]
    decode:  tokens (B, 1), pos scalar, caches for a seq_len context
    """
    b, s = shape.global_batch, shape.seq_len
    i32 = jnp.int32
    dt = pdtype(cfg)
    sds = jax.ShapeDtypeStruct
    if shape.kind == "train":
        out = {"tokens": sds((b, s), i32), "labels": sds((b, s), i32)}
        if cfg.vision_prefix:
            out["vis_embeds"] = sds((b, cfg.vision_prefix, cfg.d_model), dt)
        if cfg.encdec:
            out["enc_frames"] = sds((b, cfg.enc_seq, cfg.d_model), dt)
        return out
    if shape.kind == "prefill":
        out = {"tokens": sds((b, s), i32)}
        if cfg.vision_prefix:
            out["vis_embeds"] = sds((b, cfg.vision_prefix, cfg.d_model), dt)
        if cfg.encdec:
            out["enc_frames"] = sds((b, cfg.enc_seq, cfg.d_model), dt)
        return out
    if shape.kind == "decode":
        model = Model(cfg)
        return {
            "tokens": sds((b, 1), i32),
            "pos": sds((), i32),
            "caches": model.cache_struct(b, s),
        }
    raise ValueError(shape.kind)
