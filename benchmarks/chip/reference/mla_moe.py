"""Plain reference for the DeepSeek-V3 block: multi-head latent attention
(MLA) and a mixture of routed and shared experts after leading dense layers,
as Moonlight-16B-A3B publishes it (``DeepseekV3ForCausalLM``; the layer
equations of the ``deepseek_v3`` model type in its public ``config.json``,
arXiv:2412.19437).

Straight ``jax.numpy`` in float32 with matmuls at ``highest`` precision, one
sequence at a time, no kernels, no cache, no batching; the held experts are
computed densely for every token and weighted by its gate (0 where it did
not route there). It imports nothing of the program under test. It also
makes the weights that the benchmark hands to the program, from the seed
alone, in the program's tree layout; the reference makes them again for
itself after the window.

    x   = E[tokens]
    per layer l:
      a = RMSNorm(x) * ln1[l]
      q = a Wq                       (no query LoRA: q_lora_rank null)
      q = [q_nope, RoPE(q_rope)]                     per head, nope 128 + rope 64
      [c, k_r] = a Wkv_a;  c = RMSNorm(c) * kv_ln;  k_r = RoPE(k_r)   (shared by every head)
      [k_nope, v] = c Wkv_b;  k = [k_nope, k_r]      per head, nope 128 + v 128
      x = x + softmax(q k^T / sqrt(nope + rope) + causal mask) v Wo
      b = RMSNorm(x) * ln2[l]
      l < first_k_dense_replace:  x = x + (silu(b Wg) * (b Wu)) Wd
      otherwise (MoE):
        s = sigmoid(b Wr)                            (float32 router over all experts)
        chosen = top-k of s + bias                   (noaux_tc, n_group 1: plain top-k)
        g_i = s_i / (sum_{j in chosen} s_j + 1e-20) * routed_scaling_factor, i in chosen
        x = x + sum_{i in chosen, held here} g_i * E_i(b) + S(b)
            E_i, S: SwiGLU experts (S, the shared ones, of width n_shared * moe_intermediate)
    logits = (RMSNorm(x) * final_norm) U^T           (untied output head U)

Departures from the published code, each the program's too:

* RoPE in half rotation (GPT-NeoX), where the published code interleaves
  pairs: with seeded weights this is a permutation of ``Wq``'s rope columns
  and ``Wkv_a``'s, so the same function of other weights.
* The held share: the file's ``n_routed_experts`` experts of each MoE layer
  are held here, the ``expert_parallel`` rank's ids of
  ``n_routed_experts * size``; the router keeps every output and top-k, and
  what the experts held elsewhere would add is left out (the other ranks
  add it in the deployment).
* No ``rope_scaling`` (the published config sets none; listed in the file's
  ``assumed``), and a seeded non-zero correction bias, so that selection
  (score + bias) and gating (score alone) differ.

``fmt`` selects the operand format of every matrix product: ``None`` is
float32, ``"fp8"`` rounds both operands to float8 e4m3 with a per-tensor
scale first. The second is the control of the correctness check: the
nearest precision below the bfloat16 that the configuration states.

A request whose served gap is over the file's limit is read within
routing ties at its ``tie_searches`` widest gaps (``tie_gap``): bfloat16
rounding can tip a MoE layer's choice between two experts whose selection
scores lie within ``tie_margin``, and the reference takes either there.

Counts (``decode_flops``, ``decode_bytes``), for a B=1 decode step at cache
position ``pos``: every product a token needs, 2 operations a weight; a MoE
layer counts its router, its shared experts and ``k * held / n_experts``
routed experts, the held experts a token routes to on average; attention is
counted absorbed, over the latent cache (``kv_lora_rank + rope`` a position)
up to ``pos``; the output head once, the embedding's one row as bytes only.
Norms, RoPE, softmax and the routing's top-k are left out.
"""

from __future__ import annotations

import functools
import sys

import jax
import jax.numpy as jnp
import numpy as np

FP8_MAX = 448.0  # largest finite float8_e4m3fn
BIAS_STD = 0.1  # the seeded correction bias, against sigmoid scores spread about 0.2


def share(c: dict) -> tuple[int, int, int]:
    """(experts held here, all routed experts, this rank's first id)."""
    ep = c["expert_parallel"]
    held = c["n_routed_experts"]
    return held, held * ep["size"], held * ep["rank"]


def arch_fields(c: dict) -> dict:
    """The program's ``ArchConfig`` fields that the configuration file sets."""
    ep = c["expert_parallel"]
    held, n_experts, _ = share(c)
    if n_experts != c["published"]["n_routed_experts"]:
        raise ValueError(f"{held} experts on each of {ep['size']} ranks is not the published "
                         f"{c['published']['n_routed_experts']}")
    return dict(
        n_layers=c["num_hidden_layers"], d_model=c["hidden_size"], d_ff=c["intermediate_size"],
        n_heads=c["num_attention_heads"], n_kv_heads=c["num_key_value_heads"],
        vocab=c["vocab_size"], rope_theta=float(c["rope_theta"]), norm_eps=c["rms_norm_eps"],
        tie_embeddings=c["tie_word_embeddings"], attn_bias=c["attention_bias"],
        dtype=c["torch_dtype"],
        n_experts=n_experts, top_k=c["num_experts_per_tok"],
        n_shared_experts=c["n_shared_experts"], moe_d_ff=c["moe_intermediate_size"],
        first_dense_layers=c["first_k_dense_replace"], router_type=c["scoring_func"],
        routed_scale=float(c["routed_scaling_factor"]),
        ep_size=ep["size"], ep_rank=ep["rank"],
        q_lora_rank=c["q_lora_rank"] or 0, kv_lora_rank=c["kv_lora_rank"],
        qk_rope_dim=c["qk_rope_head_dim"], qk_nope_dim=c["qk_nope_head_dim"],
        v_head_dim=c["v_head_dim"],
    )


KEYS = ("hidden_size", "intermediate_size", "moe_intermediate_size", "num_hidden_layers",
        "first_k_dense_replace", "num_attention_heads", "kv_lora_rank", "qk_nope_head_dim",
        "qk_rope_head_dim", "v_head_dim", "vocab_size", "n_routed_experts", "n_shared_experts",
        "num_experts_per_tok", "routed_scaling_factor", "rms_norm_eps", "rope_theta")


def config_items(c: dict) -> tuple:
    """What the functions below take of the file, hashable: its sizes, and
    the share as ``n_experts`` (all routed experts) and ``first_held``."""
    held, n_experts, first = share(c)
    return tuple(sorted((k, c[k]) for k in KEYS)) + (
        ("n_experts", n_experts), ("first_held", first))


def _sizes(c: dict) -> dict:
    """``dict(config_items(c))`` of a file; such a dict as it is."""
    return dict(config_items(c)) if "expert_parallel" in c else c


def shapes(c: dict) -> dict:
    """Leaf shapes in the program's parameter tree (float32 for the router)."""
    c = _sizes(c)
    E, V, H, R = c["hidden_size"], c["vocab_size"], c["num_attention_heads"], c["kv_lora_rank"]
    Dn, Dr, Dv = c["qk_nope_head_dim"], c["qk_rope_head_dim"], c["v_head_dim"]
    L0 = c["first_k_dense_replace"]
    L1 = c["num_hidden_layers"] - L0
    held, X = c["n_routed_experts"], c["n_experts"]
    F, Fs = c["moe_intermediate_size"], c["moe_intermediate_size"] * c["n_shared_experts"]

    def attn(n):
        return {"wq": (n, E, H, Dn + Dr), "wkv_a": (n, E, R + Dr), "kv_ln": (n, R),
                "wkv_b": (n, R, H, Dn + Dv), "wo": (n, H, Dv, E)}

    Fd = c["intermediate_size"]
    return {
        "embed": (V, E), "unembed": (V, E), "final_norm": (E,),
        "blocks": {
            "g0": {"ln1": (L0, E), "ln2": (L0, E), "attn": attn(L0),
                   "ffn": {"wg": (L0, E, Fd), "wu": (L0, E, Fd), "wd": (L0, Fd, E)}},
            "g1": {"ln1": (L1, E), "ln2": (L1, E), "attn": attn(L1),
                   "ffn": {"w_router": (L1, E, X), "router_bias": (L1, X),
                           "wg": (L1, held, E, F), "wu": (L1, held, E, F),
                           "wd": (L1, held, F, E),
                           "ws_g": (L1, E, Fs), "ws_u": (L1, E, Fs), "ws_d": (L1, Fs, E)}},
        },
    }


FLOAT32 = ("w_router", "router_bias")  # the program keeps the router in float32


def _fan_in(name: str, shape: tuple) -> int:
    """Fan-in of one layer's slice (``shape`` without the layer axis)."""
    if name == "wo":
        return shape[0] * shape[1]
    if len(shape) == 3 and name in ("wg", "wu", "wd"):  # (held, in, out)
        return shape[1]
    return shape[0]


def flat(tree, prefix: str = "") -> dict:
    """``{"a/b/c": leaf}`` in sorted key order."""
    out = {}
    for k in sorted(tree):
        v = tree[k]
        path = f"{prefix}/{k}" if prefix else str(k)
        if isinstance(v, dict):
            out.update(flat(v, path))
        else:
            out[path] = v
    return out


def unflat(d: dict) -> dict:
    out: dict = {}
    for path, v in d.items():
        node = out
        *head, last = path.split("/")
        for k in head:
            node = node.setdefault(k, {})
        node[last] = v
    return out


@functools.partial(jax.jit, static_argnums=(0, 2))
def _make(c_items: tuple, seed: jax.Array, dtype_name: str):
    c = dict(c_items)
    key = jax.random.PRNGKey(seed)
    out = {}
    for i, (path, shape) in enumerate(flat(shapes(c)).items()):
        name = path.rsplit("/", 1)[-1]
        dtype = jnp.dtype("float32" if name in FLOAT32 else dtype_name)
        k = jax.random.fold_in(key, i)
        if name.endswith("norm") or name.endswith("_ln") or name in ("ln1", "ln2"):
            out[path] = jnp.ones(shape, dtype)
        elif name in ("embed", "unembed"):
            out[path] = (0.02 * jax.random.normal(k, shape, jnp.float32)).astype(dtype)
        else:
            scale = BIAS_STD if name == "router_bias" else 1.0 / np.sqrt(_fan_in(name, shape[1:]))

            # one layer at a time: a layer's float32 draw is the only temporary
            def layer(j, k=k, shape=shape, scale=scale, dtype=dtype):
                w = jax.random.normal(jax.random.fold_in(k, j), shape[1:], jnp.float32)
                return (w * scale).astype(dtype)

            out[path] = jax.lax.map(layer, jnp.arange(shape[0]))
    return unflat(out)


def make_params(c: dict, seed: int, dtype: str = "bfloat16") -> dict:
    """Every weight from ``seed`` (31-bit), on the device, in one jitted call."""
    return _make(config_items(c), jnp.uint32(seed), dtype)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _q(x, fmt):
    if fmt is None:
        return x.astype(jnp.float32)
    if fmt != "fp8":
        raise ValueError(f"unknown operand format {fmt!r}")
    x = x.astype(jnp.float32)
    scale = FP8_MAX / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    return (x * scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) / scale


def _mm(spec, a, b, fmt):
    return jnp.einsum(spec, _q(a, fmt), _q(b, fmt), precision=jax.lax.Precision.HIGHEST)


def rmsnorm(x, w, eps):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w.astype(jnp.float32)


def rope(x, theta):
    """x (S, heads, D); positions 0..S-1; half rotation."""
    s, d = x.shape[0], x.shape[-1]
    half = d // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def attention(x, p, c, fmt):
    """The MLA sublayer's output for x (S, E), a = RMSNorm(x) * ln1 given."""
    eps, theta = c["rms_norm_eps"], float(c["rope_theta"])
    R, Dn = c["kv_lora_rank"], c["qk_nope_head_dim"]
    s = x.shape[0]
    q = _mm("se,ehd->shd", x, p["wq"], fmt)
    q = jnp.concatenate([q[..., :Dn], rope(q[..., Dn:], theta)], axis=-1)
    kv = _mm("se,ek->sk", x, p["wkv_a"], fmt)
    lat = rmsnorm(kv[:, :R], p["kv_ln"], eps)
    k_r = rope(kv[:, None, R:], theta)  # (S, 1, rope)
    kvb = _mm("sk,khd->shd", lat, p["wkv_b"], fmt)
    k = jnp.concatenate([kvb[..., :Dn], jnp.broadcast_to(k_r, kvb.shape[:2] + k_r.shape[2:])],
                        axis=-1)
    v = kvb[..., Dn:]
    sc = _mm("shd,thd->hst", q, k, fmt) / np.sqrt(q.shape[-1])
    causal = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
    pr = jax.nn.softmax(jnp.where(causal[None], sc, -jnp.inf), axis=-1)
    o = _mm("hst,thd->shd", pr, v, fmt)
    return _mm("shd,hde->se", o, p["wo"], fmt)


def swiglu(x, wg, wu, wd, fmt):
    return _mm("sf,fe->se", jax.nn.silu(_mm("se,ef->sf", x, wg, fmt)) * _mm("se,ef->sf", x, wu, fmt),
               wd, fmt)


def route(b, p, c, fmt):
    """Gates (S, n_experts) over every expert: 0 where the token did not route."""
    return _route(b, p, c, fmt, None)[0]


def _route(b, p, c, fmt, swap):
    """``route``, and with ``swap`` = (row, on, tie) one row's choice at a
    near-tie: at ``row``, where ``on`` and its k-th and (k+1)-th selection
    scores lie within ``tie``, the (k+1)-th expert takes the k-th's place.
    Also that row's margin, its k-th score less its (k+1)-th (None without
    ``swap``)."""
    k, scale = c["num_experts_per_tok"], float(c["routed_scaling_factor"])
    s = jax.nn.sigmoid(_mm("se,ex->sx", b, p["w_router"], fmt))
    sel = s + p["router_bias"].astype(jnp.float32)
    if swap is None:
        _, idx = jax.lax.top_k(sel, k)
        margin = None
    else:
        row, on, tie = swap
        top, idx = jax.lax.top_k(sel, k + 1)
        margin = top[row, k - 1] - top[row, k]
        flip = (jnp.arange(s.shape[0]) == row) & on & (margin < tie)
        alt = jnp.concatenate([idx[:, : k - 1], idx[:, k:]], axis=1)
        idx = jnp.where(flip[:, None], alt, idx[:, :k])
    g = jnp.take_along_axis(s, idx, axis=-1)
    g = g / (jnp.sum(g, axis=-1, keepdims=True) + 1e-20) * scale
    return jnp.zeros_like(s).at[jnp.arange(s.shape[0])[:, None], idx].add(g), margin


def moe(b, p, c, fmt=None):
    """The MoE sublayer for b (S, E): the held experts' part and the shared experts."""
    return _moe(b, p, c, fmt, None)[0]


def _moe(b, p, c, fmt, swap):
    held, first = c["n_routed_experts"], c["first_held"]
    gates, margin = _route(b, p, c, fmt, swap)
    gates = jax.lax.dynamic_slice_in_dim(gates, first, held, axis=1)  # (S, held)
    hg = _mm("se,jef->sjf", b, p["wg"], fmt)
    hu = _mm("se,jef->sjf", b, p["wu"], fmt)
    y = _mm("sjf,jfe->sje", jax.nn.silu(hg) * hu, p["wd"], fmt)
    return jnp.einsum("sj,sje->se", gates, y, precision=jax.lax.Precision.HIGHEST) \
        + swiglu(b, p["ws_g"], p["ws_u"], p["ws_d"], fmt), margin


def _layer(x, p, c, fmt, dense: bool, swap=None):
    eps = c["rms_norm_eps"]
    x = x + attention(rmsnorm(x, p["ln1"], eps), p["attn"], c, fmt)
    b = rmsnorm(x, p["ln2"], eps)
    f = p["ffn"]
    if dense:
        return x + swiglu(b, f["wg"], f["wu"], f["wd"], fmt), None
    y, margin = _moe(b, f, c, fmt, swap)
    return x + y, margin


def hidden(params, tokens, c, fmt=None):
    """Final normed hidden states (S, E) of one sequence, float32. ``c`` is
    the file, or ``dict(config_items(file))``."""
    return _forward(params, tokens, _sizes(c), fmt, None)[0]


def _forward(params, tokens, c, fmt, swaps):
    """``hidden``; with ``swaps`` = (row, on (one flag a MoE layer), tie),
    each MoE layer's choice at ``row`` as ``_route`` takes it, and that
    row's margins (a MoE layer each)."""
    x = jnp.take(params["embed"], tokens, axis=0).astype(jnp.float32)
    x, _ = jax.lax.scan(lambda x, p: (_layer(x, p, c, fmt, True)[0], None), x,
                        params["blocks"]["g0"])
    if swaps is None:
        x, margins = jax.lax.scan(lambda x, p: (_layer(x, p, c, fmt, False)[0], None), x,
                                  params["blocks"]["g1"])
    else:
        row, on, tie = swaps

        def body(x, xs):
            p, flag = xs
            return _layer(x, p, c, fmt, False, (row, flag, tie))

        x, margins = jax.lax.scan(body, x, (params["blocks"]["g1"], on))
    return rmsnorm(x, params["final_norm"], c["rms_norm_eps"]), margins


def logits_at(params, h, c, fmt=None):
    return _mm("se,ve->sv", h, params["unembed"], fmt)


# ---------------------------------------------------------------------------
# serving: the gap of each served token below the reference's best
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnums=(2, 3, 4))
def _served_logits(params, tokens, first, c_items, fmt):
    """Logits (m, V) for the positions that chose the last m tokens."""
    c = dict(c_items)
    with jax.default_matmul_precision("highest"):
        h = hidden(params, tokens, c, fmt)
        h = jax.lax.dynamic_slice_in_dim(h, first, tokens.shape[0] - first, axis=0)
        return logits_at(params, h, c, fmt)


@functools.partial(jax.jit, static_argnums=(2,))
def _row_logits(params, tokens, c_items, row, on, tie):
    """Float32 logits (V,) at ``row`` of ``tokens``, with that row's routing
    near-ties chosen by ``on`` (``_forward``'s ``swaps``), and the row's
    margins, a MoE layer each. ``row`` is traced: one compile a length."""
    c = dict(c_items)
    with jax.default_matmul_precision("highest"):
        h, margins = _forward(params, tokens, c, None, (row, on, tie))
        return logits_at(params, jax.lax.dynamic_slice_in_dim(h, row, 1, axis=0), c)[0], margins


def tie_gap(params, tokens, row, tok, c: dict) -> float:
    """The gap by which ``tok`` lies below the best at ``row`` of ``tokens``,
    under the routing of that row nearest to it among those a bfloat16 run
    can take: at each MoE layer whose k-th and (k+1)-th selection scores lie
    within the file's ``tie_margin``, either of the two. A greedy search,
    layer by layer from the first: a layer's other choice is kept where it
    narrows the gap, and a later layer's margin is the one the earlier
    choices leave. Earlier rows keep the reference's own routing."""
    n = c["num_hidden_layers"] - c["first_k_dense_replace"]
    tie = np.float32(c["tie_margin"])
    items, tokens, row = config_items(c), jnp.asarray(tokens), jnp.int32(row)

    def gap(on):
        lg, margins = _row_logits(params, tokens, items, row, jnp.asarray(on), tie)
        lg = np.asarray(lg, np.float64)
        return float(lg.max() - lg[tok]), np.asarray(margins)

    on = np.zeros(n, bool)
    best, margins = gap(on)
    for layer in range(n):
        if best <= 0.0:
            break
        if margins[layer] >= tie:
            continue
        trial = on.copy()
        trial[layer] = True
        g, m = gap(trial)
        if g < best:
            on, best, margins = trial, g, m
    return best


def served_gap(gaps, c: dict) -> float:
    """A request's served gap from its per-position ``gaps``: the mean, or
    the widest scaled onto the same limit, whichever is larger,

        max(mean, widest * check["served_gap"] / widest_gap_limit)

    so it lies within the file's ``check["served_gap"]`` exactly when the
    mean does and the widest gap lies within its ``widest_gap_limit``.

    The mean, where ``dense_gqa`` takes the widest alone: a bfloat16 run
    routes a token to another expert wherever two selection scores lie
    closer than its rounding, and with random weights such a flip moves a
    position's logits about as far as float8 operands do; the share of
    positions moved, and so the mean, tells the two apart. The widest,
    beside it: one wrong token in an answer lies a whole spread of the
    logits below the best, which a long answer's mean dilutes below the
    limit. ``served_gaps`` hands in the gaps of a request over the limit
    with its widest read within routing ties, so a flip is not read as a
    wrong token. The readings are the file's ``check_why``.
    """
    gaps = np.asarray(gaps, np.float64)
    scale = c["check"]["served_gap"] / c["widest_gap_limit"]
    return float(max(gaps.mean(), gaps.max() * scale))


def _within_ties(params, tokens, first, chosen, gaps, c: dict):
    """``gaps`` of a request whose ``served_gap`` is over the limit with its
    ``tie_searches`` widest (non-zero) gaps replaced by their ``tie_gap``
    (never raised), and how many were searched. A request within the limit
    is not searched: the search only narrows gaps."""
    gaps = np.array(gaps, np.float64)
    if served_gap(gaps, c) <= c["check"]["served_gap"]:
        return gaps, 0
    widest = [j for j in np.argsort(-gaps, kind="stable")[: c["tie_searches"]] if gaps[j] > 0]
    for j in widest:
        found = tie_gap(params, tokens, first + j, int(chosen[j]), c)
        print(f"served gap at answer position {j}: {gaps[j]:.4f}, {found:.4f} within routing ties",
              file=sys.stderr, flush=True)
        gaps[j] = min(gaps[j], found)
    return gaps, len(widest)


def served_gaps(params, prompt, served, c: dict, fmt=None) -> dict:
    """For one request: ``served_gap`` (``served_gap()`` of the gaps by
    which the served tokens' float32 logits lie below the best, read within
    ties where the request is over the limit), with its parts ``mean_gap``,
    ``widest_gap``, both before the search (``first_mean_gap``,
    ``first_widest_gap``) and the positions searched (``tie_searched``);
    with ``fmt``, ``control_gap`` and its parts, the same for the tokens
    ``fmt`` would have put first."""
    prompt = np.asarray(prompt, np.int32)
    served = np.asarray(served, np.int32)
    tokens = np.concatenate([prompt, served[:-1]])
    first = len(prompt) - 1
    ref = np.asarray(_served_logits(params, jnp.asarray(tokens), first, config_items(c), None),
                     np.float64)
    best = ref.max(axis=1)

    def parts(chosen, prefix=""):
        raw = best - ref[np.arange(len(served)), chosen]
        gaps, searched = _within_ties(params, tokens, first, chosen, raw, c)
        return {f"{prefix}mean_gap": float(gaps.mean()), f"{prefix}widest_gap": float(gaps.max()),
                f"{prefix}first_mean_gap": float(raw.mean()), f"{prefix}first_widest_gap": float(raw.max()),
                f"{prefix}tie_searched": searched,
                f"{prefix}gap": served_gap(gaps, c)}

    out = parts(served)
    out["served_gap"] = out.pop("gap")
    if fmt is not None:
        low = np.asarray(_served_logits(params, jnp.asarray(tokens), first, config_items(c), fmt))
        out.update(parts(low.argmax(axis=1), "control_"))
    return out


# ---------------------------------------------------------------------------
# operations and bytes of one B=1 decode step
# ---------------------------------------------------------------------------


def _per_token(c: dict) -> dict:
    """Weights a decode token multiplies by, by kind, and the latent cache's width."""
    c = _sizes(c)
    E, V, H, R = c["hidden_size"], c["vocab_size"], c["num_attention_heads"], c["kv_lora_rank"]
    Dn, Dr, Dv = c["qk_nope_head_dim"], c["qk_rope_head_dim"], c["v_head_dim"]
    held, X = c["n_routed_experts"], c["n_experts"]
    F = c["moe_intermediate_size"]
    L0 = c["first_k_dense_replace"]
    L1 = c["num_hidden_layers"] - L0
    # absorbed: q_nope through Wkv_b's key half, the latent output through its value half
    attn = E * H * (Dn + Dr) + E * (R + Dr) + H * Dn * R + H * R * Dv + H * Dv * E
    routed = c["num_experts_per_tok"] * held / X * 3 * E * F
    return {
        "bf16": (c["num_hidden_layers"] * attn + L0 * 3 * E * c["intermediate_size"]
                 + L1 * (3 * E * F * c["n_shared_experts"] + routed) + V * E),
        "router": L1 * E * X,  # float32
        "bias": L1 * X,  # float32, read, not multiplied
        "small": c["num_hidden_layers"] * (2 * E + R) + 2 * E,  # norms, the embedding's row
        "latent": R + Dr, "H": H, "L": c["num_hidden_layers"],
    }


def decode_flops(c: dict, pos: int) -> float:
    """One token at absolute position ``pos`` (it sees ``pos + 1`` positions)."""
    w = _per_token(c)
    R = c["kv_lora_rank"]
    # scores over the latent and the rope key, then the latent-weighted sum, per head
    attn = 2 * w["H"] * (R + c["qk_rope_head_dim"] + R) * w["L"] * (pos + 1)
    return float(2 * (w["bf16"] + w["router"]) + attn)


def decode_bytes(c: dict, pos: int, itemsize: int = 2) -> float:
    """The weights a token reads once (the routed experts it routes to here,
    on average) plus the request's latent cache up to ``pos``."""
    w = _per_token(c)
    return float(itemsize * (w["bf16"] + w["small"]) + 4 * (w["router"] + w["bias"])
                 + itemsize * w["L"] * (pos + 1) * w["latent"])
