"""Plain reference for a dense decoder with grouped-query attention and
per-head q/k RMSNorm: the Qwen3 block (arXiv:2505.09388; the layer equations
of ``Qwen3ForCausalLM`` in its public ``config.json``).

Straight ``jax.numpy`` in float32 with matmuls at ``highest`` precision, one
sequence at a time, no kernels, no cache, no batching. It imports nothing of
the program under test. It also makes the weights that the benchmark hands
to the program, from the seed alone, in the program's tree layout; the
reference makes them again for itself after the window.

    x   = E[tokens]
    per layer l:
      a = RMSNorm(x) * ln1[l]
      q = RMSNorm_head(a Wq) * q_norm,  k = RMSNorm_head(a Wk) * k_norm,  v = a Wv
      q, k = RoPE(q), RoPE(k)                        (half rotation, rope_theta)
      x = x + softmax(q k^T / sqrt(head_dim) + causal mask) v Wo   (GQA)
      b = RMSNorm(x) * ln2[l]
      x = x + (silu(b Wg) * (b Wu)) Wd
    logits = (RMSNorm(x) * final_norm) E^T           (tied embeddings)

``fmt`` selects the operand format of every matrix product: ``None`` is
float32, ``"fp8"`` rounds both operands to float8 e4m3 with a per-tensor
scale first. The second is the control of the correctness check: the
nearest precision below the bfloat16 that the configuration states.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

# The operation and byte counts of this architecture's steps, for the metric readers.
from benchmarks.chip.flops import decode_bytes, decode_flops, train_step_flops  # noqa: F401

FP8_MAX = 448.0  # largest finite float8_e4m3fn


def arch_fields(c: dict) -> dict:
    """The program's ``ArchConfig`` fields that the configuration file sets."""
    return dict(
        n_layers=c["num_hidden_layers"], d_model=c["hidden_size"], d_ff=c["intermediate_size"],
        n_heads=c["num_attention_heads"], n_kv_heads=c["num_key_value_heads"],
        head_dim=c["head_dim"], vocab=c["vocab_size"], rope_theta=float(c["rope_theta"]),
        norm_eps=c["rms_norm_eps"], tie_embeddings=c["tie_word_embeddings"],
        attn_bias=c["attention_bias"], dtype=c["torch_dtype"],
    )


def dims(c: dict) -> dict:
    return {
        "E": c["hidden_size"], "F": c["intermediate_size"], "L": c["num_hidden_layers"],
        "H": c["num_attention_heads"], "KV": c["num_key_value_heads"], "D": c["head_dim"],
        "V": c["vocab_size"],
    }


def shapes(c: dict) -> dict:
    """Leaf shapes in the program's parameter tree."""
    d = dims(c)
    E, F, L, H, KV, D, V = (d[k] for k in ("E", "F", "L", "H", "KV", "D", "V"))
    return {
        "embed": (V, E),
        "final_norm": (E,),
        "blocks": {"g0": {
            "ln1": (L, E), "ln2": (L, E),
            "attn": {"wq": (L, E, H, D), "wk": (L, E, KV, D), "wv": (L, E, KV, D),
                     "wo": (L, H, D, E), "q_norm": (L, D), "k_norm": (L, D)},
            "ffn": {"wg": (L, E, F), "wu": (L, E, F), "wd": (L, F, E)},
        }},
    }


def _fan_in(path: str, shape: tuple) -> int:
    name = path.rsplit("/", 1)[-1]
    if name == "wo":
        return shape[1] * shape[2]
    return shape[1]


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
    dtype = jnp.dtype(dtype_name)
    key = jax.random.PRNGKey(seed)
    out = {}
    for i, (path, shape) in enumerate(flat(shapes(c)).items()):
        name = path.rsplit("/", 1)[-1]
        k = jax.random.fold_in(key, i)
        if name.endswith("norm") or name in ("ln1", "ln2"):
            w = jnp.ones(shape, jnp.float32)
        elif name == "embed":
            w = 0.02 * jax.random.normal(k, shape, jnp.float32)
        else:
            w = jax.random.normal(k, shape, jnp.float32) / np.sqrt(_fan_in(path, shape))
        out[path] = w.astype(dtype)
    return unflat(out)


def make_params(c: dict, seed: int, dtype: str = "bfloat16") -> dict:
    """Every weight from ``seed`` (31-bit), on the device, in one jitted call."""
    items = tuple(sorted((k, v) for k, v in c.items() if k in
                         ("hidden_size", "intermediate_size", "num_hidden_layers",
                          "num_attention_heads", "num_key_value_heads", "head_dim",
                          "vocab_size")))
    return _make(items, jnp.uint32(seed), dtype)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _q(x, fmt):
    if fmt is None:
        return x.astype(jnp.float32)
    if fmt != "fp8":
        raise ValueError(f"unknown operand format {fmt!r}")
    x = x.astype(jnp.float32)
    scale = jax.lax.stop_gradient(FP8_MAX / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30))
    rounded = (x * scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) / scale
    # forward in fp8, gradient straight through (as fp8 training rounds it)
    return x + jax.lax.stop_gradient(rounded - x)


def _mm(spec, a, b, fmt):
    return jnp.einsum(spec, _q(a, fmt), _q(b, fmt), precision=jax.lax.Precision.HIGHEST)


def rmsnorm(x, w, eps):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w.astype(jnp.float32)


def rope(x, theta):
    """x (S, heads, D); positions 0..S-1."""
    s, d = x.shape[0], x.shape[-1]
    half = d // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _layer(x, p, c, fmt):
    d = dims(c)
    eps, theta = c["rms_norm_eps"], float(c["rope_theta"])
    s = x.shape[0]
    g = d["H"] // d["KV"]
    a = rmsnorm(x, p["ln1"], eps)
    q = _mm("se,ehd->shd", a, p["attn"]["wq"], fmt)
    k = _mm("se,ehd->shd", a, p["attn"]["wk"], fmt)
    v = _mm("se,ehd->shd", a, p["attn"]["wv"], fmt)
    q = rope(rmsnorm(q, p["attn"]["q_norm"], eps), theta)
    k = rope(rmsnorm(k, p["attn"]["k_norm"], eps), theta)
    q = q.reshape(s, d["KV"], g, d["D"])
    sc = _mm("skgd,tkd->kgst", q, k, fmt) / np.sqrt(d["D"])
    causal = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
    sc = jnp.where(causal[None, None], sc, -jnp.inf)
    pr = jax.nn.softmax(sc, axis=-1)
    o = _mm("kgst,tkd->skgd", pr, v, fmt).reshape(s, d["H"], d["D"])
    x = x + _mm("shd,hde->se", o, p["attn"]["wo"], fmt)
    b = rmsnorm(x, p["ln2"], eps)
    gt = _mm("se,ef->sf", b, p["ffn"]["wg"], fmt)
    up = _mm("se,ef->sf", b, p["ffn"]["wu"], fmt)
    return x + _mm("sf,fe->se", jax.nn.silu(gt) * up, p["ffn"]["wd"], fmt)


def hidden(params, tokens, c, fmt=None):
    """Final normed hidden states (S, E) of one sequence, float32."""
    x = jnp.take(params["embed"], tokens, axis=0).astype(jnp.float32)
    blocks = params["blocks"]["g0"]

    def body(x, p):
        return _layer(x, p, c, fmt), None

    x, _ = jax.lax.scan(body, x, blocks)
    return rmsnorm(x, params["final_norm"], c["rms_norm_eps"])


def logits_at(params, h, c, fmt=None):
    return _mm("se,ve->sv", h, params["embed"], fmt)


# ---------------------------------------------------------------------------
# training: loss, gradients, AdamW, as the configuration states them
# ---------------------------------------------------------------------------


def _row_loss(params, tokens, labels, c, fmt):
    lg = logits_at(params, hidden(params, tokens, c, fmt), c, fmt)
    lse = jax.nn.logsumexp(lg, axis=-1)
    gold = jnp.take_along_axis(lg, labels[:, None], axis=-1)[:, 0]
    return jnp.mean(lse - gold)


@functools.partial(jax.jit, static_argnums=(3, 4))
def _loss_and_grad(params, tokens, labels, c_items, fmt):
    """Mean loss over all rows and its gradient, one row at a time."""
    c = dict(c_items)
    vg = jax.value_and_grad(_row_loss)

    def body(carry, row):
        tot, acc = carry
        loss, g = vg(params, row[0], row[1], c, fmt)
        return (tot + loss, jax.tree_util.tree_map(jnp.add, acc, g)), None

    zero = jax.tree_util.tree_map(jnp.zeros_like, params)
    with jax.default_matmul_precision("highest"):
        (tot, acc), _ = jax.lax.scan(body, (jnp.float32(0), zero), (tokens, labels))
    n = tokens.shape[0]
    return tot / n, jax.tree_util.tree_map(lambda g: g / n, acc)


def config_items(c: dict) -> tuple:
    keep = ("hidden_size", "intermediate_size", "num_hidden_layers", "num_attention_heads",
            "num_key_value_heads", "head_dim", "vocab_size", "rms_norm_eps", "rope_theta")
    return tuple(sorted((k, c[k]) for k in keep))


def lr_at(step: int, opt: dict) -> float:
    """Linear warm-up to ``peak_lr`` over ``warmup`` steps, then cosine."""
    peak, warm = opt["peak_lr"], opt["warmup"]
    if step < warm:
        return peak * step / max(warm, 1)
    frac = min(max((step - warm) / max(opt["total_steps"] - warm, 1), 0.0), 1.0)
    floor = opt["lr_floor"]
    return peak * (floor + (1 - floor) * 0.5 * (1 + np.cos(np.pi * frac)))


def train_steps(c: dict, opt: dict, params0: dict, batches: list, fmt=None) -> dict:
    """Run ``len(batches)`` AdamW steps from ``params0`` (weights as served).

    Mixed precision as the configuration states it: the weights are held in
    ``torch_dtype`` and the optimizer keeps a float32 master copy. Each step's
    loss and gradient are taken at the master rounded to ``torch_dtype`` (the
    weights as they stand), in float32 arithmetic; the update goes to the
    master. An early warm-up step moves the master by less than half a
    bfloat16 step, so the weights, and the next loss, stay where they were.

    Returns per-step losses, the first step's clipped gradient and the
    float32 master weights after the last step, each as ``{path: array}``.
    """
    wdt = jnp.dtype(c["torch_dtype"])
    master = jax.tree_util.tree_map(lambda p: p.astype(jnp.float32), params0)
    mu = jax.tree_util.tree_map(jnp.zeros_like, master)
    nu = jax.tree_util.tree_map(jnp.zeros_like, master)
    b1, b2, eps, wd = opt["b1"], opt["b2"], opt["eps"], opt["weight_decay"]
    losses, first_grad = [], None
    for i, (tokens, labels) in enumerate(batches):
        weights = jax.tree_util.tree_map(lambda w: w.astype(wdt).astype(jnp.float32), master)
        loss, g = _loss_and_grad(weights, jnp.asarray(tokens), jnp.asarray(labels),
                                 config_items(c), fmt)
        del weights
        gn = jnp.sqrt(sum(jnp.sum(x * x) for x in jax.tree_util.tree_leaves(g)))
        scale = jnp.minimum(1.0, opt["clip_norm"] / jnp.maximum(gn, 1e-9))
        g = jax.tree_util.tree_map(lambda x: x * scale, g)
        if first_grad is None:
            first_grad = flat(jax.device_get(g))
        t = i + 1
        lr = lr_at(i, opt)
        mu = jax.tree_util.tree_map(lambda m, x: b1 * m + (1 - b1) * x, mu, g)
        nu = jax.tree_util.tree_map(lambda v, x: b2 * v + (1 - b2) * x * x, nu, g)
        master = jax.tree_util.tree_map(
            lambda w, m, v: w - lr * ((m / (1 - b1 ** t)) / (jnp.sqrt(v / (1 - b2 ** t)) + eps)
                                      + wd * w),
            master, mu, nu)
        losses.append(float(loss))
    return {"losses": losses, "first_grad": first_grad, "master": flat(jax.device_get(master))}


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


def served_gaps(params, prompt, served, c: dict, fmt=None) -> dict:
    """For one request: the widest gap by which a served token's float32
    logit lies below the best, and, with ``fmt``, the widest gap of the token
    that ``fmt`` would have put first at each of those positions.
    """
    prompt = np.asarray(prompt, np.int32)
    served = np.asarray(served, np.int32)
    tokens = np.concatenate([prompt, served[:-1]])
    first = len(prompt) - 1
    ref = np.asarray(_served_logits(params, jnp.asarray(tokens), first, config_items(c), None),
                     np.float64)
    best = ref.max(axis=1)
    out = {"served_gap": float(np.max(best - ref[np.arange(len(served)), served]))}
    if fmt is not None:
        low = np.asarray(_served_logits(params, jnp.asarray(tokens), first, config_items(c), fmt))
        pick = low.argmax(axis=1)
        out["control_gap"] = float(np.max(best - ref[np.arange(len(served)), pick]))
    return out
