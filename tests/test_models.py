"""Per-arch smoke tests (reduced configs) + decode-vs-train consistency."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import SHAPES, get_config, get_smoke_config, list_archs
from repro.models import Model, input_specs


def make_batch(cfg, b=2, s=32, seed=1):
    tk = jax.random.randint(jax.random.PRNGKey(seed), (b, s), 0, cfg.vocab, jnp.int32)
    batch = {"tokens": tk, "labels": jnp.roll(tk, -1, axis=1)}
    if cfg.vision_prefix:
        batch["vis_embeds"] = (
            jax.random.normal(jax.random.PRNGKey(2), (b, cfg.vision_prefix, cfg.d_model), jnp.bfloat16) * 0.1
        )
    if cfg.encdec:
        batch["enc_frames"] = (
            jax.random.normal(jax.random.PRNGKey(3), (b, cfg.enc_seq, cfg.d_model), jnp.bfloat16) * 0.1
        )
    return batch


@pytest.mark.parametrize("arch", list_archs())
def test_smoke_loss_prefill_decode(arch):
    cfg = get_smoke_config(arch)
    m = Model(cfg)
    params, axes = m.init(jax.random.PRNGKey(0))
    b, s = 2, 32
    batch = make_batch(cfg, b, s)
    loss = m.loss(params, batch)
    assert np.isfinite(float(loss))
    # near ln(vocab) at init = sane logits scale
    assert abs(float(loss) - np.log(cfg.vocab)) < 1.5, float(loss)
    pb = dict(batch)
    pb.pop("labels")
    smax = s + cfg.vision_prefix + 4
    logits, caches = m.prefill(params, pb, s_max=smax)
    assert logits.shape == (b, cfg.vocab)
    assert np.all(np.isfinite(np.asarray(logits, np.float32)))
    lg2, caches = m.decode(
        params, caches, jnp.ones((b, 1), jnp.int32), jnp.asarray(s + cfg.vision_prefix, jnp.int32)
    )
    assert lg2.shape == (b, 1, cfg.vocab)
    assert np.all(np.isfinite(np.asarray(lg2, np.float32)))


@pytest.mark.parametrize(
    "arch",
    ["qwen3-1.7b", "deepseek-v3-671b", "moonlight-16b-a3b", "hymba-1.5b", "xlstm-1.3b",
     "whisper-tiny"],
)
def test_decode_matches_teacher_forcing(arch):
    """Greedy decode logits at position t == train-forward logits at t.

    This is the strongest correctness check for the cache paths (GQA DUS
    cache, MLA absorbed decode, SSD state step, mLSTM state step, cross
    caches): the incremental path must reproduce the parallel path.
    """
    cfg = get_smoke_config(arch)
    m = Model(cfg)
    params, _ = m.init(jax.random.PRNGKey(0))
    b, s = 2, 24
    batch = make_batch(cfg, b, s, seed=5)
    pb = dict(batch)
    pb.pop("labels")
    # parallel (teacher-forced) final hidden -> logits at every position
    from repro.models import encdec as encdec_mod
    from repro.models import transformer as tf
    from repro.models.layers import embed, pdtype, unembed_logits

    if cfg.encdec:
        enc_out = encdec_mod.encode(params, pb["enc_frames"].astype(pdtype(cfg)), cfg)
        h = encdec_mod.decode_train(params, pb["tokens"], enc_out, cfg)
    else:
        x = embed(pb["tokens"], params["embed"]).astype(pdtype(cfg))
        if cfg.vision_prefix:
            x = jnp.concatenate([pb["vis_embeds"].astype(x.dtype), x], axis=1)
        h = tf.forward_train(params, x, cfg)
    unemb = params["embed"] if cfg.tie_embeddings else params["unembed"]
    want = unembed_logits(h[:, -1], unemb)  # logits after the final token

    # incremental: prefill all but the last two tokens, decode them one-by-one
    cut = s - 2
    pb2 = dict(pb)
    pb2["tokens"] = pb["tokens"][:, :cut]
    smax = s + cfg.vision_prefix
    _, caches = m.prefill(params, pb2, s_max=smax)
    lg = None
    for i in range(cut, s):
        pos = jnp.asarray(i + cfg.vision_prefix, jnp.int32)
        lg, caches = m.decode(params, caches, pb["tokens"][:, i : i + 1], pos)
    got = lg[:, 0]
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), atol=0.1, rtol=0.05
    )


def test_param_counts_match_assignment():
    """Full configs land near their nominal sizes (sanity on the zoo)."""
    expect = {
        "yi-34b": (30e9, 40e9),
        "qwen3-1.7b": (1.2e9, 2.5e9),
        "stablelm-12b": (10e9, 14e9),
        "command-r-plus-104b": (95e9, 115e9),
        "granite-moe-1b-a400m": (0.8e9, 1.6e9),
        "deepseek-v3-671b": (600e9, 720e9),
        "moonlight-16b-a3b": (5.0e9, 5.3e9),  # one expert-parallel rank of four
        "hymba-1.5b": (1.2e9, 2.2e9),
        "xlstm-1.3b": (1.0e9, 2.0e9),  # our mLSTM block carries q/k/v/og projs
        "internvl2-76b": (65e9, 80e9),
        "whisper-tiny": (2.5e7, 6e7),
    }
    for arch, (lo, hi) in expect.items():
        n = get_config(arch).param_count()
        assert lo <= n <= hi, f"{arch}: {n/1e9:.2f}B not in [{lo/1e9}, {hi/1e9}]"


def test_moe_active_params_below_total():
    cfg = get_config("deepseek-v3-671b")
    assert cfg.active_param_count() < 0.1 * cfg.param_count()


def test_input_specs_cover_all_cells():
    for arch in list_archs():
        cfg = get_config(arch)
        for shape in SHAPES.values():
            specs = input_specs(cfg, shape)
            assert "tokens" in specs
            if shape.kind == "decode":
                assert "caches" in specs and "pos" in specs


def test_window_attention_matches_full_when_window_covers():
    """A window >= seq must equal full causal attention."""
    from repro.models.attention import blockwise_attention

    rng = np.random.default_rng(0)
    b, s, kv, g, d = 1, 64, 2, 2, 16
    q = jnp.asarray(rng.standard_normal((b, s, kv, g, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, s, kv, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, s, kv, d)), jnp.float32)
    full = blockwise_attention(q, k, v, causal=True, window=0, q_block=16)
    win = blockwise_attention(q, k, v, causal=True, window=s, q_block=16)
    np.testing.assert_allclose(np.asarray(win), np.asarray(full), atol=1e-5, rtol=1e-5)


def test_chunked_recurrence_matches_naive_scan():
    """SSD/mLSTM chunk form == step-by-step recurrence."""
    from repro.models.ssm import chunked_linear_recurrence, linear_recurrence_step

    rng = np.random.default_rng(0)
    b, s, h, n, p = 2, 37, 3, 4, 5
    q = jnp.asarray(rng.standard_normal((b, s, h, n)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, s, h, n)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, s, h, p)), jnp.float32)
    log_a = jnp.asarray(-np.abs(rng.standard_normal((b, s, h))) * 0.1, jnp.float32)
    y_chunk, final = chunked_linear_recurrence(q, k, v, log_a, chunk=8)
    state = jnp.zeros((b, h, n, p))
    ys = []
    for t in range(s):
        y, state = linear_recurrence_step(
            q[:, t], k[:, t], v[:, t], jnp.exp(log_a[:, t]), state
        )
        ys.append(y)
    y_naive = jnp.stack(ys, axis=1)
    np.testing.assert_allclose(np.asarray(y_chunk), np.asarray(y_naive), atol=2e-4, rtol=2e-4)
    np.testing.assert_allclose(np.asarray(final), np.asarray(state), atol=2e-4, rtol=2e-4)


def test_slstm_runs_and_is_stable():
    from repro.models.ssm import init_slstm, slstm_apply

    p = init_slstm(jax.random.PRNGKey(0), 16, 8)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 50, 16)) * 3.0
    h = slstm_apply(p, x)
    assert h.shape == (2, 50, 8)
    assert np.all(np.isfinite(np.asarray(h)))
