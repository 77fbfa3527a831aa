"""Moonlight-16B-A3B (MLA without a query LoRA, a held-expert MoE layer) on
seeded random weights at ``smoke()`` size, against the benchmark's plain
float32 reference (``benchmarks/chip/reference/mla_moe.py``).

The program runs in float32 here, so the two differ only in the order of
their sums: logits agree to ``ATOL``. The weights come from the reference's
``make_params``, in the program's own tree.
"""

import copy
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.chip.reference import mla_moe as ref
from repro import spans
from repro.configs import get_config, get_smoke_config
from repro.models import Model
from repro.models import moe as moe_mod

ARCH = "moonlight-16b-a3b"
FILE = Path(__file__).resolve().parents[1] / "benchmarks/chip/configs/moonlight-16b-a3b-serve.json"
# float32 on both sides: the logits (up to about 0.5 at this width) differ by
# the order of float32 sums over 3 layers, under 1e-6; 1e-4 leaves room and
# still fails on any dropped, misrouted or mis-scaled expert row.
ATOL = 1e-4


def smoke_file(**over) -> dict:
    """The benchmark's configuration file at the sizes of the registry's smoke()."""
    c = json.loads(FILE.read_text())
    s = get_smoke_config(ARCH)
    c.update(hidden_size=s.d_model, intermediate_size=s.d_ff, moe_intermediate_size=s.moe_d_ff,
             num_hidden_layers=s.n_layers, num_attention_heads=s.n_heads,
             num_key_value_heads=s.n_kv_heads, kv_lora_rank=s.kv_lora_rank,
             qk_rope_head_dim=s.qk_rope_dim, qk_nope_head_dim=s.qk_nope_dim,
             v_head_dim=s.v_head_dim, vocab_size=s.vocab, num_experts_per_tok=s.top_k,
             n_routed_experts=s.held_experts, published={"n_routed_experts": s.n_experts},
             expert_parallel={"size": s.ep_size, "rank": s.ep_rank})
    c.update(over)
    return c


def program_cfg(c: dict):
    return get_config(ARCH).with_(**ref.arch_fields(c)).with_(dtype="float32")


def test_smoke_file_maps_onto_the_registry_smoke():
    cfg = get_config(ARCH).with_(**ref.arch_fields(smoke_file()))
    smoke = get_smoke_config(ARCH)
    assert cfg == smoke.with_(loss_chunk=cfg.loss_chunk)
    assert cfg.q_lora_rank == 0 and cfg.held_experts == 2 and cfg.routed_scale == 2.446


def test_benchmark_file_maps_onto_the_registry_entry():
    """The serve harness refuses any field but norm_eps that differs."""
    c = json.loads(FILE.read_text())
    assert get_config(ARCH).with_(**ref.arch_fields(c)) == get_config(ARCH)


def _ref_logits(params, tokens, c):
    with jax.default_matmul_precision("highest"):
        return np.asarray(ref.logits_at(params, ref.hidden(params, jnp.asarray(tokens), c), c))


def _prefill_decode(cfg, params, tokens, cut):
    """Program logits of positions cut-1 .. S-1: prefill tokens[:cut], then
    decode the rest one by one through the latent cache."""
    m = Model(cfg)
    lg, caches, counts = m.prefill(params, {"tokens": jnp.asarray(tokens[None, :cut])},
                                   s_max=len(tokens), counts=True)
    out = [np.asarray(lg[0])]
    step = jax.jit(lambda p, ca, t, pos: m.decode(p, ca, t, pos, counts=True))
    reads = []
    for i in range(cut, len(tokens)):
        lg, caches, c = step(params, caches, jnp.asarray(tokens[None, i:i + 1]),
                             jnp.asarray(i, jnp.int32))
        out.append(np.asarray(lg[0, 0]))
        reads.append(int(c["experts_read"]))
    return np.stack(out), int(counts["expert_rows"]), reads


@pytest.mark.parametrize("seed", [0, 1])
def test_prefill_then_decode_match_the_reference(seed):
    c = smoke_file()
    cfg = program_cfg(c)
    params = ref.make_params(c, seed, "float32")
    tokens = np.asarray(jax.random.randint(jax.random.PRNGKey(seed + 7), (24,), 0, cfg.vocab))
    cut = 16
    got, rows, reads = _prefill_decode(cfg, params, tokens, cut)
    want = _ref_logits(params, tokens, c)[cut - 1:]
    np.testing.assert_allclose(got, want, atol=ATOL, rtol=0)
    # each decode step read the held experts its token routed to, and no other
    assert all(0 <= r <= cfg.top_k * (cfg.n_layers - cfg.first_dense_layers) for r in reads)
    assert rows <= cut * cfg.top_k * (cfg.n_layers - cfg.first_dense_layers)


def test_plain_query_projection_has_no_lora():
    cfg = get_smoke_config(ARCH)
    p, _ = Model(cfg).init(jax.random.PRNGKey(0))
    attn = p["blocks"]["g1"]["attn"]
    assert attn["wq"].shape == (2, cfg.d_model, cfg.n_heads, cfg.qk_nope_dim + cfg.qk_rope_dim)
    assert "wq_a" not in attn and "q_ln" not in attn


def test_routed_scale_changes_the_result_and_is_followed():
    c1 = smoke_file(routed_scaling_factor=1.0)
    params = ref.make_params(c1, 3, "float32")
    tokens = np.arange(12) % 256
    m1, m2 = Model(program_cfg(c1)), Model(program_cfg(smoke_file()))
    batch = {"tokens": jnp.asarray(tokens[None, :])}
    one = np.asarray(m1.prefill(params, batch, s_max=12)[0][0])
    two = np.asarray(m2.prefill(params, batch, s_max=12)[0][0])
    np.testing.assert_allclose(one, _ref_logits(params, tokens, c1)[-1], atol=ATOL, rtol=0)
    assert np.max(np.abs(one - two)) > 100 * ATOL


def _moe_inputs(c, seed=5, t=24):
    """One MoE layer's weights for every expert (an uncut file) and its input."""
    uncut = dict(c, n_routed_experts=c["published"]["n_routed_experts"],
                 expert_parallel={"size": 1, "rank": 0})
    full = ref.make_params(uncut, seed, "float32")
    p = jax.tree_util.tree_map(lambda w: w[0], full["blocks"]["g1"]["ffn"])
    b = jax.random.normal(jax.random.PRNGKey(seed), (t, c["hidden_size"]), jnp.float32)
    return uncut, p, b


def _rank_slice(p, cfg, r):
    held = cfg.held_experts
    return {k: (v[r * held:(r + 1) * held] if k in moe_mod.EXPERT_WEIGHTS else v)
            for k, v in p.items()}


@pytest.mark.parametrize("path", ["prefill", "decode"])
def test_ranks_shares_add_up_to_the_uncut_layer(path):
    """Each rank computes its held experts' part plus the shared experts; the
    four parts, with the shared experts counted once, are the whole layer."""
    c = smoke_file()
    uncut, p, b = _moe_inputs(c)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(ref.moe(b, p, ref._sizes(uncut)))
        shared = np.asarray(ref.swiglu(b, p["ws_g"], p["ws_u"], p["ws_d"], None))
    cfg0 = program_cfg(c)
    total, ref_total = 0.0, 0.0
    for r in range(cfg0.ep_size):
        cfg = cfg0.with_(ep_rank=r)
        pr = _rank_slice(p, cfg, r)
        if path == "prefill":
            y, _ = moe_mod.moe_ffn(pr, b[None], cfg)
            y = y[0]
        else:  # every token its own sequence, the experts stacked as one layer
            experts = {k: pr[k][None] for k in moe_mod.EXPERT_WEIGHTS}
            y, _ = moe_mod.moe_decode(pr, b[:, None], cfg, experts, jnp.int32(0))
            y = y[:, 0]
        total = total + np.asarray(y)
        cr = dict(c, expert_parallel={"size": cfg.ep_size, "rank": r})
        with jax.default_matmul_precision("highest"):
            ref_total = ref_total + np.asarray(ref.moe(b, pr, ref._sizes(cr)))
    lead = cfg0.ep_size - 1
    np.testing.assert_allclose(total - lead * shared, want, atol=ATOL, rtol=0)
    np.testing.assert_allclose(ref_total - lead * shared, want, atol=ATOL, rtol=0)


def test_every_token_on_one_expert_drops_none():
    """All tokens route to experts 0 and 1, both held by rank 0: a capacity
    buffer of 1.25 x the even share would drop most of their rows."""
    c = smoke_file()
    cfg = program_cfg(c)
    params = ref.make_params(c, 11, "float32")
    bias = np.zeros(cfg.n_experts, np.float32)
    bias[:2] = 10.0
    ffn = params["blocks"]["g1"]["ffn"]
    ffn["router_bias"] = jnp.broadcast_to(jnp.asarray(bias), ffn["router_bias"].shape)
    tokens = np.arange(40) * 7 % cfg.vocab
    got, rows, reads = _prefill_decode(cfg, params, tokens, 36)
    want = _ref_logits(params, tokens, c)[35:]
    np.testing.assert_allclose(got, want, atol=ATOL, rtol=0)
    n_moe = cfg.n_layers - cfg.first_dense_layers
    assert rows == 36 * cfg.top_k * n_moe  # every token-expert row computed here
    assert reads == [2 * n_moe] * 4


def test_decode_reads_only_the_routed_held_experts():
    """``experts_read`` is the number of held experts the token routed to,
    layer by layer, as the reference routes it."""
    c = smoke_file()
    cfg = program_cfg(c)
    uncut, p, b = _moe_inputs(c, seed=9, t=1)
    experts = {k: p[k][:cfg.held_experts][None] for k in moe_mod.EXPERT_WEIGHTS}
    for i in range(16):
        x = jax.random.normal(jax.random.PRNGKey(100 + i), (1, 1, cfg.d_model), jnp.float32)
        _, n = moe_mod.moe_decode(p, x, cfg, experts, jnp.int32(0))
        with jax.default_matmul_precision("highest"):
            gates = np.asarray(ref.route(x[0], p, ref._sizes(uncut), None))[0]
        assert int(n) == int(np.sum(gates[:cfg.held_experts] > 0))


def test_engine_annotates_its_spans():
    from repro.serve.engine import make_engine

    eng = make_engine(f"model:{ARCH}:smoke:seed=0")
    with spans.span("engine.prefill"):
        state = eng.prefill(np.arange(10), 4)
    pre = spans.last()
    assert pre.attrs["tokens"] == 10 and 0 < pre.attrs["expert_rows"] <= 10 * 2 * 2
    assert pre.attrs["compiled"] == 1  # the first prefill of its shape builds the program
    with spans.span("engine.decode"):
        eng.decode(state)
    assert 0 <= spans.last().attrs["experts_read"] <= 2 * 2
    with spans.span("engine.prefill"):
        eng.prefill(np.arange(10, 20), 4)
    again = spans.last()
    assert again.attrs["compiled"] == 0 and again.attrs["tokens"] == 10
    assert 0 < again.attrs["expert_rows"] <= 10 * 2 * 2


def test_share_counts():
    cfg = get_config(ARCH)
    assert cfg.held_experts == 16
    assert 5.0e9 <= cfg.param_count() <= 5.3e9
    whole = cfg.with_(ep_size=1)
    assert 15.5e9 <= whole.param_count() <= 16.5e9
    # a token routes to 6 * 16 / 64 = 1.5 held experts a layer, plus the shared ones
    per_expert = 3 * cfg.d_model * cfg.moe_d_ff
    assert cfg.param_count() - cfg.active_param_count() == int(26 * 14.5 * per_expert)
    assert whole.param_count() - whole.active_param_count() == int(26 * 58 * per_expert)


def test_decode_counts_follow_the_share():
    c = json.loads(FILE.read_text())
    wide = copy.deepcopy(c)
    wide.update(n_routed_experts=64, expert_parallel={"size": 1, "rank": 0})
    per_expert = 3 * c["hidden_size"] * c["moe_intermediate_size"]
    # 6 experts a layer over 1.5 held: 26 layers of 4.5 experts' weights more
    assert ref.decode_bytes(wide, 0) - ref.decode_bytes(c, 0) == pytest.approx(2 * 26 * 4.5 * per_expert)
    assert ref.decode_flops(wide, 0) - ref.decode_flops(c, 0) == pytest.approx(2 * 26 * 4.5 * per_expert)
    assert 3.0e9 < ref.decode_bytes(c, 0) < 3.3e9


@pytest.mark.parametrize("gaps, within", [
    ([0.05] * 127 + [1.76], True),  # a sound request: its widest gap is a routing flip's
    ([0.0] * 127 + [4.0], False),  # one wrong token in a long answer: mean 0.031
    ([0.3] * 32, False),  # every position moved a little, as float8 operands move them
])
def test_served_gap_holds_the_mean_and_the_widest(gaps, within):
    c = json.loads(FILE.read_text())
    assert (ref.served_gap(gaps, c) <= c["check"]["served_gap"]) == within


def _tie_case():
    """A smoke-size request row where the reference's other choice at its
    nearest MoE routing tie serves another token: (file, params, tokens, row,
    that token, the tie's margin)."""
    c = smoke_file()
    items = ref.config_items(c)
    n = c["num_hidden_layers"] - c["first_k_dense_replace"]
    for seed in range(8):
        params = ref.make_params(c, seed, "float32")
        tokens = jnp.asarray(jax.random.randint(jax.random.PRNGKey(seed), (24,), 0, c["vocab_size"]))
        for row in range(24):
            args = (params, tokens, items, jnp.int32(row))
            base, margins = ref._row_logits(*args, jnp.zeros(n, bool), jnp.float32(0.0))
            layer = int(np.argmin(np.asarray(margins)))
            on = jnp.asarray(np.arange(n) == layer)
            flipped, _ = ref._row_logits(*args, on, jnp.float32(1.0))
            tok = int(np.argmax(np.asarray(flipped)))
            if tok != int(np.argmax(np.asarray(base))):
                return c, params, tokens, row, tok, float(np.asarray(margins)[layer])
    raise AssertionError("no routing tie that serves another token")


def test_row_logits_without_a_swap_are_the_reference_row():
    c = smoke_file()
    params = ref.make_params(c, 3, "float32")
    tokens = np.asarray(jax.random.randint(jax.random.PRNGKey(3), (20,), 0, c["vocab_size"]))
    want = _ref_logits(params, tokens, c)
    n = c["num_hidden_layers"] - c["first_k_dense_replace"]
    for row in (0, 7, 19):
        got, margins = ref._row_logits(params, jnp.asarray(tokens), ref.config_items(c),
                                       jnp.int32(row), jnp.ones(n, bool), jnp.float32(0.0))
        np.testing.assert_allclose(np.asarray(got), want[row], atol=1e-5, rtol=0)
        assert np.asarray(margins).shape == (n,) and (np.asarray(margins) >= 0).all()


def test_tie_gap_takes_the_other_choice_at_a_tie_and_only_there():
    c, params, tokens, row, tok, margin = _tie_case()
    first = float(ref.tie_gap(params, tokens, row, tok, dict(c, tie_margin=0.0)))
    assert first > 0  # no tie admitted: the reference's own routing
    assert ref.tie_gap(params, tokens, row, tok, dict(c, tie_margin=margin * 1.001)) == 0.0
    assert ref.tie_gap(params, tokens, row, tok, dict(c, tie_margin=margin * 0.999)) == first


@pytest.mark.parametrize("gaps, searched", [
    ([0.05] * 31 + [1.0], []),  # within the limit: not searched
    ([0.0] * 22 + [0.7] * 10, list(range(22, 30))),  # mean over the limit: its widest, at most tie_searches
    ([0.0] * 31 + [7.0], [31]),  # widest over its limit: the non-zero gaps only
])
def test_only_a_request_over_the_limit_is_searched_at_its_widest_gaps(monkeypatch, gaps, searched):
    c = json.loads(FILE.read_text())
    rows = []
    monkeypatch.setattr(ref, "tie_gap", lambda params, tokens, row, tok, c: rows.append(row) or 0.0)
    got, n = ref._within_ties(None, None, 100, np.zeros(len(gaps), np.int32), gaps, c)
    assert rows == [100 + j for j in searched] and n == len(searched) <= c["tie_searches"]
    assert np.array_equal(got, np.where(np.isin(np.arange(len(gaps)), searched), 0.0, gaps))


def test_served_gaps_read_a_tie_flip_within_ties_and_a_wrong_token_not():
    """A served token that the other choice at a routing tie puts first is
    searched and let through; one that no tie explains keeps its gap."""
    c, params, tokens, row, tok, margin = _tie_case()
    c = dict(c, widest_gap_limit=1e-6, tie_margin=margin * 1.001)
    prompt = np.asarray(tokens[:row + 1])
    g = ref.served_gaps(params, prompt, [tok], c)
    assert g["first_widest_gap"] > 0 and g["widest_gap"] == 0.0 and g["tie_searched"] == 1
    lg = _ref_logits(params, np.asarray(tokens), c)[row]
    worst = int(np.argmin(lg))
    g = ref.served_gaps(params, prompt, [worst], c)
    assert g["widest_gap"] > 0.5 * (lg.max() - lg[worst]) > 0
