"""The ``serve-chat-moe`` cell's kind on the CPU at a tiny size: the program
serves the configuration through its normal path, ``correct`` holds, the
control and an altered token fail it, and the readers of the new counters
find them."""

from __future__ import annotations

import copy

from benchmarks.chip import faults, harness
from benchmarks.chip.kinds import serve
from benchmarks.chip.run import judge
from benchmarks.chip.tests import cpu

CONFIG = "moonlight-16b-a3b-serve"
# The registry's smoke() sizes: 1 dense + 2 MoE layers, 2 of 8 experts held, top 2.
TINY = {"hidden_size": 64, "intermediate_size": 128, "moe_intermediate_size": 32,
        "num_hidden_layers": 3, "num_attention_heads": 4, "num_key_value_heads": 4,
        "kv_lora_rank": 16, "qk_rope_head_dim": 8, "qk_nope_head_dim": 16, "v_head_dim": 16,
        "vocab_size": 256, "n_routed_experts": 2, "num_experts_per_tok": 2,
        "published": {"n_routed_experts": 8}}
# Set as the file's limit is, on the CPU at this size: served gap (the larger
# of a request's mean gap and its widest scaled onto the limit, as
# mla_moe.served_gap takes it), sound 0.0, the fp8 control 0.024 to 0.050
# (three seeds each); the altered token 0.106.
TINY_CHECK = {"served_gap": 0.01}


def tiny_config() -> dict:
    c = copy.deepcopy(harness.config(CONFIG))
    c.update(TINY)
    c["check"] = dict(c["check"], **TINY_CHECK)
    return c


def patch_registry(monkeypatch, c: dict) -> None:
    """The program's registry entry of ``c`` at the tiny sizes."""
    import repro.configs as configs

    real = configs.get_config
    fields = harness.reference(c).arch_fields(c)

    def get_config(name):
        cfg = real(name)
        return cfg.with_(**fields) if name == c["program"]["arch"] else cfg

    monkeypatch.setattr(configs, "get_config", get_config)


def run_serve(monkeypatch, tmp_path, seed=2**31 + 12345):
    c, tr = tiny_config(), cpu.tiny_traffic("chat_migrate_moe")
    patch_registry(monkeypatch, c)
    h = cpu.context(c, tr, tmp_path, seed=seed, seconds=3.0, cell="serve-chat-moe")
    return h, serve.run(h)


def correct(numbers: dict, limits: dict) -> bool:
    return judge(numbers, limits)[1]


def test_moe_serve_sound_and_counted(monkeypatch, tmp_path):
    h, out = run_serve(monkeypatch, tmp_path)
    reqs = out["loop"].req.values()
    assert out["failed"] == 0 and out["attempted"] == len(reqs) > 0
    assert any(r["migrated_t"] is not None for r in reqs)
    numbers = serve.check(h, out)
    assert correct(numbers, h.config["check"]), numbers
    control = serve.check(h, out, fmt="fp8")
    assert not correct(control, h.config["check"]), (numbers, control)
    run = cpu.run_view(h, out)
    read = harness.reader("decode_experts_read")(run)
    assert 0 <= read <= 2  # at most the 2 experts held here, a layer
    assert harness.reader("decode_call_ms")(run) > 0


def test_moe_altered_token_is_caught(monkeypatch, tmp_path):
    faults.plant(monkeypatch, "altered_token")
    h, out = run_serve(monkeypatch, tmp_path)
    assert not correct(serve.check(h, out), h.config["check"])


def test_decode_counts_are_the_expected_reads():
    """About 3.14 GB a step at the published widths: the routed experts a token
    hits here (1.5 a layer), not the 16 held; attention absorbed over the
    latent cache, 4 * 16 * (512 + 32) operations a layer and position."""
    c = harness.config(CONFIG)
    ref = harness.reference(c)
    assert [(ref.decode_flops(c, p), ref.decode_bytes(c, p)) for p in (0, 287, 1151)] == [
        (3134871552.0, 3141042048.0), (3404660736.0, 3149968896.0), (4216848384.0, 3176842752.0)]
