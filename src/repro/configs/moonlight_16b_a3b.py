"""moonlight-16b-a3b — DeepSeek-V3 block: MLA without a query LoRA, 64 routed
experts top 6 with sigmoid ``noaux_tc`` routing, 2 shared experts, layer 0 dense
[hf:moonshotai/Moonlight-16B-A3B config.json; arXiv:2502.16982].

``config()`` is one expert-parallel rank of the model as it is served over a
four-chip v5e host (2x2): each rank holds 16 of the 64 routed experts of every
MoE layer, and attention, the shared experts, the dense layer 0 and both
embeddings are replicated. This rank is 0 (experts 0-15). Every width, all 27
layers, the router's 64 outputs, top 6 and the whole vocabulary are as
published; the share is 5.16 B parameters, 10.33 GB in bf16.

Faithfulness notes: RoPE in half rotation where the published code
interleaves pairs (with seeded weights, a permutation of the query and
latent projections' columns); no ``rope_scaling``; ``n_group`` and
``topk_group`` 1, so group-limited selection is plain top-k.
"""

from repro.configs.base import ArchConfig


def config() -> ArchConfig:
    return ArchConfig(
        name="moonlight-16b-a3b",
        family="moe",
        n_layers=27,
        d_model=2048,
        n_heads=16,
        n_kv_heads=16,  # MLA: kv "heads" equal q heads post-expansion
        d_ff=11264,  # layer 0's dense FFN
        vocab=163840,
        rope_theta=50_000.0,
        moe=True,
        n_experts=64,
        top_k=6,
        n_shared_experts=2,
        moe_d_ff=1408,
        first_dense_layers=1,
        router_type="sigmoid",
        routed_scale=2.446,
        ep_size=4,
        ep_rank=0,
        mla=True,
        q_lora_rank=0,
        kv_lora_rank=512,
        qk_rope_dim=64,
        qk_nope_dim=128,
        v_head_dim=128,
        source="[hf:moonshotai/Moonlight-16B-A3B; arXiv:2502.16982]",
    )


def smoke() -> ArchConfig:
    """1 dense + 2 MoE layers, 8 routed experts top 2 over 4 ranks (2 held)."""
    return config().with_(
        n_layers=3, d_model=64, n_heads=4, n_kv_heads=4, d_ff=128, moe_d_ff=32,
        n_experts=8, top_k=2, kv_lora_rank=16, qk_rope_dim=8, qk_nope_dim=16,
        v_head_dim=16, vocab=256, loss_chunk=64,
    )
