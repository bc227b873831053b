"""SDAR-30B-A3B-Chat [moe, block diffusion]: 48L d_model=2048 32H (GQA
kv=4, head_dim 128) with per-head q/k RMSNorm, MoE 128 experts top-8 of
width 768 (``norm_topk_prob``, no shared expert), vocab 151936 untied,
rope_theta 1e6.  Generation is block diffusion: blocks of 4 positions,
aligned to absolute positions, under block-causal attention.
[hf:JetLM/SDAR-30B-A3B-Chat config.json]

The block length is not in the published config; 4 is the length the
chat models are generated at (SDAR's own generation script).
"""
from repro.core.arch import ArchConfig, AttentionSpec, FFNSpec


def config() -> ArchConfig:
    return ArchConfig(
        name="sdar-30b-a3b-chat",
        family="moe",
        n_layers=48,
        d_model=2048,
        vocab_size=151936,
        attention=AttentionSpec(kind="gqa", n_heads=32, n_kv_heads=4,
                                head_dim=128, qk_norm_eps=1e-6,
                                diffusion_block=4),
        ffn=FFNSpec(kind="moe", d_ff=768, activation="swiglu",
                    n_experts=128, top_k=8),
        norm_eps=1e-6,
        rope_theta=1e6,
        tie_embeddings=False,
        max_seq_len=32768,
    )


def reduced_config() -> ArchConfig:
    """CPU size: one chip's share of 4 of 16 experts (the first 4)."""
    return ArchConfig(
        name="sdar-smoke",
        family="moe",
        n_layers=2,
        d_model=64,
        vocab_size=256,
        attention=AttentionSpec(kind="gqa", n_heads=4, n_kv_heads=2,
                                head_dim=16, qk_norm_eps=1e-6,
                                diffusion_block=4),
        ffn=FFNSpec(kind="moe", d_ff=32, activation="swiglu",
                    n_experts=16, top_k=4, held=4),
        norm_eps=1e-6,
        rope_theta=1e6,
    )
