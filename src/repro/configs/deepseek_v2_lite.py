"""deepseek-v2-lite [moe]: 27L d_model=2048 16H MLA (kv_lora=512, no
q_lora, qk_nope 128 + qk_rope 64, v 128), yarn rope (factor 40 over 4096
positions, mscale 0.707), vocab=102400 untied; layer 0 a dense SwiGLU of
10944, layers 1-26 MoE: 64 routed experts of 1408, greedy top-6 softmax
routing, gates not renormalised, 2 shared experts [arXiv:2405.04434;
hf deepseek-ai/DeepSeek-V2-Lite config.json].

``full()`` holds all 64 experts (15.7B parameters); a deployment that
shares each layer over chips by expert parallelism gives each chip a
``held`` slice (``expert_share``).
"""
import dataclasses

from ..models.config import (
    GroupSpec,
    LayerSpec,
    MLAConfig,
    MoEConfig,
    ModelConfig,
    YarnRope,
)

YARN = YarnRope(factor=40.0, original_max_position=4096, beta_fast=32.0,
                beta_slow=1.0, mscale=0.707, mscale_all_dim=0.707)


def _groups(n_moe: int) -> tuple:
    return (
        GroupSpec(repeat=1, layers=(LayerSpec(mixer="mla", ffn="dense"),)),
        GroupSpec(repeat=n_moe, layers=(LayerSpec(mixer="mla", ffn="moe"),)),
    )


def full() -> ModelConfig:
    return ModelConfig(
        name="deepseek-v2-lite",
        family="moe",
        d_model=2048,
        n_heads=16,
        n_kv_heads=16,
        head_dim=128,
        d_ff=10944,  # dense layer-0 FFN
        vocab_size=102400,
        groups=_groups(26),
        attn_type="mla",
        mla=MLAConfig(q_lora_rank=None, kv_lora_rank=512, qk_nope_dim=128,
                      qk_rope_dim=64, v_head_dim=128),
        moe=MoEConfig(num_experts=64, top_k=6, d_ff=1408, num_shared=2,
                      shared_d_ff=1408, held=(0, 64), norm_topk_prob=False,
                      router_f32=True),
        ffn_type="swiglu",
        rope_theta=10000.0,
        rope_scaling=YARN,
        norm_eps=1e-6,
        tie_embeddings=False,
        max_seq_len=163840,
    )


def reduced() -> ModelConfig:
    return ModelConfig(
        name="deepseek-v2-lite-reduced",
        family="moe",
        d_model=64,
        n_heads=4,
        n_kv_heads=4,
        head_dim=16,
        d_ff=128,
        vocab_size=512,
        groups=_groups(2),
        attn_type="mla",
        mla=MLAConfig(q_lora_rank=None, kv_lora_rank=16, qk_nope_dim=16,
                      qk_rope_dim=8, v_head_dim=16),
        moe=MoEConfig(num_experts=32, top_k=6, d_ff=32, num_shared=2, shared_d_ff=32,
                      held=(0, 32), norm_topk_prob=False, router_f32=True),
        ffn_type="swiglu",
        rope_theta=10000.0,
        rope_scaling=YARN,
        norm_eps=1e-6,
        tie_embeddings=False,
    )


def expert_share(cfg: ModelConfig, first: int, count: int) -> ModelConfig:
    """``cfg`` holding experts ``first .. first + count - 1`` of each MoE
    layer: one chip's share of expert parallelism."""
    return dataclasses.replace(cfg, moe=dataclasses.replace(cfg.moe, held=(first, count)))
