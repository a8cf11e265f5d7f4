"""moonshot-v1-16b-a3b [moe]: Moonlight-16B-A3B (DeepSeek-V3 architecture).
27L d_model=2048 16H, multi-head latent attention (kv_lora_rank 512,
qk_nope 128 + qk_rope 64, v 128, no q compression), one leading dense
SwiGLU layer of width 11264, then 26 MoE layers: 64 routed experts of width
1408, 6 per token, 2 shared experts, sigmoid scores with noaux_tc selection
(a correction bias used only to choose), normalised top-k weights x 2.446.
vocab=163840, untied embeddings, rms_norm_eps 1e-5, rope_theta 50000.
[https://huggingface.co/moonshotai/Moonlight-16B-A3B/blob/main/config.json]"""
import dataclasses

from .base import ModelConfig


def config() -> ModelConfig:
    return ModelConfig(
        name="moonshot-v1-16b-a3b", family="moe", n_layers=27, d_model=2048,
        n_heads=16, n_kv_heads=16, d_ff=11264, vocab_size=163840,
        n_experts=64, top_k=6, moe_d_ff=1408, n_shared_experts=2,
        score_fn="sigmoid", norm_topk=True, routed_scale=2.446,
        kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
        v_head_dim=128, first_dense_layers=1, rope_theta=50000.0,
        norm_eps=1e-5, tie_embeddings=False,
    )


def smoke_config() -> ModelConfig:
    """Every mechanism at toy widths: MLA, a dense lead, shared and routed
    experts, the sigmoid router with its bias, two experts of eight held."""
    return dataclasses.replace(
        config(), name="moonshot-v1-16b-a3b-smoke", n_layers=3, d_model=64,
        n_heads=4, n_kv_heads=4, d_ff=96, vocab_size=512, n_experts=8,
        top_k=3, moe_d_ff=32, n_shared_experts=2, kv_lora_rank=32,
        qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
        experts_held=(2, 4), head_dim=0)
