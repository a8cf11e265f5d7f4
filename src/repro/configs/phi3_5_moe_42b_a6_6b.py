"""phi3.5-moe-42b-a6.6b [moe]: 32L d_model=4096 32H (GQA kv=8) d_ff=6400
vocab=32064, MoE 16 experts top-2 (softmax scores, renormalised top-2),
through the same dropless expert layer as Moonlight.
[hf:microsoft/Phi-3.5-MoE-instruct]"""
import dataclasses

from .base import ModelConfig


def config() -> ModelConfig:
    return ModelConfig(
        name="phi3.5-moe-42b-a6.6b", family="moe", n_layers=32, d_model=4096,
        n_heads=32, n_kv_heads=8, d_ff=6400, vocab_size=32064,
        n_experts=16, top_k=2, rope_theta=10000.0,
    )


def smoke_config() -> ModelConfig:
    return dataclasses.replace(
        config(), name="phi3.5-moe-42b-a6.6b-smoke", n_layers=2, d_model=64,
        n_heads=4, n_kv_heads=2, d_ff=48, vocab_size=512, n_experts=4,
        top_k=2, head_dim=0)
