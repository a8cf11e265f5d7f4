"""Work counts of a DeepSeek-V3-style MoE decoder (latent attention, a
leading dense layer, shared and routed experts), from its sizes alone.

Beside ``bench/work.py``: the operations the algorithm asks for, never what
an implementation pads or recomputes.  The routed experts count the share
held here: k x held / E expert passes a token.
"""
from __future__ import annotations


def forward_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Matmul multiply-adds counted twice: MLA projections, causal scores
    and values (S/2 pairs a token), the dense SwiGLU, the router, the
    shared and held routed experts, and the output head."""
    d, H = cfg["hidden_size"], cfg["num_attention_heads"]
    r, rope = cfg["kv_lora_rank"], cfg["qk_rope_head_dim"]
    nope, vd = cfg["qk_nope_head_dim"], cfg["v_head_dim"]
    qk = nope + rope
    lead = cfg["first_k_dense_replace"]
    n_moe = cfg["num_hidden_layers"] - lead
    E, K = cfg["router_experts"], cfg["num_experts_per_tok"]
    held, fe = cfg["experts_held"][1], cfg["moe_intermediate_size"]
    attn = 2.0 * (d * H * qk + d * (r + rope) + r * H * (nope + vd)
                  + H * vd * d)
    attn += 2.0 * (seq_len / 2) * H * (qk + vd)
    dense = 2.0 * 3 * d * cfg["intermediate_size"]
    passes = K * held / E + cfg["n_shared_experts"]
    moe = 2.0 * d * E + passes * 2.0 * 3 * d * fe
    head = 2.0 * d * cfg["vocab_size"]
    return lead * (attn + dense) + n_moe * (attn + moe) + head


def train_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Forward and backward (the backward pass is twice the forward);
    recomputation under rematerialisation is not useful work."""
    return 3.0 * forward_flops_per_token(cfg, seq_len)


def gmm_train_flops(cfg: dict, rows: int) -> float:
    """The grouped matmuls' FLOPs for ``rows`` held (token, expert) rows:
    gate|up (2 d f) and down (f d) forward, twice that backward, so
    18 rows d f; recomputation not counted."""
    return 18.0 * rows * cfg["hidden_size"] * cfg["moe_intermediate_size"]
