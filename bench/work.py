"""Work counts: the operations an algorithm needs, from its sizes alone.

These are the numerators of the roofline and peak shares.  They count what
the algorithm asks for, never what an implementation pads or recomputes, so
a share reads the same whatever implements the layer.
"""
from __future__ import annotations

# -- language model ----------------------------------------------------------
# Forward FLOPs of a dense decoder (matmul multiply-adds counted twice), the
# formula of ``repro.analytics.forward_flops`` for attention blocks: the
# linear layers per token, causal attention scores and values per sequence,
# and the output head.

def lm_forward_flops_per_token(cfg: dict, seq_len: int) -> float:
    d = cfg["hidden_size"]
    H, KV = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg.get("head_dim") or d // H
    ff, V, L = cfg["intermediate_size"], cfg["vocab_size"], \
        cfg["num_hidden_layers"]
    qd, kvd = H * hd, KV * hd
    linear = 2.0 * (d * qd + 2 * d * kvd + qd * d + 3 * d * ff)
    # causal self-attention: S*S/2 score pairs per sequence, QK^T and PV
    attn_per_seq = 2.0 * 2.0 * (seq_len * seq_len / 2) * H * hd
    head = 2.0 * d * V
    return L * (linear + attn_per_seq / seq_len) + head


def lm_train_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Forward and backward (the backward pass is twice the forward);
    recomputation under rematerialisation is not useful work."""
    return 3.0 * lm_forward_flops_per_token(cfg, seq_len)
