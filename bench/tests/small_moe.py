"""The ``moonlight-train`` cell at test sizes, resolved like the real one,
for runs on the CPU: every mechanism at toy widths (latent attention, the
dense lead, shared experts, a sigmoid router over 16 experts of which 4 are
held)."""
from __future__ import annotations

import copy

from bench import common


def spec(workload: str = "moonlight-train") -> dict:
    s = copy.deepcopy(common.resolve(workload))
    s["config"].update(hidden_size=64, intermediate_size=96,
                       moe_intermediate_size=32, num_hidden_layers=3,
                       num_attention_heads=4, num_key_value_heads=4,
                       kv_lora_rank=32, qk_nope_head_dim=16,
                       qk_rope_head_dim=8, v_head_dim=16, router_experts=16,
                       num_experts_per_tok=4, experts_held=[4, 4],
                       vocab_size=384)
    s["config"]["train"].update(seq_len=32, global_batch=4)
    s["mix"].update(total_steps=14, preemption_seed=29)
    return s
