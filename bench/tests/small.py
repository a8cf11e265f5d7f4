"""Cells at test sizes, resolved like the real ones, for runs on the CPU."""
from __future__ import annotations

import copy

from bench import common


def spec(workload: str) -> dict:
    s = copy.deepcopy(common.resolve(workload))
    if s["mix"]["generator"] == "refit":
        s["config"]["grid_dt_hours"] = 0.5
        s["mix"].update(job_steps=12, fits=8)
    else:
        s["config"].update(hidden_size=64, intermediate_size=96,
                           num_hidden_layers=2, num_attention_heads=4,
                           num_key_value_heads=2, vocab_size=384)
        s["config"]["train"].update(seq_len=32, global_batch=4)
        s["mix"].update(total_steps=14, preemption_seed=29)
    return s
