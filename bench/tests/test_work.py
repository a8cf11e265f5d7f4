"""Work counts against hand counts at tiny sizes."""
import sys

import pytest

from bench import common, work


TINY = dict(hidden_size=8, intermediate_size=12, num_hidden_layers=2,
            num_attention_heads=2, num_key_value_heads=1, vocab_size=10)


def test_lm_flops_hand_count():
    # per layer per token: q 8x8, k 8x4, v 8x4, o 8x8, mlp 3 x 8x12 MACs
    linear = 2 * (64 + 32 + 32 + 64 + 288)
    S = 4
    attn = 2 * 2 * (S * S / 2) * 2 * 4 / S       # per token
    head = 2 * 8 * 10
    assert work.lm_forward_flops_per_token(TINY, S) == pytest.approx(
        2 * (linear + attn) + head)
    assert work.lm_train_flops_per_token(TINY, S) == pytest.approx(
        3 * (2 * (linear + attn) + head))


def test_lm_flops_match_the_programs_formula():
    sys.path.insert(0, str(common.ROOT / "src"))
    from repro import analytics
    from repro.configs.base import ModelConfig

    cfg = common.load_json(common.BENCH / "configs"
                           / "smollm-135m-preemptible.json")
    mc = ModelConfig(name="x", family="dense",
                     n_layers=cfg["num_hidden_layers"],
                     d_model=cfg["hidden_size"],
                     n_heads=cfg["num_attention_heads"],
                     n_kv_heads=cfg["num_key_value_heads"],
                     d_ff=cfg["intermediate_size"],
                     vocab_size=cfg["vocab_size"], tie_embeddings=True)
    B, S = 8, 2048
    assert work.lm_forward_flops_per_token(cfg, S) == pytest.approx(
        analytics.forward_flops(mc, B, S, S) / (B * S))
