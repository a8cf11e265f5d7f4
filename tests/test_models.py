"""Per-arch smoke tests + model-level consistency checks."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.configs.base import TrainConfig
from repro.launch import steps
from repro.models import transformer as T
from repro.optim import adamw_init

KEY = jax.random.PRNGKey(0)


def _inputs(cfg, B, S, key=KEY):
    kw = {}
    if cfg.embeds_input:
        kw["embeds"] = jax.random.normal(key, (B, S, cfg.d_model),
                                         jnp.float32)
    else:
        kw["tokens"] = jax.random.randint(key, (B, S), 0, cfg.vocab_size)
    if cfg.pos_type == "mrope":
        kw["positions"] = jnp.broadcast_to(
            jnp.arange(S, dtype=jnp.int32)[None, None], (3, B, S))
    return kw


@pytest.mark.parametrize("arch", configs.ARCHS)
def test_smoke_forward(arch):
    """One forward on the reduced config: shapes + finiteness."""
    cfg = configs.smoke(arch)
    params, axes = T.init(cfg, KEY)
    B, S = 2, 32
    kw = _inputs(cfg, B, S)
    logits, _ = T.forward(cfg, params, kw.get("tokens"),
                          embeds=kw.get("embeds"),
                          positions=kw.get("positions"), mode="train")
    assert logits.shape == (B, S, cfg.vocab_size)
    assert bool(jnp.isfinite(logits.astype(jnp.float32)).all())


@pytest.mark.parametrize("arch", configs.ARCHS)
def test_smoke_train_step(arch):
    """One optimizer step on CPU: loss finite, params move, no NaNs."""
    cfg = configs.smoke(arch)
    params, _ = T.init(cfg, KEY)
    opt = adamw_init(T.trainable(params))
    B, S = 2, 16
    batch = _inputs(cfg, B, S)
    batch["labels"] = jax.random.randint(KEY, (B, S), 0, cfg.vocab_size)
    step = steps.make_train_step(cfg, TrainConfig(warmup_steps=1))
    new_params, new_opt, metrics = jax.jit(step)(params, opt, batch)
    assert bool(jnp.isfinite(metrics["loss"]))
    assert float(metrics["loss"]) > 0
    moved = jax.tree_util.tree_map(
        lambda a, b: float(jnp.abs(a.astype(jnp.float32)
                                   - b.astype(jnp.float32)).max()),
        params, new_params)
    assert max(jax.tree_util.tree_leaves(moved)) > 0
    for leaf in jax.tree_util.tree_leaves(new_params):
        assert bool(jnp.isfinite(leaf.astype(jnp.float32)).all())


@pytest.mark.parametrize("arch", ["llama3.2-1b", "recurrentgemma-2b",
                                  "xlstm-1.3b", "musicgen-medium"])
def test_decode_matches_full_forward(arch):
    """prefill+decode must reproduce the full-sequence forward logits."""
    cfg = dataclasses.replace(configs.smoke(arch), compute_dtype="float32")
    params, _ = T.init(cfg, KEY)
    B, S = 2, 24
    kw = _inputs(cfg, B, S + 1)
    full_logits, _ = T.forward(cfg, params, kw.get("tokens"),
                               embeds=kw.get("embeds"), mode="train")
    cache = T.init_cache(cfg, B, S + 1)
    if cfg.embeds_input:
        _, cache = T.prefill_step(cfg, params, embeds=kw["embeds"][:, :S],
                                  cache=cache)
        dec_logits, _ = T.decode_step(cfg, params,
                                      embeds=kw["embeds"][:, S:S + 1],
                                      cache=cache)
    else:
        _, cache = T.prefill_step(cfg, params, kw["tokens"][:, :S],
                                  cache=cache)
        dec_logits, _ = T.decode_step(cfg, params, kw["tokens"][:, S:S + 1],
                                      cache=cache)
    np.testing.assert_allclose(np.asarray(dec_logits[:, 0]),
                               np.asarray(full_logits[:, S]),
                               atol=2e-3, rtol=1e-3)


def test_moe_routing_mass_conservation():
    """Every (token, k) pair is computed (dropless: rows == T x K), the
    combine weights of each token sum to 1 (renormalised top-k), and zero
    input gives zero output."""
    from repro.models import moe as M
    cfg = dataclasses.replace(configs.smoke("phi3.5-moe-42b-a6.6b"),
                              compute_dtype="float32")
    p_ann = M.init_moe_mlp(jax.random.PRNGKey(1), cfg)
    from repro.sharding import split_annotated
    p, _ = split_annotated(p_ann)
    x = 0.1 * jax.random.normal(KEY, (2, 16, cfg.d_model), jnp.float32)
    y, stats = M.moe_mlp(cfg, p, x)
    assert y.shape == x.shape
    assert bool(jnp.isfinite(y).all())
    assert int(stats["rows"]) == 2 * 16 * cfg.top_k
    _, w, _ = M.route(cfg, p, x.reshape(-1, cfg.d_model))
    np.testing.assert_allclose(np.asarray(w.sum(-1)), 1.0, rtol=1e-6)
    # zero input -> zero output (router gates scale expert outputs of 0)
    y0, _ = M.moe_mlp(cfg, p, jnp.zeros_like(x))
    np.testing.assert_allclose(np.asarray(y0), 0.0, atol=1e-5)


def test_rope_rotation_invariance():
    """RoPE preserves norms and relative-position inner products."""
    from repro.models.layers import apply_rope
    x = jax.random.normal(KEY, (1, 8, 2, 64), jnp.float32)
    pos = jnp.arange(8, dtype=jnp.int32)[None]
    r = apply_rope(x, pos, 10000.0)
    np.testing.assert_allclose(np.asarray(jnp.linalg.norm(r, axis=-1)),
                               np.asarray(jnp.linalg.norm(x, axis=-1)),
                               rtol=1e-5)
    # relative property: <R(p)q, R(p+d)k> independent of p
    q = jax.random.normal(jax.random.PRNGKey(1), (1, 1, 1, 64))
    k = jax.random.normal(jax.random.PRNGKey(2), (1, 1, 1, 64))
    def ip(p, d):
        rq = apply_rope(q, jnp.asarray([[p]]), 10000.0)
        rk = apply_rope(k, jnp.asarray([[p + d]]), 10000.0)
        return float(jnp.sum(rq * rk))
    np.testing.assert_allclose(ip(0, 3), ip(7, 3), rtol=1e-4)


def test_mrope_sections_match_rope_when_positions_equal():
    """With identical t/h/w position streams, M-RoPE == RoPE."""
    from repro.models.layers import apply_mrope, apply_rope
    x = jax.random.normal(KEY, (1, 8, 2, 64), jnp.float32)
    pos = jnp.arange(8, dtype=jnp.int32)[None]
    pos3 = jnp.broadcast_to(pos[None], (3, 1, 8))
    r1 = apply_rope(x, pos, 10000.0)
    r2 = apply_mrope(x, pos3, 10000.0, (16, 8, 8))
    np.testing.assert_allclose(np.asarray(r2), np.asarray(r1), atol=1e-5)


def test_scan_vs_unrolled_forward():
    """scan-over-layers must equal the unrolled python loop."""
    cfg = dataclasses.replace(configs.smoke("llama3.2-1b"), n_layers=4,
                              compute_dtype="float32")
    params, _ = T.init(cfg, KEY)
    toks = jax.random.randint(KEY, (2, 16), 0, cfg.vocab_size)
    l1, _ = T.forward(cfg, params, toks, mode="train")
    cfg2 = dataclasses.replace(cfg, scan_layers=False)
    l2, _ = T.forward(cfg2, params, toks, mode="train")
    np.testing.assert_allclose(np.asarray(l1), np.asarray(l2), atol=1e-4)


def test_param_count_matches_init():
    for arch in configs.ARCHS:
        cfg = configs.smoke(arch)
        params, _ = T.init(cfg, KEY)
        actual = sum(int(np.prod(p.shape))
                     for p in jax.tree_util.tree_leaves(params))
        assert actual == cfg.param_count(), arch
