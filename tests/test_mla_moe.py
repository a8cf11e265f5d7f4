"""Latent attention and the dropless expert layer against the benchmark's
plain reference of Moonlight (``bench/configs/moonlight-16b-a3b-ep8_
reference.py``, loaded by path), at toy widths on the CPU with seeded
weights: the same seed gives the program and the reference the same
weights, so logits, losses and gradients are compared directly."""
import dataclasses
import importlib.util
import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ModelConfig, TrainConfig
from repro.kernels import moe_gmm, ops
from repro.kernels import ref as kref
from repro.launch import steps
from repro.models import moe as M
from repro.models import transformer as T
from repro.sharding import split_annotated

ROOT = pathlib.Path(__file__).resolve().parents[1]
SEED = 5
DATA = {"zipf_alpha": 1.1, "markov_period": 16}


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _load(ROOT / "bench/configs/moonlight-16b-a3b-ep8_reference.py",
            "moonlight_reference")


def _cfg_dict(**kw):
    """The benchmark's configuration file at toy widths (every mechanism)."""
    with open(ROOT / "bench/configs/moonlight-16b-a3b-ep8.json") as f:
        c = json.load(f)
    c.update(hidden_size=64, intermediate_size=96, moe_intermediate_size=32,
             num_hidden_layers=3, num_attention_heads=4,
             num_key_value_heads=4, kv_lora_rank=32, qk_nope_head_dim=16,
             qk_rope_head_dim=8, v_head_dim=16, router_experts=16,
             num_experts_per_tok=4, experts_held=[4, 4], vocab_size=256)
    c["train"] = dict(c["train"], seq_len=32, global_batch=2)
    c.update(kw)
    return c


def _model(c, compute="float32"):
    return ModelConfig(
        name="moonlight-toy", family="moe", n_layers=c["num_hidden_layers"],
        d_model=c["hidden_size"], n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"], d_ff=c["intermediate_size"],
        vocab_size=c["vocab_size"], n_experts=c["router_experts"],
        top_k=c["num_experts_per_tok"], moe_d_ff=c["moe_intermediate_size"],
        n_shared_experts=c["n_shared_experts"], score_fn=c["scoring_func"],
        norm_topk=c["norm_topk_prob"], routed_scale=c["routed_scaling_factor"],
        experts_held=tuple(c["experts_held"]), kv_lora_rank=c["kv_lora_rank"],
        qk_nope_head_dim=c["qk_nope_head_dim"],
        qk_rope_head_dim=c["qk_rope_head_dim"], v_head_dim=c["v_head_dim"],
        first_dense_layers=c["first_k_dense_replace"],
        rope_theta=float(c["rope_theta"]), norm_eps=c["rms_norm_eps"],
        tie_embeddings=False, compute_dtype=compute)


def _flat(tree):
    leaves, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {"/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                     for p in path): x for path, x in leaves}


def _init(cfg, c):
    """The program's initial parameters with the reference's routing biases
    (the program starts them at zero; the benchmark seeds them)."""
    params, _ = T.init(cfg, jax.random.PRNGKey(SEED))
    assert not np.any(np.asarray(params["groups"][0]["moe"][M.BIAS]))
    g = dict(params["groups"][0])
    g["moe"] = dict(g["moe"], **{M.BIAS: REF.routing_bias(c, SEED)})
    return dict(params, groups=[g])


def test_forward_logits_match_reference():
    c = _cfg_dict()
    cfg = _model(c)
    params = _init(cfg, c)
    P = REF.init_params(c, SEED)
    flat = _flat(params)
    assert sorted(flat) == sorted(P)            # the same weights, leaf by leaf
    for k in P:
        np.testing.assert_array_equal(np.asarray(flat[k]), np.asarray(P[k]))
    tokens, _ = REF.batch(c, DATA, SEED, 0)
    got, _ = T.forward(cfg, params, tokens)
    with jax.default_matmul_precision("highest"):
        want = REF.logits(c, P, tokens)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-5)


def test_train_step_loss_and_grad_norms_match_reference():
    c = _cfg_dict()
    cfg = _model(c)
    t = c["train"]
    tc = TrainConfig(learning_rate=t["learning_rate"],
                     weight_decay=t["weight_decay"], beta1=t["beta1"],
                     beta2=t["beta2"], eps=t["eps"], grad_clip=t["grad_clip"],
                     warmup_steps=t["warmup_steps"], total_steps=10,
                     moe_seq_aux_alpha=t["seq_aux_alpha"],
                     moe_bias_rate=t["bias_update_rate"])
    params = _init(cfg, c)
    tokens, labels = REF.batch(c, DATA, SEED, 0)
    _, opt, metrics = jax.jit(steps.make_train_step(cfg, tc))(
        params, steps.init_opt_state(params),
        {"tokens": tokens, "labels": labels})
    ref = REF.train(c, DATA, SEED, n_steps=1, total_steps=10)
    np.testing.assert_allclose(float(metrics["loss"]), ref["losses"][0],
                               rtol=1e-5)
    mu = {k: float(jnp.linalg.norm(x)) for k, x in _flat(opt.mu).items()}
    assert sorted(mu) == sorted(ref["mu_norms"])     # the bias has no moment
    for k, r in ref["mu_norms"].items():
        np.testing.assert_allclose(mu[k], r, rtol=2e-3, atol=1e-9, err_msg=k)
    assert int(metrics["moe_rows"]) == ref["rows"][0]


@pytest.mark.parametrize("causal", [True, False])
def test_xla_flash_unequal_qk_and_v_head_dims(causal):
    B, S, H, D, Dv = 2, 64, 4, 24, 16
    ks = jax.random.split(jax.random.PRNGKey(3), 4)
    q = jax.random.normal(ks[0], (B, S, H, D))
    k = jax.random.normal(ks[1], (B, S, H, D))
    v = jax.random.normal(ks[2], (B, S, H, Dv))
    w = jax.random.normal(ks[3], (B, S, H, Dv))
    f = lambda impl: lambda q, k, v: jnp.sum(
        w * ops.attention(q, k, v, causal=causal, impl=impl))
    out = ops.flash_attention_xla(q, k, v, causal, 0, None, 16, 16)
    want = kref.attention(q, k, v, causal=causal)
    assert out.shape == (B, S, H, Dv)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5)
    g_flash = jax.grad(
        lambda q, k, v: jnp.sum(w * ops.flash_attention_xla(
            q, k, v, causal, 0, None, 16, 16)), argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(f("ref"), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_flash, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)


def _moe_params(cfg, key=1):
    """One MoE block's parameters, with a routing bias of a router
    mid-training (the init's is zero)."""
    p, _ = split_annotated(M.init_moe_mlp(jax.random.PRNGKey(key), cfg))
    if M.BIAS in p:
        p[M.BIAS] = 0.02 * jax.random.normal(jax.random.PRNGKey(key + 1),
                                             p[M.BIAS].shape)
    return p


def _share(p, first, count):
    return dict(p, **{n: p[n][first:first + count]
                      for n in ("gate", "up", "down")})


def test_held_shares_add_up_to_the_uncut_layer():
    """Summed over all shares, with the shared experts counted once, the
    held experts' parts equal the uncut layer's, and the bench reference's
    masked computation of it."""
    c = _cfg_dict(experts_held=[0, 16])
    whole = _model(c)
    p = _moe_params(whole)
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 32, 64))
    y_all, st = M.moe_mlp(whole, p, x)
    shared = M.mlp_block(whole, p["shared"], x)
    total = shared
    rows = 0
    for first in range(0, 16, 4):
        cfg = dataclasses.replace(whole, experts_held=(first, 4))
        y, s = M.moe_mlp(cfg, _share(p, first, 4), x)
        total = total + (y - shared)
        rows += int(s["rows"])
    np.testing.assert_allclose(np.asarray(total), np.asarray(y_all),
                               atol=2e-5)
    assert rows == int(st["rows"]) == 2 * 32 * 4
    lp = {f"moe/{k}": v for k, v in p.items() if k != "shared"}
    lp.update({f"moe/shared/{k}": v for k, v in p["shared"].items()})
    with jax.default_matmul_precision("highest"):
        want, _ = REF.moe_block(c, "f32", x, lp)
    np.testing.assert_allclose(np.asarray(y_all), np.asarray(want),
                               rtol=1e-4, atol=2e-5)


def test_dropless_under_forced_imbalance():
    """A bias that sends every token to expert 4 drops none: every pair
    routed to a held expert is computed, and the layer equals the
    reference's masked computation."""
    c = _cfg_dict()
    cfg = _model(c)
    p = _moe_params(cfg)
    p[M.BIAS] = p[M.BIAS].at[4].set(100.0)
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 32, 64))
    y, st = M.moe_mlp(cfg, p, x)
    assert float(st["load"][4]) == 64.0
    assert int(st["rows"]) == int(st["routed_held"]) >= 64
    assert float(st["load_max"]) == 64.0 / (64 * 4 / 16)
    lp = {f"moe/{k}": v for k, v in p.items() if k != "shared"}
    lp.update({f"moe/shared/{k}": v for k, v in p["shared"].items()})
    with jax.default_matmul_precision("highest"):
        want, (load, rows, _) = REF.moe_block(c, "f32", x, lp)
    assert int(rows) == int(st["rows"])
    np.testing.assert_allclose(np.asarray(y), np.asarray(want),
                               rtol=1e-4, atol=2e-5)


def test_routing_bias_stays_out_of_adamw_and_moves_by_sign():
    c = _cfg_dict()
    cfg = _model(c)
    rate = 1e-3
    tc = TrainConfig(warmup_steps=1, weight_decay=0.5, moe_bias_rate=rate)
    params = _init(cfg, c)
    trainable, buffers = T.split_buffers(params)
    path = ("groups", 0, "moe", M.BIAS)
    assert list(buffers) == [path]
    opt = steps.init_opt_state(params)
    assert not any(k.endswith(M.BIAS) for k in _flat(opt.mu))
    tokens, labels = REF.batch(c, DATA, SEED, 0)
    batch = {"tokens": tokens, "labels": labels}
    new, _, _ = jax.jit(steps.make_train_step(cfg, tc))(params, opt, batch)
    _, _, stats = T.forward_with_stats(cfg, params, tokens)
    load = stats["groups"][0]["load"]                   # (layers, E)
    b0 = buffers[path]
    want = b0 + rate * jnp.sign(load.mean(-1, keepdims=True) - load)
    np.testing.assert_allclose(np.asarray(T.split_buffers(new)[1][path]),
                               np.asarray(want), rtol=0, atol=1e-7)
    assert bool(jnp.all(jnp.abs(T.split_buffers(new)[1][path] - b0)
                        <= rate * (1 + 1e-5)))


def test_moe_counters_add_up_over_microbatches():
    """With grad_accum 2 the step's counts are the whole batch's: rows
    summed over the microbatches, the largest load the largest, and the
    routing biases moved by the whole batch's loads."""
    c = _cfg_dict()
    cfg = _model(c)
    params = _init(cfg, c)
    tokens, labels = REF.batch(c, DATA, SEED, 0)
    out, bias = {}, {}
    for accum in (1, 2):
        tc = TrainConfig(warmup_steps=1, grad_accum=accum)
        new, _, out[accum] = jax.jit(steps.make_train_step(cfg, tc))(
            params, steps.init_opt_state(params),
            {"tokens": tokens, "labels": labels})
        bias[accum] = new["groups"][0]["moe"][M.BIAS]
    halves = [T.forward_with_stats(cfg, params, tokens[i:i + 1])[2]
              for i in (0, 1)]
    rows = [T.moe_aux(h)["moe_rows"] for h in halves]
    load_max = [T.moe_aux(h)["moe_load_max"] for h in halves]
    assert int(out[2]["moe_rows"]) == int(sum(rows)) \
        == int(out[1]["moe_rows"])
    assert int(out[2]["moe_routed_held"]) == int(out[1]["moe_routed_held"])
    np.testing.assert_allclose(float(out[2]["moe_load_max"]),
                               float(max(load_max)), rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(bias[2]), np.asarray(bias[1]))


def test_softmax_router_has_no_balance_term():
    """DeepSeek-V3's sequence-wise balance loss is the sigmoid router's: a
    softmax router (Phi-3.5-MoE) trains on the plain loss."""
    c = _cfg_dict()
    cfg = dataclasses.replace(_model(c), score_fn="softmax")
    params, _ = T.init(cfg, jax.random.PRNGKey(SEED))
    assert not T.split_buffers(params)[1]
    tokens, labels = REF.batch(c, DATA, SEED, 0)
    batch = {"tokens": tokens, "labels": labels}
    loss, aux = T.lm_loss(cfg, params, batch)
    assert "moe_balance" not in aux and aux["moe_rows"] > 0
    _, _, m = jax.jit(steps.make_train_step(
        cfg, TrainConfig(moe_seq_aux_alpha=1.0)))(
            params, steps.init_opt_state(params), batch)
    np.testing.assert_allclose(float(m["loss"]), float(loss), rtol=1e-6)


@pytest.mark.pallas
def test_named_gmm_matches_ragged_dot_in_interpret_mode():
    """The Pallas grouped matmul (megablox through ``moe_gmm``/``moe_tgmm``)
    against ``jax.lax.ragged_dot``: values and both gradients on the rows
    the groups cover (the rows past them are undefined, and masked)."""
    m, k, n, G = 512, 128, 256, 4
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    lhs = jax.random.normal(ks[0], (m, k), jnp.float32)
    rhs = jax.random.normal(ks[1], (G, k, n), jnp.float32)
    sizes = jnp.asarray([100, 0, 211, 90], jnp.int32)
    valid = (jnp.arange(m) < jnp.sum(sizes))[:, None]
    w = jax.random.normal(ks[2], (m, n))

    def loss(impl):
        return lambda a, b: jnp.sum(jnp.where(valid, w * moe_gmm.gmm(
            jnp.where(valid, a, 0), b, sizes, impl=impl, interpret=True),
            0))

    got = moe_gmm.gmm(lhs, rhs, sizes, impl="pallas", interpret=True)
    want = jax.lax.ragged_dot(lhs, rhs, sizes)
    np.testing.assert_allclose(np.where(valid, got, 0),
                               np.where(valid, want, 0), rtol=1e-5, atol=1e-4)
    g1 = jax.grad(loss("pallas"), argnums=(0, 1))(lhs, rhs)
    g2 = jax.grad(loss("ragged_dot"), argnums=(0, 1))(lhs, rhs)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5,
                                   atol=1e-3)


def test_undefined_rows_past_the_groups_stay_out(monkeypatch):
    """The grouped matmul leaves rows past the groups undefined (on the
    chip, whatever the buffer held): with those rows NaN the layer's output
    and every gradient stay finite and unchanged."""
    c = _cfg_dict()
    cfg = _model(c)
    p = _moe_params(cfg)
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 32, 64))

    def grads():
        f = lambda p, x: jnp.sum(M.moe_mlp(cfg, p, x)[0] ** 2)
        return jax.grad(f, argnums=(0, 1))(p, x)

    clean = grads()
    real = moe_gmm.gmm

    def nan_rows(lhs, rhs, sizes, **kw):
        out = real(lhs, rhs, sizes, **kw)
        past = jnp.arange(out.shape[0]) >= jnp.sum(sizes)
        return jnp.where(past[:, None], jnp.nan, out)

    monkeypatch.setattr(moe_gmm, "gmm", nan_rows)
    dirty = grads()
    for a, b in zip(jax.tree_util.tree_leaves(dirty),
                    jax.tree_util.tree_leaves(clean)):
        assert bool(jnp.all(jnp.isfinite(a)))
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
