"""Mixture-of-Experts block: a router over all experts and a dropless expert
layer that computes the part of the result given by the experts this device
holds (``cfg.experts_held``): expert parallelism's local half.  With every
expert held it is the whole layer; on one chip it runs without the exchange.

Routing, in float32: scores = softmax or sigmoid of x W_r over all E
experts; the k experts are chosen by score plus the DeepSeek-V3 correction
bias (``e_score_correction_bias``, sigmoid routers only: used to choose and
nowhere else); their weights are the unbiased scores, renormalised if
``norm_topk``, times ``routed_scale``.

Expert layer: the (token, k) pairs whose expert is held are sorted by expert
into one buffer that holds every such pair (at most T x min(k, held) rows,
so no capacity and no drops); one grouped matmul computes gate|up, one the
down projection (``kernels.moe_gmm``), and the weighted rows are
scatter-added back to their tokens.  Shared experts (one SwiGLU of width
``n_shared_experts x moe_d_ff``) see every token.

Used by moonshot-v1-16b-a3b (64 experts, 6 per token, sigmoid, 2 shared) and
phi3.5-moe-42b-a6.6b (16 experts, 2 per token, softmax).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .. import sharding
from ..kernels import moe_gmm
from ..sharding import annotate as A
from .layers import cdt, pdt, init_rmsnorm, rms_norm, init_attention, \
    attention_block, init_mlp, mlp_block, _normal

BIAS = "e_score_correction_bias"


def init_moe_mlp(key, cfg):
    ks = jax.random.split(key, 5)
    d, f, E, H = cfg.d_model, cfg.expert_ff, cfg.n_experts, cfg.n_held
    p = {
        "router": A(_normal(ks[0], (d, E), pdt(cfg)), "w_embed", "w_experts"),
        "gate": A(_normal(ks[1], (H, d, f), pdt(cfg)), "w_experts",
                  "w_expert_ff", None),
        "up": A(_normal(ks[2], (H, d, f), pdt(cfg)), "w_experts",
                "w_expert_ff", None),
        "down": A(_normal(ks[3], (H, f, d), pdt(cfg)), "w_experts", None,
                  "w_expert_ff"),
    }
    if cfg.score_fn == "sigmoid":
        p[BIAS] = A(jnp.zeros((E,), jnp.float32), None)
    if cfg.n_shared_experts:
        p["shared"] = init_mlp(ks[4], cfg, f=cfg.n_shared_experts * f)
    return p


def route(cfg, p, xt):
    """xt: (T, d).  Returns (idx (T, K) chosen experts, w (T, K) float32
    weights, scores (T, E) float32)."""
    logits = jnp.einsum("td,de->te", xt.astype(jnp.float32),
                        p["router"].astype(jnp.float32),
                        precision=jax.lax.Precision.HIGHEST)
    if cfg.score_fn == "sigmoid":
        scores = jax.nn.sigmoid(logits)
    elif cfg.score_fn == "softmax":
        scores = jax.nn.softmax(logits, axis=-1)
    else:
        raise ValueError(f"unknown score_fn {cfg.score_fn!r}")
    choose = scores + p[BIAS][None, :] if BIAS in p else scores
    _, idx = jax.lax.top_k(choose, cfg.top_k)
    w = jnp.take_along_axis(scores, idx, axis=-1)
    if cfg.norm_topk:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return idx, w * cfg.routed_scale, scores


def held_experts(cfg, p, xt, idx, w):
    """The held experts' part of the routed output, dropless.  Returns
    ((T, d) float32, per-held-expert row counts (H,) int32)."""
    T, d = xt.shape
    K = idx.shape[1]
    first, H = cfg.held
    dt = cdt(cfg)
    e = idx.reshape(-1)
    key = jnp.where((e >= first) & (e < first + H), e - first, H)
    order = jnp.argsort(key, stable=True)        # held pairs first, by expert
    sizes = jnp.sum(key[None, :] == jnp.arange(H)[:, None], axis=1,
                    dtype=jnp.int32)
    M = T * min(K, H)                            # every held pair fits
    tm = moe_gmm.row_tile(M)
    Mp = -(-M // tm) * tm
    sel = jnp.pad(order[:M], (0, Mp - M))
    valid = (jnp.arange(Mp) < jnp.sum(sizes))[:, None]
    tok = sel // K

    def gmm(x, weights):
        # rows past the groups come back undefined (NaN, say): select them
        # away at once, before any product whose gradient would read them
        return jnp.where(valid, moe_gmm.gmm(x, weights.astype(dt), sizes), 0)

    xs = jnp.where(valid, xt[tok].astype(dt), 0)
    gu = gmm(xs, jnp.concatenate([p["gate"], p["up"]], axis=-1))
    f = cfg.expert_ff
    ys = gmm(jax.nn.silu(gu[:, :f]) * gu[:, f:], p["down"])
    ys = ys.astype(jnp.float32) * w.reshape(-1)[sel][:, None]
    return jnp.zeros((T, d), jnp.float32).at[tok].add(ys), sizes


def balance(cfg, idx, scores, B):
    """DeepSeek-V3's sequence-wise balance term (unweighted): per sequence,
    sum_i f_i P_i with f_i = E/(K S) x the count of tokens choosing expert i
    and P_i the mean over the sequence of the normalised scores; the mean
    over sequences."""
    E, K = cfg.n_experts, cfg.top_k
    S = idx.shape[0] // B
    counts = jnp.sum(jax.nn.one_hot(idx.reshape(B, S * K), E,
                                    dtype=jnp.float32), axis=1)     # (B, E)
    f = jax.lax.stop_gradient(counts * E / (K * S))
    s = scores / jnp.sum(scores, axis=-1, keepdims=True)
    P = jnp.mean(s.reshape(B, S, E), axis=1)
    return jnp.mean(jnp.sum(f * P, axis=-1))


def update_bias(b, load, rate):
    """The aux-loss-free update: b += rate * sign(mean load - load), over
    every expert (``load`` may carry leading layer axes)."""
    return b + rate * jnp.sign(jnp.mean(load, axis=-1, keepdims=True) - load)


def moe_mlp(cfg, p, x):
    """x: (B, S, d) -> ((B, S, d), stats).  stats: ``load`` (E,) pairs routed
    to each expert, ``rows`` held pairs computed (the grouped matmuls' valid
    rows), ``routed_held`` pairs routed to held experts (counted from the
    routing alone: equal to ``rows`` when nothing drops), ``load_max`` the largest
    held expert's rows over T K / E, and for sigmoid routers ``balance``, the
    sequence-wise term."""
    B, S, d = x.shape
    xt = x.reshape(B * S, d)
    idx, w, scores = route(cfg, p, xt)
    y, sizes = held_experts(cfg, p, xt, idx, w)
    y = y.reshape(B, S, d).astype(x.dtype)
    if cfg.n_shared_experts:
        y = y + mlp_block(cfg, p["shared"], x)
    E, K = cfg.n_experts, cfg.top_k
    first, H = cfg.held
    load = jnp.sum(jax.nn.one_hot(idx.reshape(-1), E, dtype=jnp.float32),
                   axis=0)
    stats = {
        "load": load,
        "rows": jnp.sum(sizes),
        "routed_held": jnp.sum(load[first:first + H]).astype(jnp.int32),
        "load_max": jnp.max(sizes).astype(jnp.float32) / (B * S * K / E),
    }
    if cfg.score_fn == "sigmoid":      # DeepSeek-V3's objective, not softmax's
        stats["balance"] = balance(cfg, idx, scores, B)
    return sharding.constrain(y, "act_batch", "act_seq", "act_embed"), stats


def init_moe_layer(key, cfg):
    ks = jax.random.split(key, 2)
    return {"ln1": init_rmsnorm(cfg), "attn": init_attention(ks[0], cfg),
            "ln2": init_rmsnorm(cfg), "moe": init_moe_mlp(ks[1], cfg)}


def moe_layer(cfg, p, x, *, positions, cache=None, mode="train", window=0):
    h, new_cache = attention_block(cfg, p["attn"],
                                   rms_norm(x, p["ln1"], cfg.norm_eps),
                                   positions=positions, cache=cache, mode=mode,
                                   window=window)
    x = x + h
    y, stats = moe_mlp(cfg, p["moe"], rms_norm(x, p["ln2"], cfg.norm_eps))
    return x + y, new_cache, stats
