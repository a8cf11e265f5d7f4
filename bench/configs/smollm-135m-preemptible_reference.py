"""Plain reference of the ``smollm-135m-preemptible`` configuration.

Written from the published SmolLM-135M (Llama) architecture and the
configuration file alone; it imports nothing of the system under test.

* Weights from the seed as the file's ``train.init`` states: one key split
  five ways; the embedding from the first, the 30 layers from the fifth
  (split per layer, then per layer into attention and MLP keys).
* Batches: the synthetic Zipf stream with periodic copies that the
  configuration's traffic describes, a pure function of (seed, step).
* The forward pass in float32 with ``highest`` matmul precision: RMSNorm,
  GQA causal attention with rotary positions (half-split rotation), SwiGLU,
  tied output head; loss is the mean next-token cross-entropy plus the
  z-loss.  Each layer and each sequence's loss are rematerialised so that
  the reference fits one chip.
* AdamW with global-norm clipping and the warmup-cosine schedule, float32.

``mm="fp8"`` computes every matmul from per-tensor-scaled float8 (e4m3)
operands, forward and backward: the control, one precision below the
configuration's bfloat16.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

F8 = jnp.float8_e4m3fn
F8_MAX = 448.0


def _sizes(cfg):
    d, H, KV = (cfg["hidden_size"], cfg["num_attention_heads"],
                cfg["num_key_value_heads"])
    return d, H, KV, d // H, cfg["intermediate_size"], cfg["vocab_size"], \
        cfg["num_hidden_layers"]


# -- weights and data ---------------------------------------------------------

def init_params(cfg: dict, seed: int) -> dict:
    d, H, KV, hd, ff, V, L = _sizes(cfg)
    std = cfg["initializer_range"]
    keys = jax.random.split(jax.random.PRNGKey(seed), 5)
    normal = lambda k, shape: std * jax.random.normal(k, shape, jnp.float32)

    def layer(k):
        ka, km = jax.random.split(k, 2)
        a, m = jax.random.split(ka, 4), jax.random.split(km, 3)
        return {"attn/wq": normal(a[0], (d, H * hd)),
                "attn/wk": normal(a[1], (d, KV * hd)),
                "attn/wv": normal(a[2], (d, KV * hd)),
                "attn/wo": normal(a[3], (H * hd, d)),
                "mlp/gate": normal(m[0], (d, ff)),
                "mlp/up": normal(m[1], (d, ff)),
                "mlp/down": normal(m[2], (ff, d))}

    layers = jax.vmap(layer)(jax.random.split(keys[4], L))
    P = {"embed/table": normal(keys[0], (V, d)),
         "final_norm/scale": jnp.ones((d,), jnp.float32)}
    P.update({f"groups/0/{k}": v for k, v in layers.items()})
    P["groups/0/ln1/scale"] = jnp.ones((L, d), jnp.float32)
    P["groups/0/ln2/scale"] = jnp.ones((L, d), jnp.float32)
    return P


def batch(cfg: dict, data: dict, seed: int, step: int):
    """(tokens, labels) of the synthetic stream at ``step``."""
    V, S, B = cfg["vocab_size"], cfg["train"]["seq_len"], \
        cfg["train"]["global_batch"]
    ranks = np.arange(1, V + 1, dtype=np.float64)
    p = ranks ** (-data["zipf_alpha"])
    logp = jnp.log(jnp.asarray(p / p.sum(), jnp.float32))
    key = jax.random.fold_in(jax.random.fold_in(jax.random.PRNGKey(seed),
                                                step), 0)
    draw = jax.random.categorical(key, logp[None, None, :], shape=(B, S + 1))
    period = data["markov_period"]
    idx = jnp.arange(S + 1)
    src = jnp.maximum(idx - period // 2, 0)
    seq = jnp.where(((idx % period) >= period // 2)[None, :], draw[:, src],
                    draw)
    return seq[:, :-1].astype(jnp.int32), seq[:, 1:].astype(jnp.int32)


# -- matmuls ------------------------------------------------------------------

def _fake_f8(x):
    s = F8_MAX / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    return (x * s).astype(F8).astype(jnp.float32) / s


@jax.custom_vjp
def _q_in(x):                  # operand in float8; gradient passes through
    return _fake_f8(x)


_q_in.defvjp(lambda x: (_fake_f8(x), None), lambda _, g: (g,))


@jax.custom_vjp
def _q_out(y):                 # identity; its cotangent in float8
    return y


_q_out.defvjp(lambda y: (y, None), lambda _, g: (_fake_f8(g),))


def _mm(spec, a, b, mode):
    if mode == "fp8":
        return _q_out(jnp.einsum(spec, _q_in(a), _q_in(b),
                                 precision=jax.lax.Precision.HIGHEST))
    return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST)


# -- the model ----------------------------------------------------------------

def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * scale


def _rope(x, theta):
    B, S, _, D = x.shape
    freqs = 1.0 / (theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D))
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs      # (S, D/2)
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _layer(cfg, mode, x, lp):
    d, H, KV, hd, ff, V, L = _sizes(cfg)
    eps, B, S = cfg["rms_norm_eps"], x.shape[0], x.shape[1]
    h = _rms(x, lp["ln1/scale"], eps)
    q = _mm("bsd,dq->bsq", h, lp["attn/wq"], mode).reshape(B, S, H, hd)
    k = _mm("bsd,dq->bsq", h, lp["attn/wk"], mode).reshape(B, S, KV, hd)
    v = _mm("bsd,dq->bsq", h, lp["attn/wv"], mode).reshape(B, S, KV, hd)
    q, k = _rope(q, cfg["rope_theta"]), _rope(k, cfg["rope_theta"])
    k, v = jnp.repeat(k, H // KV, axis=2), jnp.repeat(v, H // KV, axis=2)
    s = _mm("bqhd,bkhd->bhqk", q, k, mode) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((S, S), bool))
    s = jnp.where(causal[None, None], s, -jnp.inf)
    o = _mm("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v, mode)
    x = x + _mm("bsq,qd->bsd", o.reshape(B, S, H * hd), lp["attn/wo"], mode)
    h = _rms(x, lp["ln2/scale"], eps)
    g = _mm("bsd,df->bsf", h, lp["mlp/gate"], mode)
    u = _mm("bsd,df->bsf", h, lp["mlp/up"], mode)
    return x + _mm("bsf,fd->bsd", jax.nn.silu(g) * u, lp["mlp/down"], mode), \
        None


def loss(cfg, P, tokens, labels, mode="f32"):
    zl = cfg["train"]["z_loss"]
    layers = {k[len("groups/0/"):]: v for k, v in P.items()
              if k.startswith("groups/0/")}
    x = P["embed/table"][tokens]
    x, _ = jax.lax.scan(jax.checkpoint(functools.partial(_layer, cfg, mode)),
                        x, layers)
    x = _rms(x, P["final_norm/scale"], cfg["rms_norm_eps"])

    @jax.checkpoint
    def one_sequence(xs):
        xb, lb = xs
        logits = _mm("sd,vd->sv", xb, P["embed/table"], mode)
        logz = jax.scipy.special.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, lb[:, None], axis=-1)[:, 0]
        return jnp.sum(logz - gold), jnp.sum(logz * logz)

    nll, z2 = jax.lax.map(one_sequence, (x, labels))
    n = labels.size
    return jnp.sum(nll) / n + zl * jnp.sum(z2) / n


def lr_at(step: int, tcfg: dict, total_steps: int) -> float:
    """Linear warmup, then cosine to ``min_lr_ratio``; step is 0-based."""
    s, w = step + 1.0, tcfg["warmup_steps"]
    if s < w:
        return tcfg["learning_rate"] * s / max(w, 1)
    frac = min(max((s - w) / max(total_steps - w, 1), 0.0), 1.0)
    r = tcfg["min_lr_ratio"]
    return tcfg["learning_rate"] * (r + (1 - r) * 0.5
                                    * (1 + math.cos(math.pi * frac)))


def _step(cfg, mode, P, m, v, t, tokens, labels, lr):
    tc = cfg["train"]
    with jax.default_matmul_precision("highest"):
        value, g = jax.value_and_grad(
            lambda p: loss(cfg, p, tokens, labels, mode))(P)
    gnorm = jnp.sqrt(sum(jnp.sum(x * x) for x in g.values()))
    scale = jnp.minimum(1.0, tc["grad_clip"] / jnp.maximum(gnorm, 1e-9))
    b1, b2 = tc["beta1"], tc["beta2"]
    t = t + 1.0
    out = {}
    for k in P:
        gk = g[k] * scale
        mk = b1 * m[k] + (1 - b1) * gk
        vk = b2 * v[k] + (1 - b2) * gk * gk
        upd = (mk / (1 - b1 ** t)) / (jnp.sqrt(vk / (1 - b2 ** t)) + tc["eps"]) \
            + tc["weight_decay"] * P[k]
        out[k] = (P[k] - lr * upd, mk, vk)
    return ({k: o[0] for k, o in out.items()}, {k: o[1] for k, o in out.items()},
            {k: o[2] for k, o in out.items()}, value)


_norms = jax.jit(lambda tree: {k: jnp.sqrt(jnp.sum(x * x))
                               for k, x in tree.items()})


def train(cfg: dict, data: dict, seed: int, *, n_steps: int,
          total_steps: int, mode: str = "f32", rows=None) -> dict:
    """Run the first ``n_steps`` steps from the seed's weights.  Returns the
    losses, the per-leaf norms of Adam's first moment after one step, and of
    the parameters' change after ``n_steps``.  ``rows`` keeps a slice of
    each batch's sequences (a planted fault: part of the batch left out)."""
    P = init_params(cfg, seed)
    P0 = dict(P)
    m = {k: jnp.zeros_like(x) for k, x in P.items()}
    v = {k: jnp.zeros_like(x) for k, x in P.items()}
    step_fn = jax.jit(functools.partial(_step, cfg, mode))
    losses, mu_norms = [], None
    for step in range(n_steps):
        tokens, labels = batch(cfg, data, seed, step)
        if rows is not None:
            tokens, labels = tokens[rows], labels[rows]
        P, m, v, value = step_fn(P, m, v, jnp.float32(step), tokens, labels,
                                 jnp.float32(lr_at(step, cfg["train"],
                                                   total_steps)))
        losses.append(float(value))
        if step == 0:
            mu_norms = {k: float(x) for k, x in _norms(m).items()}
    delta = {k: P[k] - P0[k] for k in P}
    return dict(losses=losses, mu_norms=mu_norms,
                update_norms={k: float(x) for k, x in _norms(delta).items()})
