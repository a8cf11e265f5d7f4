"""Plain reference of the ``moonlight-16b-a3b-ep8`` configuration.

Written from the published Moonlight-16B-A3B (DeepSeek-V3) architecture and
the configuration file alone; it imports nothing of the system under test,
and takes the configuration dict, so tests run it at toy widths.

* Weights from the seed as the file's ``train.init`` states: one key split
  five ways; the embedding from the first, the output head from the second,
  the leading dense layer(s) from the third, the MoE layers from the fifth
  (split per layer, then per layer into attention and MoE keys; the MoE key
  five ways: router, gate, up, down, shared experts).  The routing biases
  (MoE layers x routed experts) are ``routing_bias_std`` x a normal draw
  from the seed's key folded with 1, as a router's mid-training.
* Batches: the synthetic Zipf stream with periodic copies that the
  configuration's traffic describes, a pure function of (seed, step).
* The forward pass in float32 with ``highest`` matmul precision: RMSNorm;
  multi-head latent attention in its training form (query to 16 heads of
  128 + 64, KV down-projection to the 512 latent plus one shared 64-wide
  RoPE key, RMSNorm on the latent, up-projection to per-head 128 keys and
  128 values, RoPE on the 64-wide slices only, half-split rotation, scale
  1/sqrt(192)), computed per block of queries; the dense SwiGLU layer; the
  MoE layers: sigmoid scores over all routed experts (``router_experts``),
  the top k chosen by score + correction bias, weights from the unbiased
  scores, normalised, x ``routed_scaling_factor``; the held experts
  (``experts_held``) each applied to every token and masked by that
  expert's selection (no sort, no grouped product); the shared experts as
  one SwiGLU of width n_shared x moe_intermediate_size.  Loss: mean
  next-token cross-entropy + z-loss + ``seq_aux_alpha`` x the sequence-wise
  balance terms summed over layers.  Each layer, each query block and each
  chunk of the head is rematerialised so that the reference fits one chip.
* AdamW with global-norm clipping and the warmup-cosine schedule, float32,
  over every parameter but the routing biases; each bias then moves by
  ``bias_update_rate`` x sign(mean load - load) over all routed experts.

``mode="fp8"`` computes every matmul from per-tensor-scaled float8 (e4m3)
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
BIAS = "e_score_correction_bias"
Q_BLOCK = 1024      # queries per attention block
HEAD_CHUNK = 2048   # tokens per chunk of the output head


class _Sizes:
    def __init__(self, cfg):
        self.d, self.H = cfg["hidden_size"], cfg["num_attention_heads"]
        self.r, self.rope = cfg["kv_lora_rank"], cfg["qk_rope_head_dim"]
        self.nope, self.vd = cfg["qk_nope_head_dim"], cfg["v_head_dim"]
        self.qk = self.nope + self.rope
        self.ff, self.fe = cfg["intermediate_size"], \
            cfg["moe_intermediate_size"]
        self.E, self.K = cfg["router_experts"], cfg["num_experts_per_tok"]
        self.first, self.held = cfg["experts_held"]
        self.shared = cfg["n_shared_experts"]
        self.V = cfg["vocab_size"]
        self.lead = cfg["first_k_dense_replace"]
        self.L = cfg["num_hidden_layers"] - self.lead


# -- weights and data ---------------------------------------------------------

def init_params(cfg: dict, seed: int) -> dict:
    z = _Sizes(cfg)
    d, H = z.d, z.H
    std = cfg["initializer_range"]
    keys = jax.random.split(jax.random.PRNGKey(seed), 5)
    normal = lambda k, shape: std * jax.random.normal(k, shape, jnp.float32)

    def attn(k):
        a = jax.random.split(k, 4)
        return {"attn/wq": normal(a[0], (d, H * z.qk)),
                "attn/wkv_a": normal(a[1], (d, z.r + z.rope)),
                "attn/kv_norm/scale": jnp.ones((z.r,), jnp.float32),
                "attn/wkv_b": normal(a[2], (z.r, H * (z.nope + z.vd))),
                "attn/wo": normal(a[3], (H * z.vd, d))}

    def swiglu(k, f):
        m = jax.random.split(k, 3)
        return {"gate": normal(m[0], (d, f)), "up": normal(m[1], (d, f)),
                "down": normal(m[2], (f, d))}

    def dense_layer(k):
        ka, km = jax.random.split(k, 2)
        out = attn(ka)
        out.update({f"mlp/{n}": w for n, w in swiglu(km, z.ff).items()})
        out["ln1/scale"] = jnp.ones((d,), jnp.float32)
        out["ln2/scale"] = jnp.ones((d,), jnp.float32)
        return out

    def moe_layer(k):
        ka, km = jax.random.split(k, 2)
        out = attn(ka)
        m = jax.random.split(km, 5)
        out["moe/router"] = normal(m[0], (d, z.E))
        out["moe/gate"] = normal(m[1], (z.held, d, z.fe))
        out["moe/up"] = normal(m[2], (z.held, d, z.fe))
        out["moe/down"] = normal(m[3], (z.held, z.fe, d))
        out.update({f"moe/shared/{n}": w for n, w in
                    swiglu(m[4], z.shared * z.fe).items()})
        return out

    P = {"embed/table": normal(keys[0], (z.V, d)),
         "lm_head/out": normal(keys[1], (d, z.V)),
         "final_norm/scale": jnp.ones((d,), jnp.float32)}
    for i, k in enumerate(jax.random.split(keys[2], z.lead)):
        P.update({f"lead/{i}/{n}": w for n, w in dense_layer(k).items()})
    layers = jax.vmap(moe_layer)(jax.random.split(keys[4], z.L))
    P.update({f"groups/0/{n}": w for n, w in layers.items()})
    P["groups/0/ln1/scale"] = jnp.ones((z.L, d), jnp.float32)
    P["groups/0/ln2/scale"] = jnp.ones((z.L, d), jnp.float32)
    P[f"groups/0/moe/{BIAS}"] = routing_bias(cfg, seed)
    return P


def routing_bias(cfg: dict, seed: int):
    """(MoE layers, routed experts) routing biases of a router
    mid-training."""
    z = _Sizes(cfg)
    key = jax.random.fold_in(jax.random.PRNGKey(seed), 1)
    return cfg["train"]["routing_bias_std"] * jax.random.normal(
        key, (z.L, z.E), jnp.float32)


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


def _mla(cfg, mode, x, lp):
    z = _Sizes(cfg)
    B, S, _ = x.shape
    H, eps = z.H, cfg["rms_norm_eps"]
    q = _mm("bsd,dq->bsq", x, lp["attn/wq"], mode).reshape(B, S, H, z.qk)
    kv_a = _mm("bsd,dr->bsr", x, lp["attn/wkv_a"], mode)
    c = _rms(kv_a[..., :z.r], lp["attn/kv_norm/scale"], eps)
    kv = _mm("bsr,rq->bsq", c, lp["attn/wkv_b"], mode) \
        .reshape(B, S, H, z.nope + z.vd)
    theta = cfg["rope_theta"]
    q = jnp.concatenate([q[..., :z.nope], _rope(q[..., z.nope:], theta)], -1)
    k_rope = _rope(kv_a[..., z.r:][:, :, None, :], theta)
    k = jnp.concatenate([kv[..., :z.nope],
                         jnp.broadcast_to(k_rope, (B, S, H, z.rope))], -1)
    v = kv[..., z.nope:]
    bq = min(Q_BLOCK, S)
    scale = 1.0 / math.sqrt(z.qk)

    @jax.checkpoint
    def q_block(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * bq, bq, axis=1)
        s = _mm("bqhd,bkhd->bhqk", qb, k, mode) * scale
        causal = (jnp.arange(S)[None, :]
                  <= (i * bq + jnp.arange(bq))[:, None])
        s = jnp.where(causal[None, None], s, -jnp.inf)
        return _mm("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v, mode)

    o = jax.lax.map(q_block, jnp.arange(S // bq))        # (nq, B, bq, H, vd)
    o = jnp.moveaxis(o, 0, 1).reshape(B, S, H * z.vd)
    return _mm("bsq,qd->bsd", o, lp["attn/wo"], mode)


def _swiglu(h, gate, up, down, mode):
    g = _mm("bsd,df->bsf", h, gate, mode)
    u = _mm("bsd,df->bsf", h, up, mode)
    return _mm("bsf,fd->bsd", jax.nn.silu(g) * u, down, mode)


def _dense_layer(cfg, mode, x, lp):
    eps = cfg["rms_norm_eps"]
    x = x + _mla(cfg, mode, _rms(x, lp["ln1/scale"], eps), lp)
    h = _rms(x, lp["ln2/scale"], eps)
    return x + _swiglu(h, lp["mlp/gate"], lp["mlp/up"], lp["mlp/down"], mode)


def route(cfg, mode, h, router, bias):
    """h (B, S, d) -> chosen experts (B, S, K), their weights, scores."""
    z = _Sizes(cfg)
    scores = jax.nn.sigmoid(_mm("bsd,de->bse", h, router, mode))
    _, idx = jax.lax.top_k(scores + bias, z.K)
    w = jnp.take_along_axis(scores, idx, axis=-1)
    if cfg["norm_topk_prob"]:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return idx, w * cfg["routed_scaling_factor"], scores


def moe_block(cfg, mode, h, lp):
    """The MoE block on normed input h (B, S, d): the shared experts plus
    the held experts' part.  Returns (y, (load over all experts (E,), held
    rows, balance))."""
    z = _Sizes(cfg)
    S = h.shape[1]
    idx, w, scores = route(cfg, mode, h, lp["moe/router"],
                           jax.lax.stop_gradient(lp[f"moe/{BIAS}"]))
    y = _swiglu(h, lp["moe/shared/gate"], lp["moe/shared/up"],
                lp["moe/shared/down"], mode) if z.shared else 0.0
    rows = jnp.zeros((), jnp.int32)
    for j in range(z.held):                 # each held expert, masked
        pick = idx == z.first + j                                # (B, S, K)
        wj = jnp.sum(jnp.where(pick, w, 0.0), axis=-1)           # (B, S)
        yj = _swiglu(h, lp["moe/gate"][j], lp["moe/up"][j],
                     lp["moe/down"][j], mode)
        y = y + wj[..., None] * yj
        rows = rows + jnp.sum(pick, dtype=jnp.int32)
    onehot = jax.nn.one_hot(idx, z.E, dtype=jnp.float32)         # (B,S,K,E)
    load = jnp.sum(onehot, axis=(0, 1, 2))
    f = jnp.sum(onehot, axis=(1, 2)) * z.E / (z.K * S)           # (B, E)
    P = jnp.mean(scores / jnp.sum(scores, -1, keepdims=True), axis=1)
    bal = jnp.mean(jnp.sum(f * P, axis=-1))
    return y, (load, rows, bal)


def _moe_layer(cfg, mode, x, lp):
    eps = cfg["rms_norm_eps"]
    x = x + _mla(cfg, mode, _rms(x, lp["ln1/scale"], eps), lp)
    y, stats = moe_block(cfg, mode, _rms(x, lp["ln2/scale"], eps), lp)
    return x + y, stats


def _trunk(cfg, P, tokens, mode):
    """Embedding, the dense lead and the MoE layers, and the final norm."""
    x = P["embed/table"][tokens]
    for i in range(cfg["first_k_dense_replace"]):
        lp = {k[len(f"lead/{i}/"):]: v for k, v in P.items()
              if k.startswith(f"lead/{i}/")}
        x = jax.checkpoint(functools.partial(_dense_layer, cfg, mode))(x, lp)
    layers = {k[len("groups/0/"):]: v for k, v in P.items()
              if k.startswith("groups/0/")}
    x, stats = jax.lax.scan(
        jax.checkpoint(functools.partial(_moe_layer, cfg, mode)), x, layers)
    return _rms(x, P["final_norm/scale"], cfg["rms_norm_eps"]), stats


def logits(cfg, P, tokens, mode="f32"):
    """(B, S, V) logits of the whole forward pass (small sizes only)."""
    x, _ = _trunk(cfg, P, tokens, mode)
    return _mm("bsd,dv->bsv", x, P["lm_head/out"], mode)


def loss(cfg, P, tokens, labels, mode="f32"):
    """Returns (loss, (per-layer loads (L, E), held rows summed over the
    layers))."""
    t = cfg["train"]
    x, (load, rows, bal) = _trunk(cfg, P, tokens, mode)
    n = labels.size
    c = min(HEAD_CHUNK, n)

    @jax.checkpoint
    def chunk(xs):
        xb, lb = xs
        logits = _mm("sd,dv->sv", xb, P["lm_head/out"], mode)
        logz = jax.scipy.special.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, lb[:, None], axis=-1)[:, 0]
        return jnp.sum(logz - gold), jnp.sum(logz * logz)

    nll, z2 = jax.lax.map(chunk, (x.reshape(n // c, c, -1),
                                  labels.reshape(n // c, c)))
    value = jnp.sum(nll) / n + t["z_loss"] * jnp.sum(z2) / n \
        + t["seq_aux_alpha"] * jnp.sum(bal)
    return value, (load, jnp.sum(rows))


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
    bias_key = f"groups/0/moe/{BIAS}"
    with jax.default_matmul_precision("highest"):
        (value, (load, rows)), g = jax.value_and_grad(
            lambda p: loss(cfg, p, tokens, labels, mode), has_aux=True)(P)
    g = {k: x for k, x in g.items() if k != bias_key}
    gnorm = jnp.sqrt(sum(jnp.sum(x * x) for x in g.values()))
    scale = jnp.minimum(1.0, tc["grad_clip"] / jnp.maximum(gnorm, 1e-9))
    b1, b2 = tc["beta1"], tc["beta2"]
    t = t + 1.0
    newP, newm, newv = {}, {}, {}
    for k in g:
        gk = g[k] * scale
        mk = b1 * m[k] + (1 - b1) * gk
        vk = b2 * v[k] + (1 - b2) * gk * gk
        upd = (mk / (1 - b1 ** t)) / (jnp.sqrt(vk / (1 - b2 ** t)) + tc["eps"]) \
            + tc["weight_decay"] * P[k]
        newP[k], newm[k], newv[k] = P[k] - lr * upd, mk, vk
    newP[bias_key] = P[bias_key] + tc["bias_update_rate"] * jnp.sign(
        jnp.mean(load, axis=-1, keepdims=True) - load)
    return newP, newm, newv, value, rows


_norms = jax.jit(lambda tree: {k: jnp.sqrt(jnp.sum(x * x))
                               for k, x in tree.items()})
_diff_norms = jax.jit(lambda a, b: {k: jnp.sqrt(jnp.sum(jnp.square(a[k]
                                                                  - b[k])))
                                    for k in a})


def train(cfg: dict, data: dict, seed: int, *, n_steps: int,
          total_steps: int, mode: str = "f32", rows=None) -> dict:
    """Run the first ``n_steps`` steps from the seed's weights.  Returns the
    losses, the per-leaf norms of Adam's first moment after one step and
    of the parameters' change after ``n_steps`` (the routing biases, which
    Adam does not hold, left out of both), the held rows of each step, and
    ``bias_steps``: each routing bias's change after ``n_steps`` in units
    of ``bias_update_rate`` (the sum of its sign steps), layer by layer.
    ``rows`` keeps a slice of each batch's sequences (a planted fault: part
    of the batch left out).  The step updates its state in place (donated),
    and the initial weights are drawn again at the end, so that the state
    of a full-size configuration fits one chip."""
    P = init_params(cfg, seed)
    bias_key = f"groups/0/moe/{BIAS}"
    m = {k: jnp.zeros_like(x) for k, x in P.items() if k != bias_key}
    v = {k: jnp.zeros_like(x) for k, x in m.items()}
    step_fn = jax.jit(functools.partial(_step, cfg, mode),
                      donate_argnums=(0, 1, 2))
    losses, held, mu_norms = [], [], None
    for step in range(n_steps):
        tokens, labels = batch(cfg, data, seed, step)
        if rows is not None:
            tokens, labels = tokens[rows], labels[rows]
        P, m, v, value, n_rows = step_fn(
            P, m, v, jnp.float32(step), tokens, labels,
            jnp.float32(lr_at(step, cfg["train"], total_steps)))
        losses.append(float(value))
        held.append(int(n_rows))
        if step == 0:
            mu_norms = {k: float(x) for k, x in _norms(m).items()}
    del m, v
    steps = jnp.round((P.pop(bias_key) - routing_bias(cfg, seed))
                      / cfg["train"]["bias_update_rate"])
    P0 = {k: x for k, x in init_params(cfg, seed).items() if k != bias_key}
    return dict(losses=losses, mu_norms=mu_norms, rows=held,
                bias_steps=np.asarray(steps, np.int64).tolist(),
                update_norms={k: float(x)
                              for k, x in _diff_norms(P, P0).items()})
