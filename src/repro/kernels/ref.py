"""Pure-jnp oracles for every Pallas kernel (the correctness references).

Each function is the mathematical definition with no blocking/tiling; tests
sweep shapes and dtypes asserting the kernels (interpret=True on CPU) match
these to tolerance.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def _gqa_expand(k, q_heads):
    """(B, S, KV, D) -> (B, S, H, D) by repeating each kv head H/KV times."""
    b, s, kv, d = k.shape
    rep = q_heads // kv
    return jnp.repeat(k, rep, axis=2)


def attention(q, k, v, *, causal: bool = True, window: int = 0,
              scale: float | None = None):
    """Multi-head (GQA) attention oracle.

    q: (B, Sq, H, D); k: (B, Sk, KV, D); v: (B, Sk, KV, Dv); returns
    (B, Sq, H, Dv).
    ``window`` > 0 restricts each query to the last ``window`` keys
    (local/sliding attention); causal offsets assume q occupies the final
    Sq positions of the Sk-long context.
    """
    b, sq, h, d = q.shape
    sk = k.shape[1]
    scale = scale if scale is not None else d ** -0.5
    k = _gqa_expand(k, h)
    v = _gqa_expand(v, h)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    q_pos = jnp.arange(sq)[:, None] + (sk - sq)
    k_pos = jnp.arange(sk)[None, :]
    mask = jnp.ones((sq, sk), bool)
    if causal:
        mask &= k_pos <= q_pos
    if window > 0:
        mask &= k_pos > q_pos - window
    logits = jnp.where(mask[None, None], logits, NEG_INF)
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", p, v)
    return out.astype(q.dtype)


def decode_attention(q, k_cache, v_cache, lengths, *, scale: float | None = None):
    """Single-token decode oracle.

    q: (B, H, D); k_cache, v_cache: (B, S, KV, D); lengths: (B,) valid cache
    lengths.  Returns (B, H, D).

    GQA via grouped einsums (no KV repeat): materializing the expanded
    (B,S,H,D) cache both wastes memory and - under GSPMD - invites a
    head-sharded cache layout that reshards the multi-GB cache per layer.
    """
    b, h, d = q.shape
    s, kv = k_cache.shape[1], k_cache.shape[2]
    g = h // kv
    scale = scale if scale is not None else d ** -0.5
    from .. import sharding as _shd   # anchor only; no-op without a mesh
    qg = (q.astype(jnp.float32) * scale).reshape(b, kv, g, d)
    k32 = k_cache.astype(jnp.float32)
    v32 = v_cache.astype(jnp.float32)
    logits = jnp.einsum("bkgd,bskd->bkgs", qg, k32)        # (B,KV,G,S)
    # keep the context dim sharded like the cache: otherwise GSPMD gathers
    # the f32 cache per layer rather than emitting partial logits + a small
    # softmax all-reduce (~250 GB/chip/token on yi-34b decode_32k, §Perf C3)
    logits = _shd.constrain(logits, "cache_batch", None, None, "cache_seq")
    valid = jnp.arange(s)[None, None, None, :] < lengths[:, None, None, None]
    logits = jnp.where(valid, logits, NEG_INF)
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bkgs,bskd->bkgd", p, v32)
    return out.reshape(b, h, v_cache.shape[-1]).astype(q.dtype)


def linear_recurrence(a, b0, h0=None):
    """Gated linear recurrence oracle: h_t = a_t * h_{t-1} + b_t.

    a, b0: (B, S, D); h0: (B, D) initial state (zeros if None).
    Returns (h: (B, S, D), h_last: (B, D)).  This is the RG-LRU core once the
    gate algebra has produced (a_t, b_t).
    """
    if h0 is None:
        h0 = jnp.zeros(a.shape[:1] + a.shape[2:], a.dtype)

    def step(h, ab):
        at, bt = ab
        h = at * h + bt
        return h, h

    (a_s, b_s) = (jnp.swapaxes(a, 0, 1), jnp.swapaxes(b0, 0, 1))
    h_last, hs = jax.lax.scan(step, h0.astype(jnp.float32),
                              (a_s.astype(jnp.float32), b_s.astype(jnp.float32)))
    return jnp.swapaxes(hs, 0, 1).astype(a.dtype), h_last.astype(a.dtype)


def mlstm_chunkwise(q, k, v, log_f, log_i, *, chunk: int = 64, c0=None,
                    n0=None, m0=None, eps: float = 1e-6):
    """Chunkwise-parallel mLSTM oracle (xLSTM matrix memory, stabilized).

    q, k, v : (B, S, H, D)
    log_f   : (B, S, H) log-sigmoid forget pre-activations (log f_t)
    log_i   : (B, S, H) input-gate pre-activations (log-space i_t)
    Returns (out: (B,S,H,D), (C, n, m) final state) where C: (B,H,D,D),
    n: (B,H,D), m: (B,H).

    This is the sequential (step-by-step) definition run via scan - the
    oracle for both the chunkwise JAX implementation and any future kernel:
        m_t = max(log_f_t + m_{t-1}, log_i_t)
        C_t = exp(log_f_t + m_{t-1} - m_t) C_{t-1} + exp(log_i_t - m_t) k_t v_t^T
        n_t = exp(log_f_t + m_{t-1} - m_t) n_{t-1} + exp(log_i_t - m_t) k_t
        h_t = C_t^T q_t / max(|n_t . q_t|, exp(-m_t), eps)
    """
    b, s, h, d = q.shape
    scale = d ** -0.5
    q32, k32, v32 = (x.astype(jnp.float32) for x in (q, k, v))
    lf = log_f.astype(jnp.float32)
    li = log_i.astype(jnp.float32)
    C = jnp.zeros((b, h, d, d), jnp.float32) if c0 is None else c0.astype(jnp.float32)
    n = jnp.zeros((b, h, d), jnp.float32) if n0 is None else n0.astype(jnp.float32)
    m = jnp.full((b, h), -jnp.inf, jnp.float32) if m0 is None else m0.astype(jnp.float32)

    def step(carry, xs):
        C, n, m = carry
        qt, kt, vt, lft, lit = xs          # (B,H,D), (B,H,D), (B,H,D), (B,H), (B,H)
        m_new = jnp.maximum(lft + m, lit)
        fg = jnp.exp(lft + m - m_new)[..., None]              # (B,H,1)
        ig = jnp.exp(lit - m_new)[..., None]                  # (B,H,1)
        C = fg[..., None] * C + ig[..., None] * (kt[..., :, None] * vt[..., None, :])
        n = fg * n + ig * kt
        qs = qt * scale
        num = jnp.einsum("bhij,bhi->bhj", C, qs)
        den = jnp.maximum(jnp.abs(jnp.einsum("bhi,bhi->bh", n, qs)),
                          jnp.exp(-m_new))[..., None] + eps
        return (C, n, m_new), num / den

    xs = (jnp.moveaxis(q32, 1, 0), jnp.moveaxis(k32, 1, 0),
          jnp.moveaxis(v32, 1, 0), jnp.moveaxis(lf, 1, 0), jnp.moveaxis(li, 1, 0))
    (C, n, m), out = jax.lax.scan(step, (C, n, m), xs)
    return jnp.moveaxis(out, 0, 1).astype(q.dtype), (C, n, m)
