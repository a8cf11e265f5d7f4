"""Dispatching wrappers around the attention/recurrence compute hot-spots.

Three implementations per op:
  * ``ref``       - the pure-jnp oracle (kernels/ref.py), O(S^2) memory.
  * ``xla_flash`` - blockwise online-softmax attention written as XLA scans
                    with a hand-written flash *backward* (custom_vjp, no
                    O(S^2) residuals).  This is what the multi-pod dry-run
                    lowers, and what CPU training uses.
  * ``pallas``    - the TPU Pallas kernels (kernels/flash_attention.py etc.),
                    VMEM-blocked for real hardware; validated on CPU via
                    interpret=True against ``ref``.

``impl="auto"`` picks ``ref`` for short sequences (cheaper at small S) and
``xla_flash`` beyond ``_AUTO_FLASH_S``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import ref

_AUTO_FLASH_S = 2048
NEG_INF = -1e30


# ---------------------------------------------------------------------------
# Blockwise flash attention in pure XLA (fwd + custom bwd)
# ---------------------------------------------------------------------------

def _pick_block(s: int, want: int) -> int:
    b = min(want, s)
    while s % b:
        b //= 2
    return max(b, 1)


def _reshape_back(x, B, Sq, H, D=None):
    # x: (nq, B, KV, G, bq, [D]) -> (B, Sq, H, [D])
    nq = x.shape[0]
    bq = x.shape[4]
    kv, g = x.shape[2], x.shape[3]
    if D is None:
        x = jnp.transpose(x, (1, 0, 4, 2, 3))             # B,nq,bq,KV,G
        return x.reshape(B, Sq, H)
    x = jnp.transpose(x, (1, 0, 4, 2, 3, 5))              # B,nq,bq,KV,G,D
    return x.reshape(B, Sq, H, D)


def _flash_fwd_shaped(q, k, v, causal, window, scale, block_q, block_k):
    B, Sq, H, _ = q.shape
    out, lse = _flash_fwd_raw(q, k, v, causal, window, scale, block_q, block_k)
    out = _reshape_back(out, B, Sq, H, v.shape[-1]).astype(q.dtype)
    lse = _reshape_back(lse, B, Sq, H)
    return out, lse


def _flash_fwd_raw(q, k, v, causal, window, scale, block_q, block_k):
    """As _flash_fwd but returns the blocked (nq,B,KV,G,bq,...) layout.
    q and k share a head width D; v's (Dv) may differ (latent attention)."""
    B, Sq, H, D = q.shape
    Sk, KV, Dv = k.shape[1], k.shape[2], v.shape[-1]
    G = H // KV
    bq = _pick_block(Sq, block_q)
    bk = _pick_block(Sk, block_k)
    nq, nk = Sq // bq, Sk // bk
    off = Sk - Sq
    q32 = (q.astype(jnp.float32) * scale).reshape(B, nq, bq, KV, G, D)
    k32 = k.astype(jnp.float32).reshape(B, nk, bk, KV, D)
    v32 = v.astype(jnp.float32).reshape(B, nk, bk, KV, Dv)
    q_pos = jnp.arange(Sq).reshape(nq, bq) + off
    k_pos = jnp.arange(Sk).reshape(nk, bk)

    def q_block(qi):
        qb = q32[:, qi]
        qp = q_pos[qi]

        def kv_step(carry, ki):
            acc, m, l = carry
            kb, vb = k32[:, ki], v32[:, ki]
            s = jnp.einsum("bqkgd,bskd->bkgqs", qb, kb)
            kp = k_pos[ki]
            mask = jnp.ones((bq, bk), bool)
            if causal:
                mask = mask & (kp[None, :] <= qp[:, None])
            if window > 0:
                mask = mask & (kp[None, :] > qp[:, None] - window)
            s = jnp.where(mask[None, None, None], s, NEG_INF)
            m_new = jnp.maximum(m, s.max(axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l = l * corr + p.sum(axis=-1)
            acc = acc * corr[..., None] + jnp.einsum("bkgqs,bskd->bkgqd", p, vb)
            return (acc, m_new, l), None

        acc0 = jnp.zeros((B, KV, G, bq, Dv), jnp.float32)
        m0 = jnp.full((B, KV, G, bq), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, KV, G, bq), jnp.float32)
        (acc, m, l), _ = jax.lax.scan(kv_step, (acc0, m0, l0), jnp.arange(nk))
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        lse = m + jnp.log(jnp.maximum(l, 1e-30))
        return out, lse

    return jax.lax.map(q_block, jnp.arange(nq))


def _flash_bwd(q, k, v, out, lse, dout, causal, window, scale, block_q, block_k):
    """FlashAttention-2 backward: recompute P per block from (q,k,lse); no
    O(S^2) residuals.  All accumulation in f32."""
    B, Sq, H, D = q.shape
    Sk, KV, Dv = k.shape[1], k.shape[2], v.shape[-1]
    G = H // KV
    bq = _pick_block(Sq, block_q)
    bk = _pick_block(Sk, block_k)
    nq, nk = Sq // bq, Sk // bk
    off = Sk - Sq
    q32 = q.astype(jnp.float32).reshape(B, nq, bq, KV, G, D)
    k32 = k.astype(jnp.float32).reshape(B, nk, bk, KV, D)
    v32 = v.astype(jnp.float32).reshape(B, nk, bk, KV, Dv)
    do32 = dout.astype(jnp.float32).reshape(B, nq, bq, KV, G, Dv)
    o32 = out.astype(jnp.float32).reshape(B, nq, bq, KV, G, Dv)
    lse_b = lse.reshape(B, nq, bq, KV, G)
    # delta_i = rowsum(dO_i * O_i), per (nq, bq) block layout
    delta = jnp.einsum("bnqkgd,bnqkgd->bnqkg", do32, o32)    # (B,nq,bq,KV,G)
    q_pos = jnp.arange(Sq).reshape(nq, bq) + off
    k_pos = jnp.arange(Sk).reshape(nk, bk)

    def k_block(dq_acc, ki):
        kb, vb = k32[:, ki], v32[:, ki]
        kp = k_pos[ki]

        def q_step(carry, qi):
            dk_acc, dv_acc, dq_acc = carry
            qb = q32[:, qi]
            qp = q_pos[qi]
            s = jnp.einsum("bqkgd,bskd->bkgqs", qb * scale, kb)
            mask = jnp.ones((bq, bk), bool)
            if causal:
                mask = mask & (kp[None, :] <= qp[:, None])
            if window > 0:
                mask = mask & (kp[None, :] > qp[:, None] - window)
            s = jnp.where(mask[None, None, None], s, NEG_INF)
            p = jnp.exp(s - jnp.transpose(lse_b[:, qi], (0, 2, 3, 1))[..., None])
            dob = do32[:, qi]
            dp = jnp.einsum("bqkgd,bskd->bkgqs", dob, vb)
            dl = jnp.transpose(delta[:, qi], (0, 2, 3, 1))    # (B,KV,G,bq)
            ds = p * (dp - dl[..., None]) * scale
            dq_acc = dq_acc.at[qi].add(
                jnp.einsum("bkgqs,bskd->bqkgd", ds, kb))
            dk_acc = dk_acc + jnp.einsum("bkgqs,bqkgd->bskd", ds, qb)
            dv_acc = dv_acc + jnp.einsum("bkgqs,bqkgd->bskd", p, dob)
            return (dk_acc, dv_acc, dq_acc), None

        dk0 = jnp.zeros((B, bk, KV, D), jnp.float32)
        dv0 = jnp.zeros((B, bk, KV, Dv), jnp.float32)
        (dk_b, dv_b, dq_acc), _ = jax.lax.scan(q_step, (dk0, dv0, dq_acc),
                                               jnp.arange(nq))
        return dq_acc, (dk_b, dv_b)

    # dq accumulates over k blocks in one (nq,B,bq,KV,G,D) carry, not one
    # copy per k block (nk x the size of q: 1.6 GB at S = 8192, 16 x 192)
    dq0 = jnp.zeros((nq, B, bq, KV, G, D), jnp.float32)
    dq, (dk, dv) = jax.lax.scan(k_block, dq0, jnp.arange(nk))
    dq = jnp.transpose(dq, (1, 0, 2, 3, 4, 5)).reshape(B, Sq, KV, G, D) \
        .reshape(B, Sq, H, D)
    dk = jnp.moveaxis(dk, 0, 1).reshape(B, Sk, KV, D)
    dv = jnp.moveaxis(dv, 0, 1).reshape(B, Sk, KV, Dv)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def flash_attention_xla(q, k, v, causal=True, window=0, scale=None,
                        block_q=512, block_k=512):
    """Blockwise attention, XLA-native, flash forward + flash backward."""
    scale = q.shape[-1] ** -0.5 if scale is None else scale
    out, _ = _flash_fwd_shaped(q, k, v, causal, window, scale, block_q, block_k)
    return out


def _fa_fwd(q, k, v, causal, window, scale, block_q, block_k):
    scale_v = q.shape[-1] ** -0.5 if scale is None else scale
    out, lse = _flash_fwd_shaped(q, k, v, causal, window, scale_v,
                                 block_q, block_k)
    return out, (q, k, v, out, lse)


def _fa_bwd(causal, window, scale, block_q, block_k, res, dout):
    q, k, v, out, lse = res
    scale_v = q.shape[-1] ** -0.5 if scale is None else scale
    return _flash_bwd(q, k, v, out, lse, dout, causal, window, scale_v,
                      block_q, block_k)


flash_attention_xla.defvjp(_fa_fwd, _fa_bwd)


# ---------------------------------------------------------------------------
# Public dispatchers
# ---------------------------------------------------------------------------

def attention(q, k, v, *, causal: bool = True, window: int = 0,
              scale=None, impl: str = "auto", interpret: bool = True):
    """Training/prefill attention. q: (B,Sq,H,D); k: (B,Sk,KV,D); v:
    (B,Sk,KV,Dv), Dv may differ from D (``ref`` and ``xla_flash``); the
    default scale is D**-0.5."""
    if impl == "auto":
        impl = "ref" if k.shape[1] <= _AUTO_FLASH_S else "xla_flash"
    if impl == "ref":
        return ref.attention(q, k, v, causal=causal, window=window, scale=scale)
    if impl == "xla_flash":
        return flash_attention_xla(q, k, v, causal, window, scale)
    if impl == "pallas":
        from . import flash_attention as fa
        return fa.flash_attention(q, k, v, causal=causal, window=window,
                                  scale=scale, interpret=interpret)
    raise ValueError(f"unknown attention impl {impl!r}")


def _decode_xla(q, k_cache, v_cache, lengths, scale):
    """Serving-grade XLA decode: grouped GQA einsums with
    ``preferred_element_type`` so the multi-GB cache is consumed in its
    stored dtype (the oracle's f32 casts would materialize 2x-cache f32
    temporaries per layer); f32 only for softmax statistics."""
    from .. import sharding as _shd
    b, h, d = q.shape
    s, kv, dv = k_cache.shape[1], k_cache.shape[2], v_cache.shape[-1]
    g = h // kv
    scale = d ** -0.5 if scale is None else scale
    qg = (q.astype(jnp.float32) * scale).astype(k_cache.dtype) \
        .reshape(b, kv, g, d)
    logits = jnp.einsum("bkgd,bskd->bkgs", qg, k_cache,
                        preferred_element_type=jnp.float32)
    logits = _shd.constrain(logits, "cache_batch", None, None, "cache_seq")
    valid = jnp.arange(s)[None, None, None, :] < lengths[:, None, None, None]
    logits = jnp.where(valid, logits, NEG_INF)
    p = jax.nn.softmax(logits, axis=-1).astype(v_cache.dtype)
    out = jnp.einsum("bkgs,bskd->bkgd", p, v_cache,
                     preferred_element_type=jnp.float32)
    return out.reshape(b, h, dv).astype(q.dtype)


def decode_attention(q, k_cache, v_cache, lengths, *, scale=None,
                     impl: str = "xla", interpret: bool = True):
    """Single new token vs a KV cache. q: (B,H,D); caches: (B,S,KV,D)."""
    if impl == "ref":
        return ref.decode_attention(q, k_cache, v_cache, lengths, scale=scale)
    if impl in ("xla", "auto", "xla_flash"):
        return _decode_xla(q, k_cache, v_cache, lengths, scale)
    if impl == "pallas":
        from . import decode_attention as da
        return da.decode_attention(q, k_cache, v_cache, lengths, scale=scale,
                                   interpret=interpret)
    raise ValueError(f"unknown decode impl {impl!r}")


def linear_recurrence(a, b, h0=None, *, impl: str = "assoc", interpret: bool = True):
    """h_t = a_t * h_{t-1} + b_t over axis 1.  a, b: (B, S, D)."""
    if impl == "ref":
        return ref.linear_recurrence(a, b, h0)
    if impl == "assoc":
        B, S, D = a.shape
        h0v = jnp.zeros((B, D), a.dtype) if h0 is None else h0
        # fold h0 into the first step: h_1 = a_1*h0 + b_1
        b0 = b.at[:, 0].add(a[:, 0] * h0v)
        af = a.astype(jnp.float32)
        bf = b0.astype(jnp.float32)

        def op(x, y):
            a1, b1 = x
            a2, b2 = y
            return a1 * a2, a2 * b1 + b2

        aa, bb = jax.lax.associative_scan(op, (af, bf), axis=1)
        return bb.astype(a.dtype), bb[:, -1].astype(a.dtype)
    if impl == "pallas":
        from . import rglru_scan as rs
        return rs.linear_recurrence(a, b, h0, interpret=interpret)
    raise ValueError(f"unknown recurrence impl {impl!r}")
