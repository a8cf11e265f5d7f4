"""Shared model layers: norms, projections, rotary embeddings, GQA and
multi-head latent attention blocks, SwiGLU MLP, KV caches.

Everything is a pure function over explicit parameter pytrees.  Parameters are
created annotated with logical sharding axes (repro.sharding.P) and stripped
by the model assembler; activations pass through ``sharding.constrain`` at
strategic points so GSPMD propagation has anchors.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .. import sharding
from ..kernels import ops
from ..sharding import annotate as A

_INIT_SCALE = 0.02


def _normal(key, shape, dtype, scale=_INIT_SCALE):
    return scale * jax.random.normal(key, shape, dtype)


def cdt(cfg):
    return jnp.dtype(cfg.compute_dtype)


def pdt(cfg):
    return jnp.dtype(cfg.param_dtype)


# -- norms --------------------------------------------------------------------

def init_rmsnorm(cfg, d=None, axis="act_embed"):
    d = d or cfg.d_model
    return {"scale": A(jnp.ones((d,), pdt(cfg)), axis)}


def rms_norm(x, p, eps):
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    return (y * p["scale"].astype(jnp.float32)).astype(x.dtype)


# -- rotary embeddings ---------------------------------------------------------

def rope_freqs(head_dim, theta):
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                            / head_dim))


def apply_rope(x, positions, theta):
    """x: (B, S, H, D); positions: (B, S) int32."""
    d = x.shape[-1]
    freqs = rope_freqs(d, theta)                       # (D/2,)
    ang = positions[..., None].astype(jnp.float32) * freqs   # (B,S,D/2)
    cos, sin = jnp.cos(ang)[:, :, None], jnp.sin(ang)[:, :, None]  # (B,S,1,D/2)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def apply_mrope(x, positions, theta, sections):
    """Qwen2-VL M-RoPE. x: (B,S,H,D); positions: (3,B,S) (t/h/w streams);
    ``sections`` split D/2 rotary frequencies across the three streams."""
    d = x.shape[-1]
    assert sum(sections) == d // 2, (sections, d)
    freqs = rope_freqs(d, theta)                       # (D/2,)
    ang_all = positions[..., None].astype(jnp.float32) * freqs  # (3,B,S,D/2)
    idx = []
    for i, sec in enumerate(sections):
        idx += [i] * sec
    onehot = jax.nn.one_hot(jnp.asarray(idx), 3, dtype=jnp.float32)  # (D/2,3)
    ang = jnp.einsum("nbsd,dn->bsd", ang_all, onehot)  # (B,S,D/2)
    cos, sin = jnp.cos(ang)[:, :, None], jnp.sin(ang)[:, :, None]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# -- embedding / unembedding ---------------------------------------------------

def init_embed(key, cfg):
    p = {"table": A(_normal(key, (cfg.vocab_size, cfg.d_model), pdt(cfg)),
                    "w_vocab", "w_embed")}
    return p


def embed(p, tokens, cfg):
    x = jnp.take(p["table"].astype(cdt(cfg)), tokens, axis=0)
    return sharding.constrain(x, "act_batch", "act_seq", "act_embed")


def init_lm_head(key, cfg):
    return {"out": A(_normal(key, (cfg.d_model, cfg.vocab_size), pdt(cfg)),
                     "w_embed", "w_vocab")}


def unembed(p_head, p_embed, x, cfg):
    if cfg.tie_embeddings:
        w = p_embed["table"].astype(cdt(cfg)).T
    else:
        w = p_head["out"].astype(cdt(cfg))
    logits = jnp.einsum("bsd,dv->bsv", x, w)
    return sharding.constrain(logits, "act_batch", "act_seq", "act_vocab")


# -- attention block -----------------------------------------------------------

def init_attention(key, cfg):
    if cfg.is_mla:
        return init_mla(key, cfg)
    ks = jax.random.split(key, 4)
    d, qd, kvd = cfg.d_model, cfg.q_dim, cfg.kv_dim
    return {
        "wq": A(_normal(ks[0], (d, qd), pdt(cfg)), "w_embed", "w_qdim"),
        "wk": A(_normal(ks[1], (d, kvd), pdt(cfg)), "w_embed", "w_kv_dim"),
        "wv": A(_normal(ks[2], (d, kvd), pdt(cfg)), "w_embed", "w_kv_dim"),
        "wo": A(_normal(ks[3], (qd, d), pdt(cfg)), "w_qdim", "w_embed"),
    }


def init_mla(key, cfg):
    """Multi-head latent attention (DeepSeek-V2/V3, no query compression):
    a KV down-projection to the ``kv_lora_rank`` latent plus one RoPE key
    shared by all heads, an RMSNorm on the latent, and its up-projection to
    per-head no-RoPE keys and values."""
    ks = jax.random.split(key, 4)
    d, H, r = cfg.d_model, cfg.n_heads, cfg.kv_lora_rank
    return {
        "wq": A(_normal(ks[0], (d, H * cfg.qk_head_dim), pdt(cfg)),
                "w_embed", "w_qdim"),
        "wkv_a": A(_normal(ks[1], (d, r + cfg.qk_rope_head_dim), pdt(cfg)),
                   "w_embed", None),
        "kv_norm": init_rmsnorm(cfg, r, axis=None),
        "wkv_b": A(_normal(ks[2], (r, H * (cfg.qk_nope_head_dim
                                           + cfg.v_head_dim)), pdt(cfg)),
                   None, "w_qdim"),
        "wo": A(_normal(ks[3], (H * cfg.v_head_dim, d), pdt(cfg)),
                "w_qdim", "w_embed"),
    }


def init_kv_cache(cfg, batch, max_len, dtype=None):
    """Per-head keys and values (MLA: the up-projected, non-absorbed form)."""
    dtype = dtype or jnp.dtype(cfg.compute_dtype)
    kv = cfg.n_kv_heads
    return {
        "k": jnp.zeros((batch, max_len, kv, cfg.qk_head_dim), dtype),
        "v": jnp.zeros((batch, max_len, kv, cfg.value_head_dim), dtype),
        "pos": jnp.zeros((), jnp.int32),
    }


def _rope_qk(cfg, q, k, positions):
    if cfg.pos_type == "rope":
        return (apply_rope(q, positions, cfg.rope_theta),
                apply_rope(k, positions, cfg.rope_theta))
    if cfg.pos_type == "mrope":
        return (apply_mrope(q, positions, cfg.rope_theta, cfg.mrope_sections),
                apply_mrope(k, positions, cfg.rope_theta, cfg.mrope_sections))
    return q, k


def _gqa_qkv(cfg, p, x, positions):
    B, S, d = x.shape
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    dt = cdt(cfg)
    q = jnp.einsum("bsd,dq->bsq", x, p["wq"].astype(dt)).reshape(B, S, H, hd)
    k = jnp.einsum("bsd,dq->bsq", x, p["wk"].astype(dt)).reshape(B, S, KV, hd)
    v = jnp.einsum("bsd,dq->bsq", x, p["wv"].astype(dt)).reshape(B, S, KV, hd)
    q, k = _rope_qk(cfg, q, k, positions)
    return q, k, v


def _mla_qkv(cfg, p, x, positions):
    """Training (non-absorbed) form: keys are [k_nope_h ; k_rope] with the
    RoPE key shared across heads; RoPE acts on the rope slices only."""
    B, S, d = x.shape
    H, r = cfg.n_heads, cfg.kv_lora_rank
    nope, rope = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    dt = cdt(cfg)
    q = jnp.einsum("bsd,dq->bsq", x, p["wq"].astype(dt)) \
        .reshape(B, S, H, nope + rope)
    kv_a = jnp.einsum("bsd,dr->bsr", x, p["wkv_a"].astype(dt))
    c = rms_norm(kv_a[..., :r], p["kv_norm"], cfg.norm_eps)
    k_rope = kv_a[..., r:][:, :, None, :]                   # (B, S, 1, rope)
    kv = jnp.einsum("bsr,rq->bsq", c, p["wkv_b"].astype(dt)) \
        .reshape(B, S, H, nope + cfg.v_head_dim)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    q_rope = apply_rope(q[..., nope:], positions, cfg.rope_theta)
    k_rope = apply_rope(k_rope, positions, cfg.rope_theta)
    q = jnp.concatenate([q[..., :nope], q_rope], axis=-1)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope, (B, S, H, rope))], axis=-1)
    return q, k, v


def attention_block(cfg, p, x, *, positions, cache=None, mode="train",
                    window=0):
    """x: (B, S, d).  Returns (out, new_cache).

    train/prefill: full (windowed-)causal attention; prefill writes the cache.
    decode: S == 1; append to cache (ring buffer when windowed).
    """
    B, S, d = x.shape
    dt = cdt(cfg)
    q, k, v = (_mla_qkv if cfg.is_mla else _gqa_qkv)(cfg, p, x, positions)
    if mode != "decode":
        # under sequence parallelism the residual stream is seq-sharded, but
        # attention mixes the whole sequence: gather q/k/v ONCE here so the
        # collective hoists out of the blocked-attention scan (without this
        # anchor GSPMD re-gathers every (q-block, kv-block) iteration -
        # measured 2.06 TB/chip/step on yi-34b train_4k; see EXPERIMENTS.md
        # §Perf iteration A2)
        q = sharding.constrain(q, "act_batch", None, "act_heads", None)
        k = sharding.constrain(k, "act_batch", None, None, None)
        v = sharding.constrain(v, "act_batch", None, None, None)

    new_cache = cache
    if mode == "decode":
        assert cache is not None and S == 1
        pos = cache["pos"]
        size = cache["k"].shape[1]
        # windowed layers use a ring buffer; keys are pre-RoPEd with absolute
        # positions so softmax is order-invariant (ring alignment assumes any
        # prefill length was a multiple of the window, true for all cells)
        slot = pos % size if window > 0 else jnp.minimum(pos, size - 1)
        # one-hot masked write instead of dynamic_update_slice: elementwise,
        # so GSPMD keeps the cache sharded along seq (a dynamic slice-update
        # at a traced index on a sharded dim triggers involuntary full
        # rematerialization - ~GBs of temp per layer at 32k context)
        hit = (jnp.arange(size) == slot)[None, :, None, None]
        ck = jnp.where(hit, k.astype(cache["k"].dtype), cache["k"])
        cv = jnp.where(hit, v.astype(cache["v"].dtype), cache["v"])
        # anchor: keep the cache seq-sharded through the attention and
        # gather the (tiny) query head dim instead - otherwise GSPMD picks a
        # kv-sharded layout for the einsum and reshards the multi-GB cache
        # every layer ("involuntary full rematerialization"; §Perf C2)
        ck = sharding.constrain(ck, "cache_batch", "cache_seq", "cache_kv",
                                "cache_dim")
        cv = sharding.constrain(cv, "cache_batch", "cache_seq", "cache_kv",
                                "cache_dim")
        q0 = sharding.constrain(q[:, 0], "act_batch", None, None)
        lengths = jnp.minimum(pos + 1, size) * jnp.ones((B,), jnp.int32)
        out = ops.decode_attention(q0, ck, cv, lengths, impl="xla")
        out = out[:, None]                                  # (B,1,H,hd)
        new_cache = {"k": ck, "v": cv, "pos": pos + 1}
    else:
        impl = cfg.attention_impl
        out = ops.attention(q, k, v, causal=True, window=window, impl=impl)
        if mode == "prefill":
            assert cache is not None
            size = cache["k"].shape[1]
            if window > 0 and size < S:
                # ring buffer: slot of absolute position p is p % size, so
                # the tail S-size..S-1 lands rolled by S % size - decode's
                # next write (slot S % size) then overwrites exactly the
                # oldest entry
                kk = jnp.roll(k[:, -size:], S % size, axis=1)
                vv = jnp.roll(v[:, -size:], S % size, axis=1)
            else:
                kk, vv = k, v
            ck = jax.lax.dynamic_update_slice(
                cache["k"], kk.astype(cache["k"].dtype), (0, 0, 0, 0))
            cv = jax.lax.dynamic_update_slice(
                cache["v"], vv.astype(cache["v"].dtype), (0, 0, 0, 0))
            new_cache = {"k": ck, "v": cv,
                         "pos": jnp.asarray(S, jnp.int32)}
    out = out.reshape(B, S, cfg.n_heads * cfg.value_head_dim)
    out = jnp.einsum("bsq,qd->bsd", out, p["wo"].astype(dt))
    return sharding.constrain(out, "act_batch", "act_seq", "act_embed"), new_cache


# -- SwiGLU MLP ----------------------------------------------------------------

def init_mlp(key, cfg, f=None):
    ks = jax.random.split(key, 3)
    d, f = cfg.d_model, f or cfg.d_ff
    p = {
        "gate": A(_normal(ks[0], (d, f), pdt(cfg)), "w_embed", "w_mlp"),
        "down": A(_normal(ks[2], (f, d), pdt(cfg)), "w_mlp", "w_embed"),
    }
    if cfg.mlp_variant == "swiglu":
        p["up"] = A(_normal(ks[1], (d, f), pdt(cfg)), "w_embed", "w_mlp")
    return p


def mlp_block(cfg, p, x):
    dt = cdt(cfg)
    g = jnp.einsum("bsd,df->bsf", x, p["gate"].astype(dt))
    if cfg.mlp_variant == "swiglu":
        u = jnp.einsum("bsd,df->bsf", x, p["up"].astype(dt))
        h = jax.nn.silu(g) * u
    else:
        h = jax.nn.gelu(g)
    h = sharding.constrain(h, "act_batch", "act_seq", "act_mlp")
    y = jnp.einsum("bsf,fd->bsd", h, p["down"].astype(dt))
    return sharding.constrain(y, "act_batch", "act_seq", "act_embed")


# -- standard transformer block (attn [+ local window] + SwiGLU) ---------------

def init_attn_layer(key, cfg):
    ks = jax.random.split(key, 2)
    p = {"ln1": init_rmsnorm(cfg), "attn": init_attention(ks[0], cfg)}
    if cfg.d_ff:
        p["ln2"] = init_rmsnorm(cfg)
        p["mlp"] = init_mlp(ks[1], cfg)
    return p


def attn_layer(cfg, p, x, *, positions, cache=None, mode="train", window=0):
    h, new_cache = attention_block(cfg, p["attn"], rms_norm(x, p["ln1"], cfg.norm_eps),
                                   positions=positions, cache=cache, mode=mode,
                                   window=window)
    x = x + h
    if cfg.d_ff:
        x = x + mlp_block(cfg, p["mlp"], rms_norm(x, p["ln2"], cfg.norm_eps))
    return x, new_cache
