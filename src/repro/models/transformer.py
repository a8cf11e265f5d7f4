"""Model assembly: leading dense layers (+) block pattern -> scanned groups
(+ tail), train / prefill / decode entry points, loss.

Layers are grouped by the architecture's block-pattern period (dense/MoE: 1;
RecurrentGemma: (rglru, rglru, local_attn); xLSTM: 7x mlstm + 1x slstm) and
per-period-position parameters are stacked over groups so the forward pass is
a single ``lax.scan`` - HLO size and compile time are O(pattern), not
O(n_layers), which is what makes 60-layer 34B dry-runs tractable.
``cfg.first_dense_layers`` "attn" layers (DeepSeek-V3's
``first_k_dense_replace``) run unscanned before the groups, mirroring the
tail; a configuration without them has no ``lead`` entry.

Buffers (``BUFFERS``: the MoE routing bias) are parameters-tree state that
the optimizer does not touch: ``split_buffers`` separates them for the
train step, which updates them from the forward's routing statistics.
"""
from __future__ import annotations

import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp

from .. import sharding
from ..sharding import split_annotated
from . import layers as L
from . import moe as M
from . import rglru as R
from . import xlstm as X


def _kv_cache_len(cfg, kind, max_len):
    if kind == "local_attn" and cfg.window:
        return min(max_len, cfg.window)
    return max_len


BLOCKS = {
    "attn": dict(init=L.init_attn_layer, apply=L.attn_layer,
                 cache=lambda cfg, b, s: L.init_kv_cache(cfg, b, s),
                 window=lambda cfg: 0),
    "local_attn": dict(init=L.init_attn_layer, apply=L.attn_layer,
                       cache=lambda cfg, b, s: L.init_kv_cache(
                           cfg, b, _kv_cache_len(cfg, "local_attn", s)),
                       window=lambda cfg: cfg.window),
    "moe": dict(init=M.init_moe_layer, apply=M.moe_layer,
                cache=lambda cfg, b, s: L.init_kv_cache(cfg, b, s),
                window=lambda cfg: 0),
    "rglru": dict(init=R.init_rglru_layer, apply=R.rglru_layer,
                  cache=lambda cfg, b, s: R.init_rglru_cache(cfg, b),
                  window=lambda cfg: 0),
    "mlstm": dict(init=X.init_mlstm_layer, apply=X.mlstm_layer,
                  cache=lambda cfg, b, s: X.init_mlstm_cache(cfg, b),
                  window=lambda cfg: 0),
    "slstm": dict(init=X.init_slstm_layer, apply=X.slstm_layer,
                  cache=lambda cfg, b, s: X.init_slstm_cache(cfg, b),
                  window=lambda cfg: 0),
}


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def init(cfg, key):
    """Returns (params, logical_axes) plain pytrees."""
    period = cfg.block_pattern
    n_groups, n_tail = cfg.n_groups, cfg.n_tail
    keys = jax.random.split(key, 4 + len(period) + n_tail)
    k_embed, k_head = keys[0], keys[1]

    annotated = {
        "embed": L.init_embed(k_embed, cfg),
        "final_norm": L.init_rmsnorm(cfg),
    }
    if not cfg.tie_embeddings:
        annotated["lm_head"] = L.init_lm_head(k_head, cfg)
    params, axes = split_annotated(annotated)

    groups_p, groups_ax = [], []
    for pidx, kind in enumerate(period):
        init_fn = BLOCKS[kind]["init"]
        _, ax1 = split_annotated(init_fn(keys[4 + pidx], cfg))
        gkeys = jax.random.split(keys[4 + pidx], n_groups)
        stacked = jax.vmap(lambda k: split_annotated(init_fn(k, cfg))[0])(gkeys)
        groups_p.append(stacked)
        groups_ax.append(jax.tree_util.tree_map(
            lambda a: ("layers",) + a, ax1,
            is_leaf=lambda x: isinstance(x, tuple) and all(
                isinstance(e, (str, type(None))) for e in x)))
    params["groups"] = groups_p
    axes["groups"] = groups_ax

    tail_p, tail_ax = [], []
    for t in range(n_tail):
        kind = period[t]
        p1, ax1 = split_annotated(
            BLOCKS[kind]["init"](keys[4 + len(period) + t], cfg))
        tail_p.append(p1)
        tail_ax.append(ax1)
    params["tail"] = tail_p
    axes["tail"] = tail_ax

    if cfg.first_dense_layers:
        lead = [split_annotated(L.init_attn_layer(k, cfg))
                for k in jax.random.split(keys[2], cfg.first_dense_layers)]
        params["lead"] = [p for p, _ in lead]
        axes["lead"] = [ax for _, ax in lead]
    return params, axes


BUFFERS = (M.BIAS,)


def split_buffers(params):
    """(trainable, buffers): ``buffers`` maps the path (a tuple of keys and
    indices) of each leaf named in ``BUFFERS`` to it; ``trainable`` is the
    tree without them.  Works on parameter, axes and gradient trees."""
    buffers = {}

    def walk(node, path):
        if isinstance(node, dict):
            out = {}
            for k, v in node.items():
                if k in BUFFERS:
                    buffers[path + (k,)] = v
                else:
                    out[k] = walk(v, path + (k,))
            return out
        if isinstance(node, list):
            return [walk(v, path + (i,)) for i, v in enumerate(node)]
        return node

    return walk(params, ()), buffers


def trainable(params):
    return split_buffers(params)[0]


def merge_buffers(trainable_tree, buffers):
    """The full tree: ``trainable_tree`` with ``buffers`` put back."""
    if not buffers:
        return trainable_tree

    def put(node, path, value):
        head, rest = path[0], path[1:]
        node = list(node) if isinstance(node, list) else dict(node)
        node[head] = value if not rest else put(node[head], rest, value)
        return node

    out = trainable_tree
    for path, value in buffers.items():
        out = put(out, path, value)
    return out


def update_buffers(buffers, loads, rate):
    """Each routing bias after one step: ``loads`` is ``lm_loss``'s
    ``moe_load`` (the same ``groups``/``tail`` positions as the biases)."""
    return {path: M.update_bias(b, loads[path[0]][path[1]], rate)
            for path, b in buffers.items()}


def init_cache(cfg, batch, max_len):
    """Decode/prefill cache pytree, mirroring the group/tail structure."""
    period = cfg.block_pattern
    groups = []
    for kind in period:
        single = BLOCKS[kind]["cache"](cfg, batch, max_len)
        groups.append(jax.tree_util.tree_map(
            lambda a: jnp.zeros((cfg.n_groups,) + a.shape, a.dtype), single))
    tail = [BLOCKS[period[t]]["cache"](cfg, batch, max_len)
            for t in range(cfg.n_tail)]
    cache = {"groups": groups, "tail": tail, "t": jnp.zeros((), jnp.int32)}
    if cfg.first_dense_layers:
        cache["lead"] = [BLOCKS["attn"]["cache"](cfg, batch, max_len)
                         for _ in range(cfg.first_dense_layers)]
    return cache


def cache_axes(cfg):
    """Logical axes for the cache pytree (for dry-run shardings).

    Built from the *unstacked* per-layer cache structure (eval_shape, no
    allocation); group entries get a leading "layers" axis for the scan
    stacking.
    """
    def one_ax(name, ndim):
        if name in ("k", "v"):
            return ("cache_batch", "cache_seq", "cache_kv", "cache_dim")
        if name == "conv":
            return ("cache_batch", None, "act_lru")
        if name == "pos":
            return ()
        # recurrent states: batch-sharded, rest replicated
        return ("cache_batch",) + (None,) * (ndim - 1)

    period = cfg.block_pattern
    groups, tail = [], []
    for pidx, kind in enumerate(period):
        single = jax.eval_shape(
            lambda: BLOCKS[kind]["cache"](cfg, 2, 8))
        ax = {k: one_ax(k, v.ndim) for k, v in single.items()}
        groups.append({k: ("layers",) + tuple(v) for k, v in ax.items()})
        if pidx < cfg.n_tail:
            tail.append(ax)
    axes = {"groups": groups, "tail": tail, "t": ()}
    if cfg.first_dense_layers:
        single = jax.eval_shape(lambda: BLOCKS["attn"]["cache"](cfg, 2, 8))
        axes["lead"] = [{k: one_ax(k, v.ndim) for k, v in single.items()}
                        for _ in range(cfg.first_dense_layers)]
    return axes


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _apply_block(cfg, kind, p, x, *, positions, cache, mode):
    """Returns (x, new_cache, stats); stats is {} for blocks without."""
    window = BLOCKS[kind]["window"](cfg)
    out = BLOCKS[kind]["apply"](cfg, p, x, positions=positions, cache=cache,
                                mode=mode, window=window)
    return out if len(out) == 3 else (*out, {})


def forward(cfg, params, tokens=None, *, embeds=None, positions=None,
            cache=None, mode: str = "train"):
    """Returns (logits, new_cache)."""
    logits, new_cache, _ = forward_with_stats(
        cfg, params, tokens, embeds=embeds, positions=positions, cache=cache,
        mode=mode)
    return logits, new_cache


def forward_with_stats(cfg, params, tokens=None, *, embeds=None,
                       positions=None, cache=None, mode: str = "train"):
    """Returns (logits, new_cache, stats): stats holds each block's
    statistics ({} for blocks without) as ``{"groups": [per period
    position, stacked over groups], "tail": [...]}``."""
    period = cfg.block_pattern
    if tokens is not None:
        x = L.embed(params["embed"], tokens, cfg)
        B, S = tokens.shape
    else:
        x = embeds.astype(L.cdt(cfg))
        B, S = embeds.shape[:2]
        x = sharding.constrain(x, "act_batch", "act_seq", "act_embed")

    if positions is None:
        t0 = cache["t"] if cache is not None else jnp.zeros((), jnp.int32)
        base = t0 + jnp.arange(S, dtype=jnp.int32)[None, :]
        pos_arr = jnp.broadcast_to(base, (B, S))
        if cfg.pos_type == "mrope":
            pos_arr = jnp.broadcast_to(pos_arr[None], (3, B, S))
        positions = pos_arr

    new_lead = None if cache is None else []
    for i in range(cfg.first_dense_layers):
        c = None if cache is None else cache["lead"][i]
        x, nc, _ = _apply_block(cfg, "attn", params["lead"][i], x,
                                positions=positions, cache=c, mode=mode)
        if cache is not None:
            new_lead.append(nc)

    def group_body(x, xs):
        gparams, gcache = xs
        new_caches, stats = [], []
        for pidx, kind in enumerate(period):
            c = None if gcache is None else gcache[pidx]
            x, nc, st = _apply_block(cfg, kind, gparams[pidx], x,
                                     positions=positions, cache=c, mode=mode)
            new_caches.append(nc)
            stats.append(st)
        return x, (None if gcache is None else new_caches, stats)

    body = group_body
    if cfg.remat and mode == "train":
        body = jax.checkpoint(group_body,
                              policy=jax.checkpoint_policies.nothing_saveable)

    new_cache = None
    gcaches = None if cache is None else cache["groups"]
    if cfg.scan_layers and cfg.n_groups > 1:
        x, (new_gcaches, gstats) = jax.lax.scan(
            body, x, (params["groups"], gcaches))
    else:
        new_gcaches = [] if gcaches is not None else None
        per_group = []
        for g in range(cfg.n_groups):
            gp = jax.tree_util.tree_map(lambda a: a[g], params["groups"])
            gc = None if gcaches is None else jax.tree_util.tree_map(
                lambda a: a[g], gcaches)
            x, (nc, st) = body(x, (gp, gc))
            per_group.append(st)
            if gcaches is not None:
                new_gcaches.append(nc)
        if gcaches is not None and new_gcaches:
            new_gcaches = jax.tree_util.tree_map(
                lambda *xs: jnp.stack(xs), *new_gcaches)
        gstats = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs),
                                        *per_group) if per_group else \
            [{} for _ in period]

    new_tail = None if cache is None else []
    tstats = []
    for t in range(cfg.n_tail):
        kind = period[t]
        c = None if cache is None else cache["tail"][t]
        x, nc, st = _apply_block(cfg, kind, params["tail"][t], x,
                                 positions=positions, cache=c, mode=mode)
        tstats.append(st)
        if cache is not None:
            new_tail.append(nc)

    x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = L.unembed(params.get("lm_head"), params["embed"], x, cfg)
    if cache is not None:
        new_cache = {"groups": new_gcaches, "tail": new_tail,
                     "t": cache["t"] + S}
        if cfg.first_dense_layers:
            new_cache["lead"] = new_lead
    return logits, new_cache, {"groups": gstats, "tail": tstats}


# ---------------------------------------------------------------------------
# loss / steps
# ---------------------------------------------------------------------------

def lm_loss(cfg, params, batch):
    """Next-token cross-entropy (mean over valid positions).  ``batch`` has
    tokens (B,S) [or embeds], labels (B,S), and optional mask (B,S)."""
    logits, _, stats = forward_with_stats(
        cfg, params, batch.get("tokens"), embeds=batch.get("embeds"),
        positions=batch.get("positions"), mode="train")
    labels = batch["labels"]
    mask = batch.get("mask")
    logits = logits.astype(jnp.float32)
    logz = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = logz - gold
    if mask is None:
        mask = jnp.ones_like(nll)
    loss = jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    # z-loss keeps logits bounded on long runs (Chowdhery et al.)
    zloss = 1e-4 * jnp.sum((logz * mask) ** 2) / jnp.maximum(jnp.sum(mask), 1.0)
    return loss + zloss, {"nll": loss, "zloss": zloss, **moe_aux(stats)}


def moe_aux(stats) -> dict:
    """The MoE blocks' statistics for the step: ``moe_balance`` (the
    sequence-wise balance terms summed over layers, unweighted; sigmoid
    routers only),
    ``moe_rows`` (held (token, expert) pairs computed, summed over layers),
    ``moe_routed_held`` (pairs the router sent to held experts, summed over
    layers: ``moe_rows`` short of it counts dropped pairs),
    ``moe_load_max`` (largest over layers and held experts of rows over
    T K / E) and ``moe_load`` (per layer, pairs routed to each expert, at
    the routing biases' positions).  {} without MoE blocks."""
    found = [s for s in stats["groups"] + stats["tail"] if s]
    if not found:
        return {}
    bal = [jnp.sum(s["balance"]) for s in found if "balance" in s]
    return {
        **({"moe_balance": sum(bal)} if bal else {}),
        "moe_rows": sum(jnp.sum(s["rows"]) for s in found),
        "moe_routed_held": sum(jnp.sum(s["routed_held"]) for s in found),
        "moe_load_max": jnp.max(jnp.stack([jnp.max(s["load_max"])
                                           for s in found])),
        "moe_load": {sec: [s.get("load") for s in stats[sec]]
                     for sec in ("groups", "tail")},
    }


def prefill_step(cfg, params, tokens=None, *, embeds=None, positions=None,
                 cache=None):
    """Full-context forward building the KV/state cache."""
    logits, cache = forward(cfg, params, tokens, embeds=embeds,
                            positions=positions, cache=cache, mode="prefill")
    return logits[:, -1:], cache


def decode_step(cfg, params, tokens=None, *, embeds=None, positions=None,
                cache=None):
    """One new token against an existing cache."""
    logits, cache = forward(cfg, params, tokens, embeds=embeds,
                            positions=positions, cache=cache, mode="decode")
    return logits, cache
