"""Step builders shared by the trainer, the server, and the dry-run:
train_step / prefill_step / decode_step plus abstract (no-allocation)
parameter, optimizer-state, cache and batch specs with their shardings.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from .. import sharding
from ..configs.base import ModelConfig, ShapeConfig, TrainConfig
from ..data.pipeline import make_batch_specs
from ..models import transformer as T
from ..optim import adamw_init, adamw_update, cosine_schedule


# ---------------------------------------------------------------------------
# abstract trees (ShapeDtypeStruct; zero allocation - the dry-run pattern)
# ---------------------------------------------------------------------------

def abstract_init(cfg: ModelConfig):
    """(param ShapeDtypeStructs, logical axes) without allocating."""
    box = {}

    def f(k):
        p, ax = T.init(cfg, k)
        box["axes"] = ax            # static tuples captured at trace time
        return p

    shapes = jax.eval_shape(f, jax.random.PRNGKey(0))
    return shapes, box["axes"]


def init_opt_state(params):
    """AdamW's state for the trainable parameters (buffers stay out)."""
    return adamw_init(T.trainable(params))


def abstract_opt_state(param_shapes):
    return jax.eval_shape(init_opt_state, param_shapes)


def opt_axes(param_axes_tree):
    """Optimizer-state axes: parameter axes under ``opt::`` aliases so rule
    sets can shard m/v independently of the weights (ZeRO-1)."""
    from ..optim.adamw import AdamWState
    is_ax = lambda x: isinstance(x, tuple) and all(
        isinstance(e, (str, type(None))) for e in x)
    aliased = jax.tree_util.tree_map(sharding.opt_alias,
                                     T.trainable(param_axes_tree),
                                     is_leaf=is_ax)
    return AdamWState(step=(), mu=aliased, nu=aliased)


def abstract_cache(cfg: ModelConfig, batch: int, max_len: int):
    # batch/max_len must stay static python ints during shape evaluation
    return jax.eval_shape(lambda: T.init_cache(cfg, batch, max_len))


def batch_axes(cfg: ModelConfig, specs: dict) -> dict:
    ax = {}
    for name in specs:
        if name == "embeds":
            ax[name] = ("act_batch", "act_seq", "act_embed")
        elif name == "positions" and cfg.pos_type == "mrope":
            ax[name] = (None, "act_batch", "act_seq")
        else:
            ax[name] = ("act_batch", "act_seq")
    return ax


def input_specs(cfg: ModelConfig, shape: ShapeConfig):
    """ShapeDtypeStruct stand-ins for every model input of a cell.

    train  : {tokens/embeds, labels, mask [, positions]}
    prefill: {tokens/embeds [, positions]} + empty cache
    decode : single-token inputs + a seq_len-deep cache
    """
    B, S = shape.global_batch, shape.seq_len
    if shape.kind == "train":
        return {"batch": make_batch_specs(cfg, shape, for_loss=True)}
    if shape.kind == "prefill":
        return {"batch": make_batch_specs(cfg, shape, for_loss=False),
                "cache": abstract_cache(cfg, B, S)}
    if shape.kind == "decode":
        specs = {}
        if cfg.embeds_input:
            specs["embeds"] = jax.ShapeDtypeStruct(
                (B, 1, cfg.d_model), jnp.dtype(cfg.compute_dtype))
        else:
            specs["tokens"] = jax.ShapeDtypeStruct((B, 1), jnp.int32)
        if cfg.pos_type == "mrope":
            specs["positions"] = jax.ShapeDtypeStruct((3, B, 1), jnp.int32)
        return {"batch": specs, "cache": abstract_cache(cfg, B, S)}
    raise ValueError(shape.kind)


# ---------------------------------------------------------------------------
# step functions
# ---------------------------------------------------------------------------

# how a microbatched step combines its microbatches' metrics: the MoE counts
# add up (the whole batch's rows and loads), the largest load is the
# largest; the loss terms (and anything else) are means
_MICRO_REDUCE = {"moe_rows": jnp.sum, "moe_routed_held": jnp.sum,
                 "moe_load": jnp.sum, "moe_load_max": jnp.max}


def make_train_step(cfg: ModelConfig, tc: TrainConfig, param_axes=None):
    """One optimizer step.  AdamW updates the trainable parameters; the MoE
    routing biases (``T.BUFFERS``) move by their own rule from the step's
    routing loads, and the sequence-wise balance term enters the loss with
    weight ``tc.moe_seq_aux_alpha``.  ``metrics`` holds the loss and, for
    MoE models, ``T.moe_aux``'s counters."""
    accum = max(int(tc.grad_accum), 1)
    is_ax = lambda x: isinstance(x, tuple) and all(
        isinstance(e, (str, type(None))) for e in x)
    if param_axes is not None:
        param_axes = T.trainable(param_axes)

    def _anchor(tree):
        """Pin a grad-shaped tree to the parameter sharding: without this the
        accumulation carry propagates as replicated and GSPMD emits one
        full-shape f32 all-reduce per weight per microbatch (measured 2.1
        TB/chip/step on yi-34b; EXPERIMENTS.md §Perf iteration A4)."""
        if param_axes is None:
            return tree
        return jax.tree_util.tree_map(
            lambda g, ax: sharding.constrain(g, *ax), tree, param_axes,
            is_leaf=lambda x: is_ax(x))

    def train_step(params, opt_state, batch):
        params, buffers = T.split_buffers(params)

        def loss_fn(p, mb):
            loss, aux = T.lm_loss(cfg, T.merge_buffers(p, buffers), mb)
            if "moe_balance" in aux:
                loss = loss + tc.moe_seq_aux_alpha * aux["moe_balance"]
            return loss, aux

        if accum == 1:
            (loss, aux), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params, batch)
        else:
            # microbatch scan: bounds activation peak at fixed global batch
            mb = jax.tree_util.tree_map(
                lambda x: x.reshape((accum, x.shape[0] // accum)
                                    + x.shape[1:])
                if x.ndim >= 1 and x.shape[0] % accum == 0 else
                jnp.broadcast_to(x, (accum,) + x.shape), batch)
            if cfg.pos_type == "mrope" and "positions" in batch:
                # positions are (3, B, S): slice the batch dim, not dim 0
                p3 = batch["positions"]
                mb["positions"] = jnp.moveaxis(
                    p3.reshape(3, accum, p3.shape[1] // accum, p3.shape[2]),
                    1, 0)

            def micro(acc, mbi):
                (loss, aux), g = jax.value_and_grad(
                    loss_fn, has_aux=True)(params, mbi)
                acc = jax.tree_util.tree_map(
                    lambda a, b: a + b.astype(jnp.float32), acc, g)
                return _anchor(acc), (loss, aux)

            g0 = _anchor(jax.tree_util.tree_map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params))
            grads, (losses, auxes) = jax.lax.scan(micro, g0, mb)
            grads = jax.tree_util.tree_map(lambda g: g / accum, grads)
            loss = jnp.mean(losses)
            aux = {k: jax.tree_util.tree_map(functools.partial(
                _MICRO_REDUCE.get(k, jnp.mean), axis=0), a)
                for k, a in auxes.items()}

        lr = cosine_schedule(opt_state.step, base_lr=tc.learning_rate,
                             warmup_steps=tc.warmup_steps,
                             total_steps=tc.total_steps)
        params, opt_state, om = adamw_update(
            grads, opt_state, params, learning_rate=lr, beta1=tc.beta1,
            beta2=tc.beta2, eps=tc.eps, weight_decay=tc.weight_decay,
            grad_clip=tc.grad_clip)
        loads = aux.pop("moe_load", None)
        if buffers:
            buffers = T.update_buffers(buffers, loads, tc.moe_bias_rate)
        metrics = {"loss": loss, **aux, **om}
        return T.merge_buffers(params, buffers), opt_state, metrics

    return train_step


def make_prefill_step(cfg: ModelConfig):
    def prefill(params, cache, batch):
        logits, cache = T.prefill_step(
            cfg, params, batch.get("tokens"), embeds=batch.get("embeds"),
            positions=batch.get("positions"), cache=cache)
        return logits, cache

    return prefill


def make_decode_step(cfg: ModelConfig):
    def decode(params, cache, batch):
        logits, cache = T.decode_step(
            cfg, params, batch.get("tokens"), embeds=batch.get("embeds"),
            positions=batch.get("positions"), cache=cache)
        # greedy next token (kept in-graph so serving is one dispatch)
        next_tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        return logits, next_tok, cache

    return decode


# ---------------------------------------------------------------------------
# sharding assembly
# ---------------------------------------------------------------------------

def shardings_for_cell(cfg, shape, mesh, rules="baseline"):
    """(in_shardings, out_shardings, abstract_args, step_fn) for a cell."""
    if isinstance(rules, str):
        rules = sharding.RULE_SETS[rules]
    p_shapes, p_axes = abstract_init(cfg)
    sh = lambda ax_tree, shp_tree: jax.tree_util.tree_map(
        lambda ax, s: sharding.sharding_for(ax, s.shape, mesh, rules),
        ax_tree, shp_tree,
        is_leaf=lambda x: isinstance(x, tuple) and all(
            isinstance(e, (str, type(None))) for e in x))
    p_sh = sh(p_axes, p_shapes)
    repl = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())

    specs = input_specs(cfg, shape)
    b_ax = batch_axes(cfg, specs["batch"])
    b_sh = sh(b_ax, specs["batch"])

    if shape.kind == "train":
        opt_shapes = abstract_opt_state(p_shapes)
        o_sh = sh(opt_axes(p_axes), opt_shapes)
        args = (p_shapes, opt_shapes, specs["batch"])
        in_sh = (p_sh, o_sh, b_sh)
        out_sh = (p_sh, o_sh, repl)       # every metric replicated
        return in_sh, out_sh, args, None

    cache_shapes = specs["cache"]
    c_ax = T.cache_axes(cfg)
    c_sh = sh(c_ax, cache_shapes)
    args = (p_shapes, cache_shapes, specs["batch"])
    in_sh = (p_sh, c_sh, b_sh)
    if shape.kind == "prefill":
        logits_sh = sharding.sharding_for(
            ("act_batch", "act_seq", "act_vocab"),
            (shape.global_batch, 1, cfg.vocab_size), mesh, rules)
        out_sh = (logits_sh, c_sh)
    else:
        logits_sh = sharding.sharding_for(
            ("act_batch", "act_seq", "act_vocab"),
            (shape.global_batch, 1, cfg.vocab_size), mesh, rules)
        tok_sh = sharding.sharding_for(("act_batch",), (shape.global_batch,),
                                       mesh, rules)
        out_sh = (logits_sh, tok_sh, c_sh)
    return in_sh, out_sh, args, None
