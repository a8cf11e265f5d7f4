"""Preemption-aware training jobs of an MoE model through
``launch.train.train``: the ``train`` generator with the configuration's
MoE and latent-attention fields, the routing-bias and balance settings, and
the step's ``moe_rows`` counter.

The configuration is built first thing in set-up, so a program without
these fields fails at once.  Every step of the window adds its ``moe_rows``
(held (token, expert) pairs computed, summed over layers; a device scalar
that the step returns with its loss) to ``work()['expert_gmm_flops']``
(``bench/work_moe.py``).  ``check()`` adds ``rows_gap``: the largest
relative gap, over the checked steps, between the held pairs the program
computed and those the reference routes to its held experts, and
``dropped_rows``: over every step of the window, the pairs the program's
router sent to held experts (``moe_routed_held``) that its expert layer did
not compute (``moe_rows``).  A single dropped pair is below the reference's
routing noise (bfloat16 activations flip near-tied choices), but exact here.
``bias_step_gap`` is the share of routing biases (layer, expert) whose
change over the checked steps, in sign steps of the update's rate, differs
from the reference's: a skipped or reversed update reads about 1.

The program starts its routing biases at zero; at each job's first step the
generator sets them as the configuration's ``train.init`` states, the
reference's draw, so that choice and weights differ from the first step.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from bench import work_moe
from bench.generators import train

BIAS = "e_score_correction_bias"


class MoeRecorder(train.StepRecorder):
    """``train.StepRecorder`` that also keeps each step's ``moe_rows`` and
    ``moe_routed_held - moe_rows`` (device scalars, read after the window;
    ``last_rows`` holds the rows of the steps before the last ``reset``),
    and the routing biases' sign steps over the checked steps.  A job's
    first step takes ``bias0`` as its routing biases (``fresh``, set by the
    generator per job).  On a restore it fingerprints the state held at the
    kill and lets it go before it places the resumed state: at full size
    the two states and the step's reserved temp space do not fit one chip
    together."""

    def __init__(self, check_steps: int, bias0, rate: float):
        self.bias0, self.rate, self.fresh = bias0, rate, False
        super().__init__(check_steps)

    def reset(self):
        super().reset()
        self.last_rows = getattr(self, "rows", [])
        self.last_bias_steps = getattr(self, "bias_steps", None)
        self.rows: list = []
        self.dropped: list = []
        self.bias_steps = None

    def _bias(self, params):
        return params["groups"][0]["moe"][BIAS]

    def wrap(self, jitted):
        import jax
        import jax.numpy as jnp

        def step(params, opt_state, batch):
            if self.keep and self.prev is not None \
                    and params is not self.prev[0]:
                saved = self.fingerprint(self.prev[:2])
                self.prev = None
                params, opt_state = jax.device_put((params, opt_state))
                self.resumes.append(
                    (saved, self.fingerprint((params, opt_state))))
            if self.fresh:
                g = dict(params["groups"][0])
                g["moe"] = dict(g["moe"], **{BIAS: self.bias0})
                params = dict(params, groups=[g] + params["groups"][1:])
                self.fresh = False
            i = self.calls
            self.calls += 1
            if self.keep and i == 0:
                self.p0 = params
            out = jitted(params, opt_state, batch)
            self.rows.append(out[2]["moe_rows"])
            self.dropped.append(out[2]["moe_routed_held"] - out[2]["moe_rows"])
            if self.keep:
                if i == 0:
                    self.mu_norms = self.norms(out[1].mu)
                if i == self.check_steps - 1:
                    self.update_norms = self.diff_norms(out[0], self.p0)
                    self.bias_steps = jnp.round(
                        (self._bias(out[0]) - self._bias(self.p0))
                        / self.rate)
                    self.p0 = None
                self.prev = out
            return out

        return step


class Generator(train.Generator):
    def model_config(self):
        from repro.configs.base import ModelConfig
        c = self.cfg
        return ModelConfig(
            name=c["name"], family="moe", n_layers=c["num_hidden_layers"],
            d_model=c["hidden_size"], n_heads=c["num_attention_heads"],
            n_kv_heads=c["num_key_value_heads"],
            d_ff=c["intermediate_size"], vocab_size=c["vocab_size"],
            n_experts=c["router_experts"], top_k=c["num_experts_per_tok"],
            moe_d_ff=c["moe_intermediate_size"],
            n_shared_experts=c["n_shared_experts"],
            score_fn=c["scoring_func"], norm_topk=c["norm_topk_prob"],
            routed_scale=c["routed_scaling_factor"],
            experts_held=tuple(c["experts_held"]),
            kv_lora_rank=c["kv_lora_rank"],
            qk_nope_head_dim=c["qk_nope_head_dim"],
            qk_rope_head_dim=c["qk_rope_head_dim"],
            v_head_dim=c["v_head_dim"],
            first_dense_layers=c["first_k_dense_replace"],
            rope_theta=float(c["rope_theta"]), norm_eps=c["rms_norm_eps"],
            tie_embeddings=c["tie_word_embeddings"], mlp_variant="swiglu",
            param_dtype=c["precision"]["params"],
            compute_dtype=c["precision"]["compute"])

    def train_config(self, **kw):
        t = self.cfg["train"]
        return dataclasses.replace(
            super().train_config(**kw), moe_seq_aux_alpha=t["seq_aux_alpha"],
            moe_bias_rate=t["bias_update_rate"])

    def setup(self):
        self.mc = self.model_config()   # fails at once without the fields
        super().setup()
        self.warm_rows = self.rec.last_rows
        self.warm_bias_steps = self.rec.last_bias_steps

    def job(self, tc, steps: int):
        if not isinstance(self.rec, MoeRecorder):   # set-up's new recorder
            self.rec = MoeRecorder(self.rec.check_steps,
                                   self.ref.routing_bias(self.cfg, self.seed),
                                   self.cfg["train"]["bias_update_rate"])
        self.rec.fresh = True
        return super().job(tc, steps)

    def free(self):
        """Also drop every compiled program, so that the training step's
        reserved temp space (3 GB at full size) is free for the
        reference's."""
        import jax
        super().free()
        jax.clear_caches()

    def work(self) -> dict:
        t = self.cfg["train"]
        rows = sum(int(r) for r in self.rec.rows)
        return dict(
            train_flops_per_token=work_moe.train_flops_per_token(
                self.cfg, t["seq_len"]),
            expert_gmm_flops=work_moe.gmm_train_flops(self.cfg, rows))

    def _program(self, res, mu_norms, update_norms, rows,
                 bias_steps) -> dict:
        out = super()._program(res, mu_norms, update_norms)
        out["rows"] = [int(r) for r in rows[:self.mix["check_steps"]]]
        out["bias_steps"] = np.asarray(bias_steps, np.int64).tolist()
        return out

    def check(self) -> dict:
        res = self.jobs[0][0]
        prog = self._program(res, self.rec.mu_norms, self.rec.update_norms,
                             self.rec.rows, self.rec.bias_steps)
        self.rec.mu_norms = self.rec.update_norms = None
        out = compare(prog, self._reference())
        out["restore_mismatch"] = train.restore_mismatch(self.rec.resumes,
                                                         res.restarts)
        out["dropped_rows"] = sum(abs(int(d)) for d in self.rec.dropped)
        return out

    def readings(self) -> dict:
        """After set-up, without a window: the numbers of the warm-up job's
        steps, of the control (the reference with float8 matmuls in the
        program's place) and of a planted fault (the reference routing with
        its held experts shifted by one, so that one expert's share is
        another's)."""
        warm, mu, upd = self.warm
        prog = self._program(warm, mu, upd, self.warm_rows,
                             self.warm_bias_steps)
        self.warm = None
        self.rec.prev = self.rec.p0 = None
        ref = self._reference()
        cfg = dict(self.cfg)
        first, held = cfg["experts_held"]
        shifted = self.ref.train(
            dict(cfg, experts_held=[first + 1, held]), self.mix["data"],
            self.seed, n_steps=self.mix["check_steps"],
            total_steps=self.mix["total_steps"])
        return dict(program=compare(prog, ref),
                    control=compare(self._reference(mode="fp8"), ref),
                    shifted_experts=compare(shifted, ref))


def compare(prog: dict, ref: dict) -> dict:
    out = train.compare(prog, ref)
    rp, rr = np.asarray(prog["rows"], float), np.asarray(ref["rows"], float)
    out["rows_gap"] = float(np.max(np.abs(rp - rr) / rr)) \
        if rp.shape == rr.shape else float("nan")
    bp, br = np.asarray(prog["bias_steps"]), np.asarray(ref["bias_steps"])
    out["bias_step_gap"] = float(np.mean(bp != br)) \
        if bp.shape == br.shape else float("nan")
    return out
