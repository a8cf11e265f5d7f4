"""Preemption-aware training jobs through ``launch.train.train``.

Set-up builds the model configuration as the configuration file states it,
warms the step with a short job at the same shapes and the checkpoint
schedule's DP solve at the job's size.  The window runs whole jobs back to
back, at least one and another only while it should end within
``--seconds``: DP checkpoints, one injected preemption with its emergency
save, the restore and the resume are all inside each.

The job's jitted step is observed from outside, without changing what it
computes: the trainer's ``jax.jit`` is handed a wrapper that keeps, for the
first job, the per-leaf norms of Adam's first moment after step 1 and of the
parameters' change after the mix's ``check_steps``, and a fingerprint of the
state the job held when it was killed and of the state it resumed from.
"""
from __future__ import annotations

import dataclasses
import shutil
import statistics
import tempfile
import time
import types

import numpy as np

from bench import common, work


def _path_key(path) -> str:
    return "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                    for p in path)


def flat(tree) -> dict:
    import jax
    leaves, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {_path_key(p): x for p, x in leaves}


def _jitted_helpers():
    import jax
    import jax.numpy as jnp

    def norms(tree):
        return {k: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                for k, x in flat(tree).items()}

    def diff_norms(a, b):
        fb = flat(b)
        return {k: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)
                                               - fb[k].astype(jnp.float32))))
                for k, x in flat(a).items()}

    def fingerprint(tree):
        """Two uint32 sums of each leaf's bits: equal leaves agree."""
        out = {}
        for k, x in flat(tree).items():
            u = jax.lax.bitcast_convert_type(x.reshape(-1), jnp.uint32) \
                if x.dtype.itemsize == 4 else x.reshape(-1).astype(jnp.uint32)
            w = jnp.arange(u.size, dtype=jnp.uint32) * jnp.uint32(2) + 1
            out[k] = jnp.stack([jnp.sum(u), jnp.sum(u * w)])
        return out

    return jax.jit(norms), jax.jit(diff_norms), jax.jit(fingerprint)


class StepRecorder:
    """Wraps the trainer's jitted step; see the module docstring."""

    def __init__(self, check_steps: int):
        self.check_steps = check_steps
        self.norms, self.diff_norms, self.fingerprint = _jitted_helpers()
        self.keep = True
        self.reset()

    def reset(self):
        self.calls, self.prev, self.p0 = 0, None, None
        self.mu_norms = self.update_norms = None
        self.resumes: list = []

    def wrap(self, jitted):
        import jax

        def step(params, opt_state, batch):
            if self.keep and self.prev is not None \
                    and params is not self.prev[0]:
                # a restore: the job resumes from state it did not produce
                params, opt_state = jax.device_put((params, opt_state))
                self.resumes.append((self.fingerprint(self.prev[:2]),
                                     self.fingerprint((params, opt_state))))
            i = self.calls
            self.calls += 1
            if self.keep and i == 0:
                self.p0 = params
            out = jitted(params, opt_state, batch)
            if self.keep:
                if i == 0:
                    self.mu_norms = self.norms(out[1].mu)
                if i == self.check_steps - 1:
                    self.update_norms = self.diff_norms(out[0], self.p0)
                    self.p0 = None
                self.prev = out
            return out

        return step


class _JaxWithRecorder(types.ModuleType):
    """``jax`` as the trainer module sees it, with ``jit`` wrapped."""

    def __init__(self, real, recorder):
        super().__init__("jax")
        self._real, self._rec = real, recorder

    def __getattr__(self, name):
        return getattr(self._real, name)

    def jit(self, fn, **kw):
        return self._rec.wrap(self._real.jit(fn, **kw))


class Generator:
    def __init__(self, spec: dict, *, seed: int, reference):
        self.cfg, self.mix, self.ref = spec["config"], spec["mix"], reference
        self.seed = common.subseed(seed, "train")
        self.jobs: list = []
        self.dirs: list = []

    # -- the program, as the configuration states it --------------------------
    def model_config(self):
        from repro.configs.base import ModelConfig
        c = self.cfg
        return ModelConfig(
            name=c["name"], family="dense", n_layers=c["num_hidden_layers"],
            d_model=c["hidden_size"], n_heads=c["num_attention_heads"],
            n_kv_heads=c["num_key_value_heads"],
            d_ff=c["intermediate_size"], vocab_size=c["vocab_size"],
            rope_theta=c["rope_theta"], norm_eps=c["rms_norm_eps"],
            tie_embeddings=c["tie_word_embeddings"], mlp_variant="swiglu",
            param_dtype=c["precision"]["params"],
            compute_dtype=c["precision"]["compute"])

    def train_config(self, **kw):
        from repro.configs.base import TrainConfig
        t, p = self.cfg["train"], self.cfg["preemptible"]
        return dataclasses.replace(TrainConfig(
            learning_rate=t["learning_rate"], weight_decay=t["weight_decay"],
            beta1=t["beta1"], beta2=t["beta2"], eps=t["eps"],
            grad_clip=t["grad_clip"], warmup_steps=t["warmup_steps"],
            total_steps=self.mix["total_steps"], seed=self.seed,
            ckpt_policy=p["ckpt_policy"], ckpt_cost_hours=p["ckpt_cost_hours"],
            vm_type=p["vm_type"], async_checkpoint=p["async_checkpoint"]), **kw)

    def job(self, tc, steps: int):
        from repro.launch import train as T
        t, m = self.cfg["train"], self.mix
        real = T.jax
        T.jax = _JaxWithRecorder(real, self.rec)
        try:
            return T.train(self.mc, tc, total_steps=steps,
                           seq_len=t["seq_len"], global_batch=t["global_batch"],
                           inject_preemptions=True,
                           sim_hours_per_step=m["sim_hours_per_step"],
                           preemption_seed=m["preemption_seed"], verbose=False)
        finally:
            T.jax = real

    def new_dir(self) -> str:
        d = tempfile.mkdtemp(prefix="bench_ckpt_")
        self.dirs.append(d)
        return d

    def setup(self):
        from repro.checkpoint import CheckpointManager
        from repro.core import distributions as D

        m, p = self.mix, self.cfg["preemptible"]
        self.mc = self.model_config()
        self.rec = StepRecorder(m["check_steps"])
        # the step, the batches and the kill's draw, at the job's shapes (the
        # step closes over the schedule's total_steps, so tc is the job's)
        warm = self.job(self.train_config(ckpt_policy="none",
                                          ckpt_dir=self.new_dir()),
                        m["warmup_job_steps"])
        if warm.restarts:
            raise RuntimeError("the warm-up job was preempted: the mix's "
                               "preemption_seed kills before its first "
                               f"{m['warmup_job_steps']} steps")
        self.rec.fingerprint(self.rec.prev[:2])
        self.warm = (warm, self.rec.mu_norms, self.rec.update_norms)
        self.rec.reset()
        # the checkpoint schedule's DP solve at the job's size
        CheckpointManager(directory=self.new_dir(),
                          dist=D.constrained_for(p["vm_type"]), policy="dp",
                          delta_hours=p["ckpt_cost_hours"],
                          step_time_hours=m["sim_hours_per_step"],
                          total_steps=m["total_steps"])

    def window(self, seconds: float) -> dict:
        import jax

        start = time.perf_counter()
        while True:
            tc = self.train_config(ckpt_dir=self.new_dir())
            with jax.profiler.TraceAnnotation("bench.job"):
                t0 = time.perf_counter()
                res = self.job(tc, self.mix["total_steps"])
                self.jobs.append((res, time.perf_counter() - t0))
            self.rec.keep, self.rec.prev = False, None   # the first is checked
            # whole jobs only: another if it should still end in the window
            if time.perf_counter() + self.jobs[-1][1] > start + seconds:
                break
        t = self.cfg["train"]
        tokens = t["seq_len"] * t["global_batch"]
        steps = sum(r.steps_run for r, _ in self.jobs)
        bad = sum(int(not np.all(np.isfinite(r.losses))) for r, _ in self.jobs)
        return dict(
            attempted=steps, failed=bad,
            useful_tokens=len(self.jobs) * self.mix["total_steps"] * tokens,
            job_seconds=[s for _, s in self.jobs],
            restarts=[r.restarts for r, _ in self.jobs],
            checkpoints=[r.checkpoints for r, _ in self.jobs])

    def work(self) -> dict:
        t = self.cfg["train"]
        return dict(train_flops_per_token=work.lm_train_flops_per_token(
            self.cfg, t["seq_len"]))

    def free(self):
        self.rec.prev = self.rec.p0 = None
        for d in self.dirs:
            shutil.rmtree(d, ignore_errors=True)
        self.dirs = []

    def _program(self, res, mu_norms, update_norms) -> dict:
        return dict(losses=res.losses[:self.mix["check_steps"]],
                    mu_norms={k: float(v) for k, v in mu_norms.items()},
                    update_norms={k: float(v)
                                  for k, v in update_norms.items()})

    def _reference(self, **kw) -> dict:
        return self.ref.train(self.cfg, self.mix["data"], self.seed,
                              n_steps=self.mix["check_steps"],
                              total_steps=self.mix["total_steps"], **kw)

    def check(self) -> dict:
        res = self.jobs[0][0]
        prog = self._program(res, self.rec.mu_norms, self.rec.update_norms)
        self.rec.mu_norms = self.rec.update_norms = None
        out = compare(prog, self._reference())
        out["restore_mismatch"] = restore_mismatch(self.rec.resumes,
                                                   res.restarts)
        return out

    def readings(self) -> dict:
        """After set-up, without a window: the numbers of the warm-up job's
        steps (the window's compiled step, weights and batches), of the
        control (the reference with float8 matmuls in the program's place)
        and of a planted fault (half of each batch left out, the mean taken
        over the rest, in the reference)."""
        prog = self._program(*self.warm)
        self.warm = None
        self.rec.prev = self.rec.p0 = None
        ref = self._reference()
        half = self.cfg["train"]["global_batch"] // 2
        return dict(program=compare(prog, ref),
                    control=compare(self._reference(mode="fp8"), ref),
                    half_batch=compare(self._reference(rows=slice(0, half)),
                                       ref))


def leaf_gap(prog: dict, ref: dict, *, floor_from: dict) -> float:
    """Worst leaf's |norm - reference norm| over the larger of the reference
    leaf's norm and the median leaf's; leaves whose reference gradient is
    under a thousandth of the median leaf's are left out (they move by
    round-off alone)."""
    med = statistics.median(ref.values())
    gmed = statistics.median(floor_from.values())
    gaps = [abs(prog[k] - r) / max(r, med) for k, r in ref.items()
            if floor_from[k] >= 1e-3 * gmed]
    return max(gaps)


def compare(prog: dict, ref: dict) -> dict:
    lp, lr = np.asarray(prog["losses"]), np.asarray(ref["losses"])
    if lp.shape != lr.shape:
        return dict(loss_gap=float("nan"), grad_norm_gap=float("nan"),
                    update_norm_gap=float("nan"))
    return dict(
        loss_gap=float(np.max(np.abs(lp - lr) / np.abs(lr))),
        grad_norm_gap=leaf_gap(prog["mu_norms"], ref["mu_norms"],
                               floor_from=ref["mu_norms"]),
        update_norm_gap=leaf_gap(prog["update_norms"], ref["update_norms"],
                                 floor_from=ref["mu_norms"]))


def restore_mismatch(resumes: list, restarts: int) -> int:
    """Leaves whose resumed state differs from the state saved at the kill,
    plus any kill the job reported and the step never saw resumed."""
    bad = abs(len(resumes) - restarts)
    for saved, restored in resumes:
        for k, v in saved.items():
            bad += int(not np.array_equal(np.asarray(v),
                                          np.asarray(restored[k])))
    return bad
