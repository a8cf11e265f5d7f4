"""End-to-end preemption-aware training driver.

This is the integration point of the paper's contribution with the training
substrate: the loop trains a model on the synthetic pipeline while

  * a ``PreemptionSource`` (bathtub model) delivers simulated pod
    preemptions with the provider's 30 s warning,
  * a ``CheckpointManager`` runs the paper's DP checkpoint schedule
    (non-uniform, pod-age-dependent) and flushes an emergency checkpoint
    inside the warning window,
  * on pod loss the job restarts on a replacement pod, restores the newest
    intact checkpoint, replays the deterministic data pipeline to the
    resumed step, and recomputes the DP schedule (the paper's resume rule),
  * a ``StragglerWatchdog`` demotes slow pods (treated as preemptions).

Simulated time: ``sim_hours_per_step`` maps steps to pod age so a 200-step
CPU run can traverse hours of the preemption model.  On a real fleet the
same loop runs with wall-clock time and the metadata-server signal.

Run: PYTHONPATH=src python -m repro.launch.train --arch smollm-135m --smoke
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import numpy as np

from .. import compile_cache, configs, sharding
from ..checkpoint import CheckpointManager
from ..configs.base import ShapeConfig, TrainConfig
from ..core import distributions
from ..data.pipeline import SyntheticLM
from ..fault import PreemptionSource, StragglerWatchdog
from ..models import transformer as T
from ..optim import adamw_init
from . import steps


@dataclasses.dataclass
class TrainResult:
    losses: list
    steps_run: int
    restarts: int
    checkpoints: int
    emergency_checkpoints: int
    wasted_steps: int
    final_loss: float
    # one entry per injected preemption: the step it struck at and the
    # step the restored checkpoint resumed from
    preempted_at: list = dataclasses.field(default_factory=list)
    resumed_from: list = dataclasses.field(default_factory=list)


def train(cfg, tc: TrainConfig, *, total_steps: int = 200,
          seq_len: int = 64, global_batch: int = 8,
          inject_preemptions: bool = False, sim_hours_per_step: float = 0.02,
          preemption_seed: int = 7, mesh=None, rules: str = "baseline",
          log_every: int = 25, verbose: bool = True) -> TrainResult:
    """Train ``cfg`` for ``total_steps``.  With a ``mesh``, parameters,
    optimizer state and batches are placed by the ``rules`` logical-axis
    table (``steps.shardings_for_cell``) and the step is jitted with those
    shardings; without one, everything sits on the default device."""
    dist = distributions.constrained_for(tc.vm_type)
    pipe = SyntheticLM(vocab_size=cfg.vocab_size, seq_len=seq_len,
                       global_batch=global_batch, seed=tc.seed)
    key = jax.random.PRNGKey(tc.seed)

    params, axes = T.init(cfg, key)
    opt_state = adamw_init(params)
    if mesh is None:
        place = lambda tree, i: tree
        jitted = jax.jit(steps.make_train_step(cfg, tc))
    else:
        in_sh, out_sh, _, _ = steps.shardings_for_cell(
            cfg, ShapeConfig("train", "train", seq_len, global_batch), mesh,
            rules)
        place = lambda tree, i: jax.device_put(tree, in_sh[i])
        jitted = jax.jit(steps.make_train_step(cfg, tc, param_axes=axes),
                         in_shardings=in_sh, out_shardings=out_sh)
        params, opt_state = place(params, 0), place(opt_state, 1)

    mgr = CheckpointManager(
        directory=tc.ckpt_dir, dist=dist, policy=tc.ckpt_policy,
        delta_hours=tc.ckpt_cost_hours, step_time_hours=sim_hours_per_step,
        total_steps=total_steps, async_write=tc.async_checkpoint)
    src = PreemptionSource(dist, n_pods=1, seed=preemption_seed) \
        if inject_preemptions else None
    dog = StragglerWatchdog()

    # resume if a checkpoint exists
    step = 0
    restarts = 0
    wasted = 0
    preempted_at, resumed_from = [], []
    restored = mgr.restore((params, opt_state))
    if restored is not None:
        (params, opt_state), step, _ = restored
        params, opt_state = place(params, 0), place(opt_state, 1)
        if verbose:
            print(f"resumed from checkpoint at step {step}")

    losses = []
    sim_now = 0.0
    while step < total_steps:
        t0 = time.time()
        batch = place(pipe.batch(step), 2)
        with sharding.use(mesh, rules):   # the model's logical constraints
            params, opt_state, metrics = jitted(params, opt_state, batch)
        loss = float(metrics["loss"])
        losses.append(loss)
        step += 1
        sim_now += sim_hours_per_step
        mgr.observe_step_time(sim_hours_per_step * 3600.0)
        dog.observe(time.time() - t0)

        if verbose and step % log_every == 0:
            print(f"step {step:5d} loss {loss:.4f} "
                  f"grad {float(metrics['grad_norm']):.3f} "
                  f"ckpts {mgr.n_saved}")

        # --- the paper's policies in action ---
        if mgr.should_checkpoint(step):
            mgr.save(step, (params, opt_state))
        if src is not None:
            events = src.poll(sim_now)
            if events:
                # 30 s warning: emergency checkpoint, then the pod dies
                mgr.on_preemption_warning(step, (params, opt_state))
                # relaunch on a fresh pod + restore + replay pipeline
                restarts += 1
                src.replace_pod(0, sim_now)
                restored = mgr.restore((params, opt_state))
                assert restored is not None
                (params, opt_state), ckpt_step, _ = restored
                params, opt_state = place(params, 0), place(opt_state, 1)
                preempted_at.append(step)
                resumed_from.append(ckpt_step)
                wasted += step - ckpt_step
                step = ckpt_step
                mgr.on_restart(pod_age_hours=0.0, resumed_step=step)
                if verbose:
                    print(f"  !! pod preempted at sim t={sim_now:.2f}h -> "
                          f"restart from step {step}")

    mgr.wait()
    return TrainResult(losses=losses, steps_run=len(losses),
                       restarts=restarts, checkpoints=mgr.n_saved,
                       emergency_checkpoints=mgr.n_emergency,
                       wasted_steps=wasted,
                       final_loss=float(np.mean(losses[-10:])),
                       preempted_at=preempted_at, resumed_from=resumed_from)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-135m")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU-friendly)")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--preemptions", action="store_true")
    ap.add_argument("--ckpt-policy", default="dp",
                    choices=("dp", "young_daly", "fixed", "none"))
    ap.add_argument("--ckpt-dir", default=TrainConfig.ckpt_dir)
    args = ap.parse_args()
    compile_cache.enable()

    cfg = configs.smoke(args.arch) if args.smoke else configs.get(args.arch)
    tc = TrainConfig(ckpt_policy=args.ckpt_policy, ckpt_dir=args.ckpt_dir,
                     total_steps=args.steps)
    res = train(cfg, tc, total_steps=args.steps,
                inject_preemptions=args.preemptions)
    print(f"done: {res.steps_run} steps, final loss {res.final_loss:.4f}, "
          f"{res.restarts} restarts, {res.checkpoints} checkpoints "
          f"({res.emergency_checkpoints} emergency), "
          f"{res.wasted_steps} wasted steps")


if __name__ == "__main__":
    main()
