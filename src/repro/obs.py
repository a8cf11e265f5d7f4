"""Named host spans on the profiler's clock.

Each span is a ``jax.profiler.TraceAnnotation`` (a TraceMe event), so a
profiler trace holds it on the same clock as the device's operations, on
the thread that did the work; with no profiler running one costs about a
microsecond.  Names are fixed here so that a trace reduction can key on
them; keyword arguments become the event's stats.

The spans, from the closed loop and the trainer down:

  repro.swap             FleetRuntime._try_swap: solve, validate, publish
  repro.solve.grids      solve_batch: the scenarios' CDF grids (and dollar
                         inputs), stacked
  repro.solve.kernel     solve_batch: the backend's dispatch
  repro.solve.fetch      solve_batch: V and K copied to the host (waits for
                         the kernel)
  repro.solve.validate   FleetRuntime._solve: the tables' validation
  repro.train.first_step train(): the first step of a call (trace, compile
                         or cache load, and the step)
  repro.train.step       train(): every later step, batch to loss on host
  repro.train.resume     train(): replace the pod, restore, recompute the
                         schedule after a preemption
  repro.ckpt.save        CheckpointManager.save: waits, copies the state to
                         the host; checksums and writes only when blocking
                         (else on the writer thread; bytes:
                         checkpoint.manager.save_bytes())
  repro.ckpt.wait        CheckpointManager.wait on a writer in flight
  repro.ckpt.restore     restore_latest: each leaf read once into its host
                         array and CRC-checked there (bytes read:
                         checkpoint.manager.restore_bytes())
  repro.ckpt.plan        CheckpointManager._recompute: the DP schedule

Counters and named device ops beside them, for MoE models:

  moe_rows               the train step's metrics: held (token, expert)
                         pairs computed, summed over layers (device scalar,
                         returned with the loss: no host sync of its own)
  moe_routed_held        the train step's metrics: pairs the router sent to
                         held experts, summed over layers (moe_rows short
                         of it counts dropped pairs)
  moe_load_max           the train step's metrics: the largest held
                         expert's rows over T K / E, over layers
  moe_gmm, moe_tgmm      device ops of the expert layer's grouped matmuls
                         (kernels/moe_gmm.py): forward and input gradient,
                         weight gradient
"""
from __future__ import annotations

import jax

PREFIX = "repro."

SWAP = "repro.swap"
SOLVE_GRIDS = "repro.solve.grids"
SOLVE_KERNEL = "repro.solve.kernel"
SOLVE_FETCH = "repro.solve.fetch"
SOLVE_VALIDATE = "repro.solve.validate"
TRAIN_FIRST_STEP = "repro.train.first_step"
TRAIN_STEP = "repro.train.step"
TRAIN_RESUME = "repro.train.resume"
CKPT_SAVE = "repro.ckpt.save"
CKPT_WAIT = "repro.ckpt.wait"
CKPT_RESTORE = "repro.ckpt.restore"
CKPT_PLAN = "repro.ckpt.plan"

SPANS = (SWAP, SOLVE_GRIDS, SOLVE_KERNEL, SOLVE_FETCH, SOLVE_VALIDATE,
         TRAIN_FIRST_STEP, TRAIN_STEP, TRAIN_RESUME, CKPT_SAVE, CKPT_WAIT,
         CKPT_RESTORE, CKPT_PLAN)


def span(name: str, **args) -> jax.profiler.TraceAnnotation:
    """A context manager that records ``name`` (one of ``SPANS``) around
    its block, with ``args`` as the event's stats."""
    return jax.profiler.TraceAnnotation(name, **args)
