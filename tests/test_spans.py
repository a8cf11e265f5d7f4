"""The program's named spans (``repro.obs``) in a profiler trace.

One CPU trace holds a warm ``FleetRuntime`` refresh (2 scenarios, J = 10,
the ``xla`` backend), two small ``train()`` calls (the first with one
injected preemption, DP checkpoints) and a checkpoint save that waits on
the writer before it.  Each span must appear under its bare name, with its
arguments as stats, inside its parent on the same thread.
"""
import dataclasses
import glob
import os
import time

import jax
import pytest
from jax.profiler import ProfileData

from repro import configs, obs
from repro.checkpoint import manager as M
from repro.configs.base import TrainConfig
from repro.core import distributions as D
from repro.core import runtime as rt
from repro.core import scenarios as SC
from repro.launch.train import train

TRAIN_STEPS = (6, 4)
# each span and the stats it carries
ARGS = {obs.SWAP: {"reason"}, obs.SOLVE_GRIDS: set(),
        obs.SOLVE_KERNEL: {"backend"}, obs.SOLVE_FETCH: set(),
        obs.SOLVE_VALIDATE: set(), obs.TRAIN_FIRST_STEP: {"step"},
        obs.TRAIN_STEP: {"step"}, obs.TRAIN_RESUME: {"step"},
        obs.CKPT_SAVE: {"step", "emergency"}, obs.CKPT_WAIT: set(),
        obs.CKPT_RESTORE: set(), obs.CKPT_PLAN: set()}


def _events(logdir) -> list:
    """``(name, thread, start_ns, end_ns, stats)`` of every ``repro.`` event
    on the host, ``thread`` being the plane and line it is on."""
    path, = glob.glob(os.path.join(logdir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            out += [(e.name, (plane.name, i), int(e.start_ns),
                     int(e.start_ns + e.duration_ns), dict(e.stats))
                    for e in line.events if e.name.startswith(obs.PREFIX)]
    return out


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("spans")
    fr = rt.FleetRuntime(rt.RuntimeConfig(
        base_scenarios=(SC.default_grid()[0].name,), job_steps=10,
        grid_dt=0.25, solver_backend="xla"))
    cfg = dataclasses.replace(configs.smoke("smollm-135m"), n_layers=2,
                              d_model=32, d_ff=64, vocab_size=256)
    real_member = M._write_member

    def slow_member(*a):
        time.sleep(0.3)
        return real_member(*a)

    with jax.profiler.trace(str(tmp / "trace")):
        fr._try_swap("initial-fit")
        runs = [train(cfg, TrainConfig(ckpt_dir=str(tmp / f"job{i}"),
                                       ckpt_policy="dp", warmup_steps=2),
                      total_steps=n, seq_len=16, global_batch=2,
                      inject_preemptions=i == 0, sim_hours_per_step=0.5,
                      preemption_seed=8, verbose=False)
                for i, n in enumerate(TRAIN_STEPS)]
        # a writer still in flight when the next save starts
        mgr = M.CheckpointManager(directory=str(tmp / "slow"),
                                  dist=D.constrained_for(), policy="none")
        with pytest.MonkeyPatch.context() as mp:
            mp.setattr(M, "_write_member", slow_member)
            mgr.save(1, {"w": jax.numpy.ones(4)})
            mgr.save(2, {"w": jax.numpy.ones(4)})
            mgr.wait()
    assert [r.restarts for r in runs] == [1, 0], "one kill, in the first job"
    return _events(tmp / "trace"), runs


def _named(events, name):
    return [e for e in events if e[0] == name]


def _inside(child, parent) -> bool:
    return child[1] == parent[1] and parent[2] <= child[2] \
        and child[3] <= parent[3]


@pytest.mark.parametrize("name", obs.SPANS)
def test_span_recorded_under_its_bare_name(traced, name):
    assert name.startswith(obs.PREFIX) and name in ARGS
    found = _named(traced[0], name)
    assert found, f"no {name} event in the trace"
    assert all(set(e[4]) == ARGS[name] for e in found), [e[4] for e in found]


@pytest.mark.parametrize("child,parent", [
    (obs.SOLVE_GRIDS, obs.SWAP), (obs.SOLVE_KERNEL, obs.SWAP),
    (obs.SOLVE_FETCH, obs.SWAP), (obs.SOLVE_VALIDATE, obs.SWAP),
    (obs.CKPT_RESTORE, obs.TRAIN_RESUME), (obs.CKPT_PLAN, obs.TRAIN_RESUME),
    (obs.CKPT_WAIT, obs.CKPT_SAVE)])
def test_span_nested_in_its_parent(traced, child, parent):
    parents = _named(traced[0], parent)
    children = _named(traced[0], child)
    assert any(_inside(c, p) for c in children for p in parents)
    if parent == obs.SWAP:     # the refresh is the trace's only solve
        assert all(any(_inside(c, p) for p in parents) for c in children)


def test_solve_spans_in_order(traced):
    swap, = _named(traced[0], obs.SWAP)
    inner = sorted((e for e in traced[0] if _inside(e, swap) and e != swap),
                   key=lambda e: e[2])
    assert [e[0] for e in inner] == [obs.SOLVE_GRIDS, obs.SOLVE_KERNEL,
                                     obs.SOLVE_FETCH, obs.SOLVE_VALIDATE]
    assert inner[1][4] == {"backend": "xla"}


def test_one_first_step_per_train_call(traced):
    events, runs = traced
    firsts = _named(events, obs.TRAIN_FIRST_STEP)
    assert [e[4]["step"] for e in firsts] == [0] * len(TRAIN_STEPS)
    steps = _named(events, obs.TRAIN_STEP)
    assert len(firsts) + len(steps) == sum(r.steps_run for r in runs)


def test_emergency_save_precedes_resume(traced):
    events, runs = traced
    resume, = _named(events, obs.TRAIN_RESUME)
    emergency = [e for e in _named(events, obs.CKPT_SAVE)
                 if e[4]["emergency"]]
    assert len(emergency) == runs[0].emergency_checkpoints == 1
    assert emergency[0][3] <= resume[2]
    assert resume[4]["step"] == runs[0].preempted_at[0]
