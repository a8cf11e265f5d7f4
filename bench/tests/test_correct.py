"""``correct`` fails when it should: the control (the reference one precision
below the configuration's, put in the program's place) and runs driven
end to end on the CPU at test sizes with the timed path broken underneath.
The chip's look for a TPU is skipped; everything else is the real run."""
import json

import jax.numpy as jnp
import numpy as np
import pytest

from bench import common, run
from bench.tests import small


def _run(workload, capsys, seed=6000000001):
    assert run.main(["--workload", workload, "--seed", str(seed),
                     "--seconds", "1", "--trace", "0"],
                    spec=small.spec(workload), require_tpu=False) == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def _fails_a_limit(checks: dict, limits: dict) -> bool:
    return any(not checks[k] <= v for k, v in limits.items() if k in checks)


# -- fleet-refit ---------------------------------------------------------------

def test_refit_sound_run_is_correct(capsys):
    out = _run("fleet-refit", capsys)
    assert out["correct"] and out["failed"] == 0 and out["attempted"] > 0


def test_refit_control_fails():
    s = small.spec("fleet-refit")
    drv = common.load_module(s["generator"])
    ref = common.load_module(s["reference"])
    cfg, mix = s["config"], s["mix"]
    fits = drv.live_fits(cfg, mix, 11)
    kept = [(n, object()) for n in range(3)]
    solve = lambda F, H, c0: ref.dp_solve(
        F, H, col0=c0, grid_dt=cfg["grid_dt_hours"], j_max=mix["job_steps"],
        delta=cfg["delta_steps"], n_sweeps=mix["warm_sweeps"],
        dtype=jnp.bfloat16)
    checks = drv.compare(cfg, mix, fits, kept, ref, solve=solve)
    assert _fails_a_limit(checks, s["limits"]), checks


@pytest.mark.parametrize("fault", ["V", "K"])
def test_refit_altered_answer_fails(fault, capsys, monkeypatch):
    from repro.core.policies import checkpointing as C
    real = C.solve_batch

    def altered(*a, **kw):
        tab = real(*a, **kw)
        V, K = np.array(tab.V), np.array(tab.K)
        if fault == "V":
            V = V * 1.01
        else:
            K = np.maximum(K // 2, np.minimum(K, 1))    # halve the intervals
        return C.BatchDPTables(V=V, K=K, grid_dt=tab.grid_dt,
                               delta_steps=tab.delta_steps,
                               restart_overhead=tab.restart_overhead,
                               horizon_idx=tab.horizon_idx,
                               backend=tab.backend, objective=tab.objective)

    monkeypatch.setattr(C, "solve_batch", altered)
    assert not _run("fleet-refit", capsys)["correct"]


# -- smollm-train --------------------------------------------------------------

def test_train_sound_run_is_correct(capsys):
    out = _run("smollm-train", capsys)
    assert out["correct"] and out["failed"] == 0, out


def test_train_control_fails():
    s = small.spec("smollm-train")
    drv = common.load_module(s["generator"])
    ref = common.load_module(s["reference"])
    kw = dict(n_steps=s["mix"]["check_steps"],
              total_steps=s["mix"]["total_steps"])
    f32 = ref.train(s["config"], s["mix"]["data"], 17, **kw)
    fp8 = ref.train(s["config"], s["mix"]["data"], 17, mode="fp8", **kw)
    assert _fails_a_limit(drv.compare(fp8, f32), s["limits"])


def _broken_step(kind):
    import jax.numpy as jnp
    from repro.launch import steps

    real = steps.make_train_step

    def make(cfg, tc, **kw):
        step = real(cfg, tc, **kw)

        def broken(params, opt_state, batch):
            if kind == "half_batch":
                batch = {k: v[: v.shape[0] // 2] for k, v in batch.items()}
            if kind == "shifted_tokens":
                batch = dict(batch, tokens=jnp.roll(batch["tokens"], 1, 1))
            p, o, m = step(params, opt_state, batch)
            if kind == "unchanged":
                return params, opt_state, m
            return p, o, m

        return broken

    return make


@pytest.mark.parametrize("fault", ["unchanged", "half_batch",
                                   "shifted_tokens"])
def test_train_broken_step_fails(fault, capsys, monkeypatch):
    from repro.launch import steps
    monkeypatch.setattr(steps, "make_train_step", _broken_step(fault))
    assert not _run("smollm-train", capsys)["correct"]


def test_train_altered_restore_fails(capsys, monkeypatch):
    from repro.checkpoint import manager
    real = manager.restore_latest

    def altered(directory, template):
        out = real(directory, template)
        if out is None:
            return out
        (params, opt), step, meta = out
        params = dict(params, final_norm={"scale": params["final_norm"]
                                          ["scale"] * 1.001})
        return (params, opt), step, meta

    monkeypatch.setattr(manager, "restore_latest", altered)
    out = _run("smollm-train", capsys)
    assert out["checks"]["restore_mismatch"]["value"] > 0
    assert not out["correct"]
