"""Bring-up smoke test: drive the main paths once on a TPU chip.

    python chip_smoke.py            # one chip: every main path
    python chip_smoke.py --chips 4  # four chips: the sharded paths only

Run from the root of a checkout.  Every phase goes through the entry points a
user calls, at the sizes users run, and checks its output against the
repository's own reference (the serial paths, the ``reference`` DP backend on
the host CPU, a one-device run).  Each phase prints its results and timings
as it finishes, ending with one line that holds them as JSON.  The last line
of standard output is one JSON object naming the device, printed only when
every phase passed.  With no TPU, or outside a checkout, the script
exits non-zero before any phase runs.  Timings here are bring-up
observations, not benchmark numbers.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import shutil
import sys
import time
import traceback

ROOT = pathlib.Path(__file__).resolve().parent

# sizes users run (see README / ROADMAP cells); depth of the trainer is the
# published smollm-135m, only the sequence and batch are sized to one chip
J_SOLVE = 720                # 12 h job at 1 min steps, the Fig. 7 job
GRID_DT = 1.0 / 60.0         # 1 min grid over the 24 h deadline: T = 1441
SWEEP_TRIALS = 5000          # Fig. 7 trial count
SWEEP_CHECK_TRIALS = 200     # serial reference comparison
SERVICE_JOBS = 10_000        # 10^4 jobs x 50 lanes in one dispatch
SERVICE_CHECK_JOBS = 40
LOOP_J = 300                 # the paper's 5 h job at 1 min steps
TRAIN_STEPS = 20
TRAIN_SEQ, TRAIN_BATCH = 2048, 8     # SmolLM context; ~9.3 GB compiled
TRAIN_SIM_HOURS_PER_STEP, TRAIN_PREEMPTION_SEED = 0.1, 8  # one preemption

# the Pallas tolerance contract (docs/solver.md): V to 1e-5, and the policy
# K picks costs what the reference policy costs, to the same tolerance, for
# every job length started on a fresh VM.  Raw K agreement is printed, not
# gated: at J = 720 two f32 solvers with identical grids agree on ~97 % of
# entries, the rest being argmin ties below f32 resolution.
PALLAS_TOL = dict(rtol=1e-5, atol=1e-5)


class SmokeFailure(AssertionError):
    pass


def check(cond, msg):
    if not cond:
        raise SmokeFailure(msg)


def log(msg):
    print(msg, flush=True)


# ---------------------------------------------------------------------------
# one chip
# ---------------------------------------------------------------------------

def phase_solve(res):
    import jax
    import numpy as np
    from repro.core import market as M
    from repro.core import scenarios as SC
    from repro.core.policies import checkpointing as C

    grid = SC.default_grid()
    dists = [sc.dist() for sc in grid]
    price = M.MarketModel.for_scenarios(grid).grid()
    flat = M.PriceGrid.from_prices(np.ones((1, 1)), 24.0)   # $1/h = hours
    cpu = jax.devices("cpu")[0]
    for objective in ("makespan", "dollars"):
        kw = dict(grid_dt=GRID_DT, objective=objective,
                  price=price if objective == "dollars" else None)
        for start in ("cold", "warm"):
            warm = start == "warm"
            skw = dict(kw, n_sweeps=1 if warm else 3,
                       v_init=cold.V if warm else None)
            t0 = time.perf_counter()
            tab = C.solve_batch(dists, J_SOLVE, backend="auto", **skw)
            secs = time.perf_counter() - t0
            with jax.default_device(cpu):
                ref = C.solve_batch(dists, J_SOLVE, backend="reference", **skw)
            T = int(round(float(dists[0].L) / GRID_DT)) + 1
            check(tab.V.shape == (len(grid), J_SOLVE + 1, T), tab.V.shape)
            check(np.all(np.isfinite(tab.V)), "non-finite V")
            err = np.abs(tab.V - ref.V) - PALLAS_TOL["rtol"] * np.abs(ref.V)
            agree = float((tab.K == ref.K).mean())
            # both policies priced by the float64 evaluator, fresh VM
            ev = [C.evaluate_policy_dollars(
                k, dists, price if objective == "dollars" else flat,
                grid_dt=GRID_DT)[:, 1:, 0] for k in (tab.K, ref.K)]
            gap = float(((ev[0] - ev[1]) / np.abs(ev[1])).max())
            excess = ev[0] - ev[1] - PALLAS_TOL["rtol"] * np.abs(ev[1])
            key = f"{objective}/{start}"
            res[key] = dict(backend=tab.backend, seconds=secs,
                            max_abs_diff=float(np.abs(tab.V - ref.V).max()),
                            k_agreement=agree, policy_gap=gap)
            log(f"solve {key}: backend={tab.backend} S={len(grid)} "
                f"J={J_SOLVE} T={T} {secs:.2f}s max|dV|="
                f"{res[key]['max_abs_diff']:.3g} K agreement={agree:.6f} "
                f"policy gap={gap:.3g}")
            check(float(err.max()) <= PALLAS_TOL["atol"],
                  f"{key}: V outside the Pallas tolerance vs reference")
            check(float(excess.max()) <= PALLAS_TOL["atol"],
                  f"{key}: K costs {gap:.3g} more than the reference policy")
            if not warm:
                cold = tab
    res["backend"] = res["makespan/cold"]["backend"]


def phase_sweep(res):
    import jax
    import numpy as np
    from repro.core import scenarios as SC

    grid = SC.default_grid()
    kw = dict(job_steps=J_SOLVE, grid_dt=GRID_DT, seeds=(0,))
    t0 = time.perf_counter()
    rows = SC.sweep_checkpointing(grid, mode="batched",
                                  n_trials=SWEEP_TRIALS, **kw)
    secs = time.perf_counter() - t0
    stats = np.array([[r["makespan_mean"], r["makespan_p95"],
                       r["expected_makespan_dp"]] for r in rows])
    unfinished = float(np.mean([r["unfinished_frac"] for r in rows]))
    check(len(rows) == len(grid) * 3, len(rows))
    check(np.all(np.isfinite(stats)), "non-finite sweep rows")
    res["full"] = dict(rows=len(rows), trials=SWEEP_TRIALS, seconds=secs,
                       unfinished_frac=unfinished)
    log(f"sweep batched: {len(rows)} rows x {SWEEP_TRIALS} trials J={J_SOLVE} "
        f"{secs:.2f}s unfinished (NaN-flagged) share={unfinished:.6f}")

    # the serial reference path at a reduced trial count; its DP runs on the
    # host CPU, as the solve phase's reference does
    t0 = time.perf_counter()
    small = SC.sweep_checkpointing(grid, mode="batched",
                                   n_trials=SWEEP_CHECK_TRIALS, **kw)
    with jax.default_device(jax.devices("cpu")[0]):
        serial = SC.sweep_checkpointing(grid, mode="serial",
                                        n_trials=SWEEP_CHECK_TRIALS, **kw)
    for b, s in zip(small, serial):
        check((b["scenario"], b["policy"]) == (s["scenario"], s["policy"]),
              "row order")
        np.testing.assert_allclose(b["expected_makespan_dp"],
                                   s["expected_makespan_dp"], rtol=1e-5)
        check(b["unfinished_frac"] == s["unfinished_frac"],
              f"unfinished share {b['unfinished_frac']} vs "
              f"{s['unfinished_frac']}")
        np.testing.assert_allclose(b["makespan_mean"], s["makespan_mean"],
                                   rtol=5e-3)
    res["vs_serial"] = dict(rows=len(small), trials=SWEEP_CHECK_TRIALS,
                            seconds=time.perf_counter() - t0)
    log(f"sweep batched vs serial: {len(small)} rows x {SWEEP_CHECK_TRIALS} "
        f"trials agree")


def phase_service(res):
    import numpy as np
    from repro.core import service as SV

    grid = dict(vm_types=("n1-highcpu-32",), policies=("model", "memoryless"),
                cluster_sizes=(16, 32, 64, 128, 256), seeds=tuple(range(5)))
    t0 = time.perf_counter()
    rows = SV.run_bag_grid(mode="batched", n_jobs=SERVICE_JOBS,
                           pool_size=1 << 16, **grid)
    secs = time.perf_counter() - t0
    check(len(rows) == 50, len(rows))
    mk = np.array([r["result"].makespan for r in rows])
    vmh = np.array([r["result"].vm_hours for r in rows])
    check(np.all(np.isfinite(mk)) and np.all(mk > 0), "bad makespans")
    check(np.all(vmh >= SERVICE_JOBS * 2.0 * 0.95), "vm-hours below the bag")
    res["full"] = dict(lanes=len(rows), jobs=SERVICE_JOBS, seconds=secs,
                       jobs_per_second=len(rows) * SERVICE_JOBS / secs)
    log(f"service batched: {len(rows)} lanes x {SERVICE_JOBS} jobs in one "
        f"dispatch {secs:.2f}s")

    small = dict(grid, cluster_sizes=(4,), seeds=(0, 1))
    kw = dict(n_jobs=SERVICE_CHECK_JOBS, pool_size=4096, **small)
    batched = SV.run_bag_grid(mode="batched", **kw)
    serial = SV.run_bag_grid(mode="serial", **kw)
    for b, s in zip(batched, serial):
        rb, rs = b["result"], s["result"]
        check((rb.n_preemptions, rb.n_job_failures)
              == (rs.n_preemptions, rs.n_job_failures),
              f"event counts differ on lane {b['policy']}/{b['seed']}")
        np.testing.assert_allclose([rb.makespan, rb.vm_hours, rb.cost],
                                   [rs.makespan, rs.vm_hours, rs.cost],
                                   rtol=1e-5)
    res["vs_serial"] = dict(lanes=len(batched), jobs=SERVICE_CHECK_JOBS)
    log(f"service batched vs serial BatchService: {len(batched)} lanes x "
        f"{SERVICE_CHECK_JOBS} jobs agree")


def phase_loop(res):
    from repro.core import runtime as rt
    from repro.core import scenarios as SC

    cfg = rt.RuntimeConfig(
        base_scenarios=tuple(sc.name for sc in SC.default_grid()),
        job_steps=LOOP_J, grid_dt=GRID_DT, stream_vm_types=("n1-highcpu-2",))
    t0 = time.perf_counter()
    fr = rt.FleetRuntime(cfg)
    fr.run(400)
    # the fleet moves to the harshest type: a change-point refit and swap
    fr.stream.set_regime(("n1-highcpu-32",))
    rep = fr.run(400)
    secs = time.perf_counter() - t0
    swaps = [(s.reason, s.warm, s.solve_seconds) for s in rep.swaps]
    res.update(obs=rep.n_obs, refits=rep.n_refits, swaps=swaps,
               retries=rep.retries, degraded=rep.degraded, seconds=secs,
               backend=fr.live_tables.backend)
    log(f"loop: {rep.n_obs} obs {rep.n_refits} refits swaps={swaps} "
        f"retries={rep.retries} degraded={rep.degraded} "
        f"backend={fr.live_tables.backend} {secs:.2f}s")
    check(len(rep.swaps) >= 2, "fewer than two refit-and-swap cycles")
    check(rep.retries == {"fit": 0, "solve": 0}, rep.retries)
    check(rep.degraded is False, "degraded")


def phase_train(res, out_dir):
    import numpy as np
    from repro import configs
    from repro.configs.base import TrainConfig
    from repro.launch.train import train

    cfg = configs.get("smollm-135m")
    ckpt_dir = out_dir / "ckpt"
    shutil.rmtree(ckpt_dir, ignore_errors=True)
    tc = TrainConfig(ckpt_dir=str(ckpt_dir), ckpt_policy="dp",
                     warmup_steps=5, total_steps=TRAIN_STEPS)
    t0 = time.perf_counter()
    try:
        r = train(cfg, tc, total_steps=TRAIN_STEPS, seq_len=TRAIN_SEQ,
                  global_batch=TRAIN_BATCH, inject_preemptions=True,
                  sim_hours_per_step=TRAIN_SIM_HOURS_PER_STEP,
                  preemption_seed=TRAIN_PREEMPTION_SEED, verbose=False)
    finally:
        shutil.rmtree(ckpt_dir, ignore_errors=True)
    secs = time.perf_counter() - t0
    res.update(arch=cfg.name, layers=cfg.n_layers, d_model=cfg.d_model,
               vocab=cfg.vocab_size, seq_len=TRAIN_SEQ, batch=TRAIN_BATCH,
               steps_run=r.steps_run, restarts=r.restarts,
               checkpoints=r.checkpoints, preempted_at=r.preempted_at,
               resumed_from=r.resumed_from, first_loss=r.losses[0],
               final_loss=r.final_loss, seconds=secs)
    log(f"train {cfg.name}: {cfg.n_layers}L d_model={cfg.d_model} "
        f"vocab={cfg.vocab_size} seq={TRAIN_SEQ} batch={TRAIN_BATCH} "
        f"{r.steps_run} steps, loss {r.losses[0]:.4f} -> "
        f"{r.final_loss:.4f}, {r.checkpoints} checkpoints, preempted at "
        f"{r.preempted_at} resumed from {r.resumed_from} {secs:.2f}s")
    check(np.all(np.isfinite(r.losses)), "non-finite loss")
    check(r.restarts == 1, f"expected one injected preemption: {r.restarts}")
    check(r.resumed_from == r.preempted_at,
          "resume did not restore the step saved at the preemption")


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------

def phase_solve_sharded(res):
    import jax
    import numpy as np
    from repro import sharding
    from repro.core import scenarios as SC
    from repro.core.policies import checkpointing as C
    from repro.launch.mesh import make_host_mesh

    dists = [sc.dist() for sc in SC.default_grid()]
    one = C.solve_batch(dists, J_SOLVE, grid_dt=GRID_DT)
    mesh = make_host_mesh()
    t0 = time.perf_counter()
    with jax.set_mesh(mesh), sharding.use(mesh):
        shd = C.solve_batch(dists, J_SOLVE, grid_dt=GRID_DT)
    secs = time.perf_counter() - t0
    equal = bool(np.array_equal(one.V, shd.V) and np.array_equal(one.K, shd.K))
    res.update(backend=shd.backend, mesh=dict(mesh.shape), seconds=secs,
               tables_equal=equal)
    log(f"solve sharded over {dict(mesh.shape)}: backend={shd.backend} "
        f"S={len(dists)} J={J_SOLVE} {secs:.2f}s tables equal to one "
        f"device: {equal}")
    check(equal, "sharded tables differ from the one-device solve")


def phase_train_sharded(res, out_dir):
    import numpy as np
    from repro import configs
    from repro.configs.base import TrainConfig
    from repro.launch.mesh import make_host_mesh
    from repro.launch.train import train

    cfg = configs.get("smollm-135m")
    mesh = make_host_mesh()
    losses = {}
    for name, m in (("one", None), ("mesh", mesh)):
        ckpt_dir = out_dir / f"ckpt_{name}"
        tc = TrainConfig(ckpt_dir=str(ckpt_dir), ckpt_policy="none",
                         warmup_steps=2, total_steps=5)
        t0 = time.perf_counter()
        try:
            r = train(cfg, tc, total_steps=5, seq_len=TRAIN_SEQ,
                      global_batch=TRAIN_BATCH, mesh=m, verbose=False)
        finally:
            shutil.rmtree(ckpt_dir, ignore_errors=True)
        losses[name] = r.losses
        res[name] = dict(losses=r.losses, seconds=time.perf_counter() - t0)
    res["mesh"]["shape"] = dict(mesh.shape)
    log(f"train {cfg.name} on {dict(mesh.shape)}: losses {losses['mesh']} vs "
        f"one chip {losses['one']}")
    check(np.all(np.isfinite(losses["mesh"])), "non-finite loss")
    np.testing.assert_allclose(losses["mesh"], losses["one"], rtol=2e-2)


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: every main path on one chip; 4: the sharded "
                         "paths and their one-device comparisons only")
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT / "src"))
    try:
        from repro import compile_cache
    except ImportError as e:
        print(f"chip_smoke: run from a checkout of the repository ({e})",
              file=sys.stderr)
        return 2
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {devices[0].platform}); this "
              f"script has no CPU path", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 1
    cache = compile_cache.enable()
    device = dict(platform=devices[0].platform, kind=devices[0].device_kind,
                  count=len(devices))
    log(f"device: {device} compile cache: {cache}")

    out_dir = ROOT / "runs" / "chip_smoke"        # checkpoints; git-ignored
    out_dir.mkdir(parents=True, exist_ok=True)
    if args.chips == 1:
        phases = [("solve", phase_solve), ("sweep", phase_sweep),
                  ("service", phase_service), ("loop", phase_loop),
                  ("train", lambda r: phase_train(r, out_dir))]
    else:
        phases = [("solve_sharded", phase_solve_sharded),
                  ("train_sharded", lambda r: phase_train_sharded(r, out_dir))]
    for name, fn in phases:
        res = {}
        t0 = time.perf_counter()
        try:
            fn(res)
        except Exception:
            traceback.print_exc()
            log(f"phase {name}: FAILED ({time.perf_counter() - t0:.1f}s)")
            return 1
        res["wall_seconds"] = time.perf_counter() - t0
        log(f"phase {name}: ok ({res['wall_seconds']:.1f}s) "
            f"{json.dumps(res, default=str)}")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
