"""Scenario-sweep benchmark (paper Obs. 5 x Figs. 7/8 workloads) with the
one-kernel-vs-PR-3-batched-vs-serial sweep comparison.

Expands the default (zone x diurnal phase x VM type) scenario grid
(>= 8 scenarios) from ``repro.core.scenarios`` over both vectorized
evaluation paths:

  * the checkpointing executor — the full (scenario x policy x seed) grid
    folded to ONE deduplicated kernel dispatch
    (``sweep_checkpointing(mode="batched")``, PR 4);
  * the batch service — (scenario x policy x cluster x seed) cells with all
    scenarios' reuse grids from one folded ``engine.ReuseTables`` tensor.

Three checkpointing sweep implementations are timed against each other on
the same grid: the one-kernel fold, the PR-3 path (``mode="grouped"``:
scenario axis batched, (seed x policy) cell groups looped in Python), and
the per-scenario serial reference.  The PR-3 comparison is taken twice:
against today's ``mode="grouped"`` (same jit-cached Newton pools, isolating
the fold itself) and against the path as PR 3 shipped it (the generic
64-iteration bisection icdf invoked eagerly, re-traced and re-compiled on
every pool call — both costs PR 4 removed) — the cross-PR perf-trajectory
number.
"Combined" always means the post-solve stages combined (pool draws +
policy-table prep + executor dispatch + row assembly); the DP solve is an
identical shared ``solve_batch`` call in every non-serial mode and is timed
separately (``batch_vs_serial``, continued from schema 2).

``BENCH_scenarios.json`` (repo root, see docs/bench_schemas.md) records::

    {"schema": 4, "mode": "full"|"quick", "generated_unix": ...,
     "grid": {...},
     "checkpointing": {"workload": {...}, "wall_clock_s": ...,
                       "rows": [...one-kernel per-cell makespan stats...]},
     "service": {"workload": {...}, "wall_clock_s": ..., "rows": [...]},
     "one_kernel": {"n_cells": ...,
                    "sweep_wall_clock_s": {"batched": ..., "grouped": ...,
                                           "serial": ...},
                    "post_solve": {"one_kernel_s": ..., "grouped_s": ...,
                                   "pr3_grouped_s": ...,
                                   "combined_speedup_vs_pr3": ...,
                                   "combined_speedup_vs_grouped": ...},
                    "agreement": {"rows_max_rel_diff_vs_serial": ...,
                                  "rows_bitexact_x64": ...,
                                  "x64_check_n_trials": ...}},
     "batch_vs_serial": {"n_scenarios": ..., "solver": {...}, "pool": {...},
                         "combined_speedup": ..., "dp_values_bitexact": ...},
     "solver": {...solver_bench.measure: plain-XLA vs coarse-to-fine wall
                clock, speedup, verification + bit-agreement (schema 4)...},
     "summary": {...Obs. 5 ratios + one_kernel/solver speedups...}}

``--quick`` (or run(quick=True)) shrinks trials/steps so the module finishes
fast; the JSON records which mode produced it.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import distributions as D
from repro.core import engine as E
from repro.core import scenarios as SC
from repro.core.policies import checkpointing as ckpt

from .common import emit, write_bench_json

ZONES = ("us-east1-b", "us-central1-a")
PHASES = ("day", "night")
VM_TYPES = ("n1-highcpu-16", "n1-highcpu-32")
CKPT_POLICIES = ("dp", "young_daly", "none")
SERVICE_POLICIES = ("model", "memoryless")


class _Pr3Constrained(D.Constrained):
    """Eq. 1 with the PR-3-era sampler: the generic 64-iteration bisection
    icdf (still shipped as ``distributions._bisect_icdf``) instead of the
    bracketed-Newton inversion PR 4 gave :class:`~repro.core.distributions.
    Constrained`.  Only used to time the PR-3 batched path as it shipped."""

    def icdf(self, u):
        return D._bisect_icdf(self.cdf, u, 0.0, self.L)


_Pr3Constrained = D._dist(_Pr3Constrained)


def _pr3_dists(dist_list):
    out = []
    for d in dist_list:
        eff = d.effective() if hasattr(d, "effective") else d
        out.append(_Pr3Constrained(tau1=eff.tau1, tau2=eff.tau2, b=eff.b,
                                   A=eff.A, L=eff.L))
    return out


def _pr3_draw_lifetime_pool_batch(dists, n_trials, *, max_restarts, seed):
    """``engine.draw_lifetime_pool_batch`` as PR 3 shipped it: one shared
    seed, and — the crucial cost difference — the inverse CDF invoked
    *eagerly*, so the bisection graph was re-traced and re-compiled through
    a fresh closure on every call (PR 4 fixed this by routing all sampling
    through one jitted kernel that takes the distribution as an argument).
    Retained verbatim here so the baseline costs what the PR-3 path
    actually cost per sweep."""
    dtype = jnp.result_type(float)
    norm = [jax.tree_util.tree_map(lambda l: jnp.asarray(l, dtype), d)
            for d in dists]
    d_b = jax.tree_util.tree_map(lambda *ls: jnp.stack(ls)[:, None], *norm)
    S = len(dists)
    rng = np.random.default_rng(seed)
    u_pool = rng.uniform(size=n_trials * (max_restarts + 2))
    u_first = rng.uniform(size=n_trials)
    fl = np.array([float(d.cdf(d.L)) for d in norm])[:, None]
    L = np.array([float(d.L) for d in norm])[:, None]

    def capped(u):
        t = np.asarray(d_b.icdf(jnp.minimum(jnp.asarray(u),
                                            jnp.asarray(fl * (1.0 - 1e-6)))),
                       np.float64)
        return np.where(u >= fl, L, t)

    pool = capped(np.broadcast_to(u_pool, (S, u_pool.size)))
    first = capped(np.broadcast_to(u_first, (S, u_first.size)))
    return first, pool.reshape(S, n_trials, max_restarts + 2)


def _phase_mean(rows, phase, key, **match):
    vals = [r[key] for r in rows
            if r["phase"] == phase and not np.isnan(r[key])
            and all(r[k] == v for k, v in match.items())]
    return float(np.mean(vals)) if vals else float("nan")


def _rows_equal(a_rows, b_rows) -> bool:
    """Exact row-for-row equality, treating NaN == NaN (the engine's flag
    for unfinished trials must survive the unflattening unchanged)."""
    if len(a_rows) != len(b_rows):
        return False
    for a, b in zip(a_rows, b_rows):
        if set(a) != set(b):
            return False
        for k, va in a.items():
            vb = b[k]
            if isinstance(va, float) and isinstance(vb, float) \
                    and np.isnan(va) and np.isnan(vb):
                continue
            if va != vb:
                return False
    return True


def _rows_max_rel_diff(a_rows, b_rows, key="makespan_mean") -> float:
    rel = [abs(a[key] - b[key]) / max(abs(b[key]), 1e-9)
           for a, b in zip(a_rows, b_rows)
           if np.isfinite(a[key]) and np.isfinite(b[key])]
    return float(np.max(rel)) if rel else 0.0


def _bench_one_kernel(grid, dist_list, batch, *, policies, seeds,
                      workload) -> dict:
    """Warm re-evaluation comparison of the post-solve sweep stages (solver
    tables reused via ``tables=``): the PR-4 one-kernel fold vs the PR-3
    grouped dispatch, the latter both with today's pools and with the
    PR-3-era bisection pools."""
    wk = dict(workload)
    job_steps, n_trials = wk["job_steps"], wk["n_trials"]
    grid_dt, delta_steps = wk["grid_dt"], wk["delta_steps"]
    max_restarts = wk["max_restarts"]

    def sweep(mode):
        return lambda: SC.sweep_checkpointing(
            grid, policies=policies, seeds=seeds, mode=mode, tables=batch,
            **wk)

    run_one, run_grouped = sweep("batched"), sweep("grouped")

    # the PR-3 path as shipped: per-seed pool calls through the eagerly
    # re-compiled 64-iteration bisection icdf, one executor dispatch per
    # (seed, policy) cell group, and the same row assembly
    pr3 = _pr3_dists(dist_list)
    ptables = {p: SC._policy_tables_batch(p, batch, job_steps, grid_dt,
                                          delta_steps, dist_list)
               for p in policies}

    def run_pr3():
        # the per-call scalar evals PR-3's sweep performed stay inside the
        # timed closure, like the one-kernel sweep's own
        p_fail_fresh = [float(d.cdf(job_steps * grid_dt))
                        for d in dist_list]
        cells = {}
        for seed in seeds:
            first, pool = _pr3_draw_lifetime_pool_batch(
                pr3, n_trials, max_restarts=max_restarts, seed=seed)
            for p in policies:
                cells[seed, p] = E.simulate_makespan_batch(
                    ptables[p], job_steps, first=first, pool=pool,
                    grid_dt=grid_dt, delta_steps=delta_steps,
                    max_restarts=max_restarts, unfinished="nan",
                    return_finished=True)
        rows = []
        for s, sc in enumerate(grid):
            for seed in seeds:
                for p in policies:
                    mk, finished = cells[seed, p]
                    rows.append(SC._ckpt_row(
                        sc, p, seed, mk[s], finished[s], n_trials=n_trials,
                        job_steps=job_steps, p_fail_fresh=p_fail_fresh[s],
                        expected_makespan_dp=batch.expected_makespan(
                            s, job_steps)))
        return rows

    # interleaved median-of-5: one sample of every path per round, so a
    # noisy-neighbor phase on this shared box biases all three paths alike
    # instead of whichever happened to be timed during it
    samples = {"one": [], "grouped": [], "pr3": []}
    for fn in (run_one, run_grouped, run_pr3):
        fn()  # warm (the pr3 eager icdf recompiles per call regardless)
    for _ in range(5):
        for key, fn in (("one", run_one), ("grouped", run_grouped),
                        ("pr3", run_pr3)):
            t0 = time.perf_counter()
            fn()
            samples[key].append(time.perf_counter() - t0)
    t_one, t_grouped, t_pr3 = (float(np.median(samples[k]))
                               for k in ("one", "grouped", "pr3"))

    return {
        "n_cells": len(grid) * len(policies) * len(seeds),
        "timing": "interleaved median of 5",
        "post_solve": {
            "one_kernel_s": t_one,
            "grouped_s": t_grouped,
            "pr3_grouped_s": t_pr3,
            "combined_speedup_vs_pr3": t_pr3 / t_one,
            "combined_speedup_vs_grouped": t_grouped / t_one,
        },
    }


def run(quick: bool = False):
    grid = SC.default_grid(vm_types=VM_TYPES, phases=PHASES, zones=ZONES)
    seeds = (0,) if quick else (0, 1)

    ck_workload = dict(job_steps=180 if quick else 300,
                       n_trials=300 if quick else 4000,
                       grid_dt=1.0 / 60.0, delta_steps=1, max_restarts=64)
    job_steps, n_trials = ck_workload["job_steps"], ck_workload["n_trials"]
    dist_list = [sc.dist() for sc in grid]

    # the one-kernel sweep (the production path; includes its own solve)
    t0 = time.perf_counter()
    ck_rows = SC.sweep_checkpointing(grid, policies=CKPT_POLICIES,
                                     seeds=seeds, **ck_workload)
    t_ck = time.perf_counter() - t0
    emit(f"scenarios/ckpt_{len(ck_rows)}cells_J{job_steps}_n{n_trials}",
         t_ck / len(ck_rows) * 1e6,
         f"wall_s={t_ck:.2f};"
         f"day_dp={_phase_mean(ck_rows, 'day', 'makespan_mean', policy='dp'):.3f}h;"
         f"night_dp={_phase_mean(ck_rows, 'night', 'makespan_mean', policy='dp'):.3f}h")

    # the PR-3 grouped sweep and the serial reference, same grid
    t0 = time.perf_counter()
    rows_grouped = SC.sweep_checkpointing(grid, policies=CKPT_POLICIES,
                                          seeds=seeds, mode="grouped",
                                          **ck_workload)
    t_ck_grouped = time.perf_counter() - t0
    t0 = time.perf_counter()
    rows_serial = SC.sweep_checkpointing(grid, policies=CKPT_POLICIES,
                                         seeds=seeds, mode="serial",
                                         **ck_workload)
    t_ck_serial = time.perf_counter() - t0

    # warm post-solve comparison on reused solver tables, at the sweep's
    # own stats workload — the whole-grid re-evaluation regime the fold
    # targets, where the PR-3 path's per-sweep recompile cost is real
    batch = ckpt.solve_batch(dist_list, job_steps,
                             grid_dt=ck_workload["grid_dt"],
                             delta_steps=ck_workload["delta_steps"])
    onek_workload = dict(ck_workload, n_trials=1000 if quick else 4000)
    onek = _bench_one_kernel(grid, dist_list, batch, policies=CKPT_POLICIES,
                             seeds=seeds, workload=onek_workload)
    onek["workload"] = onek_workload
    onek["sweep_wall_clock_s"] = {"batched": t_ck, "grouped": t_ck_grouped,
                                  "serial": t_ck_serial}

    # x64 bit-exactness of the unflattening: one-kernel rows must equal the
    # serial reference rows exactly (reduced trials keep the check cheap)
    n64 = 80 if quick else 250
    wk64 = dict(ck_workload, n_trials=n64)
    with jax.enable_x64(True):
        rows64_b = SC.sweep_checkpointing(grid, policies=CKPT_POLICIES,
                                          seeds=seeds, **wk64)
        rows64_s = SC.sweep_checkpointing(grid, policies=CKPT_POLICIES,
                                          seeds=seeds, mode="serial", **wk64)
    onek["agreement"] = {
        "rows_max_rel_diff_vs_serial": _rows_max_rel_diff(ck_rows,
                                                          rows_serial),
        "rows_max_rel_diff_grouped_vs_serial":
            _rows_max_rel_diff(rows_grouped, rows_serial),
        "rows_bitexact_x64": _rows_equal(rows64_b, rows64_s),
        "x64_check_n_trials": n64,
    }
    ps = onek["post_solve"]
    emit(f"scenarios/one_kernel_B{onek['n_cells']}",
         ps["one_kernel_s"] / onek["n_cells"] * 1e6,
         f"vs_pr3={ps['combined_speedup_vs_pr3']:.2f}x;"
         f"vs_grouped={ps['combined_speedup_vs_grouped']:.2f}x;"
         f"rows_bitexact_x64={onek['agreement']['rows_bitexact_x64']};"
         f"rows_maxrel={onek['agreement']['rows_max_rel_diff_vs_serial']:.1e}")

    # solver/pool batched-vs-serial continuity block (schema 2 lineage)
    bvs = _bench_batch_vs_serial(
        dist_list, job_steps=job_steps, n_trials=n_trials,
        grid_dt=ck_workload["grid_dt"],
        max_restarts=ck_workload["max_restarts"], seeds=seeds)
    emit(f"scenarios/batch_vs_serial_S{len(grid)}",
         bvs["solver"]["batched_s"] / len(grid) * 1e6,
         f"solver={bvs['solver']['speedup']:.2f}x;"
         f"pool={bvs['pool']['speedup']:.2f}x;"
         f"combined={bvs['combined_speedup']:.2f}x;"
         f"dp_bitexact={bvs['dp_values_bitexact']}")

    # solver backend block (schema 4): plain XLA vs coarse-to-fine at this
    # sweep's own workload — the cross-PR solver wall-clock trajectory
    from . import solver_bench
    solver = solver_bench.measure(dist_list, job_steps=job_steps,
                                  grid_dt=ck_workload["grid_dt"])
    emit(f"scenarios/solver_ctf_S{len(grid)}",
         solver["refine_s"] / len(grid) * 1e6,
         f"xla_s={solver['xla_s']:.2f};refine_s={solver['refine_s']:.2f};"
         f"speedup={solver['speedup']:.2f}x;"
         f"bitexact={solver['bit_identical_to_plain']}")

    n_jobs = 20 if quick else 60
    cluster_sizes = (8,) if quick else (16,)
    t0 = time.perf_counter()
    sv_rows = SC.sweep_service(grid, policies=SERVICE_POLICIES,
                               cluster_sizes=cluster_sizes, seeds=seeds,
                               n_jobs=n_jobs, job_hours=2.0)
    t_sv = time.perf_counter() - t0
    red = float(np.mean([r["cost_reduction"] for r in sv_rows
                         if r["policy"] == "model"]))
    emit(f"scenarios/service_{len(sv_rows)}cells_n{n_jobs}",
         t_sv / len(sv_rows) * 1e6,
         f"wall_s={t_sv:.2f};reduction={red:.2f}x")

    day_mk = _phase_mean(ck_rows, "day", "makespan_mean", policy="dp")
    night_mk = _phase_mean(ck_rows, "night", "makespan_mean", policy="dp")
    day_pf = _phase_mean(ck_rows, "day", "p_fail_fresh", policy="dp")
    night_pf = _phase_mean(ck_rows, "night", "p_fail_fresh", policy="dp")
    day_fr = _phase_mean(sv_rows, "day", "job_failure_rate", policy="model")
    night_fr = _phase_mean(sv_rows, "night", "job_failure_rate",
                           policy="model")
    payload = {
        "schema": 4,
        "mode": "quick" if quick else "full",
        "generated_unix": time.time(),
        "grid": {"zones": list(ZONES), "phases": list(PHASES),
                 "vm_types": list(VM_TYPES),
                 "checkpoint_policies": list(CKPT_POLICIES),
                 "service_policies": list(SERVICE_POLICIES),
                 "seeds": list(seeds)},
        "checkpointing": {
            "workload": dict(ck_workload),
            "wall_clock_s": t_ck, "rows": ck_rows},
        "service": {
            "workload": {"n_jobs": n_jobs, "job_hours": 2.0,
                         "cluster_sizes": list(cluster_sizes)},
            "wall_clock_s": t_sv, "rows": sv_rows},
        "one_kernel": onek,
        "batch_vs_serial": bvs,
        "solver": solver,
        "summary": {
            # Obs. 5 headline: night launches preempt less (< 1).  Makespan
            # need not follow — night failures arrive later in a VM's life,
            # so each failed attempt wastes more wall-clock; both ratios are
            # recorded so the trade-off is visible across PRs.
            "night_over_day_fail_prob": night_pf / day_pf,
            "night_over_day_makespan": night_mk / day_mk,
            "night_over_day_failure_rate":
                night_fr / day_fr if day_fr else float("nan"),
            "cost_reduction_mean": red,
            "one_kernel_combined_speedup":
                ps["combined_speedup_vs_pr3"],
            "batched_combined_speedup": bvs["combined_speedup"],
            "solver_ctf_speedup": solver["speedup"]},
    }
    write_bench_json("BENCH_scenarios.json", payload, emit_as="scenarios/json")


def _bench_batch_vs_serial(dist_list, *, job_steps, n_trials, grid_dt,
                           max_restarts, seeds) -> dict:
    """Warm-timed comparison of the per-scenario setup work the batched
    scenario axis replaced in PR 3: the DP solves and the lifetime-pool
    draws (schema-2 continuity block)."""
    S = len(dist_list)
    # warm both compile caches at the measured shapes
    ckpt.solve(dist_list[0], job_steps, grid_dt=grid_dt)
    ckpt.solve_batch(dist_list, job_steps, grid_dt=grid_dt)
    E.draw_lifetime_pool(ckpt.model_lifetimes_fn(dist_list[0]), n_trials,
                         max_restarts=max_restarts, seed=seeds[0])
    E.draw_lifetime_pool_batch(dist_list, n_trials,
                               max_restarts=max_restarts, seed=seeds[0])

    t0 = time.perf_counter()
    serial_tabs = [ckpt.solve(d, job_steps, grid_dt=grid_dt)
                   for d in dist_list]
    t_solver_serial = time.perf_counter() - t0
    t0 = time.perf_counter()
    batch_tabs = ckpt.solve_batch(dist_list, job_steps, grid_dt=grid_dt)
    t_solver_batched = time.perf_counter() - t0
    bitexact = all(
        np.array_equal(serial_tabs[s].V, batch_tabs.V[s])
        and np.array_equal(serial_tabs[s].K, batch_tabs.K[s])
        for s in range(S))

    t0 = time.perf_counter()
    for seed in seeds:
        for d in dist_list:
            E.draw_lifetime_pool(ckpt.model_lifetimes_fn(d), n_trials,
                                 max_restarts=max_restarts, seed=seed)
    t_pool_serial = time.perf_counter() - t0
    t0 = time.perf_counter()
    for seed in seeds:
        E.draw_lifetime_pool_batch(dist_list, n_trials,
                                   max_restarts=max_restarts, seed=seed)
    t_pool_batched = time.perf_counter() - t0

    return {
        "n_scenarios": S,
        "solver": {"serial_s": t_solver_serial,
                   "batched_s": t_solver_batched,
                   "speedup": t_solver_serial / t_solver_batched},
        "pool": {"serial_s": t_pool_serial, "batched_s": t_pool_batched,
                 "speedup": t_pool_serial / t_pool_batched},
        "combined_speedup": (t_solver_serial + t_pool_serial)
                            / (t_solver_batched + t_pool_batched),
        "dp_values_bitexact": bool(bitexact),
    }


if __name__ == "__main__":
    import sys

    run(quick="--quick" in sys.argv)
