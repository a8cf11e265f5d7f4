"""Spot-market benchmark: dollar-denominated policy evaluation.

Runs ``scenarios.sweep_market`` over the default (zone x phase x vm_type)
grid under both market regimes — calm, and a capacity crunch scheduled on
the tight zone — and records what each cost policy (fixed / cheapest /
migrate) actually pays, in dollars, against the seeded OU price traces.
The DP tables are solved once per regime through
``scenarios.solve_market_tables`` and reused through ``tables=`` for every
policy/seed re-evaluation (the PR-4 whole-grid reuse contract).

``BENCH_market.json`` (repo root, see docs/bench_schemas.md) records::

    {"schema": 2, "mode": "full"|"quick", "generated_unix": ...,
     "grid": {...workload coordinates...},
     "wall_clock_s": ...,
     "expected_dollars": {regime: {policy: mean over scenario rows}},
     "crunch_vs_calm": {policy: crunch/calm expected-dollar ratio},
     "policy_vs_fixed_crunch": {policy: policy/fixed ratio on crunch rows},
     "dollar_dp_vs_makespan_dp": {regime: {"per_leaf": [...],
                                           "mean_ratio": ...}},
     "agreement": {"rows_bitexact_x64": ..., "x64_check_n_trials": ...},
     "acceptance": {"cost_aware_beats_fixed_crunch": ...,
                    "dollar_dp_beats_makespan_dp_crunch": ...},
     "rows": [...per (scenario x regime x policy x seed) row...]}

``agreement.rows_bitexact_x64`` re-runs a reduced sweep under x64 through
BOTH cost paths (the batched ``engine.accumulate_price_cost`` gather and
the serial ``market.integrate_cost_ref`` loop) and asserts every row's
dollars match bit-for-bit — the acceptance criterion that the batched cost
rows are x64 bit-identical to the serial reference.

``dollar_dp_vs_makespan_dp`` solves each regime's tables twice — once per
objective — and compares the two K policies IN THE SAME CURRENCY through
``checkpointing.evaluate_policy_dollars`` (the float64 model-based
evaluator: no Monte-Carlo noise, so the comparison is exact up to the
solver's float32 argmin slack).  ``ratio`` is dollar-DP / makespan-DP
expected dollars for a fresh full job; the acceptance flag
``dollar_dp_beats_makespan_dp_crunch`` requires ratio <= 1 + 1e-6 on every
crunch-scheduled leaf — the dollar DP may never pay MORE than the makespan
DP under the model both were given.
"""
from __future__ import annotations

import time

import jax
import numpy as np

from repro.core import market as M
from repro.core import scenarios as SC
from repro.core.policies import checkpointing as ckpt

from .common import emit, write_bench_json

REGIMES = ("calm", "crunch")
POLICIES = ("fixed", "cheapest", "migrate")


def _mean(vals):
    vals = [v for v in vals if v == v]      # drop NaN
    return sum(vals) / len(vals) if vals else float("nan")


def _aggregate(rows):
    by = {}
    for r in rows:
        by.setdefault((r["regime"], r["policy"]), []).append(
            r["expected_dollars"])
    return {reg: {pol: _mean(by.get((reg, pol), []))
                  for pol in POLICIES} for reg in REGIMES}


def run(quick: bool = False) -> dict:
    job_steps = 60 if quick else 300
    n_trials = 60 if quick else 400
    seeds = (0,) if quick else (0, 1)
    scs = SC.default_grid()
    market = M.MarketModel.for_scenarios(scs)

    t0 = time.perf_counter()
    tables = SC.solve_market_tables(scs, market, regimes=REGIMES,
                                    job_steps=job_steps)
    solve_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    rows = SC.sweep_market(scs, market=market, regimes=REGIMES,
                           policies=POLICIES, seeds=seeds,
                           job_steps=job_steps, n_trials=n_trials,
                           tables=tables)
    sweep_s = time.perf_counter() - t0

    agg = _aggregate(rows)
    crunch_vs_calm = {pol: (agg["crunch"][pol] / agg["calm"][pol]
                            if agg["calm"][pol] else float("nan"))
                      for pol in POLICIES}
    vs_fixed = {pol: (agg["crunch"][pol] / agg["crunch"]["fixed"]
                      if agg["crunch"]["fixed"] else float("nan"))
                for pol in POLICIES}

    # the acceptance criterion: on every scenario leaf that actually has a
    # crunch scheduled, the cost-aware policy pays less than fixed
    fixed_d = {(r["scenario"], r["seed"]): r["expected_dollars"]
               for r in rows if r["regime"] == "crunch"
               and r["policy"] == "fixed" and r["crunch"]}
    cheap_d = {(r["scenario"], r["seed"]): r["expected_dollars"]
               for r in rows if r["regime"] == "crunch"
               and r["policy"] == "cheapest" and r["crunch"]}
    beats = bool(fixed_d) and all(cheap_d[k] < fixed_d[k] for k in fixed_d)

    # dollar-DP vs makespan-DP: solve each regime under both objectives and
    # price BOTH K policies through the float64 model-based evaluator — same
    # currency, same model, no Monte-Carlo noise
    t0 = time.perf_counter()
    tables_d = SC.solve_market_tables(scs, market, regimes=REGIMES,
                                      job_steps=job_steps,
                                      dp_objective="dollars")
    dollar_solve_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    grid0 = market.grid()
    crunched = [float(np.float64(p.crunch_t1)) > float(np.float64(p.crunch_t0))
                for p in market.processes]
    ev_kw = dict(grid_dt=1.0 / 60.0, delta_steps=1, n_sweeps=3,
                 restart_overhead=0.0)
    ddp = {}
    crunch_ok = []
    for regime in REGIMES:
        t_launch = market.launch_time(regime)
        dists = market.crunch_dists(scs, t_launch)
        g = grid0.shift(t_launch)
        ev_mk = ckpt.evaluate_policy_dollars(
            np.asarray(tables[regime].K), dists, g, **ev_kw)
        ev_d = ckpt.evaluate_policy_dollars(
            np.asarray(tables_d[regime].K), dists, g, **ev_kw)
        leaves = []
        for s, sc in enumerate(scs):
            mk_d = float(ev_mk[s, job_steps, 0])
            dl_d = float(ev_d[s, job_steps, 0])
            on = regime == "crunch" and crunched[s]
            leaves.append(dict(
                scenario=sc.name, crunch=on, makespan_dp_dollars=mk_d,
                dollar_dp_dollars=dl_d,
                ratio=dl_d / mk_d if mk_d else float("nan")))
            if on:
                crunch_ok.append(dl_d <= mk_d * (1.0 + 1e-6))
        ddp[regime] = dict(per_leaf=leaves,
                           mean_ratio=_mean([l["ratio"] for l in leaves]))
    ddp_beats = bool(crunch_ok) and all(crunch_ok)
    dollar_eval_s = time.perf_counter() - t0

    # x64 bit-identity: batched gather vs serial reference, row for row
    x64_trials = 40 if quick else 100
    with jax.enable_x64(True):
        kw = dict(market=market, regimes=REGIMES, policies=POLICIES,
                  seeds=(0,), job_steps=min(job_steps, 120),
                  n_trials=x64_trials)
        rk = SC.sweep_market(scs, cost_path="kernel", **kw)
        rr = SC.sweep_market(scs, cost_path="reference", **kw)
    bitexact = all(
        a["expected_dollars"] == b["expected_dollars"]
        or (a["expected_dollars"] != a["expected_dollars"]
            and b["expected_dollars"] != b["expected_dollars"])
        for a, b in zip(rk, rr))

    payload = dict(
        schema=2,
        mode="quick" if quick else "full",
        generated_unix=int(time.time()),
        grid=dict(
            scenarios=[sc.name for sc in scs], regimes=list(REGIMES),
            policies=list(POLICIES), seeds=list(seeds),
            job_steps=job_steps, n_trials=n_trials,
            horizon_hours=market.horizon, price_dt=market.dt,
            market_seed=market.seed),
        wall_clock_s=dict(solve=solve_s, sweep=sweep_s,
                          dollar_solve=dollar_solve_s,
                          dollar_eval=dollar_eval_s),
        expected_dollars=agg,
        crunch_vs_calm=crunch_vs_calm,
        policy_vs_fixed_crunch=vs_fixed,
        dollar_dp_vs_makespan_dp=ddp,
        agreement=dict(rows_bitexact_x64=bitexact,
                       x64_check_n_trials=x64_trials),
        acceptance=dict(cost_aware_beats_fixed_crunch=beats,
                        dollar_dp_beats_makespan_dp_crunch=ddp_beats),
        rows=rows)
    write_bench_json("BENCH_market.json", payload, emit_as="market_json")
    emit("market_sweep", sweep_s * 1e6,
         f"cheapest/fixed_crunch={vs_fixed['cheapest']:.3f} "
         f"bitexact={bitexact} beats_fixed={beats}")
    emit("market_dollar_dp", dollar_eval_s * 1e6,
         f"crunch_ratio={ddp['crunch']['mean_ratio']:.4f} "
         f"dollar_dp_beats_makespan_dp={ddp_beats}")
    if not bitexact:
        raise AssertionError(
            "market dollars: batched gather diverged from the serial "
            "reference under x64")
    return payload
