"""Scenario registry + vectorized sweep runner on top of the engine.

PR 1 removed the simulation bottleneck; this module turns the single static
per-VM-type evaluation into *scenario diversity*: a scenario names a market
condition — zone x diurnal launch phase x VM type (paper Obs. 5 plus the
ZONE_PARAMS capacity-pressure regimes), with optional parameter overrides —
and resolves to a :class:`~repro.core.distributions.DiurnalConstrained`
model.  The sweep runners expand

    (scenario x policy x seed)                 checkpointing executor grids
    (scenario x policy x cluster_size x seed)  batch-service grids

over the batched engine entry points.

Sweep execution modes and their equivalence contract
----------------------------------------------------
:func:`sweep_checkpointing` runs the same grid two ways, differing in how
much of it is folded into the leading batch axis (the engine's leading-axis
convention):

  * ``mode="batched"`` (default, PR 4) — the ONE-KERNEL path: the whole
    (scenario x policy x seed) grid is flattened to a cell axis
    ``B = S*P*R``; one ``checkpointing.solve_batch`` call solves every DP,
    one ``engine.draw_lifetime_pool_batch`` call draws every (scenario,
    seed) pool from per-cell seeds, policy tables of differing provenance
    are stacked by ``engine.stack_policy_tables``, and a SINGLE
    scenario-batched executor dispatch produces every cell's makespans,
    which are then unflattened back to labeled rows.
  * ``mode="serial"`` — the per-scenario reference path (one DP solve + one
    numpy pool round-trip + one executor call per cell group, scenario by
    scenario).  This is the semantic ground truth.

Both modes emit identical row order and schema.  Equivalence contract
(enforced by ``tests/test_batched.py`` / ``tests/test_scenarios.py``): DP
tables and derived scalars (``expected_makespan_dp``, ``p_fail_fresh``) are
bit-exact across modes at any dtype; with x64 enabled the makespan
statistics are bit-identical row-for-row too, because each folded lane then
reproduces the serial cell's IEEE operations exactly (see the engine module
docstring).  In default float32 mode rows agree to the pool's float32
inverse-CDF rounding, far below Monte-Carlo noise.  Truncated trials are
NaN-flagged by the engine and excluded from row statistics, never silently
averaged in; ``unfinished_frac`` records them per row in every mode.

Adding a scenario is one :func:`register` call (see ROADMAP "Scenario
sweeps").
"""
from __future__ import annotations

import dataclasses
import itertools
from typing import Dict, Iterable, Mapping, Optional, Sequence

import numpy as np

from . import distributions as dists
from . import engine
from . import service as service_mod
from .policies import checkpointing as ckpt
from .policies import young_daly as yd

__all__ = [
    "Scenario", "register", "get", "names", "default_grid",
    "sweep_checkpointing", "sweep_service", "sweep_market",
    "solve_market_tables", "PHASE_CLOCKS", "ZONE_PARAMS",
]

# Wall-clock launch hour per diurnal phase label.  "day" is the busiest
# launch hour (the DiurnalConstrained peak), "night" the quietest, 12 h
# away; "shoulder" sits at the zero crossing (= the static fit).
PHASE_CLOCKS: Dict[str, float] = {"day": 20.0, "night": 8.0, "shoulder": 14.0}

# Per-zone parameter regimes (CloudSim-Plus-style market diversity): zones
# differ in capacity pressure, scaling the Eq. 1 initial-phase severity.
# ``A_scale`` multiplies the type's fitted A (more pressure -> more
# preemptions), ``tau1_scale`` the initial-phase time constant (more
# pressure -> faster decay onto the young-VM wall).  The paper's fits are
# from us-east1-b, which is therefore the identity zone.
ZONE_PARAMS: Dict[str, Dict[str, float]] = {
    "us-east1-b": dict(A_scale=1.0, tau1_scale=1.0),
    "us-central1-a": dict(A_scale=1.08, tau1_scale=0.85),   # tighter market
    "europe-west1-d": dict(A_scale=0.92, tau1_scale=1.20),  # slacker market
}


@dataclasses.dataclass(frozen=True)
class Scenario:
    """One named market condition the policies are evaluated against."""

    name: str
    vm_type: str = "n1-highcpu-16"
    phase: str = "shoulder"            # diurnal label (see PHASE_CLOCKS)
    zone: str = "us-east1-b"           # parameter regime (see ZONE_PARAMS)
    launch_clock: Optional[float] = None  # overrides the phase's clock
    dist_kwargs: Mapping = dataclasses.field(default_factory=dict)
    description: str = ""
    # a live fitted distribution (e.g. the closed-loop runtime's latest
    # Eq. 1 refit) served verbatim instead of the catalog resolution —
    # lets an online model participate in sweeps alongside catalog regimes
    dist_override: Optional[object] = None

    @property
    def clock(self) -> float:
        if self.launch_clock is not None:
            return float(self.launch_clock)
        return PHASE_CLOCKS[self.phase]

    def dist(self):
        """The scenario's resolved lifetime model (full pytree contract, so
        the DP solver, ReuseTable and lifetime pools work unchanged).  The
        zone's capacity-pressure scaling is applied to the type's base
        Eq. 1 fit before any explicit ``dist_kwargs`` overrides; a
        ``dist_override`` (a live fitted model) short-circuits all of it."""
        if self.dist_override is not None:
            return self.dist_override
        zone = ZONE_PARAMS[self.zone]
        base = dists.VM_TYPE_PARAMS[self.vm_type]
        kw = dict(A=base["A"] * zone["A_scale"],
                  tau1=base["tau1"] * zone["tau1_scale"])
        kw.update(self.dist_kwargs)
        return dists.diurnal_for(self.vm_type, self.clock, **kw)

    def coords(self) -> dict:
        """Grid coordinates every sweep row is tagged with."""
        return dict(scenario=self.name, vm_type=self.vm_type,
                    phase=self.phase, zone=self.zone, launch_clock=self.clock)


_REGISTRY: Dict[str, Scenario] = {}


def register(scenario: Scenario, *, overwrite: bool = False,
             replace: Optional[bool] = None) -> Scenario:
    """Add a scenario to the global registry.  Re-registering a taken name
    raises unless ``overwrite=True`` — a silent clobber would invalidate
    any grid that already resolved the old definition.  ``replace`` is the
    deprecated pre-PR-3 spelling of the same flag."""
    if replace is not None:
        overwrite = replace
    if not overwrite and scenario.name in _REGISTRY:
        raise ValueError(f"scenario {scenario.name!r} already registered "
                         f"(pass overwrite=True to replace it)")
    _REGISTRY[scenario.name] = scenario
    return scenario


def get(name: str) -> Scenario:
    return _REGISTRY[name]


def names() -> list:
    return sorted(_REGISTRY)


def default_grid(vm_types: Sequence[str] = ("n1-highcpu-16", "n1-highcpu-32"),
                 phases: Sequence[str] = ("day", "night"),
                 zones: Sequence[str] = ("us-east1-b", "us-central1-a"),
                 ) -> list:
    """The (zone x diurnal phase x vm_type) product as a list of scenarios
    (shared with the registry; repeated calls return the same objects).
    The default product is 2 x 2 x 2 = 8 scenarios."""
    out = []
    for zone, phase, vm_type in itertools.product(zones, phases, vm_types):
        name = f"{zone}/{phase}/{vm_type}"
        if name not in _REGISTRY:
            register(Scenario(
                name=name, vm_type=vm_type, phase=phase, zone=zone,
                description=f"{vm_type} in {zone} launched at the {phase} "
                            f"clock ({PHASE_CLOCKS[phase]:.0f}h)"))
        out.append(_REGISTRY[name])
    return out


def _resolve(scenarios) -> list:
    return [get(s) if isinstance(s, str) else s for s in scenarios]


# ---------------------------------------------------------------------------
# checkpointing-executor sweep
# ---------------------------------------------------------------------------

_CKPT_POLICY_BUILDERS = ("dp", "young_daly", "none")


def _policy_tables(policy: str, tables: ckpt.DPTables, job_steps: int,
                   grid_dt: float, delta_steps: int, dist):
    if policy == "dp":
        return engine.dp_policy_table(tables)
    if policy == "young_daly":
        # paper Fig. 7 baseline setup, per scenario: the MTTF implied by
        # THIS distribution's initial failure rate (a day-phase launch has
        # a faster initial phase and therefore a shorter YD interval), with
        # the sweep's actual checkpoint-write cost delta
        tau = float(yd.interval(delta_steps * grid_dt,
                                yd.mttf_from_initial_rate(dist)))
        tau_steps = max(1, int(round(tau / grid_dt)))
        return engine.young_daly_policy_table(tau_steps, job_steps)
    if policy == "none":
        return engine.no_checkpoint_policy_table(job_steps)
    raise ValueError(f"unknown checkpointing policy {policy!r}; "
                     f"choose from {_CKPT_POLICY_BUILDERS}")


def _policy_tables_batch(policy: str, batch: "ckpt.BatchDPTables",
                         job_steps: int, grid_dt: float, delta_steps: int,
                         dist_list):
    """Scenario-stacked policy tables for the batched executor: (S, ...) for
    per-scenario policies, a plain 2-D table for scenario-independent ones
    (the executor broadcasts it)."""
    if policy == "dp":
        return np.asarray(batch.K, np.int32)
    if policy == "young_daly":
        # per scenario, as in the serial path: the YD interval implied by
        # THIS scenario's initial failure rate
        tabs = []
        for dist in dist_list:
            tau = float(yd.interval(delta_steps * grid_dt,
                                    yd.mttf_from_initial_rate(dist)))
            tau_steps = max(1, int(round(tau / grid_dt)))
            tabs.append(engine.young_daly_policy_table(tau_steps, job_steps))
        return np.stack(tabs)
    if policy == "none":
        return engine.no_checkpoint_policy_table(job_steps)   # shared 2-D
    raise ValueError(f"unknown checkpointing policy {policy!r}; "
                     f"choose from {_CKPT_POLICY_BUILDERS}")


def _ckpt_row(sc, policy, seed, mk, finished, *, n_trials, job_steps,
              p_fail_fresh, expected_makespan_dp):
    ok = mk[finished]
    return dict(
        sc.coords(), policy=policy, seed=seed,
        n_trials=n_trials, job_steps=job_steps,
        p_fail_fresh=p_fail_fresh,
        expected_makespan_dp=expected_makespan_dp,
        makespan_mean=float(ok.mean()) if ok.size else float("nan"),
        makespan_p50=float(np.median(ok)) if ok.size else float("nan"),
        makespan_p95=float(np.percentile(ok, 95)) if ok.size else float("nan"),
        unfinished_frac=float(1.0 - finished.mean()))


def sweep_checkpointing(scenarios: Iterable, *,
                        policies: Sequence[str] = ("dp", "young_daly", "none"),
                        seeds: Sequence[int] = (0,), job_steps: int = 300,
                        n_trials: int = 1000, grid_dt: float = 1.0 / 60.0,
                        delta_steps: int = 1, max_restarts: int = 64,
                        restart_overhead: float = 0.0,
                        n_sweeps: int = 3, mode: str = "batched",
                        tables: Optional["ckpt.BatchDPTables"] = None,
                        solver_backend: str = "auto") -> list:
    """Expand (scenario x policy x seed) over the vectorized executor.

    ``mode="batched"`` (default) folds the WHOLE grid into the engine's
    leading batch axis and dispatches one compiled executor call for all
    ``B = S*P*R`` cells: one ``checkpointing.solve_batch`` DP call, one
    ``engine.draw_lifetime_pool_batch`` call drawing every (scenario, seed)
    pool from per-cell seeds, one ``engine.stack_policy_tables`` stack of
    the per-cell policy tables, one kernel dispatch, then unflattening back
    to labeled rows.  Cell ``b`` of the flat axis is the row-order index
    ``(s*R + r)*P + p`` (scenario outer, seed, policy inner), and its pool
    is shared across the P policies of the same (scenario, seed) — exactly
    the sharing the serial path expresses with its nested loops.

    ``mode="serial"`` is the per-scenario reference path (one solve + one
    numpy pool round-trip per scenario): the semantic ground truth.

    Row order and schema are identical in both modes; the equivalence
    contract between them (bit-exact DP scalars always; bit-identical rows
    under x64; float32-rounding-close otherwise) is stated in the module
    docstring and enforced by the test suite.  Truncated trials are
    NaN-flagged by the engine, never silently averaged in.

    ``tables`` (batched mode) reuses a previously solved
    ``checkpointing.BatchDPTables`` for this scenario list, skipping the DP
    solve entirely — the whole-grid *re-evaluation* path (fresh seeds,
    trial counts or policies against fixed market models) then costs only
    the pool draw and the single executor dispatch.

    ``solver_backend`` passes straight through to
    ``checkpointing.solve_batch`` (batched mode; the serial reference path
    always runs the reference kernel) — see ``docs/solver.md``.
    """
    if mode not in ("batched", "serial"):
        raise ValueError(f"mode must be 'batched' or 'serial', got {mode!r}")
    scs = _resolve(scenarios)          # once: scenarios may be a generator
    if tables is not None:
        if mode == "serial":
            raise ValueError("tables= reuse is for the batched mode; the "
                             "serial reference path always re-solves")
        if len(tables) != len(scs) or tables.K.shape[1] != job_steps + 1:
            raise ValueError(
                f"tables has {len(tables)} scenarios x j_max "
                f"{tables.K.shape[1] - 1}; this sweep needs "
                f"{len(scs)} x {job_steps}")
        if tables.delta_steps != delta_steps \
                or abs(tables.grid_dt - grid_dt) > 1e-12 \
                or tables.restart_overhead != restart_overhead:
            raise ValueError("tables was solved for a different "
                             "(grid_dt, delta_steps, restart_overhead) "
                             "workload")
    rows = []
    if mode == "serial":
        for sc in scs:
            dist = sc.dist()
            tables = ckpt.solve(dist, job_steps, grid_dt=grid_dt,
                                delta_steps=delta_steps, n_sweeps=n_sweeps,
                                restart_overhead=restart_overhead)
            ptables = {p: _policy_tables(p, tables, job_steps, grid_dt,
                                         delta_steps, dist)
                       for p in policies}
            lifetimes_fn = ckpt.model_lifetimes_fn(dist)
            # single-attempt failure probability of the whole job on a fresh
            # VM — the scenario's Obs. 5 "how gentle is this phase" scalar
            p_fail_fresh = float(dist.cdf(job_steps * grid_dt))
            for seed in seeds:
                first, pool = engine.draw_lifetime_pool(
                    lifetimes_fn, n_trials, max_restarts=max_restarts,
                    seed=seed)
                for policy in policies:
                    mk, finished = engine.simulate_makespan_batch(
                        ptables[policy], job_steps, first=first, pool=pool,
                        grid_dt=grid_dt, delta_steps=delta_steps,
                        restart_overhead=restart_overhead,
                        max_restarts=max_restarts, unfinished="nan",
                        return_finished=True)
                    rows.append(_ckpt_row(
                        sc, policy, seed, mk, finished, n_trials=n_trials,
                        job_steps=job_steps, p_fail_fresh=p_fail_fresh,
                        expected_makespan_dp=tables.expected_makespan(job_steps)))
        return rows

    dist_list = [sc.dist() for sc in scs]
    batch = tables if tables is not None else ckpt.solve_batch(
        dist_list, job_steps, grid_dt=grid_dt, delta_steps=delta_steps,
        n_sweeps=n_sweeps, restart_overhead=restart_overhead,
        backend=solver_backend)
    ptables = {p: _policy_tables_batch(p, batch, job_steps, grid_dt,
                                       delta_steps, dist_list)
               for p in policies}
    p_fail_fresh = [float(d.cdf(job_steps * grid_dt)) for d in dist_list]
    S, P, R = len(scs), len(policies), len(seeds)

    # one-kernel fold: flat cell axis b = (s*R + r)*P + p, i.e. row order.
    # Pools depend on (scenario, seed) only, so the S*R unique pools are
    # drawn in one per-cell-seeded call; tables depend on (policy,
    # scenario) only.  Both stay deduplicated on device — the executor
    # fans them out to the B lanes through table_index/pool_index gathers
    # (see the engine's "deduplicated fold" notes), so the gathers read the
    # unique tables and pools instead of B replicated copies.
    first_sr, pool_sr = engine.draw_lifetime_pool_batch(
        [d for d in dist_list for _ in seeds], n_trials,
        max_restarts=max_restarts,
        seed=[seed for _ in dist_list for seed in seeds])
    uniq, keys = [], {}
    table_ix = np.empty(S * R * P, np.int32)
    pool_ix = np.repeat(np.arange(S * R), P)
    for b, (s, _seed, policy) in enumerate(
            itertools.product(range(S), seeds, policies)):
        key = (policy, s if np.asarray(ptables[policy]).ndim == 3 else -1)
        if key not in keys:
            keys[key] = len(uniq)
            uniq.append(ptables[policy][s] if key[1] >= 0
                        else ptables[policy])
        table_ix[b] = keys[key]
    table_u = engine.stack_policy_tables(uniq, t_axis=batch.K.shape[2])
    mk_b, fin_b = engine.simulate_makespan_batch(
        table_u, job_steps, first=first_sr[pool_ix], pool=pool_sr,
        grid_dt=grid_dt, delta_steps=delta_steps,
        restart_overhead=restart_overhead, max_restarts=max_restarts,
        unfinished="nan", return_finished=True,
        table_index=table_ix, pool_index=pool_ix)
    for b, (s, seed, policy) in enumerate(
            itertools.product(range(S), seeds, policies)):
        rows.append(_ckpt_row(
            scs[s], policy, seed, mk_b[b], fin_b[b], n_trials=n_trials,
            job_steps=job_steps, p_fail_fresh=p_fail_fresh[s],
            expected_makespan_dp=batch.expected_makespan(s, job_steps)))
    return rows


# ---------------------------------------------------------------------------
# batch-service sweep
# ---------------------------------------------------------------------------

def sweep_service(scenarios: Iterable, *,
                  policies: Sequence[str] = ("model", "memoryless"),
                  cluster_sizes: Sequence[int] = (16,),
                  seeds: Sequence[int] = (0,), n_jobs: int = 40,
                  job_hours: float = 2.0, jitter: float = 0.1,
                  mode: str = "serial", pool_size: int = 4096,
                  deadline_hours=None, deflate_factor: float = 0.5,
                  **kw) -> list:
    """Expand (scenario x policy x cluster_size x seed) over the batch
    service.  The model policy's reuse grids for ALL scenarios are folded
    into one :class:`engine.ReuseTables` tensor up front — a single vmapped
    grid call, one backing allocation shared by every cluster size (the bag
    lengths depend only on the seeds, so every scenario shares one
    remaining-work axis).

    ``mode="serial"`` (ground truth) routes each scenario's cell group
    through ``service.run_bag_grid`` with its shared view of that tensor,
    keeping the event loops numpy-only; ``mode="batched"`` folds EVERY
    (scenario x policy x cluster_size x seed) cell into ONE jitted
    ``service_kernel`` dispatch — rows bit-identical to serial under x64
    on the shared per-seed lifetime streams — and additionally supports
    ``deadline_hours`` admission control and ``"+deflate"`` policies.
    Returns flat dict rows with the headline service metrics.
    """
    from . import service_kernel
    if mode not in ("serial", "batched"):
        raise ValueError(f"unknown mode {mode!r}")
    if mode == "serial" and deadline_hours is not None:
        raise ValueError("deadline admission control needs mode='batched'")
    scs = _resolve(scenarios)
    policies = tuple(policies)
    dist_list = [sc.dist() for sc in scs]
    bases = [service_kernel.split_policy(p)[0] for p in policies]
    tables = None
    if "model" in bases and kw.get("vectorized_reuse", True):
        tables = engine.ReuseTables(
            dist_list,
            service_mod.grid_reuse_values(dist_list[0], seeds=tuple(seeds),
                                          n_jobs=n_jobs, job_hours=job_hours,
                                          jitter=jitter, **kw))

    def _row(sc, cell):
        r = cell["result"]
        return dict(
            sc.coords(), policy=cell["policy"],
            cluster_size=cell["cluster_size"], seed=cell["seed"],
            n_jobs=n_jobs, job_hours=job_hours,
            makespan=r.makespan, vm_hours=r.vm_hours, cost=r.cost,
            on_demand_cost=r.on_demand_cost,
            cost_reduction=r.cost_reduction,
            n_preemptions=r.n_preemptions,
            n_job_failures=r.n_job_failures,
            n_deflations=r.n_deflations, n_rejected=r.n_rejected,
            job_failure_rate=r.n_job_failures / max(n_jobs, 1))

    if mode == "batched":
        lengths = {s: service_mod._bag_lengths(n_jobs, job_hours, jitter, s)
                   for s in seeds}
        cells = [dict(dist_index=si, vm_type=sc.vm_type, policy=policy,
                      cluster_size=cs, seed=seed)
                 for si, sc in enumerate(scs)
                 for policy, cs, seed in itertools.product(
                     policies, tuple(cluster_sizes), tuple(seeds))]
        grid = service_kernel.run_cells_batched(
            cells=cells, dists=dist_list, lengths_by_seed=lengths,
            reuse_tables=tables, pool_size=pool_size,
            deadline_hours=deadline_hours, deflate_factor=deflate_factor,
            checkpointing=kw.get("checkpointing", False),
            ckpt_interval=kw.get("ckpt_interval", 0.5),
            ckpt_cost=kw.get("ckpt_cost", 1.0 / 60.0),
            return_jobs=False)
        per_sc = len(grid) // max(len(scs), 1)
        return [_row(scs[i // per_sc], cell) for i, cell in enumerate(grid)]

    rows = []
    for si, sc in enumerate(scs):
        dist = dist_list[si]
        grid = service_mod.run_bag_grid(
            vm_types=(sc.vm_type,), policies=policies,
            cluster_sizes=tuple(cluster_sizes), seeds=tuple(seeds),
            n_jobs=n_jobs, job_hours=job_hours, jitter=jitter,
            dist_for=lambda _vm_type: dist, pool_size=pool_size,
            reuse_table=tables.view(si) if tables is not None else None,
            **kw)
        rows.extend(_row(sc, cell) for cell in grid)
    return rows


# ---------------------------------------------------------------------------
# spot-market sweep (dollar-denominated policy evaluation)
# ---------------------------------------------------------------------------

_MARKET_POLICIES = ("fixed", "cheapest", "migrate")


def solve_market_tables(scenarios: Iterable, market, *,
                        regimes: Sequence[str] = ("calm", "crunch"),
                        job_steps: int = 300, grid_dt: float = 1.0 / 60.0,
                        delta_steps: int = 1, n_sweeps: int = 3,
                        restart_overhead: float = 0.0,
                        solver_backend: str = "auto",
                        dp_objective: str = "makespan") -> dict:
    """Solve one ``BatchDPTables`` per market regime, for ``tables=`` reuse.

    Each regime's tables are solved against the CRUNCH-COUPLED Eq. 1 models
    at that regime's launch time (``market.crunch_dists``): calm tables
    equal the plain per-scenario tables (zero crunch intensity passes the
    base fit through unchanged), crunch tables price in the boosted early
    hazard.  Feed the result to :func:`sweep_market` ``tables=`` to
    re-evaluate fresh seeds/trial counts/policies without re-solving — the
    same whole-grid reuse contract as ``sweep_checkpointing``.

    ``dp_objective="dollars"`` solves each regime under the dollar
    objective against the market's own price grid as seen from that
    regime's launch time (``market.grid().shift(launch_time)``) — V becomes
    expected dollars-to-completion and K stretches checkpoint intervals
    through priced windows.
    """
    scs = _resolve(scenarios)
    grid0 = market.grid() if dp_objective == "dollars" else None
    out = {}
    for regime in regimes:
        t0 = market.launch_time(regime)
        dist_list = market.crunch_dists(scs, t0)
        price = None if grid0 is None else grid0.shift(t0)
        out[regime] = ckpt.solve_batch(
            dist_list, job_steps, grid_dt=grid_dt, delta_steps=delta_steps,
            n_sweeps=n_sweeps, restart_overhead=restart_overhead,
            backend=solver_backend, objective=dp_objective, price=price)
    return out


def _market_row(sc, regime, policy, seed, chosen, launch_price, dollars,
                mk_row, fin_row, *, n_trials, job_steps, crunch):
    ok = np.asarray(fin_row, bool)
    d_ok = np.asarray(dollars)[ok]
    m_ok = np.asarray(mk_row)[ok]
    return dict(
        sc.coords(), regime=regime, policy=policy, seed=seed,
        chosen=chosen, launch_price=float(launch_price),
        n_trials=n_trials, job_steps=job_steps, crunch=bool(crunch),
        expected_dollars=float(d_ok.mean()) if d_ok.size else float("nan"),
        dollars_p50=float(np.median(d_ok)) if d_ok.size else float("nan"),
        makespan_mean=float(m_ok.mean()) if m_ok.size else float("nan"),
        unfinished_frac=float(1.0 - ok.mean()))


def sweep_market(scenarios: Iterable, *, market=None,
                 regimes: Sequence[str] = ("calm", "crunch"),
                 policies: Sequence[str] = _MARKET_POLICIES,
                 seeds: Sequence[int] = (0,), job_steps: int = 300,
                 n_trials: int = 400, grid_dt: float = 1.0 / 60.0,
                 delta_steps: int = 1, max_restarts: int = 64,
                 restart_overhead: float = 0.0, n_sweeps: int = 3,
                 tables: Optional[dict] = None,
                 feasible_slack: float = 1.25,
                 migrate_threshold: float = 1.15,
                 migrate_overhead_hours: float = 2.0 / 60.0,
                 cost_path: str = "kernel",
                 solver_backend: str = "auto",
                 dp_objective: str = "makespan") -> list:
    """Expand (scenario x regime x cost-policy x seed) in dollars.

    The market layer on the checkpointing sweep: each regime launches the
    whole scenario grid at ``market.launch_time(regime)`` against the
    crunch-coupled Eq. 1 models (``market.crunch_dists``), runs ONE batched
    executor dispatch per (regime, seed), and bills every trial's makespan
    against the (launch-shifted) ``(S, T)`` price grid through
    ``engine.accumulate_price_cost`` — one jit-cached gather for every
    policy (``tests/test_market.py`` asserts zero retracing).

    Cost policies are *selection* policies over the scenario leaves (the
    checkpoint schedule is always the DP table):

    * ``"fixed"`` — run and bill the scenario's own leaf (the repo's
      pre-market behavior, now in moving dollars).
    * ``"cheapest"`` — cheapest-feasible substitution at launch: run and
      bill the same-vm_type leaf with the lowest launch price among those
      whose DP expected makespan is within ``feasible_slack`` of the own
      leaf's.  Falls back to the own leaf when nothing cheaper qualifies.
    * ``"migrate"`` — migrate-on-price-signal: start on the own leaf; at
      the first grid cell where the own price exceeds ``migrate_threshold``
      times the substitute's, the remaining trace is billed at the
      substitute's prices, and trials still running at the crossing pay
      ``migrate_overhead_hours`` at the substitute's crossing-cell price.
      No crossing (or no substitute) degrades to ``"fixed"``.

    ``tables=`` takes the dict of per-regime ``BatchDPTables`` from
    :func:`solve_market_tables`, skipping every DP solve.
    ``cost_path="reference"`` bills through the serial
    ``market.integrate_cost_ref`` loop instead of the batched gather — the
    bit-exactness cross-check used by ``benchmarks/market_bench.py``.

    ``dp_objective="dollars"`` solves (or expects, with ``tables=``) the
    dollar-objective tables against each regime's launch-shifted price
    grid: the checkpoint schedule itself then minimizes expected dollars.
    With dollar tables the ``feasible_slack`` gate for ``"cheapest"``/
    ``"migrate"`` substitution compares expected *dollars* instead of
    expected makespans — the slack becomes dollar-denominated, which is
    the natural reading of "feasible" under a cost objective.  Supplied
    ``tables=`` must match: a makespan table under
    ``dp_objective="dollars"`` (or vice versa) raises.
    """
    from . import market as market_mod
    scs = _resolve(scenarios)
    S = len(scs)
    if market is None:
        market = market_mod.MarketModel.for_scenarios(scs)
    if len(market) != S:
        raise ValueError(f"market has {len(market)} leaves for {S} scenarios")
    if cost_path not in ("kernel", "reference"):
        raise ValueError(f"cost_path must be 'kernel' or 'reference', "
                         f"got {cost_path!r}")
    unknown = set(policies) - set(_MARKET_POLICIES)
    if unknown:
        raise ValueError(f"unknown market policies {sorted(unknown)}; "
                         f"choose from {_MARKET_POLICIES}")

    def bill(grid, mk, price_index):
        if cost_path == "kernel":
            return engine.accumulate_price_cost(grid, mk, price_index)
        return np.array([
            [market_mod.integrate_cost_ref(grid.prices[price_index[s]],
                                           grid.cum[price_index[s]],
                                           grid.dt, m)
             for m in mk[s]] for s in range(S)])

    grid0 = market.grid()
    T = grid0.prices.shape[1]
    rows = []
    for regime in regimes:
        t0 = market.launch_time(regime)
        dist_list = market.crunch_dists(scs, t0)
        g = grid0.shift(t0)
        if tables is not None:
            if regime not in tables:
                raise ValueError(f"tables= has no entry for regime "
                                 f"{regime!r}")
            batch = tables[regime]
            if len(batch) != S or batch.K.shape[1] != job_steps + 1:
                raise ValueError(
                    f"tables[{regime!r}] has {len(batch)} scenarios x "
                    f"j_max {batch.K.shape[1] - 1}; this sweep needs "
                    f"{S} x {job_steps}")
            if batch.delta_steps != delta_steps \
                    or abs(batch.grid_dt - grid_dt) > 1e-12 \
                    or batch.restart_overhead != restart_overhead:
                raise ValueError("tables was solved for a different "
                                 "(grid_dt, delta_steps, restart_overhead) "
                                 "workload")
            got = getattr(batch, "objective", "makespan")
            if got != dp_objective:
                raise ValueError(
                    f"tables[{regime!r}] was solved with objective={got!r}; "
                    f"this sweep requested dp_objective={dp_objective!r}")
        else:
            batch = ckpt.solve_batch(
                dist_list, job_steps, grid_dt=grid_dt,
                delta_steps=delta_steps, n_sweeps=n_sweeps,
                restart_overhead=restart_overhead, backend=solver_backend,
                objective=dp_objective,
                price=g if dp_objective == "dollars" else None)
        # per-leaf expected cost of a fresh job (hours, or dollars under the
        # dollar objective) — the substitution policies' feasibility signal
        exp_mk = np.array([batch.expected_makespan(s, job_steps)
                           for s in range(S)])
        launch_p = g.prices[:, 0]
        crunch_on = [regime == "crunch"
                     and float(np.float64(p.crunch_t1))
                     > float(np.float64(p.crunch_t0))
                     for p in market.processes]
        # cheapest-feasible substitute per leaf, resolved at launch: same
        # vm_type, DP expected makespan within the slack, lowest launch
        # price (ties keep the own leaf — substitution must strictly win)
        target = np.arange(S)
        for s in range(S):
            cands = [j for j in range(S)
                     if scs[j].vm_type == scs[s].vm_type
                     and exp_mk[j] <= feasible_slack * exp_mk[s]
                     and launch_p[j] < launch_p[s]]
            if cands:
                target[s] = min(cands, key=lambda j: launch_p[j])
        # migrate-on-price-signal: first cell where own price exceeds
        # threshold x substitute price; compose the billed row from the
        # own prefix and the substitute suffix
        composed = g.prices.copy()
        kc = np.full(S, T, np.int64)
        for s in range(S):
            j = target[s]
            if j == s:
                continue
            hit = np.flatnonzero(g.prices[s]
                                 > migrate_threshold * g.prices[j])
            if hit.size:
                kc[s] = hit[0]
                composed[s, hit[0]:] = g.prices[j, hit[0]:]
        g_migrate = market_mod.PriceGrid.from_prices(composed, g.dt)

        ptab = np.asarray(batch.K, np.int32)
        idx = np.arange(S, dtype=np.int32)
        for seed in seeds:
            first, pool = engine.draw_lifetime_pool_batch(
                dist_list, n_trials, max_restarts=max_restarts, seed=seed)
            mk, fin = engine.simulate_makespan_batch(
                ptab, job_steps, first=first, pool=pool, grid_dt=grid_dt,
                delta_steps=delta_steps, restart_overhead=restart_overhead,
                max_restarts=max_restarts, unfinished="nan",
                return_finished=True)
            mk = np.asarray(mk)
            fin = np.asarray(fin)
            for policy in policies:
                if policy == "fixed":
                    chosen, m_bill, f_bill = idx, mk, fin
                    dollars = bill(g, m_bill, idx)
                elif policy == "cheapest":
                    chosen = target.astype(np.int32)
                    m_bill, f_bill = mk[chosen], fin[chosen]
                    dollars = bill(g, m_bill, chosen)
                else:   # migrate
                    chosen, m_bill, f_bill = idx, mk, fin
                    dollars = bill(g_migrate, m_bill, idx)
                    # trials still running at the crossing pay the
                    # migration overhead at the substitute's price there
                    cross_t = kc[:, None] * g.dt
                    sur = np.where(
                        m_bill > cross_t,
                        migrate_overhead_hours
                        * g.prices[target, np.minimum(kc, T - 1)][:, None],
                        0.0)
                    dollars = dollars + sur
                for s in range(S):
                    rows.append(_market_row(
                        scs[s], regime, policy, seed,
                        scs[int(chosen[s]) if policy != "migrate"
                            else int(target[s])].name,
                        launch_p[s], dollars[s], m_bill[s], f_bill[s],
                        n_trials=n_trials, job_steps=job_steps,
                        crunch=crunch_on[s]))
    return rows
