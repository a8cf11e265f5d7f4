"""Model-based optimal checkpointing via dynamic programming (Eqs. 11-15).

Discretization follows the paper: a job of J steps, each step one grid unit
``grid_dt`` (hours); a checkpoint costs ``delta_steps`` grid units.  The DP
computes

    V[j, t] = min_{1<=i<=j}  P_succ(t, w) * ( w*dt + V[j-i, t+w] )
                           + P_fail(t, w) * ( E_lost(t, w) + R_j )

where w = i + delta (no trailing checkpoint on the final segment, i == j),
``t`` is the VM age index and R_j the cost of restarting the j remaining
steps on a fresh VM (relaunch overhead + V[j, 0], fixed-pointed over a few
sweeps - the paper's executor likewise recomputes E[M*(J_rem, 0)] after every
failure).

Faithfulness notes (see DESIGN.md §6):
  * P_fail uses the *conditional* form (F~(t+w) - F~(t)) / S~(t) with the
    24 h atom included in F~ (the printed Eq. 12 'F(t+i+d) - F(i+d)' is read
    as a typo for F(t+i+d) - F(t)).
  * E_lost is the conditional expected time-in-segment at failure
    E[x - t | fail in (t, t+w]], which reduces to the paper's memoryless
    approximation (i+delta)/2 under a flat hazard; the printed Eq. 15
    (integral of x f(x) dx, an *absolute-age* moment) is dimensionally a
    makespan, not a lost-work, term.

The solver dispatches to a pluggable backend package
(``repro.core.policies.solver_backends``; see ``docs/solver.md``): the
retained serial reference, the batched XLA kernel, a Pallas VMEM-resident
kernel (``repro.kernels.dp_recurrence``), optionally ``shard_map``-sharded
over the ``scenario`` logical axis when a ``repro.sharding`` mesh is active.
Schedule extraction and the Monte-Carlo executor used by Fig. 7 live below
the dispatchers.

Bit-exactness contract (what each batched kernel must reproduce)
----------------------------------------------------------------
This module holds both ends of two reference/production pairs; the reference
side is retained forever, and restructuring the production side is only
legal while these matches hold (enforced by ``tests/test_batched.py`` /
``tests/test_sim_engine.py``):

  * :func:`solve_batch` (``backend="xla"``) vs the per-scenario
    :func:`solve` — V *and* K bit-identical per scenario slice at the
    solver's native float32, at any session dtype: both build their
    ``Fc``/``Hc`` grids through one shared helper (:func:`_cdf_grids`) and
    the batched kernel keeps the reference expression tree (hoisting,
    column-patching and argmin-restructuring may reorder the schedule,
    never the per-element arithmetic, so XLA's FMA contraction stays
    identical).  The Pallas backend is the deliberate exception: it
    recomputes the probability grids in-kernel and is tolerance-tested
    instead.
  * The vectorized executor ``engine.simulate_makespan_batch`` vs
    :func:`simulate_makespan` (the per-trial Python loop kept at the bottom
    of this file) — bit-identical makespans on a shared pre-drawn pool with
    x64 enabled, ~1e-6-relative in default float32 mode.  The loop body
    works in integer grid units with lifetimes pre-converted OUTSIDE the
    loop, so no multiply-add pattern exists for XLA to contract into an
    FMA; any policy table handed to either executor must yield the same
    interval for the same ``(remaining, age)`` lookup (this is why
    ``engine.stack_policy_tables`` may only *replicate* age-independent
    columns, never resample age-dependent ones).
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ... import obs
from . import solver_backends
from .solver_backends.grids import (  # noqa: F401
    _EPS, cdf_grids as _cdf_grids, dollar_loss_grids as _dollar_loss_grids,
    price_cum_grids as _price_cum_grids)

OBJECTIVES = ("makespan", "dollars")

# retained names for the two kernels this module used to define inline; the
# implementations moved to the backend package unchanged
_solve_tables = solver_backends.reference.solve_tables
_solve_tables_batch = solver_backends.xla.solve_tables_batch


@dataclasses.dataclass(frozen=True)
class DPTables:
    """Solved DP: V[j, t] expected remaining cost-to-completion, K[j, t]
    optimal next-checkpoint interval (steps).  ``objective`` records the
    unit of V: hours (``"makespan"``, the paper's Eqs. 11-15) or dollars
    (``"dollars"``, price-weighted segments + launch-priced restarts)."""
    V: np.ndarray
    K: np.ndarray
    grid_dt: float
    delta_steps: int
    restart_overhead: float
    horizon_idx: int
    objective: str = "makespan"

    def interval_steps(self, remaining_steps: int, age_idx: int) -> int:
        j = int(np.clip(remaining_steps, 0, self.K.shape[0] - 1))
        t = int(np.clip(age_idx, 0, self.K.shape[1] - 1))
        return int(self.K[j, t])

    def expected_makespan(self, job_steps: int, age_idx: int = 0) -> float:
        """V at (job_steps, age_idx) — expected hours under the makespan
        objective, expected dollars under the dollar objective."""
        return float(self.V[int(job_steps), int(age_idx)])


@dataclasses.dataclass(frozen=True)
class BatchDPTables:
    """Solved DP for a whole scenario batch: V/K carry a leading ``(S,)``
    scenario axis (see the leading-axis convention in ``repro.core.engine``).
    ``tables(s)`` returns a plain per-scenario :class:`DPTables` view for the
    existing single-scenario API."""
    V: np.ndarray                # (S, j_max+1, t_max+1)
    K: np.ndarray                # (S, j_max+1, t_max+1)
    grid_dt: float
    delta_steps: int
    restart_overhead: float
    horizon_idx: int
    # provenance (not part of table identity): which backend produced them
    backend: str = "xla"
    # unit of V: "makespan" (hours, Eqs. 11-15) or "dollars"
    objective: str = "makespan"

    def __len__(self) -> int:
        return self.V.shape[0]

    def tables(self, s: int) -> DPTables:
        return DPTables(V=self.V[s], K=self.K[s], grid_dt=self.grid_dt,
                        delta_steps=self.delta_steps,
                        restart_overhead=self.restart_overhead,
                        horizon_idx=self.horizon_idx,
                        objective=self.objective)

    def expected_makespan(self, s: int, job_steps: int,
                          age_idx: int = 0) -> float:
        """V at (s, job_steps, age_idx) — expected hours under the makespan
        objective, expected dollars under the dollar objective."""
        return float(self.V[int(s), int(job_steps), int(age_idx)])

    def validate(self) -> "BatchDPTables":
        """Reject half-written / diverged tables before they are served.

        The closed-loop runtime calls this between ``solve_batch`` and the
        atomic table swap: a table passes only if every V entry is finite
        and non-negative and every K row respects the DP's own invariant
        (``0 <= K[j] <= j``, with ``K[j] >= 1`` whenever work remains).
        The invariants are objective-independent (dollar V is a price
        integral of non-negative work, so it is non-negative too); only the
        unit named in the error message changes.
        Raises ``ValueError``; returns ``self`` so calls chain.
        """
        unit = "dollars" if self.objective == "dollars" else "makespans"
        if not np.all(np.isfinite(self.V)):
            raise ValueError(
                f"BatchDPTables.validate: non-finite V entries ({unit})")
        if np.any(self.V < 0.0):
            raise ValueError(f"BatchDPTables.validate: negative {unit} in V")
        j = np.arange(self.K.shape[1])[None, :, None]
        if np.any(self.K < 0) or np.any(self.K > j):
            raise ValueError("BatchDPTables.validate: K outside [0, j]")
        if np.any(self.K[:, 1:, :] < 1):
            raise ValueError("BatchDPTables.validate: K < 1 with work "
                             "remaining (j >= 1)")
        return self


def _check_objective(objective: str, price) -> None:
    if objective not in OBJECTIVES:
        raise ValueError(f"objective={objective!r}; expected one of "
                         f"{OBJECTIVES}")
    if objective == "dollars" and price is None:
        raise ValueError("objective='dollars' requires price= (a "
                         "market.PriceGrid)")
    if objective == "makespan" and price is not None:
        raise ValueError("price= is only meaningful with objective='dollars'")


def _dollar_inputs(price, grid_dt: float, t_max: int, job_steps: int,
                   delta_steps: int, restart_overhead: float, S: int):
    """Solver inputs for the dollar objective: the float32 cumulative-dollar
    grid ``Pc`` (``(S, TX)``, extended past the horizon so segment gathers
    never clip) and the per-scenario dollar restart overhead ``ro``
    (``(S,)``, overhead hours billed at the launch-cell price).  A one-row
    ``price`` broadcasts over the scenario axis."""
    rows = np.asarray(price.prices).shape[0]
    if rows not in (1, S):
        raise ValueError(
            f"price= has {rows} rows; expected 1 (broadcast) or S={S}")
    Pc, P0 = _price_cum_grids(price.prices, price.cum, price.dt, grid_dt,
                              t_max, int(job_steps) + int(delta_steps))
    if rows == 1 and S > 1:
        Pc = np.broadcast_to(Pc, (S,) + Pc.shape[1:])
        P0 = np.broadcast_to(P0, (S,))
    ro = (float(restart_overhead) * P0).astype(np.float32)
    return jnp.asarray(Pc), jnp.asarray(ro)


def solve(dist, job_steps: int, *, grid_dt: float = 1.0 / 60.0,
          delta_steps: int = 1, n_sweeps: int = 3,
          restart_overhead: float = 0.0, backend: str = "auto",
          objective: str = "makespan", price=None) -> DPTables:
    """Solve the checkpointing DP for jobs up to ``job_steps`` grid steps on
    VMs following ``dist`` (any repro.core.distributions family).

    ``backend="auto"`` runs the serial reference kernel: the single-scenario
    path IS the reference side of the bit-exactness contract, so rerouting
    it through a production kernel would collapse the very pairing
    ``tests/test_batched.py`` enforces (``REPRO_SOLVER_BACKEND`` therefore
    does not apply here).  An explicit ``"xla"``/``"pallas"`` routes through
    the batched machinery with ``S=1`` and unwraps.

    ``objective="dollars"`` with a ``price`` grid solves for expected
    dollars-to-completion instead of hours (row 0 of a multi-row grid);
    see :func:`solve_batch` for the recurrence.
    """
    _check_objective(objective, price)
    Fc, Hc, t_max = _cdf_grids(dist, grid_dt)
    # scalars pinned to the solver's native f32 (see _cdf_grids): keeps
    # solve/solve_batch bit-identical to each other at any session dtype
    gdt, ro = jnp.float32(grid_dt), jnp.float32(restart_overhead)
    Pc = Elp = None
    if objective == "dollars":
        rows = int(np.asarray(price.prices).shape[0])
        Pc, ro = _dollar_inputs(price, grid_dt, t_max, job_steps,
                                delta_steps, restart_overhead, rows)
        Pc, ro = Pc[:1], ro[:1]          # single scenario: row 0
        Elp = jnp.asarray(_dollar_loss_grids(
            Fc[None], Hc[None], Pc, grid_dt, j_max=int(job_steps),
            t_max=t_max, delta_steps=int(delta_steps)))
    if backend in ("auto", "reference"):
        pc0 = None if Pc is None else Pc[0]
        ro0 = ro if Pc is None else ro[0]
        ep0 = None if Elp is None else Elp[0]
        V, K = _solve_tables(Fc, Hc, gdt, ro0, None, pc0, ep0,
                             j_max=int(job_steps), t_max=t_max,
                             delta_steps=int(delta_steps), n_sweeps=n_sweeps)
    else:
        name = solver_backends.resolve(backend)
        V, K = _dispatch_plain(name, Fc[None], Hc[None], gdt, ro, None, Pc,
                               Elp, j_max=int(job_steps), t_max=t_max,
                               delta_steps=int(delta_steps),
                               n_sweeps=n_sweeps)
        V, K = V[0], K[0]
    return DPTables(V=np.asarray(V), K=np.asarray(K), grid_dt=grid_dt,
                    delta_steps=int(delta_steps),
                    restart_overhead=restart_overhead, horizon_idx=t_max,
                    objective=objective)


def _dispatch_plain(name: str, Fc, Hc, gdt, ro, v_init, Pc=None, Elp=None, *,
                    j_max: int, t_max: int, delta_steps: int, n_sweeps: int):
    """Run one backend on stacked grids, sharding the scenario axis over an
    active ``repro.sharding`` mesh when its rules allow (transparent
    single-device fallback: the unwrapped call is byte-identical to the
    pre-refactor one).

    In dollar mode (``Pc``/``Elp`` given) ``ro`` is the per-scenario ``(S,)``
    dollar overhead and rides the sharded operand list with ``Pc`` and the
    host-precomputed loss grids ``Elp`` — a closure capture would replicate
    them at full length inside each shard."""
    mod = solver_backends.get(name)
    statics = dict(j_max=j_max, t_max=t_max, delta_steps=delta_steps,
                   n_sweeps=n_sweeps)
    if name == "reference":
        # the Python-loop batch adapter: per-scenario dispatches, no shard
        return mod.solve_tables_batch(Fc, Hc, gdt, ro, v_init, Pc, Elp,
                                      **statics)
    if Pc is None:
        if v_init is None:
            kern = lambda fc, hc: mod.solve_tables_batch(
                fc, hc, gdt, ro, None, **statics)
            args = (Fc, Hc)
        else:
            kern = lambda fc, hc, vi: mod.solve_tables_batch(
                fc, hc, gdt, ro, vi, **statics)
            args = (Fc, Hc, v_init)
    else:
        if v_init is None:
            kern = lambda fc, hc, pc, ep, rv: mod.solve_tables_batch(
                fc, hc, gdt, rv, None, pc, ep, **statics)
            args = (Fc, Hc, Pc, Elp, ro)
        else:
            kern = lambda fc, hc, vi, pc, ep, rv: mod.solve_tables_batch(
                fc, hc, gdt, rv, vi, pc, ep, **statics)
            args = (Fc, Hc, v_init, Pc, Elp, ro)
    fn, _ = solver_backends.shard_scenarios(kern, Fc.shape[0], len(args), 2)
    return fn(*args)


def solve_batch(dists: Sequence, job_steps: int, *, grid_dt: float = 1.0 / 60.0,
                delta_steps: int = 1, n_sweeps: int = 3,
                restart_overhead: float = 0.0, v_init=None,
                backend: str = "auto", objective: str = "makespan",
                price=None) -> BatchDPTables:
    """Solve the checkpointing DP for a whole scenario batch in ONE compiled
    call (see ``solver_backends`` and ``docs/solver.md``).

    ``dists`` is a sequence of distributions sharing one deadline ``L``.
    Each scenario's ``Fc``/``Hc`` grid is built by the shared
    :func:`_cdf_grids` helper (the same eager ops :func:`solve` uses), then
    the stacked grids go through the selected backend — for ``"xla"`` (the
    ``"auto"`` default off-TPU) every returned slice matches the
    per-scenario :func:`solve` result table-for-table, bit-exactly.

    ``backend`` selects the kernel (``"auto"``/``"reference"``/``"xla"``/
    ``"pallas"``; ``"auto"`` honors the ``REPRO_SOLVER_BACKEND`` env var).

    ``v_init`` optionally warm-starts the restart-cost fixed point from a
    previous solve's ``V`` array of matching shape ``(S, j_max+1, t_max+1)``
    (e.g. ``prev.V`` after a drift refit on the same grid) — the cold path
    (``v_init=None``) is untouched and keeps the bit contract above.  A warm
    start must come from tables solved under the SAME objective (V's unit is
    the seed's unit; the shapes cannot tell them apart, so this is the
    caller's contract — ``FleetRuntime`` guards it).

    ``objective="dollars"`` with ``price=`` (a ``market.PriceGrid``; one row
    broadcasts, otherwise one row per scenario) switches V to expected
    dollars-to-completion:

        V[j, t] = min_i  P_succ * ( dP(t, w) + V[j-i, t+w] )
                       + P_fail * ( E_lost * pbar(t, w) + R_j )

    where ``dP(t, w) = Pc(t+w) - Pc(t)`` is the integrated price over the
    segment's age window (``grids.price_cum_grids``, ages beyond the price
    horizon billed at the final cell), ``pbar = dP / (w*dt)`` its average
    $/hour, and ``R_j = restart_overhead x launch price + V[j, 0]``.  The
    failure branches' probabilities and expected lost time are unchanged —
    only the pricing of time changes — so K stretches checkpoint intervals
    exactly where the price makes lost work cheap or checkpoint overhead
    expensive.  On a flat grid at p $/h every cost term is p x the makespan
    term, so V reduces to ``p x V_makespan`` (up to float32 rounding; the
    property tests pin this).  All backends, warm starts and scenario
    sharding work identically under either objective, and the
    reference<->xla bit-identity contract covers both.
    """
    _check_objective(objective, price)
    dists = list(dists)
    if not dists:
        raise ValueError("solve_batch() needs at least one distribution")
    L = float(dists[0].L)
    if any(abs(float(d.L) - L) > 1e-12 for d in dists[1:]):
        raise ValueError("solve_batch() requires a shared deadline L")
    t_max = int(round(L / grid_dt))
    if v_init is not None:
        want = (len(dists), int(job_steps) + 1, t_max + 1)
        v_init = np.asarray(v_init)
        if v_init.shape != want:
            raise ValueError(
                f"solve_batch(v_init=...): shape {v_init.shape} does not "
                f"match this solve's tables {want}; warm starts require the "
                f"same scenario count, job_steps and grid")
        if not np.all(np.isfinite(v_init)):
            raise ValueError("solve_batch(v_init=...): non-finite warm start")
        v_init = jnp.asarray(v_init, jnp.float32)
    with obs.span(obs.SOLVE_GRIDS):
        grids_fh = [_cdf_grids(d, grid_dt) for d in dists]
        Fc = jnp.stack([g[0] for g in grids_fh])
        Hc = jnp.stack([g[1] for g in grids_fh])
        # f32-pinned scalars: see _cdf_grids — keeps V/K identical at any dtype
        gdt, ro = jnp.float32(grid_dt), jnp.float32(restart_overhead)
        Pc = Elp = None
        if objective == "dollars":
            Pc, ro = _dollar_inputs(price, grid_dt, t_max, job_steps,
                                    delta_steps, restart_overhead, len(dists))
            Elp = jnp.asarray(_dollar_loss_grids(
                Fc, Hc, Pc, grid_dt, j_max=int(job_steps), t_max=t_max,
                delta_steps=int(delta_steps)))
    statics = dict(j_max=int(job_steps), t_max=t_max,
                   delta_steps=int(delta_steps), n_sweeps=n_sweeps)
    name = solver_backends.resolve(backend)
    with obs.span(obs.SOLVE_KERNEL, backend=name):
        V, K = _dispatch_plain(name, Fc, Hc, gdt, ro, v_init, Pc, Elp,
                               **statics)
    with obs.span(obs.SOLVE_FETCH):
        V, K = np.asarray(V), np.asarray(K)
    return BatchDPTables(V=V, K=K, grid_dt=grid_dt,
                         delta_steps=int(delta_steps),
                         restart_overhead=restart_overhead, horizon_idx=t_max,
                         backend=name, objective=objective)


def extract_schedule(tables: DPTables, job_steps: int,
                     start_age_idx: int = 0) -> list[int]:
    """Planned checkpoint intervals (steps) assuming no failures - the paper's
    i1, i2, ... sequence (e.g. (15, 28, 38, 59, 128) min for a 5 h job at
    age 0 with a 1-min grid)."""
    out, j, t = [], int(job_steps), int(start_age_idx)
    while j > 0:
        i = tables.interval_steps(j, t)
        i = max(1, min(i, j))
        out.append(i)
        j -= i
        t = min(t + i + (tables.delta_steps if j > 0 else 0), tables.horizon_idx)
    return out


def evaluate_policy_dollars(K, dists: Sequence, price, *, grid_dt: float,
                            delta_steps: int = 1, n_sweeps: int = 3,
                            restart_overhead: float = 0.0) -> np.ndarray:
    """Expected dollars-to-completion of executing FIXED policy tables ``K``
    under the dollar objective's own model.

    A float64 host mirror of the dollar recurrence with the min over
    candidate intervals replaced by K's choice (clipped to ``[1, j]``), run
    through the same restart-cost fixed point and row order as the solver.
    Because the solver minimizes over every candidate the evaluator merely
    follows, ``solve_batch(objective="dollars").V <= evaluate(K_any)``
    pointwise per sweep by induction — which is what lets the market
    benchmark compare a makespan-optimal K against a dollar-optimal K in
    the same currency without Monte-Carlo noise (the solver's float32
    argmin leaves ~1e-6-relative slack against this float64 evaluation).

    ``K``: ``(S, j_max+1, t_max+1)`` int tables (e.g. ``BatchDPTables.K``);
    ``dists``: the S lifetime distributions; ``price``: a PriceGrid (one
    row broadcasts).  Returns float64 ``(S, j_max+1, t_max+1)`` dollar
    tables; entry ``[s, J, 0]`` is the expected cost of a fresh J-step job.
    """
    K = np.asarray(K)
    S, J1, T = K.shape
    j_max, t_max = J1 - 1, T - 1
    prices = np.asarray(price.prices, np.float64)
    cum = np.asarray(price.cum, np.float64)
    if prices.shape[0] == 1 and S > 1:
        prices = np.broadcast_to(prices, (S, prices.shape[1]))
        cum = np.broadcast_to(cum, (S, cum.shape[1]))
    pdt = float(price.dt)
    TX = t_max + 1 + j_max + int(delta_steps)
    tau = np.arange(TX, dtype=np.float64) * grid_dt
    kc = np.clip(np.floor(tau / pdt).astype(np.int64), 0, prices.shape[1] - 1)
    Pc = cum[:, kc] + prices[:, kc] * (tau[None, :] - kc[None, :] * pdt)
    t = np.arange(t_max + 1)
    out = np.empty((S, J1, T), np.float64)
    for s in range(S):
        d = dists[s]
        tk = np.arange(t_max + 1, dtype=np.float64) * grid_dt
        F = np.clip(np.array(d.cdf(tk), np.float64), 0.0, 1.0)
        atom = max(1.0 - F[-1], 0.0)
        F[-1] = 1.0
        H = np.array(d.partial_expectation(np.zeros_like(tk), tk),
                     np.float64)
        H[-1] += atom * float(d.L)
        dead = (1.0 - F) < 1e-6
        V = np.broadcast_to(Pc[s, :J1, None], (J1, T)).copy()
        for _ in range(n_sweeps):
            R = float(restart_overhead) * prices[s, 0] + V[:, 0].copy()
            for j in range(1, J1):
                i = np.clip(K[s, j], 1, j)
                w = np.where(i == j, i, i + int(delta_steps))
                end = np.minimum(t + w, t_max)
                endx = t + w
                Ft, Fe = F[t], F[end]
                p_fail = np.clip((Fe - Ft) / np.maximum(1.0 - Ft, _EPS),
                                 0.0, 1.0)
                dF = np.maximum(Fe - Ft, _EPS)
                e_lost = np.clip((H[end] - H[t]) / dF - t * grid_dt,
                                 0.0, w * grid_dt)
                dP = Pc[s, endx] - Pc[s, t]
                pb = dP / (w * grid_dt)
                v_succ = dP + V[j - i, end]
                v_fail = e_lost * pb + R[j]
                vj = (1.0 - p_fail) * v_succ + p_fail * v_fail
                V[j] = np.where(dead, R[j], vj)
        out[s] = V
    return out


# ---------------------------------------------------------------------------
# Monte-Carlo executor (Fig. 7 evaluation; also used by tests)
#
# This per-trial Python loop is the REFERENCE implementation; the production
# path is the batched lax.while_loop kernel in repro.core.engine, which
# performs the same operations on (n_trials,)-vectors.  Exactness contract:
# lifetimes are pre-converted to grid-step units (minus the initial VM's
# sub-grid age offset) OUTSIDE the hot loop, so the loop body contains no
# multiply-add pattern XLA could contract into an FMA; given a shared pool,
# the kernel run in float64 matches this loop bit-for-bit.
# ---------------------------------------------------------------------------

def simulate_makespan(policy_fn: Callable[[int, int], int], lifetimes_fn,
                      job_steps: int, *, grid_dt: float = 1.0 / 60.0,
                      delta_steps: int = 1, start_age: float = 0.0,
                      n_trials: int = 2000, seed: int = 0,
                      restart_overhead: float = 0.0,
                      max_restarts: int = 64, pool=None, first=None):
    """Execute a job under sampled preemptions.

    policy_fn(remaining_steps, age_idx) -> steps until next checkpoint.
    lifetimes_fn(rng, n, min_age=0.0) -> n sampled VM lifetimes (hours),
    conditioned on survival to ``min_age`` (used for the first VM when the
    job starts on an aged machine).  Alternatively pass pre-drawn ``first``
    (n_trials,) and ``pool`` (n_trials, max_restarts+2) arrays from
    ``engine.draw_lifetime_pool`` — the equivalence tests share one pool
    between this reference and the vectorized kernel.

    Semantics: failure during a work segment or during the checkpoint write
    loses progress back to the last durable checkpoint; the job resumes on a
    fresh VM (age 0) after ``restart_overhead`` hours, recomputing its
    schedule (the paper's resume-event behavior).  Returns makespans (hours),
    shape (n_trials,).
    """
    if pool is None:
        from .. import engine  # local import: engine imports this module too

        first, pool = engine.draw_lifetime_pool(
            lifetimes_fn, n_trials, max_restarts=max_restarts, seed=seed,
            start_age=start_age)
    else:
        first = pool[:, 0] if first is None else first
        n_trials = len(first)
    age0_idx = int(round(start_age / grid_dt))
    off0 = start_age - age0_idx * grid_dt
    # lifetimes in grid-step units, initial VM age offset removed (see the
    # exactness note above: all comparisons are int-vs-precomputed-float)
    first_steps = (np.asarray(first, np.float64) - off0) / grid_dt
    pool_steps = np.asarray(pool, np.float64) / grid_dt
    out = np.empty((n_trials,), np.float64)
    for n in range(n_trials):
        remaining = int(job_steps)
        age_idx = age0_idx
        draw = 0
        life_s = first_steps[n]
        done_steps = 0          # completed work+checkpoint segments (grid units)
        lost_steps = 0.0        # preempted partial segments (grid units)
        restarts = 0
        while remaining > 0 and restarts <= max_restarts:
            i = int(policy_fn(remaining, age_idx))
            i = max(1, min(i, remaining))
            w = i + (delta_steps if i < remaining else 0)
            if age_idx + w <= life_s:
                # segment + checkpoint complete
                done_steps += w
                age_idx += w
                remaining -= i
            else:
                # preempted mid-segment: progress since last checkpoint lost
                lost_steps += max(life_s - age_idx, 0.0)
                draw += 1
                life_s = pool_steps[n, min(draw, max_restarts + 1)]
                age_idx = 0
                restarts += 1
        out[n] = (done_steps + lost_steps) * grid_dt \
            + restarts * restart_overhead
    return out


def dp_policy_fn(tables: DPTables):
    return lambda remaining, age_idx: tables.interval_steps(remaining, age_idx)


def young_daly_policy_fn(tau_hours: float, grid_dt: float):
    tau_steps = max(1, int(round(tau_hours / grid_dt)))
    return lambda remaining, age_idx: min(tau_steps, remaining)


def no_checkpoint_policy_fn():
    return lambda remaining, age_idx: remaining


def model_lifetimes_fn(dist):
    """lifetimes_fn adapter: numpy rng -> inverse-CDF samples from ``dist``,
    optionally conditioned on survival to ``min_age`` (F restricted to
    [F(min_age), 1], with the residual >=F(L) mass preempted at L).

    Draws go through ``engine.capped_icdf_draw``, whose jitted kernel takes
    the distribution as a pytree *argument* — this reference sampler and
    ``engine.draw_lifetime_pool_batch`` therefore share one compiled
    inversion with no parameter constants baked into either graph, which is
    what makes the batched pool reproduce this reference bit-for-bit under
    x64.  Leaves are still normalized to jnp arrays up front so both paths
    present identical leaf dtypes to that cache.
    """
    dist = jax.tree_util.tree_map(
        lambda l: jnp.asarray(l, jnp.result_type(float)), dist)

    def fn_capped(rng, n, min_age: float = 0.0):
        from .. import engine  # local import, matching simulate_makespan

        u = rng.uniform(size=n)
        f_lo = float(dist.cdf(min_age)) if min_age > 0 else 0.0
        u = f_lo + u * (1.0 - f_lo)
        fl = float(dist.cdf(dist.L))
        return engine.capped_icdf_draw(dist, u, fl, float(dist.L))

    return fn_capped
