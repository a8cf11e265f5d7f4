"""Vectorized JAX Monte-Carlo engine for policy evaluation.

This module is the batched/jitted counterpart of the pure-Python simulation
hot paths used by the paper's headline figures:

  * :func:`simulate_makespan_batch` — the Fig. 7 checkpointing executor
    (``repro.core.policies.checkpointing.simulate_makespan``) rewritten as a
    single ``lax.while_loop`` over *events*, operating on ``(n_trials,)``
    state vectors with the policy table and the pre-drawn lifetime pool
    resident on device.  One event = one work segment attempt (success or
    preemption) for every still-running trial; the loop exits as soon as all
    trials have finished, so the wall-clock cost is the *slowest* trial's
    event count, not ``n_trials`` Python iterations.
  * :func:`reuse_decision_table` — the scheduling policy's Eq. 9-vs-Eq. 10
    reuse decision evaluated for a whole ``(remaining-work x VM-age)`` grid
    in one jitted call, so the batch-service event loop never dispatches to
    JAX per idle-VM candidate.
  * :func:`draw_lifetime_pool` — the shared pre-drawn lifetime pool.  The
    Python reference executor and the vectorized kernel both consume pools
    drawn by this helper, which is what makes exact (same-seed, same-pool)
    equivalence testable.

Policies are represented as integer *tables* ``P[j, t] -> interval`` (steps
until the next checkpoint given ``j`` remaining steps and VM age index
``t``); :func:`dp_policy_table`, :func:`young_daly_policy_table` and
:func:`no_checkpoint_policy_table` build them for the three Fig. 7 policies.
Age-independent policies use a ``(j_max+1, 1)`` table — the kernel clips the
age index into the table's second dimension.

Bit-exactness contract (the PR-1 equivalence discipline)
---------------------------------------------------------
Every batched kernel in this module has a retained reference implementation
it must reproduce, and the dtype under which the match is *bit-exact* is part
of the contract:

  * :func:`simulate_makespan_batch` vs the per-trial Python loop
    ``checkpointing.simulate_makespan`` — on a shared pre-drawn pool with x64
    enabled (``jax.enable_x64(True)``), makespans match bit-for-bit:
    the kernel works in integer grid-step units with the only float
    accumulation (lost partial segments) ordered exactly as the reference,
    so XLA cannot contract a multiply-add into an FMA.  In default float32
    mode results agree to ~1e-6 relative, far below Monte-Carlo noise.
  * Batched (leading-axis) kernels vs their own unbatched form — per slice,
    same dtype rule: the ``lax.while_loop`` batching rule freezes finished
    lanes with selects, so each lane performs the reference IEEE operations.
  * :func:`draw_lifetime_pool_batch` vs the numpy-reference
    :func:`draw_lifetime_pool` — per (entry, seed) slice, bit-exact under
    x64 (both paths share :func:`capped_icdf_draw` and compile the same
    array-constant bisection graph), float32-close (~1e-6) otherwise.

``tests/test_sim_engine.py`` and ``tests/test_batched.py`` enforce all
three; any kernel restructuring must keep them green.

Leading-axis convention (batching scenarios — or whole sweep grids)
-------------------------------------------------------------------
Every batched entry point treats an optional leading axis as a *batch of
independent cells*.  In the simplest use the axis is the scenario axis
``S``, threaded end-to-end from the distribution layer up; since PR 4 the
same axis folds the full (scenario x policy x seed) sweep grid as a
flattened cell axis ``B = S*P*R`` — the executor does not care what the
axis means, only that lane ``b`` carries that cell's table, first lifetime
and pool:

  * ``distributions.stack(dists)`` stacks a scenario list into one pytree
    whose parameter leaves carry a leading ``(S,)`` axis;
  * ``checkpointing.solve_batch`` returns ``(S, j_max+1, t_max+1)`` V/K
    tables from one compiled call;
  * :func:`draw_lifetime_pool_batch` draws ``(S, n_trials, max_restarts+2)``
    pools on-device in one shot; ``seed`` may be a per-entry sequence, so a
    flattened (scenario x seed) cell list draws every cell's pool — each
    from its own seed's reference rng stream — in the same single call;
  * :func:`stack_policy_tables` stacks per-cell policy tables of differing
    provenance (age-dependent DP tables next to age-independent
    Young-Daly/no-checkpoint columns) into one ``(B, j_max+1, t_max+1)``
    tensor without changing any lookup result;
  * :func:`simulate_makespan_batch` accepts the leading axis on
    ``policy_table`` (optional — a 2-D table is shared), ``first`` and
    ``pool``, vmapping the event kernel and returning ``(B, n_trials)``
    makespans.  The bit-exactness contract above holds per lane;
  * :meth:`ReuseTable.batch` / :class:`ReuseTables` evaluate all scenarios'
    reuse grids in one vmapped call, sharing one backing tensor.

``scenarios.sweep_checkpointing(mode="batched")`` composes these into ONE
executor dispatch for an entire sweep; see its docstring for the
cell-index/unflattening bookkeeping.

Typical use (Fig. 7 workload)::

    tables = checkpointing.solve(dist, 720)
    table = engine.dp_policy_table(tables)
    first, pool = engine.draw_lifetime_pool(
        checkpointing.model_lifetimes_fn(dist), n_trials=5000,
        max_restarts=64, seed=0)
    makespans = engine.simulate_makespan_batch(table, 720, first=first,
                                               pool=pool)
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from . import distributions as dists_mod
from .policies import scheduling as sched_policy

__all__ = [
    "dp_policy_table", "young_daly_policy_table", "no_checkpoint_policy_table",
    "stack_policy_tables",
    "draw_lifetime_pool", "draw_lifetime_pool_batch",
    "simulate_makespan_batch", "simulate_makespan_engine",
    "ReuseTable", "ReuseTables",
]


# ---------------------------------------------------------------------------
# policy tables
# ---------------------------------------------------------------------------

def dp_policy_table(tables) -> np.ndarray:
    """The DP's optimal-interval table ``K[j, t]`` (see checkpointing.solve)."""
    return np.asarray(tables.K, np.int32)


def young_daly_policy_table(tau_steps: int, job_steps: int) -> np.ndarray:
    """Fixed-interval policy ``min(tau, remaining)`` as a (j_max+1, 1) table."""
    j = np.arange(job_steps + 1, dtype=np.int32)
    return np.minimum(np.maximum(int(tau_steps), 1), j)[:, None].astype(np.int32)


def no_checkpoint_policy_table(job_steps: int) -> np.ndarray:
    """Run-to-completion: the next 'segment' is the whole remaining job."""
    return np.arange(job_steps + 1, dtype=np.int32)[:, None]


def validate_policy_table(table) -> np.ndarray:
    """Reject a policy table the executor must never serve from: NaN/inf
    (a half-written or diverged solve) or intervals outside ``[0, j]`` /
    zero with work remaining (which would wedge the executor's progress
    loop at ``max(1, min(i, remaining))`` in ways the solve never
    intended).  Returns the table as int32 on success; raises ValueError.

    The closed-loop runtime calls this on every candidate table before the
    atomic hot-swap — validation failures degrade to the last-good table.
    """
    raw = np.asarray(table)
    if not np.all(np.isfinite(raw)):
        raise ValueError("validate_policy_table: non-finite entries")
    t = raw.astype(np.int32)
    if t.ndim != 2:
        raise ValueError(f"validate_policy_table: expected a 2-D (j, t) "
                         f"table, got shape {raw.shape}")
    j = np.arange(t.shape[0], dtype=np.int32)[:, None]
    if np.any(t < 0) or np.any(t > j):
        raise ValueError("validate_policy_table: intervals outside [0, j]")
    if t.shape[0] > 1 and np.any(t[1:] < 1):
        raise ValueError("validate_policy_table: zero interval with work "
                         "remaining (j >= 1)")
    return t


def stack_policy_tables(tables, t_axis: int | None = None) -> np.ndarray:
    """Stack per-cell 2-D policy tables into one ``(B, j_max+1, t_axis)``
    int32 tensor for the one-kernel executor.

    The three Fig. 7 policy families produce tables of differing provenance
    and age-axis width: the DP's ``K[j, t]`` is fully age-dependent
    (``t_axis = t_max+1``) while Young-Daly and no-checkpoint tables are
    age-independent ``(j_max+1, 1)`` columns.  An age-independent column is
    widened by replication, which cannot change any lookup: the kernel reads
    ``table[clip(j), clip(age)]`` and every age column holds the same
    interval the 1-wide table would have produced via its age clip.  Tables
    must share the remaining-work axis; a table that is neither 1-wide nor
    ``t_axis``-wide is rejected rather than resampled.
    """
    tables = [np.asarray(t, np.int32) for t in tables]
    if not tables:
        raise ValueError("stack_policy_tables() needs at least one table")
    if any(t.ndim != 2 for t in tables):
        raise ValueError("stack_policy_tables() stacks 2-D (j, t) tables")
    j_axis = tables[0].shape[0]
    if any(t.shape[0] != j_axis for t in tables):
        raise ValueError("policy tables must share the remaining-work axis; "
                         f"got {sorted({t.shape[0] for t in tables})}")
    if t_axis is None:
        t_axis = max(t.shape[1] for t in tables)
    out = np.empty((len(tables), j_axis, int(t_axis)), np.int32)
    for b, t in enumerate(tables):
        if t.shape[1] == t_axis:
            out[b] = t
        elif t.shape[1] == 1:
            out[b] = np.broadcast_to(t, (j_axis, int(t_axis)))
        else:
            raise ValueError(
                f"table {b} has age axis {t.shape[1]}; expected 1 (age-"
                f"independent) or {t_axis} — widening an age-dependent "
                f"table would need resampling, not replication")
    return out


# ---------------------------------------------------------------------------
# lifetime pools
# ---------------------------------------------------------------------------

def draw_lifetime_pool(lifetimes_fn: Callable, n_trials: int, *,
                       max_restarts: int = 64, seed: int = 0,
                       start_age: float = 0.0):
    """Pre-draw the `(first, pool)` lifetimes consumed by one executor run.

    ``pool`` has shape ``(n_trials, max_restarts + 2)``; draw ``k`` (k >= 1)
    after the k-th preemption of trial ``n`` is ``pool[n, min(k, max_restarts
    + 1)]``.  ``first`` is the initial VM's lifetime, conditioned on survival
    to ``start_age`` when the sampler supports ``min_age`` (falls back to
    ``pool[:, 0]`` otherwise).  Draw order matches the historical reference
    executor, so a given ``seed`` yields the same lifetimes in both engines.
    """
    rng = np.random.default_rng(seed)
    pool = np.asarray(lifetimes_fn(rng, n_trials * (max_restarts + 2)),
                      np.float64).reshape(n_trials, max_restarts + 2)
    try:
        first = np.asarray(lifetimes_fn(rng, n_trials, min_age=start_age),
                           np.float64)
    except TypeError:  # sampler without conditioning support
        first = pool[:, 0].copy()
    return first, pool


@jax.jit
def _capped_icdf_kernel(dist, u, fl, L):
    t = dist.icdf(jnp.minimum(u, fl * (1.0 - 1e-6)))
    return jnp.where(u >= fl, jnp.asarray(L, t.dtype), t)


def capped_icdf_draw(dist, u, fl, L):
    """The capped inverse-CDF draw both samplers share: lifetimes
    ``icdf(min(u, fl * (1 - 1e-6)))`` with the residual ``u >= fl`` mass
    preempted AT the deadline ``L``.  Broadcasts over scalar parameters
    (``checkpointing.model_lifetimes_fn``, the numpy reference) and
    ``(S, 1)``-stacked ones (:func:`draw_lifetime_pool_batch`) — keeping
    this contract in ONE place is what keeps the two paths bit-identical
    under x64.

    The whole draw — inversion and deadline cap — runs through one
    module-level jitted kernel that takes the distribution as a pytree
    *argument*: the compiled bisection is cached per (family, shape,
    dtype) instead of being re-traced through each fresh distribution
    instance's closure, neither path can bake parameter constants into
    its graph — both see literally the same executable — and the capped
    result crosses the device boundary exactly once."""
    return np.asarray(_capped_icdf_kernel(dist, jnp.asarray(u),
                                          jnp.asarray(fl), jnp.asarray(L)),
                      np.float64)


def draw_lifetime_pool_batch(dists, n_trials: int, *, max_restarts: int = 64,
                             seed=0, start_age: float = 0.0):
    """Batched :func:`draw_lifetime_pool` for a list of cells: ``first`` has
    shape ``(S, n_trials)`` and ``pool`` ``(S, n_trials, max_restarts + 2)``.

    ``seed`` is either one integer — a scenario batch, every entry sharing
    that seed's uniforms — or a sequence of ``len(dists)`` per-entry seeds,
    which is how a flattened (scenario x seed) sweep cell list draws every
    cell's pool in ONE call.  Either way each entry's uniforms come from its
    own ``np.random.default_rng(seed)`` stream in the reference draw order
    (pool first, then the conditioned first draw), so entry ``i`` sees
    exactly the uniforms the serial per-scenario path would see for
    ``(dists[i], seed_i)``.  The inverse CDF then runs as one on-device
    bisection over all ``S * n_trials * (max_restarts + 2)`` lifetimes —
    replacing S per-scenario numpy round-trips — by stacking each
    entry's launch-phase-resolved parameters to ``(S, 1)`` so the
    distribution methods broadcast over the trailing draw axis.

    Exactness: per-entry parameters are resolved with the same scalar
    eager ops as ``checkpointing.model_lifetimes_fn`` (``effective()`` for
    the diurnal family), so under x64 every slice reproduces the
    numpy-reference pool bit-for-bit; in default float32 mode slices agree
    to float32 precision (~1e-6), far below Monte-Carlo noise.
    """
    dists = list(dists)
    dtype = jnp.result_type(float)
    # normalize leaves first (as model_lifetimes_fn does), then resolve any
    # launch-phase modulation with the same scalar eager ops the reference
    # sampler performs at trace time; finally stack to (S, 1)
    norm = [jax.tree_util.tree_map(lambda l: jnp.asarray(l, dtype), d)
            for d in dists]
    eff = [d.effective() if hasattr(d, "effective") else d for d in norm]
    d_b = jax.tree_util.tree_map(
        lambda *ls: jnp.stack(ls)[:, None], *eff)
    S = len(dists)
    n_pool = n_trials * (max_restarts + 2)
    if np.ndim(seed) == 0:
        rng = np.random.default_rng(seed)
        u_pool = np.broadcast_to(rng.uniform(size=n_pool), (S, n_pool))
        u_first = np.broadcast_to(rng.uniform(size=n_trials), (S, n_trials))
    else:
        seed = list(seed)
        if len(seed) != S:
            raise ValueError(f"per-entry seeds need one seed per entry: got "
                             f"{len(seed)} seeds for {S} distributions")
        # entries sharing a seed see the same reference stream — draw each
        # unique seed's uniforms once; the big pool block is fanned out to
        # the S entries on DEVICE (upload unique rows + take) instead of
        # materializing an S-times-duplicated host copy.  take is an exact
        # copy, so entry i's uniforms are bit-identical to its stream's.
        draws, order = {}, []
        for s in seed:
            if s not in draws:
                r = np.random.default_rng(s)
                draws[s] = (len(order), r.uniform(size=n_pool),
                            r.uniform(size=n_trials))
                order.append(s)
        u_pool = jnp.take(
            jnp.asarray(np.stack([draws[s][1] for s in order])),
            jnp.asarray([draws[s][0] for s in seed]), axis=0)
        u_first = np.stack([draws[s][2] for s in seed])
    # scalar pre/post quantities, per entry, as the numpy reference
    fl = np.array([float(d.cdf(d.L)) for d in eff])[:, None]
    L = np.array([float(d.L) for d in eff])[:, None]
    pool = capped_icdf_draw(d_b, u_pool, fl, L)
    if start_age > 0:
        f_lo = np.array([float(d.cdf(start_age)) for d in eff])[:, None]
    else:
        f_lo = np.zeros((S, 1))
    first = capped_icdf_draw(d_b, f_lo + u_first * (1.0 - f_lo), fl, L)
    return first, pool.reshape(S, n_trials, max_restarts + 2)


# ---------------------------------------------------------------------------
# market dollars: the price-grid gather
# ---------------------------------------------------------------------------

@jax.jit
def _price_cost_kernel(prices, cum, sidx, m, dt):
    """Batched gather for ``integral_0^m p`` against a precomputed ``(S, T)``
    price grid: ``k = floor(m/dt)`` (tail-clamped) per trial, returning
    ``(cum[s, k], prices[s, k], k)``.  The kernel deliberately stops at the
    gathers — the partial-cell arithmetic ``cum + prices * (m - k*dt)`` runs
    in host float64 (``accumulate_price_cost``): inside the fused kernel
    XLA:CPU contracts the multiply-subtract / multiply-add pairs into FMAs
    that round once where the serial reference
    ``market.integrate_cost_ref`` rounds twice — 1-ulp mismatches that break
    the x64 bit-identity contract (``lax.optimization_barrier`` does not
    reliably stop the contraction).  Like ``_capped_icdf_kernel``, this is
    ONE module-level jitted kernel taking its tensors as arguments: the
    compiled gather is cached per shape/dtype, never re-traced per sweep
    (``tests/test_market.py`` spies on it)."""
    Tn = prices.shape[1]
    m0 = jnp.where(jnp.isnan(m), 0.0, m)
    k = jnp.clip(jnp.floor(m0 / dt).astype(jnp.int32), 0, Tn - 1)
    s = sidx[:, None]
    return cum[s, k], prices[s, k], k


def accumulate_price_cost(grid, makespans, price_index=None) -> np.ndarray:
    """Dollars per trial for ``(B, n_trials)`` makespans billed against a
    ``market.PriceGrid``: lane ``b`` integrates price row
    ``price_index[b]`` (identity when omitted) over ``[0, m)``.  NaN
    makespans (unfinished trials) stay NaN.  The whole batch is one jitted
    gather dispatch; under x64 every element is bit-identical to the
    retained serial reference ``market.integrate_cost_ref`` — the
    established reference/production contract (see the module docstring).
    """
    m = np.atleast_2d(np.asarray(makespans, np.float64))
    B = m.shape[0]
    if price_index is None:
        price_index = np.arange(B, dtype=np.int32)
    sidx = np.broadcast_to(np.asarray(price_index, np.int32), (B,))
    if sidx.size and (sidx.min() < 0 or sidx.max() >= len(grid.prices)):
        raise ValueError("price_index out of range for the price grid")
    dtype = jnp.result_type(float)
    base, pk, k = _price_cost_kernel(
        jnp.asarray(grid.prices, dtype), jnp.asarray(grid.cum, dtype),
        jnp.asarray(sidx), jnp.asarray(m, dtype),
        jnp.asarray(float(grid.dt), dtype))
    # partial-cell arithmetic in host float64 — the same IEEE rounding
    # sequence as the serial reference's
    # ``cum[k] + prices[k] * (m - k*dt)`` (see the kernel docstring)
    base = np.asarray(base, np.float64)
    pk = np.asarray(pk, np.float64)
    kf = np.asarray(k, np.int64).astype(np.float64)
    frac = m - kf * np.float64(grid.dt)
    out = base + pk * frac
    out[np.isnan(m)] = np.nan
    return out if np.ndim(makespans) > 1 else out[0]


# ---------------------------------------------------------------------------
# the event kernel
# ---------------------------------------------------------------------------

def _event_loop(policy_lookup, pool_lookup, first_steps, job_steps, age0_idx,
                delta_steps, max_restarts, max_events):
    """THE makespan event loop — one ``lax.while_loop`` over events, all
    state in (n_trials,) vectors; every executor kernel is this loop with a
    different pair of lookups (``policy_lookup(rem, age) -> interval``,
    ``pool_lookup(draw) -> next lifetime``), so the traced operations per
    trial — the bit-exactness contract — live in exactly one place.

    Works entirely in grid-step units: lifetimes arrive pre-converted to
    steps (initial sub-grid age offset already removed), VM age is an integer
    grid index, and the only float accumulation is the sum of preempted
    partial segments.  The loop body therefore contains no multiply-add
    pattern XLA could contract into an FMA — given a shared pool, a float64
    run matches the Python reference loop bit-for-bit.  Returns
    ``(done_steps, lost_steps, restarts, finished)`` — ``finished`` marks
    trials that completed all their work; the caller converts to hours.
    """
    n = first_steps.shape[0]
    fdt = first_steps.dtype

    state = dict(
        remaining=jnp.full((n,), job_steps, jnp.int32),
        age_idx=jnp.full((n,), age0_idx, jnp.int32),
        draw=jnp.zeros((n,), jnp.int32),
        life_s=first_steps,
        done_steps=jnp.zeros((n,), jnp.int32),
        lost_steps=jnp.zeros((n,), fdt),
        restarts=jnp.zeros((n,), jnp.int32),
        events=jnp.zeros((), jnp.int32),
    )

    def active(s):
        return (s["remaining"] > 0) & (s["restarts"] <= max_restarts)

    def cond(s):
        return jnp.any(active(s)) & (s["events"] < max_events)

    def body(s):
        act = active(s)
        rem, age = s["remaining"], s["age_idx"]
        i = policy_lookup(rem, age)
        i = jnp.clip(i, 1, jnp.maximum(rem, 1))
        w = jnp.where(i < rem, i + delta_steps, i)
        survive = (age + w).astype(fdt) <= s["life_s"]
        # preemption: time since VM start minus checkpointed prefix is lost
        loss = jnp.maximum(s["life_s"] - age.astype(fdt), 0.0)
        nxt_draw = s["draw"] + 1
        nxt_life = pool_lookup(nxt_draw)

        def upd(old, succ_val, fail_val):
            return jnp.where(act, jnp.where(survive, succ_val, fail_val), old)

        return dict(
            remaining=upd(rem, rem - i, rem),
            age_idx=upd(age, age + w, jnp.zeros((), jnp.int32)),
            draw=upd(s["draw"], s["draw"], nxt_draw),
            life_s=upd(s["life_s"], s["life_s"], nxt_life),
            done_steps=upd(s["done_steps"], s["done_steps"] + w,
                           s["done_steps"]),
            lost_steps=upd(s["lost_steps"], s["lost_steps"],
                           s["lost_steps"] + loss),
            restarts=upd(s["restarts"], s["restarts"], s["restarts"] + 1),
            events=s["events"] + 1,
        )

    out = jax.lax.while_loop(cond, body, state)
    return (out["done_steps"], out["lost_steps"], out["restarts"],
            out["remaining"] == 0)


@jax.jit
def _makespan_kernel(policy, first_steps, pool_steps, job_steps, age0_idx,
                     delta_steps, max_restarts, max_events):
    """:func:`_event_loop` with direct per-call table/pool lookups."""
    n = first_steps.shape[0]
    j_hi = policy.shape[0] - 1
    t_hi = policy.shape[1] - 1
    return _event_loop(
        lambda rem, age: policy[jnp.clip(rem, 0, j_hi),
                                jnp.clip(age, 0, t_hi)],
        lambda draw: pool_steps[jnp.arange(n),
                                jnp.minimum(draw, max_restarts + 1)],
        first_steps, job_steps, age0_idx, delta_steps, max_restarts,
        max_events)


# cell-batched kernels: vmap the event loop over the leading (B,) axis.
# The while_loop batching rule freezes finished slices with selects, so each
# cell slice performs the reference IEEE operations — on a shared pool a
# float64 slice is bit-identical to the unbatched kernel.
_KERNEL_SCALARS = (None,) * 5
_makespan_kernel_batch = jax.jit(jax.vmap(
    _makespan_kernel.__wrapped__, in_axes=(0, 0, 0) + _KERNEL_SCALARS))
_makespan_kernel_batch_shared = jax.jit(jax.vmap(
    _makespan_kernel.__wrapped__, in_axes=(None, 0, 0) + _KERNEL_SCALARS))


def _makespan_kernel_cell(policy_u, tidx, pool_all, pidx, first_steps,
                          job_steps, age0_idx, delta_steps, max_restarts,
                          max_events):
    """One lane of the deduplicated one-kernel fold: the :func:`_event_loop`
    reading the policy via ``policy_u[tidx]`` and the pool via
    ``pool_all[pidx]`` instead of materialized per-lane copies.

    Vmapped over ``(tidx, pidx, first_steps)`` with the unique-table tensor
    ``(U, j_max+1, t_max+1)`` and the unique-pool tensor ``(Q, n_trials,
    max_restarts+2)`` UNBATCHED, the whole sweep's gathers hit tens of MB
    instead of the ``B``-times-replicated tensors, so the fold's memory
    traffic grows with the unique tables and pools, not with the lane count.
    Per lane the lookups return the very same integers/floats, so the
    bit-exactness contract is untouched.
    """
    n = first_steps.shape[0]
    j_hi = policy_u.shape[1] - 1
    t_hi = policy_u.shape[2] - 1
    return _event_loop(
        lambda rem, age: policy_u[tidx, jnp.clip(rem, 0, j_hi),
                                  jnp.clip(age, 0, t_hi)],
        lambda draw: pool_all[pidx, jnp.arange(n),
                              jnp.minimum(draw, max_restarts + 1)],
        first_steps, job_steps, age0_idx, delta_steps, max_restarts,
        max_events)


_makespan_kernel_indexed = jax.jit(jax.vmap(
    _makespan_kernel_cell,
    in_axes=(None, 0, None, 0, 0) + _KERNEL_SCALARS))


def simulate_makespan_batch(policy_table, job_steps: int, *, first, pool,
                            grid_dt: float = 1.0 / 60.0, delta_steps: int = 1,
                            start_age: float = 0.0,
                            restart_overhead: float = 0.0,
                            max_restarts: int = 64,
                            max_events: int | None = None,
                            unfinished: str = "nan",
                            return_finished: bool = False,
                            table_index=None, pool_index=None,
                            price=None, price_index=None):
    """Vectorized executor over a shared pre-drawn lifetime pool.

    Semantics are identical to the Python reference
    ``checkpointing.simulate_makespan``: a preemption mid-segment (work or
    checkpoint write) loses progress back to the last durable checkpoint and
    the job resumes on a fresh VM after ``restart_overhead`` hours.  Returns
    makespans (hours), shape ``(n_trials,)``.

    Cell batching (leading-axis convention): when ``pool`` has a leading
    cell axis — shape ``(B, n_trials, max_restarts + 2)``, with ``first``
    of shape ``(B, n_trials)`` — the event kernel is vmapped over it and
    the result is ``(B, n_trials)``.  ``policy_table`` may then be either
    per-cell ``(B, j_max+1, t_axis)`` (see :func:`stack_policy_tables`) or
    a shared 2-D table.  The axis can be a scenario batch or a flattened
    (scenario x policy x seed) sweep grid — each lane keeps the
    bit-exactness contract in the module docstring either way.

    Deduplicated fold (``table_index``/``pool_index``): a sweep grid
    replicates tables across seeds and pools across policies.  Passing
    ``table_index`` (shape ``(B,)`` into a ``(U, j_max+1, t_axis)``
    ``policy_table`` of *unique* tables) and ``pool_index`` (shape ``(B,)``
    into a ``(Q, n_trials, max_restarts + 2)`` ``pool`` of *unique* pools,
    with ``first`` still per-cell ``(B, n_trials)``) runs the same B lanes
    while the kernel gathers from the compact tensors — avoiding both the
    host-side replication and the cache-hostile reads of B-times-duplicated
    data.  Lane ``b`` computes bit-identically to the materialized
    ``policy_table[table_index[b]]`` / ``pool[pool_index[b]]`` call.

    Trials can exit the event loop *unfinished* — either their ``max_restarts``
    budget is exhausted or the whole batch hits the ``max_events`` safety cap.
    ``unfinished`` selects how those trials are reported:

    * ``"nan"`` (default) — the makespan is NaN, so a truncated trial can
      never silently pass for a completed one in downstream statistics;
    * ``"partial"`` — the accumulated ``done + lost`` time is returned, which
      is exactly what the Python reference loop yields on restart exhaustion;
    * ``"raise"`` — a ``RuntimeError`` naming the count of unfinished trials.

    ``return_finished=True`` additionally returns the boolean completion mask
    (shape ``(n_trials,)``), regardless of ``unfinished`` mode.

    Market dollars (``price=``): a ``market.PriceGrid`` bills every trial's
    makespan — the checkpointing executor runs one VM at a time, so a
    trial's vm_hours IS its makespan — by integrating its price row over
    ``[0, m)`` through :func:`accumulate_price_cost` (one batched gather
    against the precomputed grid; ``price_index`` maps cells to grid rows,
    identity when omitted).  The dollars array is appended to the return
    value: ``(mk, dollars)``, or ``(mk, finished, dollars)`` with
    ``return_finished=True``.  NaN-flagged trials cost NaN.
    """
    if unfinished not in ("nan", "partial", "raise"):
        raise ValueError(f"unfinished must be 'nan', 'partial' or 'raise', "
                         f"got {unfinished!r}")
    dtype = jnp.result_type(float)  # float64 under enable_x64, else float32
    if max_events is None:
        max_events = int(job_steps) + int(max_restarts) + 2
    age0_idx = int(round(start_age / grid_dt))
    off0 = start_age - age0_idx * grid_dt
    # unit conversion in float64 numpy, identical to the reference loop
    first_steps = (np.asarray(first, np.float64) - off0) / grid_dt
    pool_steps = np.asarray(pool, np.float64) / grid_dt
    table = np.asarray(policy_table, np.int32)
    scalars = (jnp.int32(job_steps), jnp.int32(age0_idx),
               jnp.int32(delta_steps), jnp.int32(max_restarts),
               jnp.int32(max_events))
    if (table_index is None) != (pool_index is None):
        raise ValueError("table_index and pool_index must be passed together")
    if table_index is not None:
        tix = np.asarray(table_index, np.int32)
        pix = np.asarray(pool_index, np.int32)
        if table.ndim != 3 or pool_steps.ndim != 3:
            raise ValueError("the indexed fold needs a (U, j, t) policy_table "
                             "and a (Q, n_trials, max_restarts + 2) pool")
        if first_steps.ndim != 2 \
                or not (tix.shape == pix.shape == first_steps.shape[:1]) \
                or first_steps.shape[1] != pool_steps.shape[1]:
            raise ValueError(
                f"indexed fold needs first of shape (B, n_trials) with "
                f"(B,) table_index/pool_index and a matching pool trial "
                f"axis; got first {first_steps.shape}, pool "
                f"{pool_steps.shape}, table_index {tix.shape}, "
                f"pool_index {pix.shape}")
        if tix.size and (tix.min() < 0 or tix.max() >= table.shape[0]):
            raise ValueError("table_index out of range")
        if pix.size and (pix.min() < 0 or pix.max() >= pool_steps.shape[0]):
            raise ValueError("pool_index out of range")
        done, lost, restarts, finished = _makespan_kernel_indexed(
            jnp.asarray(table), jnp.asarray(tix),
            jnp.asarray(pool_steps, dtype), jnp.asarray(pix),
            jnp.asarray(first_steps, dtype), *scalars)
    else:
        if pool_steps.ndim == 3:             # leading cell axis
            if first_steps.shape != pool_steps.shape[:2]:
                raise ValueError(
                    f"scenario-batched pool {pool_steps.shape} needs first of "
                    f"shape {pool_steps.shape[:2]}, got {first_steps.shape}")
            kernel = (_makespan_kernel_batch if table.ndim == 3
                      else _makespan_kernel_batch_shared)
        elif table.ndim == 3:
            raise ValueError("per-scenario policy_table needs a "
                             "scenario-batched pool "
                             "(S, n_trials, max_restarts + 2)")
        else:
            kernel = _makespan_kernel
        done, lost, restarts, finished = kernel(
            jnp.asarray(table),
            jnp.asarray(first_steps, dtype), jnp.asarray(pool_steps, dtype),
            *scalars)
    done = np.asarray(done, np.float64)
    lost = np.asarray(lost, np.float64)
    restarts = np.asarray(restarts, np.float64)
    finished = np.asarray(finished, bool)
    out = (done + lost) * grid_dt + restarts * restart_overhead
    if not finished.all():
        if unfinished == "raise":
            raise RuntimeError(
                f"{int((~finished).sum())}/{finished.size} trials exited "
                f"unfinished (max_restarts={max_restarts}, "
                f"max_events={max_events})")
        if unfinished == "nan":
            out = np.where(finished, out, np.nan)
    if price is None:
        if price_index is not None:
            raise ValueError("price_index needs price= (a market.PriceGrid)")
        if return_finished:
            return out, finished
        return out
    dollars = accumulate_price_cost(price, out, price_index)
    if return_finished:
        return out, finished, dollars
    return out, dollars


def simulate_makespan_engine(policy_table, lifetimes_fn, job_steps: int, *,
                             grid_dt: float = 1.0 / 60.0, delta_steps: int = 1,
                             start_age: float = 0.0, n_trials: int = 2000,
                             seed: int = 0, restart_overhead: float = 0.0,
                             max_restarts: int = 64, **kw):
    """Drop-in vectorized replacement for ``checkpointing.simulate_makespan``
    (same sampler protocol, same seed -> same lifetime draws).  Extra
    keywords (``unfinished``, ``return_finished``, ``max_events``) pass
    through to :func:`simulate_makespan_batch`; with
    ``return_finished=True`` the result is a ``(makespans, finished)``
    tuple instead of a bare array."""
    first, pool = draw_lifetime_pool(lifetimes_fn, n_trials,
                                     max_restarts=max_restarts, seed=seed,
                                     start_age=start_age)
    return simulate_makespan_batch(policy_table, job_steps, first=first,
                                   pool=pool, grid_dt=grid_dt,
                                   delta_steps=delta_steps,
                                   start_age=start_age,
                                   restart_overhead=restart_overhead,
                                   max_restarts=max_restarts, **kw)


# ---------------------------------------------------------------------------
# batched reuse decisions for the service simulator
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("n_age",))
def _reuse_grid(dist, T_values, L, n_age):
    age = jnp.linspace(0.0, L, n_age)
    return sched_policy.reuse_decision(dist, T_values[:, None], age[None, :])


@functools.partial(jax.jit, static_argnames=("n_age",))
def _reuse_grid_batch(dist, T_values, L, n_age):
    """(S,)-stacked distribution -> (S, len(T_values), n_age) decisions in
    one compiled call (vmap of the per-scenario grid)."""
    return jax.vmap(
        lambda d: _reuse_grid.__wrapped__(d, T_values, L, n_age))(dist)


class ReuseTable:
    """Precomputed reuse decisions over (remaining work x VM age).

    One jitted call evaluates Eq. 10 < Eq. 9 for every grid point; lookups
    from the service's event loop are then pure numpy indexing.  ``T_values``
    is exact in the remaining-work axis (pass the actual job lengths when
    they are known, e.g. a non-checkpointing bag); ages are quantized to
    ``n_age`` points over [0, L] (nearest), 1-min resolution by default.
    """

    def __init__(self, dist, T_values, *, n_age: int = 1441, _table=None):
        self.T_values = np.asarray(np.sort(np.unique(T_values)), np.float64)
        self.L = float(np.asarray(dist.L).reshape(-1)[0])
        self.n_age = int(n_age)
        self.table = np.asarray(_reuse_grid(
            dist, jnp.asarray(self.T_values), self.L, self.n_age)) \
            if _table is None else np.asarray(_table)

    @classmethod
    def batch(cls, dists, T_values, *, n_age: int = 1441) -> list:
        """Build one table per scenario from a SINGLE vmapped grid call
        (leading-axis convention; the scenarios must share ``L``).  Returns
        a list of per-scenario :class:`ReuseTable` views, interchangeable
        with individually constructed ones.  The views share one backing
        tensor — see :class:`ReuseTables`, which this wraps."""
        return list(ReuseTables(dists, T_values, n_age=n_age))

    def decide(self, remaining_work: float, vm_age: float) -> bool:
        ti = int(np.searchsorted(self.T_values, remaining_work))
        if ti >= len(self.T_values) or (
                ti > 0 and remaining_work - self.T_values[ti - 1]
                < self.T_values[ti] - remaining_work):
            ti -= 1
        ai = int(round(vm_age / self.L * (self.n_age - 1)))
        return bool(self.table[ti, min(max(ai, 0), self.n_age - 1)])


class ReuseTables:
    """The folded scenario batch of reuse-decision grids.

    ONE vmapped grid call evaluates every scenario's (remaining-work x
    VM-age) Eq. 10-vs-Eq. 9 decisions into a single ``(S, len(T_values),
    n_age)`` boolean tensor; :meth:`view` (or indexing/iteration) returns
    per-scenario :class:`ReuseTable` views that *share* that backing tensor,
    so a whole service sweep costs one JAX dispatch and one allocation no
    matter how many (policy x cluster x seed) cells later consume each
    scenario's grid.  All scenarios must share the deadline ``L``.
    """

    def __init__(self, dists, T_values, *, n_age: int = 1441):
        self._dists = list(dists)
        if not self._dists:
            raise ValueError("ReuseTables needs at least one distribution")
        L = float(self._dists[0].L)
        if any(abs(float(d.L) - L) > 1e-12 for d in self._dists[1:]):
            raise ValueError("ReuseTables requires a shared L")
        self.T_values = np.asarray(np.sort(np.unique(T_values)), np.float64)
        self.L = L
        self.n_age = int(n_age)
        self.tables = np.asarray(_reuse_grid_batch(
            dists_mod.stack(self._dists), jnp.asarray(self.T_values), L,
            self.n_age))

    def __len__(self) -> int:
        return len(self._dists)

    def view(self, s: int) -> ReuseTable:
        """A per-scenario :class:`ReuseTable` over the shared tensor."""
        return ReuseTable(self._dists[s], self.T_values, n_age=self.n_age,
                          _table=self.tables[s])

    def __getitem__(self, s: int) -> ReuseTable:
        return self.view(s)

    def __iter__(self):
        return (self.view(s) for s in range(len(self)))
