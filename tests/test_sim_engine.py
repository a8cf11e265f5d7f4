"""Vectorized-engine tests: the batched JAX Monte-Carlo executor must agree
with the retained Python reference (bit-for-bit on a shared lifetime pool in
float64), the table-driven batch service must match the exact-dispatch
service distributionally, and the simulator fast paths must preserve
values."""
import jax
import numpy as np
import pytest

from repro.core import distributions as D
from repro.core import engine as E
from repro.core import service as SV
from repro.core import simulator as SIM
from repro.core.policies import checkpointing as C
from repro.core.policies import young_daly as YD

GRID = 1.0 / 60.0
JOB = 300  # 5h job


@pytest.fixture(scope="module")
def dist():
    return D.constrained_for("n1-highcpu-16")


@pytest.fixture(scope="module")
def tables(dist):
    return C.solve(dist, JOB, grid_dt=GRID, delta_steps=1, n_sweeps=3)


def _policies(tables):
    tau = float(YD.interval(GRID, 1.0))
    tau_steps = max(1, int(round(tau / GRID)))
    return [
        ("dp", C.dp_policy_fn(tables), E.dp_policy_table(tables)),
        ("young_daly", C.young_daly_policy_fn(tau, GRID),
         E.young_daly_policy_table(tau_steps, JOB)),
        ("none", C.no_checkpoint_policy_fn(), E.no_checkpoint_policy_table(JOB)),
    ]


# ---------------------------------------------------------------------------
# executor equivalence
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("start_age,restart_overhead",
                         [(0.0, 0.0), (6.0, 2.0 / 60.0), (15.25, 0.0)])
def test_vectorized_executor_exact_vs_reference(dist, tables, start_age,
                                                restart_overhead):
    """Same pre-drawn pool, float64 kernel: makespans must be IDENTICAL."""
    lf = C.model_lifetimes_fn(dist)
    first, pool = E.draw_lifetime_pool(lf, 300, seed=7, start_age=start_age)
    for name, policy_fn, table in _policies(tables):
        ref = C.simulate_makespan(policy_fn, lf, JOB, grid_dt=GRID,
                                  delta_steps=1, start_age=start_age,
                                  restart_overhead=restart_overhead,
                                  pool=pool, first=first)
        with jax.enable_x64(True):
            vec = E.simulate_makespan_batch(
                table, JOB, first=first, pool=pool, grid_dt=GRID,
                delta_steps=1, start_age=start_age,
                restart_overhead=restart_overhead)
        assert np.array_equal(ref, vec), \
            f"{name}: max diff {np.abs(ref - vec).max()}"


def test_vectorized_executor_float32_close(dist, tables):
    """Default float32 kernel: agreement to well below Monte-Carlo noise."""
    lf = C.model_lifetimes_fn(dist)
    first, pool = E.draw_lifetime_pool(lf, 300, seed=3)
    table = E.dp_policy_table(tables)
    ref = C.simulate_makespan(C.dp_policy_fn(tables), lf, JOB, grid_dt=GRID,
                              pool=pool, first=first)
    vec = E.simulate_makespan_batch(table, JOB, first=first, pool=pool,
                                    grid_dt=GRID)
    np.testing.assert_allclose(vec, ref, rtol=1e-4)


def test_engine_seed_matches_reference_draws(dist, tables):
    """simulate_makespan_engine(seed) must consume the same lifetimes as
    simulate_makespan(seed) - drop-in replacement contract."""
    lf = C.model_lifetimes_fn(dist)
    ref = C.simulate_makespan(C.dp_policy_fn(tables), lf, JOB, grid_dt=GRID,
                              n_trials=200, seed=42)
    vec = E.simulate_makespan_engine(E.dp_policy_table(tables), lf, JOB,
                                     grid_dt=GRID, n_trials=200, seed=42)
    np.testing.assert_allclose(vec, ref, rtol=1e-4)


def test_executor_trivial_cases(dist, tables):
    """A job that always fits its first VM takes exactly its work time plus
    checkpoint writes; pool exhaustion terminates."""
    table = E.no_checkpoint_policy_table(60)
    first = np.full((8,), 24.0)
    pool = np.full((8, 66), 24.0)
    out = E.simulate_makespan_batch(table, 60, first=first, pool=pool,
                                    grid_dt=GRID)
    np.testing.assert_allclose(out, 1.0, rtol=1e-6)  # 60 steps, no ckpt
    # immortal failure loop: every VM dies at 0.5h, job needs 1h contiguous;
    # unfinished="partial" is the Python reference's restart-exhaustion value
    first = np.full((4,), 0.5)
    pool = np.full((4, 66), 0.5)
    out = E.simulate_makespan_batch(table, 60, first=first, pool=pool,
                                    grid_dt=GRID, max_restarts=16,
                                    unfinished="partial")
    np.testing.assert_allclose(out, 0.5 * 17, rtol=1e-5)  # 17 failed attempts


def test_executor_restart_exhaustion_is_flagged(dist, tables):
    """Trials that run out of restarts must never masquerade as completed:
    NaN by default, partial time matching the Python reference on request,
    error on 'raise', and an explicit mask via return_finished."""
    table = E.no_checkpoint_policy_table(60)
    # trials 0/2 finish on the first VM; trials 1/3 can never finish
    first = np.array([24.0, 0.5, 24.0, 0.5])
    pool = np.tile(np.array([24.0, 0.5, 24.0, 0.5])[:, None], (1, 66))
    kw = dict(first=first, pool=pool, grid_dt=GRID, max_restarts=16)
    out, finished = E.simulate_makespan_batch(table, 60, return_finished=True,
                                              **kw)
    assert finished.tolist() == [True, False, True, False]
    np.testing.assert_allclose(out[finished], 1.0, rtol=1e-6)
    assert np.isnan(out[~finished]).all()
    # 'partial' reproduces the reference loop's value for the same pool
    ref = C.simulate_makespan(C.no_checkpoint_policy_fn(), None, 60,
                              grid_dt=GRID, max_restarts=16, pool=pool,
                              first=first)
    part = E.simulate_makespan_batch(table, 60, unfinished="partial", **kw)
    np.testing.assert_allclose(part, ref, rtol=1e-5)
    with pytest.raises(RuntimeError, match="2/4 trials"):
        E.simulate_makespan_batch(table, 60, unfinished="raise", **kw)
    with pytest.raises(ValueError):
        E.simulate_makespan_batch(table, 60, unfinished="bogus", **kw)


def test_executor_max_events_truncation_is_flagged(dist, tables):
    """An undersized max_events cap truncates even finishable trials — the
    engine must flag them instead of returning the partial makespan."""
    table = E.young_daly_policy_table(10, 60)
    first = np.full((4,), 24.0)
    pool = np.full((4, 66), 24.0)
    out, finished = E.simulate_makespan_batch(
        table, 60, first=first, pool=pool, grid_dt=GRID, max_events=3,
        return_finished=True)
    assert not finished.any()
    assert np.isnan(out).all()
    # a sufficient cap finishes the same workload
    out2 = E.simulate_makespan_batch(table, 60, first=first, pool=pool,
                                     grid_dt=GRID, unfinished="raise")
    assert np.isfinite(out2).all()


# ---------------------------------------------------------------------------
# batch service
# ---------------------------------------------------------------------------

def test_service_table_matches_exact_distributionally():
    """Table-driven reuse decisions vs per-candidate exact dispatches: the
    service-level metrics must agree within (tight) statistical tolerance."""
    dist = D.constrained_for("n1-highcpu-32")
    seeds = range(4)
    kw = dict(n_jobs=40, job_hours=2.0, cluster_size=8)
    exact = [SV.run_bag(dist, seed=s, vectorized_reuse=False, **kw)
             for s in seeds]
    table = [SV.run_bag(dist, seed=s, **kw) for s in seeds]
    for r in table:
        assert all(j.finished is not None for j in r.jobs)
    cost_e = np.mean([r.cost for r in exact])
    cost_t = np.mean([r.cost for r in table])
    np.testing.assert_allclose(cost_t, cost_e, rtol=0.05)
    mk_e = np.mean([r.makespan for r in exact])
    mk_t = np.mean([r.makespan for r in table])
    np.testing.assert_allclose(mk_t, mk_e, rtol=0.05)


def test_reuse_table_matches_pointwise_policy():
    """ReuseTable.decide == scheduling.reuse_decision on its own grid."""
    from repro.core.policies import scheduling as S

    dist = D.constrained_for("n1-highcpu-32")
    T_vals = np.array([0.5, 1.0, 2.0, 4.0])
    rt = E.ReuseTable(dist, T_vals, n_age=97)
    for T in T_vals:
        for age in np.linspace(0.0, 23.9, 13):
            # quantize age exactly onto the table's grid for the comparison
            ai = int(round(age / rt.L * (rt.n_age - 1)))
            age_q = ai * rt.L / (rt.n_age - 1)
            assert rt.decide(T, age) == bool(
                S.reuse_decision(dist, T, age_q)), (T, age)


def test_run_bag_grid_cells_match_run_bag():
    """Each grid cell equals the corresponding run_bag call when both use
    the same shared reuse table."""
    dist = D.constrained_for("n1-highcpu-32")
    rows = SV.run_bag_grid(vm_types=("n1-highcpu-32",),
                           policies=("model", "memoryless"),
                           cluster_sizes=(8,), seeds=(0, 1), n_jobs=30,
                           job_hours=2.0)
    assert len(rows) == 4
    for row in rows:
        if row["policy"] != "memoryless":
            continue
        # memoryless makes no reuse decisions: must match run_bag exactly
        r_ref = SV.run_bag(dist, n_jobs=30, job_hours=2.0, cluster_size=8,
                           policy="memoryless", seed=row["seed"])
        assert row["result"].makespan == r_ref.makespan
        assert row["result"].cost == r_ref.cost


def test_service_rebuilds_table_for_new_lengths():
    """A second run() with different job lengths must not reuse the first
    run's auto-built table (its T-grid would miss the new lengths)."""
    dist = D.constrained_for("n1-highcpu-32")
    svc = SV.BatchService(dist, cluster_size=8, seed=0)
    svc.run([2.0] * 10)
    t_first = svc._run_reuse_table
    svc.run([0.5] * 10)
    assert svc._run_reuse_table is not t_first
    assert 0.5 in svc._run_reuse_table.T_values
    # exact-dispatch agreement for the short bag
    svc_exact = SV.BatchService(dist, cluster_size=8, seed=0,
                                vectorized_reuse=False)
    r_e = svc_exact.run([0.5] * 10)
    assert all(j.finished is not None for j in r_e.jobs)


def test_service_event_heap_keys_unique(monkeypatch):
    """Every event (finish/preempt/expire) must carry a distinct monotonic
    seq tiebreaker: the old expire key ``len(jobs) + vm_id`` could collide
    with early seq values, making same-timestamp ordering nondeterministic."""
    import heapq

    keys = []
    orig = heapq.heappush

    def record(heap, item):
        if isinstance(item, tuple) and len(item) == 4:
            keys.append(item[:2])
        return orig(heap, item)

    monkeypatch.setattr(heapq, "heappush", record)
    dist = D.constrained_for("n1-highcpu-32")
    r = SV.run_bag(dist, n_jobs=30, job_hours=2.0, cluster_size=8, seed=0)
    assert all(j.finished is not None for j in r.jobs)
    kinds = len(keys)
    assert kinds > 30, "expected finish+preempt+expire events to be recorded"
    assert len(set(keys)) == kinds, "heap keys (time, seq) must be unique"
    seqs = [s for _, s in keys]
    assert len(set(seqs)) == len(seqs), "seq tiebreakers must never repeat"


# ---------------------------------------------------------------------------
# simulator fast paths
# ---------------------------------------------------------------------------

def test_ground_truth_grid_cached():
    gt1 = SIM.ground_truth_for("n1-highcpu-16")
    gt2 = SIM.ground_truth_for("n1-highcpu-16")
    t1, F1 = gt1._grid()
    t2, F2 = gt2._grid()
    assert t1 is t2 and F1 is F2, "identical processes must share one grid"
    # different parameters => different grid
    t3, F3 = SIM.ground_truth_for("n1-highcpu-32")._grid()
    assert F3 is not F1


def test_grid_cache_consistent_with_compute():
    gt = SIM.ground_truth_for("n1-highcpu-8", launch_clock=3.0)
    t_c, F_c = gt._grid()
    t_r, F_r = gt._grid_compute()
    np.testing.assert_array_equal(np.asarray(F_c), np.asarray(F_r))


def test_fleet_trace_grouped_sampling_statistics():
    """Grouped per-type sampling: each type's lifetimes follow its own
    process (KS-style bound against the type's own CDF)."""
    tr = SIM.generate_fleet_trace(jax.random.PRNGKey(0), n_vms=1000)
    life = np.asarray(tr.lifetime)
    types = np.asarray(tr.vm_type_idx)
    assert life.shape == (1000,) and life.min() > 0 and life.max() <= 24.0
    vm_types = ("n1-highcpu-2", "n1-highcpu-4", "n1-highcpu-8",
                "n1-highcpu-16", "n1-highcpu-32")
    for ti, name in enumerate(vm_types):
        sel = life[types == ti]
        assert sel.size > 100  # ~200 expected per type
        gt = SIM.ground_truth_for(name)  # clock-averaged check, loose bound
        emp = (sel < 3.0).mean()
        model = float(gt.cdf(3.0))
        assert abs(emp - model) < 0.12, (name, emp, model)
    # Obs. 4 ordering: larger VMs die earlier on average
    means = [life[types == ti].mean() for ti in range(5)]
    assert means[0] > means[4]
