"""Batched scenario axis + the PR-4 one-kernel fold: distribution stacking,
the batched DP solver, the device lifetime pools, the cell-batched executor
(including the deduplicated table/pool indexing) and the folded ReuseTables.

The core contracts under test:

  * ``checkpointing.solve_batch`` matches the per-scenario reference
    ``checkpointing.solve`` table-for-table (bit-exact V and K) on the full
    default scenario grid — the batched kernel restructures the loop but
    keeps the reference expression tree;
  * ``engine.draw_lifetime_pool_batch`` slices reproduce the numpy-reference
    ``engine.draw_lifetime_pool`` under a shared seed — and under PER-ENTRY
    seeds, entry ``i`` reproduces the reference draw for ``seed_i`` (bit-exact
    under x64, float32-close otherwise);
  * a cell-batched ``engine.simulate_makespan_batch`` keeps the float64
    bit-exactness contract per lane on a shared pool, whether lanes are
    materialized ``(B, ...)`` slices or ``table_index``/``pool_index``
    gathers into deduplicated tensors;
  * ``scenarios.sweep_checkpointing(mode="batched")`` — the whole
    (scenario x policy x seed) grid in ONE executor dispatch — unflattens
    to rows that are exactly the serial reference's rows (property test,
    x64, NaN-flagged unfinished trials included).
"""
import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import distributions as D
from repro.core import engine as E
from repro.core import scenarios as SC
from repro.core.policies import checkpointing as C

GRID = 1.0 / 60.0


@pytest.fixture(scope="module")
def grid_dists():
    return [sc.dist() for sc in SC.default_grid()]


# ---------------------------------------------------------------------------
# distribution stacking
# ---------------------------------------------------------------------------

def test_stack_leading_axis_and_vmap(grid_dists):
    stacked = D.stack(grid_dists)
    S = len(grid_dists)
    assert type(stacked) is type(grid_dists[0])
    for leaf in jax.tree_util.tree_leaves(stacked):
        assert leaf.shape[:1] == (S,)
    t = jnp.linspace(0.0, 24.0, 33)
    batched = jax.vmap(lambda d: d.cdf(t))(stacked)
    assert batched.shape == (S, 33)
    for s, d in enumerate(grid_dists):
        np.testing.assert_allclose(np.asarray(batched[s]),
                                   np.asarray(d.cdf(t)), rtol=1e-6,
                                   atol=1e-7)


def test_stack_unstack_roundtrip(grid_dists):
    back = D.unstack(D.stack(grid_dists))
    assert len(back) == len(grid_dists)
    for orig, d in zip(grid_dists, back):
        assert float(d.tau1) == pytest.approx(float(orig.tau1))
        assert float(d.launch_clock) == pytest.approx(float(orig.launch_clock))


def test_stack_rejects_mixed_families_and_empty():
    with pytest.raises(TypeError):
        D.stack([D.Constrained(), D.Exponential()])
    with pytest.raises(ValueError):
        D.stack([])
    with pytest.raises(ValueError):
        D.unstack(D.Constrained())


# ---------------------------------------------------------------------------
# batched DP solver
# ---------------------------------------------------------------------------

def test_solve_batch_matches_solve_on_default_grid(grid_dists):
    """Table-for-table equivalence on the FULL default grid: every scenario
    slice of solve_batch must be bit-identical to the per-scenario solve."""
    job = 72
    batch = C.solve_batch(grid_dists, job, grid_dt=GRID)
    assert batch.V.shape == (len(grid_dists), job + 1, batch.horizon_idx + 1)
    assert len(batch) == len(grid_dists)
    for s, d in enumerate(grid_dists):
        ref = C.solve(d, job, grid_dt=GRID)
        assert np.array_equal(ref.V, batch.V[s]), f"V differs at scenario {s}"
        assert np.array_equal(ref.K, batch.K[s]), f"K differs at scenario {s}"
        view = batch.tables(s)
        assert np.array_equal(view.K, ref.K)
        assert view.expected_makespan(job) == ref.expected_makespan(job)
        assert batch.expected_makespan(s, job) == ref.expected_makespan(job)


def test_solve_batch_nondefault_workload():
    """delta_steps > 1, restart overhead and a tiny job exercise the
    final-column patch and the segment split edge cases."""
    ds = [D.constrained_for("n1-highcpu-16"), D.constrained_for("n1-highcpu-32")]
    for job, delta, ro in [(2, 1, 0.0), (25, 3, 0.1)]:
        batch = C.solve_batch(ds, job, grid_dt=1.0 / 20.0, delta_steps=delta,
                              restart_overhead=ro)
        for s, d in enumerate(ds):
            ref = C.solve(d, job, grid_dt=1.0 / 20.0, delta_steps=delta,
                          restart_overhead=ro)
            assert np.array_equal(ref.V, batch.V[s]), (job, delta, s)
            assert np.array_equal(ref.K, batch.K[s]), (job, delta, s)


def test_solve_batch_input_validation():
    with pytest.raises(ValueError):
        C.solve_batch([], 10)
    with pytest.raises(ValueError, match="shared deadline"):
        C.solve_batch([D.Constrained(), D.Constrained(L=12.0)], 10)


# ---------------------------------------------------------------------------
# batched lifetime pools
# ---------------------------------------------------------------------------

def test_pool_batch_close_to_reference(grid_dists):
    """Default float32 mode: batched pool slices match the float64 numpy
    reference to float32 precision for every scenario and seed."""
    n, mr = 200, 16
    for seed in (0, 3):
        first_b, pool_b = E.draw_lifetime_pool_batch(
            grid_dists, n, max_restarts=mr, seed=seed)
        assert first_b.shape == (len(grid_dists), n)
        assert pool_b.shape == (len(grid_dists), n, mr + 2)
        for s, d in enumerate(grid_dists):
            first, pool = E.draw_lifetime_pool(
                C.model_lifetimes_fn(d), n, max_restarts=mr, seed=seed)
            np.testing.assert_allclose(pool_b[s], pool, rtol=2e-5, atol=2e-4)
            np.testing.assert_allclose(first_b[s], first, rtol=2e-5,
                                       atol=2e-4)


@pytest.mark.slow
def test_pool_batch_bitexact_x64(grid_dists):
    """Under x64 a batched pool slice reproduces the numpy-reference pool
    bit-for-bit (shared seed, shared draw order), including the conditioned
    first draw of an aged VM."""
    n, mr = 200, 16
    with jax.enable_x64(True):
        for start_age in (0.0, 6.0):
            first_b, pool_b = E.draw_lifetime_pool_batch(
                grid_dists, n, max_restarts=mr, seed=11, start_age=start_age)
            for s, d in enumerate(grid_dists):
                first, pool = E.draw_lifetime_pool(
                    C.model_lifetimes_fn(d), n, max_restarts=mr, seed=11,
                    start_age=start_age)
                assert np.array_equal(pool, pool_b[s]), (start_age, s)
                assert np.array_equal(first, first_b[s]), (start_age, s)


def test_pool_batch_per_entry_seeds(grid_dists):
    """The (B,)-keyed seed fold: entry i of a per-entry-seeded call must
    reproduce the same distribution's single-seed batched draw for seed_i —
    the contract the one-kernel sweep's (scenario x seed) flattening rests
    on.  Also: a constant seed list equals the scalar-seed call exactly."""
    ds = grid_dists[:2]
    n, mr = 60, 6
    cells = [(d, s) for d in ds for s in (0, 7)]
    first_b, pool_b = E.draw_lifetime_pool_batch(
        [d for d, _ in cells], n, max_restarts=mr,
        seed=[s for _, s in cells])
    assert pool_b.shape == (len(cells), n, mr + 2)
    for i, (d, s) in enumerate(cells):
        ref_first, ref_pool = E.draw_lifetime_pool_batch(
            [d], n, max_restarts=mr, seed=s)
        np.testing.assert_array_equal(pool_b[i], ref_pool[0])
        np.testing.assert_array_equal(first_b[i], ref_first[0])
    f_scalar, p_scalar = E.draw_lifetime_pool_batch(ds, n, max_restarts=mr,
                                                    seed=3)
    f_list, p_list = E.draw_lifetime_pool_batch(ds, n, max_restarts=mr,
                                                seed=[3, 3])
    np.testing.assert_array_equal(p_scalar, p_list)
    np.testing.assert_array_equal(f_scalar, f_list)
    with pytest.raises(ValueError, match="one seed per entry"):
        E.draw_lifetime_pool_batch(ds, n, max_restarts=mr, seed=[0])


# ---------------------------------------------------------------------------
# scenario-batched executor
# ---------------------------------------------------------------------------

def test_batched_executor_bitexact_per_slice(grid_dists):
    """Shared pool, float64: every scenario slice of the batched executor is
    bit-identical to the unbatched kernel, for per-scenario and shared
    policy tables alike."""
    ds = grid_dists[:3]
    job = 60
    batch = C.solve_batch(ds, job, grid_dt=GRID)
    tables3 = np.asarray(batch.K, np.int32)             # (S, j+1, t+1)
    shared = E.no_checkpoint_policy_table(job)          # 2-D, broadcast
    first_b, pool_b = E.draw_lifetime_pool_batch(ds, 150, max_restarts=16,
                                                 seed=5)
    with jax.enable_x64(True):
        for table_b, table_of in [(tables3, lambda s: tables3[s]),
                                  (shared, lambda s: shared)]:
            mk_b = E.simulate_makespan_batch(
                table_b, job, first=first_b, pool=pool_b, grid_dt=GRID,
                max_restarts=16, unfinished="partial")
            assert mk_b.shape == (len(ds), 150)
            for s in range(len(ds)):
                mk = E.simulate_makespan_batch(
                    table_of(s), job, first=first_b[s], pool=pool_b[s],
                    grid_dt=GRID, max_restarts=16, unfinished="partial")
                assert np.array_equal(mk, mk_b[s]), s


def test_stack_policy_tables_widening_and_errors():
    """Stacking tables of differing provenance: age-independent columns are
    replicated (identical lookups), age-dependent tables pass through, and
    anything that would need resampling is rejected."""
    job = 12
    dp_like = np.tile(np.arange(job + 1, dtype=np.int32)[:, None], (1, 5))
    yd = E.young_daly_policy_table(3, job)                 # (job+1, 1)
    none = E.no_checkpoint_policy_table(job)               # (job+1, 1)
    out = E.stack_policy_tables([dp_like, yd, none])
    assert out.shape == (3, job + 1, 5) and out.dtype == np.int32
    np.testing.assert_array_equal(out[0], dp_like)
    for t in range(5):                                     # replication only
        np.testing.assert_array_equal(out[1][:, t], yd[:, 0])
        np.testing.assert_array_equal(out[2][:, t], none[:, 0])
    # explicit t_axis widens 1-wide tables too
    assert E.stack_policy_tables([yd], t_axis=7).shape == (1, job + 1, 7)
    with pytest.raises(ValueError, match="at least one"):
        E.stack_policy_tables([])
    with pytest.raises(ValueError, match="share the remaining-work axis"):
        E.stack_policy_tables([yd, E.no_checkpoint_policy_table(job + 1)])
    with pytest.raises(ValueError, match="resampling"):
        E.stack_policy_tables([dp_like[:, :3], dp_like])
    with pytest.raises(ValueError, match="2-D"):
        E.stack_policy_tables([np.zeros((2, 3, 4), np.int32)])


def test_indexed_executor_matches_materialized(grid_dists):
    """table_index/pool_index gathers into deduplicated tensors must run
    each lane bit-identically to the materialized (B, ...) call (shared
    x64 pool => exact equality is required, not approximate)."""
    ds = grid_dists[:2]
    job = 60
    batch = C.solve_batch(ds, job, grid_dt=GRID)
    uniq = E.stack_policy_tables(
        [np.asarray(batch.K[0]), np.asarray(batch.K[1]),
         E.no_checkpoint_policy_table(job)])
    first_q, pool_q = E.draw_lifetime_pool_batch(
        [d for d in ds for _ in (0, 1)], 80, max_restarts=8,
        seed=[s for _ in ds for s in (0, 1)])
    # B = 8 lanes: (scenario s, seed r, policy p in {dp, none})
    cells = [(s, r, p) for s in range(2) for r in range(2) for p in range(2)]
    tix = np.array([s if p == 0 else 2 for s, r, p in cells], np.int32)
    pix = np.array([s * 2 + r for s, r, p in cells], np.int32)
    with jax.enable_x64(True):
        mk_idx = E.simulate_makespan_batch(
            uniq, job, first=first_q[pix], pool=pool_q, grid_dt=GRID,
            max_restarts=8, unfinished="partial",
            table_index=tix, pool_index=pix)
        mk_mat = E.simulate_makespan_batch(
            uniq[tix], job, first=first_q[pix], pool=pool_q[pix],
            grid_dt=GRID, max_restarts=8, unfinished="partial")
    assert mk_idx.shape == (8, 80)
    np.testing.assert_array_equal(mk_idx, mk_mat)


def test_indexed_executor_validation(grid_dists):
    job = 30
    table = E.no_checkpoint_policy_table(job)
    uniq = E.stack_policy_tables([table])
    first = np.full((2, 4), 24.0)
    pool = np.full((1, 4, 6), 24.0)
    ix = np.zeros(2, np.int32)
    with pytest.raises(ValueError, match="passed together"):
        E.simulate_makespan_batch(uniq, job, first=first, pool=pool,
                                  max_restarts=4, table_index=ix)
    with pytest.raises(ValueError, match="indexed fold needs"):
        E.simulate_makespan_batch(table, job, first=first, pool=pool,
                                  max_restarts=4, table_index=ix,
                                  pool_index=ix)
    with pytest.raises(ValueError, match="table_index out of range"):
        E.simulate_makespan_batch(uniq, job, first=first, pool=pool,
                                  max_restarts=4,
                                  table_index=np.array([0, 5], np.int32),
                                  pool_index=ix)
    with pytest.raises(ValueError, match="pool_index out of range"):
        E.simulate_makespan_batch(uniq, job, first=first, pool=pool,
                                  max_restarts=4, table_index=ix,
                                  pool_index=np.array([0, 1], np.int32))


def test_batched_executor_finished_mask_and_errors():
    job = 60
    table = E.no_checkpoint_policy_table(job)
    # scenario 0 finishes, scenario 1 never does (VMs die at 0.5 h)
    first = np.stack([np.full(4, 24.0), np.full(4, 0.5)])
    pool = np.stack([np.full((4, 18), 24.0), np.full((4, 18), 0.5)])
    mk, fin = E.simulate_makespan_batch(table, job, first=first, pool=pool,
                                        grid_dt=GRID, max_restarts=16,
                                        return_finished=True)
    assert fin.shape == (2, 4)
    assert fin[0].all() and not fin[1].any()
    assert np.isnan(mk[1]).all()
    np.testing.assert_allclose(mk[0], 1.0, rtol=1e-6)
    with pytest.raises(ValueError, match="scenario-batched pool"):
        E.simulate_makespan_batch(np.stack([table, table]), job,
                                  first=first[0], pool=pool[0], grid_dt=GRID)
    with pytest.raises(ValueError, match="needs first of shape"):
        E.simulate_makespan_batch(table, job, first=first[0], pool=pool,
                                  grid_dt=GRID)


# ---------------------------------------------------------------------------
# market dollars through the batched executor
# ---------------------------------------------------------------------------

def test_executor_dollar_rows_bitexact_x64(grid_dists):
    """simulate_makespan_batch(price=...) bills every lane's makespans
    bit-identically to the serial market.integrate_cost_ref loop on a
    shared x64 pool — NaN-flagged unfinished trials cost NaN in both
    paths, and price_index dedup cannot change any lane's dollars."""
    from repro.core import market as M
    ds = grid_dists[:3]
    job = 60
    batch = C.solve_batch(ds, job, grid_dt=GRID)
    tables3 = np.asarray(batch.K, np.int32)
    # max_restarts=2 leaves some trials unfinished => NaN dollars covered
    first_b, pool_b = E.draw_lifetime_pool_batch(ds, 80, max_restarts=2,
                                                 seed=5)
    grid = M.MarketModel(
        processes=[M.spot_price_process(z) for z in M.MARKET_ZONE_PARAMS],
        horizon=12.0, seed=3).grid()
    with jax.enable_x64(True):
        mk, fin, dollars = E.simulate_makespan_batch(
            tables3, job, first=first_b, pool=pool_b, grid_dt=GRID,
            max_restarts=2, return_finished=True, price=grid)
        mk_plain = E.simulate_makespan_batch(
            tables3, job, first=first_b, pool=pool_b, grid_dt=GRID,
            max_restarts=2)
        _, d_indexed = E.simulate_makespan_batch(
            tables3, job, first=first_b, pool=pool_b, grid_dt=GRID,
            max_restarts=2, price=grid,
            price_index=np.arange(3, dtype=np.int32))
    assert not fin.all(), "workload failed to produce unfinished trials"
    np.testing.assert_array_equal(mk, mk_plain)   # billing changes nothing
    np.testing.assert_array_equal(dollars, d_indexed)
    assert dollars.shape == mk.shape
    for s in range(len(ds)):
        for j in range(mk.shape[1]):
            ref = M.integrate_cost_ref(grid.prices[s], grid.cum[s],
                                       grid.dt, mk[s, j])
            if np.isnan(ref):
                assert np.isnan(dollars[s, j]), (s, j)
            else:
                assert dollars[s, j] == ref, (s, j)
    with pytest.raises(ValueError, match="price_index needs price"):
        E.simulate_makespan_batch(tables3, job, first=first_b, pool=pool_b,
                                  grid_dt=GRID, max_restarts=2,
                                  price_index=np.arange(3, dtype=np.int32))


# ---------------------------------------------------------------------------
# batched ReuseTable
# ---------------------------------------------------------------------------

def test_reuse_table_batch_matches_per_scenario(grid_dists):
    T_vals = np.array([0.5, 1.0, 2.0, 4.0])
    batched = E.ReuseTable.batch(grid_dists, T_vals, n_age=97)
    assert len(batched) == len(grid_dists)
    for d, bt in zip(grid_dists, batched):
        ref = E.ReuseTable(d, T_vals, n_age=97)
        assert bt.L == ref.L and bt.n_age == ref.n_age
        assert np.array_equal(bt.T_values, ref.T_values)
        # boolean decisions may flip only where Eq. 9 and Eq. 10 tie to
        # within float rounding; on this grid they must agree everywhere
        assert np.array_equal(bt.table, ref.table)


def test_reuse_table_batch_requires_shared_L():
    with pytest.raises(ValueError, match="shared L"):
        E.ReuseTable.batch([D.Constrained(), D.Constrained(L=12.0)],
                           np.array([1.0]))


def test_reuse_tables_container_shares_backing_tensor(grid_dists):
    """ReuseTables is the folded form: one (S, T, age) tensor, per-scenario
    views that share it (no copies) and decide exactly like individually
    constructed tables."""
    ds = grid_dists[:3]
    T_vals = np.array([0.5, 1.5, 3.0])
    folded = E.ReuseTables(ds, T_vals, n_age=65)
    assert len(folded) == 3 and folded.tables.shape == (3, 3, 65)
    for s, (d, view) in enumerate(zip(ds, folded)):
        assert view.table.base is folded.tables
        ref = E.ReuseTable(d, T_vals, n_age=65)
        assert np.array_equal(view.table, ref.table)
        assert view.decide(1.5, 2.0) == ref.decide(1.5, 2.0)
    assert np.array_equal(folded[1].table, folded.view(1).table)
    with pytest.raises(ValueError, match="at least one"):
        E.ReuseTables([], T_vals)


# ---------------------------------------------------------------------------
# one-kernel sweep: unflattening bookkeeping (PR-4 fold)
# ---------------------------------------------------------------------------

def _assert_rows_identical(a_rows, b_rows):
    """Exact row-for-row equality, treating the engine's NaN flag for
    unfinished-trial statistics as equal to itself."""
    assert len(a_rows) == len(b_rows)
    for ra, rb in zip(a_rows, b_rows):
        assert set(ra) == set(rb)
        for k, va in ra.items():
            vb = rb[k]
            if isinstance(va, float) and np.isnan(va):
                assert isinstance(vb, float) and np.isnan(vb), k
            else:
                assert va == vb, (k, va, vb)


_SWEEP_GRID = None


def _sweep_scenarios():
    global _SWEEP_GRID
    if _SWEEP_GRID is None:
        _SWEEP_GRID = SC.default_grid(vm_types=("n1-highcpu-16",),
                                      phases=("day", "night"),
                                      zones=("us-east1-b",))
    return _SWEEP_GRID


def test_one_kernel_unfinished_rows_match_serial():
    """max_restarts=0 forces unfinished trials: the NaN-flagged statistics
    (makespan_* NaN when no trial finished, unfinished_frac > 0) must come
    through the one-kernel unflattening exactly as the serial path reports
    them."""
    kw = dict(policies=("dp", "none"), seeds=(0, 3), job_steps=30,
              n_trials=24, max_restarts=0)
    with jax.enable_x64(True):
        rows_b = SC.sweep_checkpointing(_sweep_scenarios(), mode="batched",
                                        **kw)
        rows_s = SC.sweep_checkpointing(_sweep_scenarios(), mode="serial",
                                        **kw)
    assert any(r["unfinished_frac"] > 0 for r in rows_s), \
        "workload failed to produce unfinished trials"
    _assert_rows_identical(rows_b, rows_s)


def test_sweep_tables_reuse_and_validation():
    """tables= skips the DP solve for whole-grid re-evaluation: rows equal
    the self-solving sweep exactly; mismatched workloads are rejected."""
    scs = _sweep_scenarios()
    kw = dict(policies=("dp", "none"), seeds=(1,), job_steps=30, n_trials=20)
    batch = C.solve_batch([sc.dist() for sc in scs], 30, grid_dt=1.0 / 60.0)
    _assert_rows_identical(
        SC.sweep_checkpointing(scs, mode="batched", tables=batch, **kw),
        SC.sweep_checkpointing(scs, mode="batched", **kw))
    with pytest.raises(ValueError, match="serial reference"):
        SC.sweep_checkpointing(scs, mode="serial", tables=batch, **kw)
    with pytest.raises(ValueError, match="needs 2 x 40"):
        SC.sweep_checkpointing(scs, tables=batch,
                               **dict(kw, job_steps=40))
    with pytest.raises(ValueError, match="different"):
        SC.sweep_checkpointing(scs, tables=batch, delta_steps=2, **kw)


try:
    import hypothesis.strategies as st
    from hypothesis import given, settings
except ImportError:  # pragma: no cover - hypothesis is a test dependency
    st = None

if st is not None:
    _sweep_cases = st.fixed_dictionaries({
        "policies": st.sampled_from([
            ("dp",), ("none",), ("young_daly",),
            ("dp", "none"), ("none", "young_daly", "dp")]),
        "seeds": st.sampled_from([(0,), (1, 4), (2, 0)]),
        "max_restarts": st.sampled_from([0, 2, 64]),
    })

    @settings(max_examples=5, deadline=None)
    @given(_sweep_cases)
    def test_one_kernel_rows_equal_serial_property(case):
        """Property: for ANY (policy subset, seed list, restart budget) the
        one-kernel sweep's labeled rows — produced by one executor dispatch
        plus unflattening — are exactly the serial reference's rows under
        x64, NaN flags included."""
        kw = dict(job_steps=30, n_trials=24, **case)
        with jax.enable_x64(True):
            rows_b = SC.sweep_checkpointing(_sweep_scenarios(),
                                            mode="batched", **kw)
            rows_s = SC.sweep_checkpointing(_sweep_scenarios(),
                                            mode="serial", **kw)
        coords = [(r["scenario"], r["policy"], r["seed"]) for r in rows_b]
        assert len(set(coords)) == len(coords) == \
            len(_sweep_scenarios()) * len(case["policies"]) * \
            len(case["seeds"])
        _assert_rows_identical(rows_b, rows_s)
else:  # pragma: no cover
    @pytest.mark.skip(reason="property tests need hypothesis installed")
    def test_one_kernel_rows_equal_serial_property():
        pass


# ---------------------------------------------------------------------------
# bench-artifact stamping (benchmarks.common satellite)
# ---------------------------------------------------------------------------

def test_write_bench_json_stamps_commit_and_schema(tmp_path, monkeypatch):
    import json

    from benchmarks import common

    monkeypatch.setattr(common, "REPO_ROOT", str(tmp_path))
    path = common.write_bench_json("BENCH_stamp_test.json",
                                   {"schema": 9, "payload": [1, 2]},
                                   emit_as="test/json")
    data = json.loads(open(path).read())
    assert data["schema"] == 9 and data["payload"] == [1, 2]
    assert data["bench_schema_version"] == common.BENCH_SCHEMA_VERSION
    commit = data["git_commit"]
    assert isinstance(commit, str) and commit
    # stamped commit matches the repo's HEAD when running inside the repo
    assert commit == common.git_commit()
