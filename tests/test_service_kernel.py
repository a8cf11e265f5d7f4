"""The batched event-synchronous service kernel vs the serial heap loop.

The core contract under test: on a shared per-seed lifetime pool and under
x64, every ``service_kernel`` lane is bit-identical to the retained
``service.BatchService`` ground truth — per-job completion times, failure
and attempt counts, ``vm_hours`` and the full cost accounting (the same
contract ``tests/test_batched.py`` enforces for the makespan executor).
Also covered: the (time, seq) event-tie order, the kernel-only policy
branches (deadline admission, VM deflation), pool/table dedup across the
grid, pool-exhaustion handling, and ``sweep_service``'s two modes.
"""
import itertools

import jax
import numpy as np
import pytest

from repro.core import distributions as D
from repro.core import engine as E
from repro.core import scenarios as SC
from repro.core import service as S
from repro.core import service_kernel as K

RO = S.RELAUNCH_OVERHEAD


def _dist():
    return D.constrained_for("n1-highcpu-32")


def _row_fields(res):
    return (res.makespan, res.vm_hours, res.cost, res.on_demand_cost,
            res.n_preemptions, res.n_job_failures)


def _job_fields(res):
    return [(j.finished, j.attempts, j.failures, j.done_work)
            for j in res.jobs]


def _assert_rows_identical(rows_serial, rows_batched, *, jobs=True):
    assert len(rows_serial) == len(rows_batched)
    for a, b in zip(rows_serial, rows_batched):
        coords = ("vm_type", "policy", "cluster_size", "seed")
        assert {k: a[k] for k in coords} == {k: b[k] for k in coords}
        assert _row_fields(a["result"]) == _row_fields(b["result"])
        if jobs:
            assert _job_fields(a["result"]) == _job_fields(b["result"])


# ---------------------------------------------------------------------------
# x64 bit-identity vs the serial BatchService
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("checkpointing", [False, True])
def test_kernel_bit_identical_to_serial_x64(checkpointing):
    """All policies x several cluster sizes x seeds: batched rows ==
    serial rows float-for-float, including per-job records."""
    kw = dict(vm_types=("n1-highcpu-32",), policies=("model", "memoryless"),
              cluster_sizes=(2, 3), seeds=(0, 1), n_jobs=8,
              job_hours=2.0, jitter=0.1, pool_size=512,
              checkpointing=checkpointing)
    with jax.enable_x64(True):
        rows_s = S.run_bag_grid(mode="serial", **kw)
        rows_b = S.run_bag_grid(mode="batched", **kw)
    _assert_rows_identical(rows_s, rows_b)


@pytest.mark.slow
def test_kernel_bit_identical_multi_vm_type_x64():
    """Two VM types share one folded ReuseTables tensor (their dists share
    the deadline L); rows stay bit-identical lane-for-lane."""
    kw = dict(vm_types=("n1-highcpu-16", "n1-highcpu-32"),
              policies=("model", "memoryless"), cluster_sizes=(2, 4),
              seeds=(0, 1, 2), n_jobs=8, pool_size=512, checkpointing=True)
    with jax.enable_x64(True):
        rows_s = S.run_bag_grid(mode="serial", **kw)
        rows_b = S.run_bag_grid(mode="batched", **kw)
    _assert_rows_identical(rows_s, rows_b)


def test_sweep_service_modes_agree_x64():
    """sweep_service(mode='batched') — every (scenario x policy x cluster x
    seed) cell in ONE kernel dispatch — returns exactly the serial rows."""
    kw = dict(policies=("model", "memoryless"), cluster_sizes=(2,),
              seeds=(0,), n_jobs=6, pool_size=512)
    scs = SC.default_grid()[:2]
    with jax.enable_x64(True):
        rows_s = SC.sweep_service(scs, mode="serial", **kw)
        rows_b = SC.sweep_service(scs, mode="batched", **kw)
    assert rows_b == rows_s


# ---------------------------------------------------------------------------
# event ordering
# ---------------------------------------------------------------------------

def test_event_tie_preempt_beats_finish():
    """A VM whose lifetime exactly equals its job segment dies at the same
    timestamp the finish would fire; the serial heap pops the preempt first
    (its seq is older) — the kernel must resolve the tie the same way."""
    lengths = [[1.0]]
    pool = [[1.0, 5.0]]  # first VM dies exactly at segment end
    with jax.enable_x64(True):
        res = K.simulate_service_batch(
            lengths=lengths, pools=pool, bag_index=[0], pool_index=[0],
            policy=["memoryless"], cluster_size=[1])
        svc = S.BatchService(_dist(), cluster_size=1, policy="memoryless",
                             lifetime_pool=np.array(pool[0]))
        ref = svc.run(lengths[0])
    assert int(res.n_job_failures[0]) == ref.n_job_failures == 1
    assert int(res.n_preemptions[0]) == 1
    # restart: launch at RO, die at RO+1, relaunch at RO+1+RO, finish +1
    assert float(res.makespan[0]) == ref.makespan == 2.0 + 2 * RO


def test_expire_frees_capacity_for_blocked_jobs():
    """A hot spare the model policy refuses pins the 1-slot cluster; its
    expiry must wake the scheduler (serial loop regression, PR 2)."""
    kw = dict(vm_types=("n1-highcpu-32",), policies=("model",),
              cluster_sizes=(1,), seeds=(0, 3), n_jobs=4, pool_size=512)
    with jax.enable_x64(True):
        rows_s = S.run_bag_grid(mode="serial", **kw)
        rows_b = S.run_bag_grid(mode="batched", **kw)
    _assert_rows_identical(rows_s, rows_b)
    for r in rows_b:
        assert all(j.finished is not None for j in r["result"].jobs)


# ---------------------------------------------------------------------------
# kernel-only policy branches
# ---------------------------------------------------------------------------

def test_deadline_admission_rejects_before_launch():
    res = K.simulate_service_batch(
        lengths=[[2.0, 2.0]], pools=[[9.0] * 4], bag_index=[0],
        pool_index=[0], policy=["memoryless"], cluster_size=[2],
        deadlines=[[0.5, 0.5]])
    assert int(res.n_rejected[0]) == 2
    assert int(res.n_launches[0]) == 0          # no VM ever provisioned
    assert res.attempts[0].tolist() == [0, 0]   # no lifetime consumed
    assert res.rejected[0].tolist() == [True, True]
    assert np.isnan(res.finished_time[0]).all()


def test_deadline_loose_matches_no_deadline():
    kw = dict(lengths=[[1.0, 2.0, 1.5]], pools=[[9.0] * 8], bag_index=[0],
              pool_index=[0], policy=["memoryless"], cluster_size=[2])
    free = K.simulate_service_batch(**kw)
    loose = K.simulate_service_batch(deadlines=[[1e6] * 3], **kw)
    assert int(loose.n_rejected[0]) == 0
    assert loose.finished_time.tolist() == free.finished_time.tolist()
    assert float(loose.vm_hours[0]) == float(free.vm_hours[0])


def test_deflation_absorbs_first_preemption():
    """len-2 job, lifetime 1: the preemption at RO+1 becomes a capacity
    halving — the remaining 1h stretches to 2h, finish at RO+3 exactly, no
    job failure, one fresh lifetime drawn for the survivor."""
    with jax.enable_x64(True):
        res = K.simulate_service_batch(
            lengths=[[2.0]], pools=[[1.0, 99.0]], bag_index=[0],
            pool_index=[0], policy=["memoryless"], cluster_size=[1],
            deflate=[True], deflate_factor=0.5)
    assert int(res.n_deflations[0]) == 1
    assert int(res.n_preemptions[0]) == 0
    assert int(res.n_job_failures[0]) == 0
    assert float(res.finished_time[0, 0]) == RO + 3.0
    # second preemption of a deflated VM is a real kill
    res2 = K.simulate_service_batch(
        lengths=[[2.0]], pools=[[1.0, 0.5, 99.0]], bag_index=[0],
        pool_index=[0], policy=["memoryless"], cluster_size=[1],
        deflate=[True], deflate_factor=0.5)
    assert int(res2.n_deflations[0]) == 1
    assert int(res2.n_job_failures[0]) == 1


def test_deflate_policy_suffix_through_grid():
    rows = S.run_bag_grid(mode="batched", policies=("memoryless+deflate",),
                          cluster_sizes=(2,), seeds=(0,), n_jobs=6,
                          pool_size=512)
    assert rows[0]["policy"] == "memoryless+deflate"
    r = rows[0]["result"]
    assert r.n_deflations >= 0 and r.n_preemptions >= 0
    with pytest.raises(ValueError, match="batched"):
        S.run_bag_grid(mode="serial", policies=("model+deflate",),
                       n_jobs=4, pool_size=512)
    with pytest.raises(ValueError, match="unknown service policy"):
        K.split_policy("model+inflate")
    assert K.split_policy("model+deflate") == ("model", True)
    assert K.split_policy("memoryless") == ("memoryless", False)


def test_serial_mode_rejects_deadline():
    with pytest.raises(ValueError, match="batched"):
        S.run_bag_grid(mode="serial", deadline_hours=5.0, n_jobs=4,
                       policies=("memoryless",), pool_size=512)
    with pytest.raises(ValueError, match="batched"):
        SC.sweep_service(SC.default_grid()[:1], mode="serial",
                         deadline_hours=5.0, n_jobs=4,
                         policies=("memoryless",), pool_size=512)


# ---------------------------------------------------------------------------
# shared streams + dedup (satellites 1 & 2)
# ---------------------------------------------------------------------------

def test_pooled_draw_matches_lazy_stream_x64():
    """An up-front draw_service_pool pool leaves the serial results
    unchanged: PCG64 uniforms are call-size invariant, and the sampler
    realigns the rng past the external pool before any refill."""
    bag = S._bag_lengths(6, 2.0, 0.1, 0)
    with jax.enable_x64(True):
        lazy = S.BatchService(_dist(), cluster_size=3, policy="memoryless",
                              seed=0, pool_size=64).run(bag)
        pool = S.draw_service_pool(_dist(), seed=0, size=64)
        pooled = S.BatchService(_dist(), cluster_size=3, policy="memoryless",
                                seed=0, pool_size=64,
                                lifetime_pool=pool).run(bag)
    assert _row_fields(lazy) == _row_fields(pooled)
    assert _job_fields(lazy) == _job_fields(pooled)


def test_draw_service_pool_batch_matches_serial_pools_x64():
    dists = [D.constrained_for("n1-highcpu-16"),
             D.constrained_for("n1-highcpu-32"),
             D.constrained_for("n1-highcpu-16")]
    seeds = [0, 0, 7]
    with jax.enable_x64(True):
        mat = K.draw_service_pool_batch(dists, seeds, size=128)
        refs = [S.draw_service_pool(d, seed=s, size=128)
                for d, s in zip(dists, seeds)]
    assert mat.shape == (3, 128)
    for row, ref in zip(mat, refs):
        np.testing.assert_array_equal(row, ref)


def test_one_reuse_table_build_per_grid(monkeypatch):
    """run_bag_grid builds ONE ReuseTables tensor for the whole grid —
    every cluster size, seed and vm_type shares it (satellite 2)."""
    calls = []
    orig_batch = E._reuse_grid_batch

    def spy(*a, **k):
        calls.append(1)
        return orig_batch(*a, **k)

    monkeypatch.setattr(E, "_reuse_grid_batch", spy)

    def no_single(*a, **k):
        raise AssertionError("per-cell reuse grid evaluated")

    no_single.__wrapped__ = E._reuse_grid.__wrapped__  # batch path uses it
    monkeypatch.setattr(E, "_reuse_grid", no_single)
    rows = S.run_bag_grid(vm_types=("n1-highcpu-16", "n1-highcpu-32"),
                          policies=("model",), cluster_sizes=(2, 3, 4),
                          seeds=(0, 1), n_jobs=4, pool_size=512)
    assert len(rows) == 2 * 3 * 2
    assert len(calls) == 1   # ONE vmapped grid call for the whole grid


def test_one_pool_dispatch_per_grid(monkeypatch):
    """All serial cells' lifetime pools come from ONE batched device draw
    (per unique (vm_type, seed)); no per-cell pool refills (satellite 1)."""
    calls = []
    orig = K.draw_service_pool_batch

    def spy(dists, seeds, **kw):
        calls.append(len(list(seeds)))
        return orig(dists, seeds, **kw)

    monkeypatch.setattr(K, "draw_service_pool_batch", spy)
    monkeypatch.setattr(
        S, "draw_service_pool",
        lambda *a, **k: (_ for _ in ()).throw(
            AssertionError("serial cell re-entered the pool helper")))
    rows = S.run_bag_grid(vm_types=("n1-highcpu-32",),
                          policies=("memoryless",), cluster_sizes=(2, 3),
                          seeds=(0, 1), n_jobs=4, pool_size=512)
    assert len(rows) == 4
    assert calls == [2]   # one call, one entry per unique (vm_type, seed)


# ---------------------------------------------------------------------------
# market dollars: launch-price billing (PR-8)
# ---------------------------------------------------------------------------

def test_service_dollars_bit_identical_to_serial_x64():
    """On shared per-seed pools and per-cell price rows, the kernel's
    launch-price dollar accounting equals the serial BatchService's
    bit-for-bit under x64 — the PR-7 equivalence contract extended to
    dollars, for the model and memoryless policies alike.  Unpriced cells
    fall back to dollars == the flat-rate cost in both paths."""
    from repro.core import market as M
    dist = _dist()
    seeds = (0, 1)
    bags = {s: S._bag_lengths(6, 2.0, 0.1, s) for s in seeds}
    values = S.grid_reuse_values(dist, seeds=seeds, n_jobs=6, job_hours=2.0,
                                 jitter=0.1, vm_type="n1-highcpu-32")
    tables = E.ReuseTables([dist], values)
    cells = [dict(dist_index=0, vm_type="n1-highcpu-32", policy=pol,
                  cluster_size=cs, seed=sd)
             for pol in ("memoryless", "model")
             for cs in (2, 3) for sd in seeds]
    price_dt = 0.25
    rows_p = np.stack([M.price_trace(M.spot_price_process(), horizon=48.0,
                                     dt=price_dt, seed=7, leaf=i)
                       for i in range(len(cells))])
    with jax.enable_x64(True):
        rows_b = K.run_cells_batched(
            cells=cells, dists=[dist], lengths_by_seed=bags,
            reuse_tables=tables, pool_size=512,
            price_rows=rows_p, price_dt=price_dt)
        for i, (cell, row) in enumerate(zip(cells, rows_b)):
            pool = S.draw_service_pool(dist, seed=cell["seed"], size=512)
            ref = S.BatchService(
                dist, cluster_size=cell["cluster_size"],
                policy=cell["policy"], seed=cell["seed"], pool_size=512,
                reuse_table=tables.view(0), lifetime_pool=pool,
                price_trace=rows_p[i], price_dt=price_dt,
            ).run(bags[cell["seed"]])
            assert row["result"].dollars == ref.dollars, cell
            assert row["result"].vm_hours == ref.vm_hours, cell
            assert ref.dollars > 0.0
        # unpriced cells: dollars degrades to the flat-rate cost
        rows_u = K.run_cells_batched(cells=cells[:2], dists=[dist],
                                     lengths_by_seed=bags,
                                     reuse_tables=tables, pool_size=512)
    for row in rows_u:
        assert row["result"].dollars == row["result"].cost


def test_service_price_rows_validation():
    base = dict(lengths=[[1.0]], pools=[[5.0] * 4], bag_index=[0],
                pool_index=[0], policy=["memoryless"], cluster_size=[1])
    with pytest.raises(ValueError, match="strictly positive"):
        K.simulate_service_batch(price_rows=[[1.0, 0.0]], **base)
    with pytest.raises(ValueError, match="price_dt"):
        K.simulate_service_batch(price_rows=[[1.0]], price_dt=0.0, **base)
    with pytest.raises(ValueError, match=r"price_rows must be \(B, Tp\)"):
        K.simulate_service_batch(price_rows=np.ones((3, 4)), **base)


# ---------------------------------------------------------------------------
# guard rails
# ---------------------------------------------------------------------------

def test_pool_exhaustion_raises_and_flags():
    kw = dict(lengths=[[2.0] * 3], pools=[[0.1, 0.1]], bag_index=[0],
              pool_index=[0], policy=["memoryless"], cluster_size=[2])
    with pytest.raises(RuntimeError, match="pool exhausted"):
        K.simulate_service_batch(**kw)
    res = K.simulate_service_batch(on_exhausted="flag", **kw)
    assert bool(res.pool_exhausted[0])
    with pytest.raises(ValueError, match="on_exhausted"):
        K.simulate_service_batch(on_exhausted="ignore", **kw)


def test_validation_errors():
    base = dict(lengths=[[1.0]], pools=[[5.0] * 4], bag_index=[0],
                pool_index=[0], cluster_size=[1])
    with pytest.raises(ValueError, match="tables"):
        K.simulate_service_batch(policy=["model"], **base)
    with pytest.raises(ValueError, match="bag_index"):
        K.simulate_service_batch(policy=["memoryless"],
                                 **dict(base, bag_index=[2]))
    with pytest.raises(ValueError, match="pool_index"):
        K.simulate_service_batch(policy=["memoryless"],
                                 **dict(base, pool_index=[-1]))
    with pytest.raises(ValueError, match="cluster_size"):
        K.simulate_service_batch(policy=["memoryless"],
                                 **dict(base, cluster_size=[0]))
    with pytest.raises(ValueError, match="deflate_factor"):
        K.simulate_service_batch(policy=["memoryless"], deflate=[True],
                                 deflate_factor=0.0, **base)
    with pytest.raises(ValueError, match="max_slots"):
        K.simulate_service_batch(policy=["memoryless"], max_slots=1,
                                 **dict(base, cluster_size=[4]))
    with pytest.raises(ValueError, match="does not support"):
        S.run_bag_grid(mode="batched", policies=("memoryless",), n_jobs=4,
                       pool_size=512, lifetimes_fn=lambda rng, n: [1.0])


def test_kernel_result_shape_and_counters():
    res = K.simulate_service_batch(
        lengths=[[1.0, 1.5], [2.0, 0.5]], pools=[[9.0] * 8],
        bag_index=[0, 1], pool_index=[0, 0],
        policy=["memoryless", "memoryless"], cluster_size=[2, 2])
    assert len(res) == 2
    assert res.finished_time.shape == (2, 2)
    assert not res.deadlocked.any() and not res.truncated.any()
    # 2 finish events per lane (the loop exits at all-finished, before the
    # hot-spare expiries fire — exactly like the serial loop's break)
    assert (res.n_events == 2).all()
    assert (res.n_launches >= 1).all()


# ---------------------------------------------------------------------------
# property test: random bags / cluster sizes (hypothesis)
# ---------------------------------------------------------------------------

try:
    import hypothesis.strategies as st
    from hypothesis import given, settings
except ImportError:  # pragma: no cover - hypothesis is a test dependency
    st = None

if st is not None:
    _cases = st.fixed_dictionaries({
        # small shape set bounds jit recompiles; variation comes from the
        # seeds (different bags + lifetime streams) and the policy mix
        "n_jobs": st.sampled_from([5, 9]),
        "cluster_sizes": st.sampled_from([(2,), (3,), (2, 4)]),
        "seeds": st.sampled_from([(0,), (3,), (1, 6)]),
        "policies": st.sampled_from([("model",), ("memoryless",),
                                     ("model", "memoryless")]),
        "job_hours": st.sampled_from([1.0, 2.5]),
        "checkpointing": st.booleans(),
    })

    @pytest.mark.slow
    @settings(max_examples=8, deadline=None)
    @given(_cases)
    def test_kernel_equals_serial_property(case):
        """Property: for ANY (bag, cluster mix, policy mix, seed list) the
        batched kernel's rows equal the serial loop's rows under x64."""
        kw = dict(case, pool_size=512)
        with jax.enable_x64(True):
            rows_s = S.run_bag_grid(mode="serial", **kw)
            rows_b = S.run_bag_grid(mode="batched", **kw)
        _assert_rows_identical(rows_s, rows_b)
else:  # pragma: no cover
    @pytest.mark.skip(reason="property tests need hypothesis installed")
    def test_kernel_equals_serial_property():
        pass
