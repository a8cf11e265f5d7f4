"""Closed-loop fleet runtime: fault-injection matrix, drift adaptation,
retry/backoff degradation, table validation, DP warm starts, and the
mid-sweep table-swap bit-identity contract.

The fault matrix is the PR's acceptance criterion made executable: for
every injected fault kind (drift burst, preemption storm, fit divergence,
solve timeout) — alone and combined — the runtime must finish its run with
ZERO unhandled exceptions, serving only validated tables (last-good under
degradation), with retries recovering inside the configured backoff budget.
All schedules and streams are seeded, so each run replays identically.
"""
import dataclasses

import jax
import numpy as np
import pytest

from repro import fault
from repro.core import distributions as D
from repro.core import engine as E
from repro.core import fitting as F
from repro.core import market as M
from repro.core import runtime as rt
from repro.core import scenarios as SC
from repro.core.policies import checkpointing as C

# one shared small workload shape across the module, so every runtime test
# after the first reuses the solver/executor/fit jit caches; the stream is
# the gentle type so the drift events (to the harshest type) sit well above
# the tracker's KS cut
CFG = dict(job_steps=40, grid_dt=0.25, window=128, refit_every=32,
           min_samples=48, stream_block=128, regret_trials=32,
           stream_vm_types=("n1-highcpu-2",),
           retry_backoff_obs=8, max_retries=2)


def _runtime(schedule=(), **over):
    cfg = rt.RuntimeConfig(**{**CFG, **over})
    inj = fault.FaultInjector(schedule, seed=0) if schedule else None
    return rt.FleetRuntime(cfg, injector=inj)


def _assert_serving_valid(fr):
    """The invariant the whole envelope exists to protect: whatever
    happened, the tables being served are finite and well-formed."""
    fr.live_tables.validate()
    for s in range(len(fr.live_tables)):
        E.validate_policy_table(fr.live_tables.K[s])


# ---------------------------------------------------------------------------
# FaultInjector unit behavior
# ---------------------------------------------------------------------------

def test_fault_event_rejects_bad_kind_and_schedule():
    with pytest.raises(ValueError, match="unknown fault kind"):
        fault.FaultEvent("meteor", 10)
    with pytest.raises(ValueError, match="at_obs"):
        fault.FaultEvent("drift", -1)
    with pytest.raises(ValueError, match="duration"):
        fault.FaultEvent("storm", 5, duration=0)


def test_injector_budgets_drift_once_and_storm_window():
    sched = (fault.FaultEvent("drift", 10, param={"vm_types": ("n1-highcpu-2",)}),
             fault.FaultEvent("fit_divergence", 10, duration=2),
             fault.FaultEvent("storm", 20, duration=5))
    inj = fault.FaultInjector(sched, seed=0)
    assert inj.drift_event(9) is None
    assert inj.drift_event(10) is not None
    assert inj.drift_event(10) is None, "a drift fires exactly once"
    # stage budget: duration failures, then drained
    assert inj.take("fit_divergence", 11)
    assert inj.take("fit_divergence", 15)
    assert not inj.take("fit_divergence", 16)
    assert not inj.take("solve_timeout", 16), "no armed event of that kind"
    # storm covers [at_obs, at_obs + duration)
    assert inj.storm_active(19) is None
    ev = inj.storm_active(24)
    assert ev is not None and inj.storm_active(25) is None
    life = inj.storm_lifetime(ev)
    assert 0.0 < life <= 0.05
    assert inj.counts()["storm"] == 1


def test_injector_is_deterministic_under_seed():
    lifes = []
    for _ in range(2):
        inj = fault.FaultInjector((fault.FaultEvent("storm", 0, duration=4),),
                                  seed=7)
        lifes.append([inj.storm_lifetime(inj.storm_active(i))
                      for i in range(4)])
    assert lifes[0] == lifes[1]


# ---------------------------------------------------------------------------
# table validation (engine + BatchDPTables)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def small_tables():
    d = D.constrained_for()
    return C.solve_batch([d], CFG["job_steps"], grid_dt=CFG["grid_dt"])


def test_validate_policy_table_accepts_real_tables(small_tables):
    out = E.validate_policy_table(small_tables.K[0])
    assert out.dtype == np.int32
    E.validate_policy_table(E.young_daly_policy_table(5, 40))
    E.validate_policy_table(E.no_checkpoint_policy_table(40))


def test_validate_policy_table_rejects_poison(small_tables):
    K = small_tables.K[0]
    with pytest.raises(ValueError, match="non-finite"):
        bad = K.astype(np.float64).copy()
        bad[3, 4] = np.nan
        E.validate_policy_table(bad)
    with pytest.raises(ValueError, match="outside"):
        bad = K.copy()
        bad[2, 0] = 7            # interval > remaining work
        E.validate_policy_table(bad)
    with pytest.raises(ValueError, match="zero interval"):
        bad = K.copy()
        bad[5, 1] = 0
        E.validate_policy_table(bad)
    with pytest.raises(ValueError, match="2-D"):
        E.validate_policy_table(np.zeros((2, 3, 4)))


def test_batch_tables_validate_rejects_poison(small_tables):
    assert small_tables.validate() is small_tables
    badV = small_tables.V.copy()
    badV[0, 1, 1] = np.inf
    with pytest.raises(ValueError, match="non-finite V"):
        dataclasses.replace(small_tables, V=badV).validate()
    negV = small_tables.V.copy()
    negV[0, 1, 0] = -0.5
    with pytest.raises(ValueError, match="negative"):
        dataclasses.replace(small_tables, V=negV).validate()
    badK = small_tables.K.copy()
    badK[0, 4, 2] = 40
    with pytest.raises(ValueError, match="outside"):
        dataclasses.replace(small_tables, K=badK).validate()


def test_batch_tables_validate_rejects_subset_scenario_violation():
    """Regression: the K >= 1 invariant is enforced across the WHOLE
    scenario axis — a violation in only one scenario of a healthy batch
    must still reject (a per-scenario reduction that any-reduces the wrong
    axis would pass it)."""
    ds = [D.constrained_for(), D.Exponential(mttf=8.0)]
    tabs = C.solve_batch(ds, CFG["job_steps"], grid_dt=CFG["grid_dt"])
    assert tabs.validate() is tabs
    badK = tabs.K.copy()
    badK[1, 7, 3] = 0            # work remains (j=7) in scenario 1 only
    with pytest.raises(ValueError, match="K < 1"):
        dataclasses.replace(tabs, K=badK).validate()
    assert np.all(badK[0] == tabs.K[0]), "scenario 0 stayed healthy"


def test_batch_tables_validate_dollar_unit_messages():
    """Dollar tables share the objective-independent invariants but name
    their own unit in the rejection message."""
    price = M.PriceGrid.from_prices(np.full((1, 8), 0.2), 4.0)
    tabs = C.solve_batch([D.constrained_for()], CFG["job_steps"],
                         grid_dt=CFG["grid_dt"], objective="dollars",
                         price=price)
    assert tabs.validate() is tabs
    badV = tabs.V.copy()
    badV[0, 2, 2] = np.nan
    with pytest.raises(ValueError, match=r"non-finite V entries \(dollars\)"):
        dataclasses.replace(tabs, V=badV).validate()
    negV = tabs.V.copy()
    negV[0, 1, 0] = -0.01
    with pytest.raises(ValueError, match="negative dollars"):
        dataclasses.replace(tabs, V=negV).validate()


# ---------------------------------------------------------------------------
# DP warm starts
# ---------------------------------------------------------------------------

def test_warm_start_extends_the_cold_sweep_sequence_exactly(small_tables):
    """The warm start is EXACTLY a continuation of the restart-cost fixed
    point: k warm sweeps seeded with an n-sweep cold V must be bit-identical
    to an (n+k)-sweep cold solve (same scan body, same arithmetic — v_init
    only replaces the carry)."""
    d = D.constrained_for()
    warm = C.solve_batch([d], CFG["job_steps"], grid_dt=CFG["grid_dt"],
                         n_sweeps=1, v_init=small_tables.V)
    cold4 = C.solve_batch([d], CFG["job_steps"], grid_dt=CFG["grid_dt"],
                          n_sweeps=4)
    assert np.array_equal(warm.V, cold4.V)
    assert np.array_equal(warm.K, cold4.K)


def test_warm_start_rejects_mismatched_or_poisoned_init(small_tables):
    d = D.constrained_for()
    with pytest.raises(ValueError, match="shape"):
        C.solve_batch([d], CFG["job_steps"] + 10, grid_dt=CFG["grid_dt"],
                      v_init=small_tables.V)
    bad = small_tables.V.copy()
    bad[0, 0, 0] = np.nan
    with pytest.raises(ValueError, match="non-finite"):
        C.solve_batch([d], CFG["job_steps"], grid_dt=CFG["grid_dt"],
                      v_init=bad)


def test_warm_start_none_is_bit_identical_cold_path(small_tables):
    """The v_init=None path must remain byte-identical to the historical
    cold solve (the solve/solve_batch bit contract depends on it)."""
    d = D.constrained_for()
    again = C.solve_batch([d], CFG["job_steps"], grid_dt=CFG["grid_dt"])
    assert np.array_equal(again.V, small_tables.V)
    assert np.array_equal(again.K, small_tables.K)
    ref = C.solve(d, CFG["job_steps"], grid_dt=CFG["grid_dt"])
    assert np.array_equal(again.V[0], ref.V)
    assert np.array_equal(again.K[0], ref.K)


# ---------------------------------------------------------------------------
# the fault matrix (acceptance criterion: zero unhandled exceptions)
# ---------------------------------------------------------------------------

_MATRIX = {
    "drift": (fault.FaultEvent("drift", 120,
                               param={"vm_types": ("n1-highcpu-32",)}),),
    "storm": (fault.FaultEvent("storm", 120, duration=24),),
    "fit_divergence": (fault.FaultEvent("fit_divergence", 40, duration=2),),
    "solve_timeout": (fault.FaultEvent("drift", 120,
                                       param={"vm_types": ("n1-highcpu-32",)}),
                      fault.FaultEvent("solve_timeout", 120, duration=1)),
    "combined": fault.default_schedule(320),
}


@pytest.mark.parametrize("kind", sorted(_MATRIX))
def test_fault_matrix_no_unhandled_exceptions(kind):
    fr = _runtime(_MATRIX[kind])
    rep = fr.run(320)                     # any unhandled exception fails here
    _assert_serving_valid(fr)
    assert rep.n_obs == 320
    # the live model can never be poisoned either
    theta_like = [float(fr.tracker.model.A), float(fr.tracker.model.tau1)]
    assert np.all(np.isfinite(theta_like))
    # injected stage faults are all accounted for as handled retries
    if kind == "fit_divergence":
        assert rep.retries["fit"] >= 2
    if kind == "solve_timeout":
        assert rep.retries["solve"] >= 1


def test_drift_adapts_and_swaps_tables():
    fr = _runtime(_MATRIX["drift"])
    before = fr.live_sc.dist_override
    rep = fr.run(320)
    assert rep.change_points >= 1
    cps = [s for s in rep.swaps if s.reason == "change-point"]
    assert cps, "a confirmed drift must produce a table swap"
    assert rep.adaptation_lag_obs is not None and rep.adaptation_lag_obs > 0
    assert cps[0].warm, "re-solve on an unchanged grid should warm-start"
    # the live scenario now serves the refitted model, not the original
    after = fr.live_sc.dist_override
    assert float(after.tau1) != pytest.approx(float(before.tau1))
    assert rep.regret_hours is not None and np.isfinite(rep.regret_hours)
    _assert_serving_valid(fr)


def test_fit_divergence_degrades_then_recovers():
    """Inject more consecutive fit failures than the retry budget: the
    runtime must degrade to the last-good model (never crash, never adopt
    NaN), then recover on the first clean refit after the budget drains."""
    sched = (fault.FaultEvent("fit_divergence", 40, duration=4),)
    fr = _runtime(sched, max_retries=2)
    rep = fr.run(320)
    kinds = [k for _, k, _ in rep.events]
    assert "fit-failure" in kinds and "fit-degraded" in kinds
    assert rep.retries["fit"] >= 3
    assert rep.n_refits >= 1, "a clean refit must land once the burst drains"
    assert not rep.degraded, "recovery must clear the degraded flag"
    _assert_serving_valid(fr)


def test_solve_timeout_serves_stale_then_swaps():
    fr = _runtime(_MATRIX["solve_timeout"])
    rep = fr.run(320)
    assert rep.retries["solve"] >= 1
    kinds = [k for _, k, _ in rep.events]
    assert "solve-failure" in kinds and "solve-retry-scheduled" in kinds
    cps = [s for s in rep.swaps if s.reason == "change-point"]
    assert cps, "the retried solve must eventually swap"
    assert cps[0].stale_obs > 0, \
        "the failed solve must register as served-stale observations"
    assert rep.stale_obs_total >= cps[0].stale_obs
    _assert_serving_valid(fr)


class _Backend:
    """Stand-in solver backend: raises ``exc`` or returns NaN tables."""

    def __init__(self, exc=None):
        self.exc = exc

    def solve_tables_batch(self, Fc, Hc, *args, j_max, t_max, **kw):
        if self.exc is not None:
            raise self.exc
        S = Fc.shape[0]
        return (np.full((S, j_max + 1, t_max + 1), np.nan, np.float32),
                np.ones((S, j_max + 1, t_max + 1), np.int32))


def test_solver_compile_error_raises_out_of_the_loop(monkeypatch):
    """A kernel that fails to lower is a fault, not a solve failure: it must
    raise out of ``run`` instead of degrading to last-good tables."""
    fr = _runtime()
    lowering = ValueError("Mosaic failed to compile TPU kernel: cannot "
                          "statically prove that index in dimension 1 is a "
                          "multiple of 128")
    monkeypatch.setattr(C.solver_backends, "get",
                        lambda name: _Backend(lowering))
    with pytest.raises(ValueError, match="Mosaic failed to compile"):
        fr.run(320)
    assert fr.retries == {"fit": 0, "solve": 0}
    assert not fr.degraded


def test_invalid_tables_retry_then_degrade(monkeypatch):
    """Tables that fail validation are a solve failure: retried with
    backoff, then degraded to the last-good tables, never raised."""
    fr = _runtime()
    good = fr.live_tables
    monkeypatch.setattr(C.solver_backends, "get", lambda name: _Backend())
    rep = fr.run(320)
    kinds = [k for _, k, _ in rep.events]
    assert "solve-failure" in kinds and "solve-degraded" in kinds
    assert rep.retries["solve"] > CFG["max_retries"]
    assert fr.live_tables is good
    _assert_serving_valid(fr)


@pytest.mark.parametrize("exc, raises", [(ZeroDivisionError("probe"), False),
                                         (RuntimeError("probe"), True)])
def test_regret_probe_raises_all_but_numerical_failures(monkeypatch, exc,
                                                        raises):
    fr = _runtime(_MATRIX["drift"])

    def probe(**kw):
        raise exc

    monkeypatch.setattr(fr, "measure_regret", probe)
    if raises:
        with pytest.raises(RuntimeError, match="probe"):
            fr.run(320)
    else:
        rep = fr.run(320)
        cps = [s for s in rep.swaps if s.reason == "change-point"]
        assert cps and all(s.regret_hours is None for s in cps)


def test_stream_regime_switch_is_immediate():
    st = rt.FleetStream(seed=0, block=64)
    st.next()
    assert st._buf, "stream should hold buffered draws"
    st.set_regime(("n1-highcpu-2",))
    assert not st._buf, "regime switch must drop buffered old-regime draws"
    assert st.vm_types == ("n1-highcpu-2",)
    x = st.next()
    assert np.isfinite(x) and 0.0 < x <= 24.0


# ---------------------------------------------------------------------------
# mid-sweep table swap: bit-identity with a fresh sweep (satellite)
# ---------------------------------------------------------------------------

def test_mid_sweep_table_swap_rows_bit_identical():
    """Swap semantics of the `tables=` hook: rows evaluated AFTER a hot
    swap must be bit-identical (x64) to a fresh sweep solved directly on
    the new tables — a swap may never leave residue from the old solve."""
    kw = dict(policies=("dp", "none"), seeds=(0,), job_steps=30, n_trials=24)
    name = "test/hot-swap"
    pre = SC.register(SC.Scenario(name=name,
                                  dist_override=D.Constrained(tau1=1.2)),
                      overwrite=True)
    with jax.enable_x64(True):
        tables_pre = C.solve_batch([pre.dist()], 30)
        rows_pre = SC.sweep_checkpointing([name], tables=tables_pre, **kw)
        # the drift refit lands: swap the live dist + tables atomically
        post = SC.register(
            dataclasses.replace(pre,
                                dist_override=D.Constrained(tau1=0.5)),
            overwrite=True)
        tables_post = C.solve_batch([post.dist()], 30)
        rows_swapped = SC.sweep_checkpointing([name], tables=tables_post,
                                              **kw)
        # reference: a cold sweep that solves the post-drift model itself
        rows_fresh = SC.sweep_checkpointing([name], **kw)
    assert rows_swapped != rows_pre, "swap must actually change the rows"
    assert len(rows_swapped) == len(rows_fresh)
    for a, b in zip(rows_swapped, rows_fresh):
        assert set(a) == set(b)
        for k, va in a.items():
            vb = b[k]
            if isinstance(va, float) and np.isnan(va):
                assert isinstance(vb, float) and np.isnan(vb), k
            else:
                assert va == vb, (k, va, vb)


def test_runtime_evaluate_serves_from_live_tables():
    fr = _runtime()
    fr.run(64)                           # past the initial fit
    rows = fr.evaluate(policies=("dp",), seeds=(0,), n_trials=16)
    assert len(rows) == len(fr.scenario_names)
    live = [r for r in rows if r["scenario"] == fr.cfg.live_name]
    assert live and np.isfinite(live[0]["expected_makespan_dp"])


def test_runtime_dollar_objective_serves_dollar_tables():
    """dp_objective='dollars' without a ticker is a config error; with one,
    every solve — bootstrap and refits alike — prices against the feed's
    forward snapshot and the fleet serves validated dollar tables
    throughout."""
    with pytest.raises(ValueError, match="price_feed"):
        rt.FleetRuntime(rt.RuntimeConfig(**{**CFG,
                                            "dp_objective": "dollars"}))
    feed = M.PriceFeed(seed=3)
    fr = rt.FleetRuntime(rt.RuntimeConfig(**{**CFG,
                                             "dp_objective": "dollars"}),
                         price_feed=feed)
    assert fr.live_tables.objective == "dollars"
    rep = fr.run(64)                     # past the initial fit -> a refit
    assert rep.n_refits >= 1
    assert fr.live_tables.objective == "dollars"
    assert rep.dollars_streamed > 0.0
    _assert_serving_valid(fr)


def test_scenario_dist_override_short_circuits_catalog():
    d = D.Constrained(tau1=0.77)
    sc = SC.Scenario(name="test/override", dist_override=d)
    assert sc.dist() is d
    assert SC.Scenario(name="test/no-override").dist() is not d
