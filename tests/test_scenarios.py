"""Scenario layer: DiurnalConstrained distribution contract + sweep runner.

The contract tests mirror tests/test_distributions.py but do not need
hypothesis, so they run in the quick tier too — the diurnal family must
satisfy exactly the same cdf/pdf/partial_expectation/icdf invariants as the
static families (that is what lets the DP solver, ReuseTable and lifetime
pools consume it unchanged).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import distributions as D
from repro.core import engine as E
from repro.core import scenarios as SC
from repro.core.policies import checkpointing as C

DIURNAL = {
    "day": lambda: D.diurnal_for("n1-highcpu-16", launch_clock=20.0),
    "night": lambda: D.diurnal_for("n1-highcpu-16", launch_clock=8.0),
    "day_32": lambda: D.diurnal_for("n1-highcpu-32", launch_clock=20.0),
    "night_32": lambda: D.diurnal_for("n1-highcpu-32", launch_clock=8.0),
}


# ---------------------------------------------------------------------------
# distribution contract
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", sorted(DIURNAL))
def test_cdf_monotone_and_bounded(name):
    d = DIURNAL[name]()
    f = np.asarray(d.cdf(jnp.linspace(0.0, 24.0, 512)))
    assert np.all(f >= -1e-6) and np.all(f <= 1 + 1e-6)
    assert np.all(np.diff(f) >= -1e-6), "CDF must be nondecreasing"


@pytest.mark.parametrize("name", sorted(DIURNAL))
def test_pdf_is_cdf_derivative(name):
    d = DIURNAL[name]()
    t = jnp.linspace(0.1, 23.9, 64)
    eps = 1e-3
    numeric = (d.cdf(t + eps) - d.cdf(t - eps)) / (2 * eps)
    np.testing.assert_allclose(np.asarray(d.pdf(t)), np.asarray(numeric),
                               rtol=2e-2, atol=2e-3)


@pytest.mark.parametrize("name", sorted(DIURNAL))
def test_partial_expectation_matches_quadrature(name):
    d = DIURNAL[name]()
    closed = float(d.partial_expectation(2.0, 17.0))
    numeric = float(D._gauss_legendre(lambda x: x * d.pdf(x), 2.0, 17.0))
    np.testing.assert_allclose(closed, numeric, rtol=1e-3, atol=1e-4)


@pytest.mark.parametrize("name", sorted(DIURNAL))
def test_icdf_roundtrip(name):
    d = DIURNAL[name]()
    u = jnp.linspace(0.02, float(d.cdf(d.L)) - 0.02, 25)
    np.testing.assert_allclose(np.asarray(d.cdf(d.icdf(u))), np.asarray(u),
                               atol=1e-5)


def test_icdf_newton_accuracy_bounds():
    """The bracketed-Newton inversion must hit machine precision for every
    proper (production-envelope) fit under x64, and degrade gracefully —
    not silently stall — on out-of-envelope saturating fits whose clipped
    CDF plateaus at 1 before L (the documented ~1e-4 worst case)."""
    with jax.enable_x64(True):
        for vm_type in D.VM_TYPE_PARAMS:
            d = D.constrained_for(vm_type)
            u = jnp.linspace(1e-9, float(d.cdf(d.L)) - 1e-9, 20001)
            err = np.abs(np.asarray(d.cdf(d.icdf(u))) - np.asarray(u))
            assert err.max() < 1e-12, (vm_type, err.max())
        sat = D.Constrained(tau1=0.2, tau2=0.05, b=23.9, A=0.49)
        assert float(sat.cdf_raw(23.95)) > 1.0, "fit must saturate"
        u = jnp.linspace(1e-9, float(sat.cdf(sat.L)) - 1e-9, 20001)
        err = np.abs(np.asarray(sat.cdf(sat.icdf(u))) - np.asarray(u))
        assert err.max() < 5e-4, err.max()


def test_sampling_matches_cdf():
    d = DIURNAL["night"]()
    s = d.sample(jax.random.PRNGKey(0), (40000,))
    assert float(s.min()) >= 0 and float(s.max()) <= 24.0
    for t in (1.0, 3.0, 12.0, 23.0):
        np.testing.assert_allclose(float((s <= t).mean()), float(d.cdf(t)),
                                   atol=0.02)


def test_diurnal_phase_ordering():
    """Obs. 5: day launches preempt more than night launches; the shoulder
    (zero-modulation) launch recovers the static per-type fit exactly."""
    day, night = DIURNAL["day"](), DIURNAL["night"]()
    static = D.constrained_for("n1-highcpu-16")
    assert float(day.cdf(3.0)) > float(static.cdf(3.0)) > float(night.cdf(3.0))
    shoulder = D.diurnal_for("n1-highcpu-16", launch_clock=14.0)
    t = jnp.linspace(0.0, 24.0, 97)
    np.testing.assert_allclose(np.asarray(shoulder.cdf(t)),
                               np.asarray(static.cdf(t)), atol=1e-6)


def test_diurnal_never_inverts_below_static():
    """The properness cap on the day-phase A boost must saturate, never
    invert: for every VM type, day A_eff >= static A >= night A_eff (for
    large-A types the boost is fully absorbed by the cap and the day-phase
    severity comes from tau1 alone)."""
    for vm_type in D.VM_TYPE_PARAMS:
        static_A = D.VM_TYPE_PARAMS[vm_type]["A"]
        day = D.diurnal_for(vm_type, launch_clock=20.0).effective()
        night = D.diurnal_for(vm_type, launch_clock=8.0).effective()
        assert float(day.A) >= static_A - 1e-9, vm_type
        assert float(night.A) < static_A, vm_type
        assert float(day.tau1) < float(night.tau1), vm_type
        # the effective day-phase model still stays proper on [0, L)
        raw = float(day.cdf_raw(23.9))
        assert raw <= 1.0 + 1e-6, (vm_type, raw)


def test_diurnal_for_overrides_base_params():
    """Scenario.dist_kwargs must be able to override the type's base Eq. 1
    parameters, not just the diurnal knobs."""
    d = D.diurnal_for("n1-highcpu-16", launch_clock=8.0, A=0.3, amp_A=0.0)
    assert float(d.A) == pytest.approx(0.3)
    sc = SC.Scenario(name="override-test", vm_type="n1-highcpu-16",
                     phase="night", dist_kwargs={"A": 0.3, "tau2": 0.9})
    dist = sc.dist()
    assert float(dist.A) == pytest.approx(0.3)
    assert float(dist.tau2) == pytest.approx(0.9)


def test_diurnal_vmap_over_launch_clock():
    """The pytree contract: one vmapped call evaluates the whole profile."""
    clocks = jnp.linspace(0.0, 24.0, 13)
    f3 = jax.vmap(lambda c: D.DiurnalConstrained(launch_clock=c).cdf(3.0))(clocks)
    f3 = np.asarray(f3)
    assert f3.argmax() != f3.argmin()
    np.testing.assert_allclose(f3[0], f3[-1], rtol=1e-6)  # 24 h periodic


# ---------------------------------------------------------------------------
# registry + sweep runner
# ---------------------------------------------------------------------------

def test_registry_roundtrip_and_duplicate_guard():
    grid = SC.default_grid(vm_types=("n1-highcpu-16",), phases=("day",))
    assert SC.get(grid[0].name) is grid[0]
    assert grid[0].name in SC.names()
    with pytest.raises(ValueError, match="already registered"):
        SC.register(SC.Scenario(name=grid[0].name))
    # repeated default_grid calls reuse the registered scenarios
    assert SC.default_grid(vm_types=("n1-highcpu-16",),
                           phases=("day",))[0] is grid[0]


def test_register_overwrite():
    """Re-registering a taken name must be an explicit decision: it raises
    by default (a silent clobber would invalidate resolved grids) and
    replaces the scenario only with overwrite=True."""
    name = "overwrite-regression"
    first = SC.register(SC.Scenario(name=name, phase="day"))
    with pytest.raises(ValueError, match="overwrite=True"):
        SC.register(SC.Scenario(name=name, phase="night"))
    assert SC.get(name) is first, "failed registration must not clobber"
    second = SC.register(SC.Scenario(name=name, phase="night"),
                         overwrite=True)
    assert SC.get(name) is second
    assert SC.get(name).phase == "night"
    # the deprecated pre-PR-3 spelling keeps working
    third = SC.register(SC.Scenario(name=name, phase="day"), replace=True)
    assert SC.get(name) is third


def test_default_grid_zone_dimension():
    """The grown default grid is the (zone x phase x vm_type) product, and
    zone scaling orders the initial-phase severity: a tighter market
    (us-central1-a) preempts young VMs more than the identity zone."""
    grid = SC.default_grid()
    assert len(grid) == 8
    assert {sc.zone for sc in grid} == {"us-east1-b", "us-central1-a"}
    coords = {(sc.zone, sc.phase, sc.vm_type) for sc in grid}
    assert len(coords) == 8
    base = SC.get("us-east1-b/day/n1-highcpu-16").dist()
    tight = SC.get("us-central1-a/day/n1-highcpu-16").dist()
    assert float(tight.cdf(1.0)) > float(base.cdf(1.0))
    # the identity zone reproduces the pre-zone scenario definition
    legacy = D.diurnal_for("n1-highcpu-16", SC.PHASE_CLOCKS["day"])
    t = jnp.linspace(0.0, 24.0, 49)
    np.testing.assert_allclose(np.asarray(base.cdf(t)),
                               np.asarray(legacy.cdf(t)), rtol=1e-6)


def test_sweep_checkpointing_grid_shape_and_determinism():
    grid = SC.default_grid(vm_types=("n1-highcpu-16", "n1-highcpu-32"),
                           phases=("day", "night"))
    kw = dict(policies=("dp", "none"), seeds=(0, 1), job_steps=60,
              n_trials=50)
    rows = SC.sweep_checkpointing(grid, **kw)
    assert len(rows) == len(grid) * 2 * 2  # scenario x policy x seed
    coords = {(r["scenario"], r["policy"], r["seed"]) for r in rows}
    assert len(coords) == len(rows), "grid coordinates must be unique"
    assert all(r["unfinished_frac"] == 0.0 for r in rows)
    # per-seed determinism: a re-run reproduces every cell exactly
    again = SC.sweep_checkpointing(grid, **kw)
    for a, b in zip(rows, again):
        assert a == b


def test_sweep_checkpointing_modes_match_serial():
    """The one-kernel fold (mode="batched") must reproduce the serial
    per-scenario sweep: identical row order/coords, bit-identical DP
    expectations and fresh-VM failure probabilities, and makespan
    statistics within the pool's float32 inverse-CDF rounding (far below
    Monte-Carlo noise)."""
    grid = SC.default_grid(vm_types=("n1-highcpu-16", "n1-highcpu-32"),
                           phases=("day", "night"), zones=("us-east1-b",))
    kw = dict(policies=("dp", "young_daly", "none"), seeds=(0, 1),
              job_steps=60, n_trials=80)
    serial = SC.sweep_checkpointing(grid, mode="serial", **kw)
    rows = SC.sweep_checkpointing(grid, mode="batched", **kw)
    assert len(rows) == len(serial) == len(grid) * 3 * 2
    for b, s in zip(rows, serial):
        assert (b["scenario"], b["policy"], b["seed"]) == \
            (s["scenario"], s["policy"], s["seed"])
        assert b["expected_makespan_dp"] == s["expected_makespan_dp"]
        assert b["p_fail_fresh"] == s["p_fail_fresh"]
        assert b["unfinished_frac"] == s["unfinished_frac"] == 0.0
        np.testing.assert_allclose(b["makespan_mean"],
                                   s["makespan_mean"], rtol=5e-3)
    with pytest.raises(ValueError, match="mode"):
        SC.sweep_checkpointing(grid, mode="bogus", **kw)


def test_sweep_service_grid_shape():
    grid = SC.default_grid(vm_types=("n1-highcpu-32",), phases=("day", "night"),
                           zones=("us-east1-b",))
    rows = SC.sweep_service(grid, policies=("model", "memoryless"),
                            cluster_sizes=(8,), seeds=(0,), n_jobs=15)
    assert len(rows) == len(grid) * 2 * 1 * 1 == 4
    for r in rows:
        assert r["cost"] > 0 and r["cost_reduction"] > 1.0
        assert 0.0 <= r["job_failure_rate"] <= r["n_job_failures"]


def test_diurnal_cell_engine_matches_reference():
    """One diurnal cell, shared pool, float64 kernel: the vectorized engine
    must match the Python reference bit-for-bit — the scenario layer must
    not disturb the PR-1 exactness contract."""
    dist = SC.default_grid(vm_types=("n1-highcpu-16",),
                           phases=("night",))[0].dist()
    job = 120
    tables = C.solve(dist, job, grid_dt=1.0 / 60.0, delta_steps=1, n_sweeps=3)
    lf = C.model_lifetimes_fn(dist)
    first, pool = E.draw_lifetime_pool(lf, 200, seed=11)
    ref = C.simulate_makespan(C.dp_policy_fn(tables), lf, job,
                              grid_dt=1.0 / 60.0, pool=pool, first=first)
    with jax.enable_x64(True):
        vec = E.simulate_makespan_batch(E.dp_policy_table(tables), job,
                                        first=first, pool=pool,
                                        grid_dt=1.0 / 60.0)
    assert np.array_equal(ref, vec)
