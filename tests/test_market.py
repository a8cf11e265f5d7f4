"""Spot-market layer: the seeded OU price process, the crunch -> Eq. 1
coupling, the ``(S, T)`` price grid, and the dollar-denominated sweep.

The core contracts under test:

  * ``market.price_trace`` is strictly positive and bit-deterministic per
    (seed, leaf) — one independent reproducible noise stream per scenario
    leaf, never shared across leaves;
  * ``PriceProcess`` rides the standard leading-axis convention:
    ``distributions.stack``/``unstack`` round-trip its parameter leaves;
  * ``market.crunch_effective`` goes through the SAME properness cap as
    ``DiurnalConstrained`` (``distributions.capped_constrained``): a crunch
    boost can saturate the cap but never produces an improper Eq. 1 fit
    and never pushes ``A`` below the base fit, and zero crunch intensity
    passes the base model through unchanged;
  * the batched gather ``engine.accumulate_price_cost`` reproduces the
    serial reference ``market.integrate_cost_ref`` BIT-FOR-BIT under x64
    on shared makespans (NaN-flagged unfinished trials included) — the
    market extension of the PR-4/PR-7 equivalence contract;
  * ``scenarios.sweep_market``'s two cost paths (``kernel`` vs
    ``reference``) produce identical rows, ``tables=`` reuse matches the
    self-solving sweep, and one sweep compiles each jitted kernel exactly
    once — repeat sweeps never retrace (trace-count spies).
"""
import dataclasses

import jax
import numpy as np
import pytest

from repro.core import distributions as D
from repro.core import engine as E
from repro.core import market as M
from repro.core import scenarios as SC

ZONES = tuple(M.MARKET_ZONE_PARAMS)


# ---------------------------------------------------------------------------
# price process: positivity, determinism, leaf independence
# ---------------------------------------------------------------------------

def test_price_trace_positive_and_deterministic():
    proc = M.spot_price_process("us-central1-a", crunch_t0=8.0,
                                crunch_t1=16.0)
    a = M.price_trace(proc, seed=3, leaf=2)
    assert a.dtype == np.float64
    assert a.shape == (int(round(M.DEFAULT_HORIZON_HOURS
                                 / M.DEFAULT_PRICE_DT)),)
    assert np.all(a > 0.0) and np.all(np.isfinite(a))
    # bit-identical redraw; different leaf or seed gives a different stream
    np.testing.assert_array_equal(a, M.price_trace(proc, seed=3, leaf=2))
    assert not np.array_equal(a, M.price_trace(proc, seed=3, leaf=3))
    assert not np.array_equal(a, M.price_trace(proc, seed=4, leaf=2))


def test_price_trace_crunch_lifts_exactly_the_window():
    calm = M.spot_price_process()
    crunch = M.spot_price_process(crunch_t0=8.0, crunch_t1=16.0,
                                  crunch_amp=0.9)
    a = M.price_trace(calm, seed=0)
    b = M.price_trace(crunch, seed=0)
    t = M.DEFAULT_PRICE_DT * np.arange(len(a))
    win = (t >= 8.0) & (t < 16.0)
    assert win.any()
    # same OU path underneath: the crunch is a pure exp(amp) price lift
    np.testing.assert_allclose(b[win], a[win] * np.exp(0.9), rtol=1e-12)
    np.testing.assert_array_equal(b[~win], a[~win])


def test_crunch_intensity_window_period_and_disabled():
    p = M.PriceProcess(crunch_t0=2.0, crunch_t1=4.0)
    np.testing.assert_array_equal(
        M.crunch_profile(p, [0.0, 2.0, 3.9, 4.0]), [0.0, 1.0, 1.0, 0.0])
    per = M.PriceProcess(crunch_t0=2.0, crunch_t1=4.0, crunch_period=10.0)
    np.testing.assert_array_equal(
        M.crunch_profile(per, [12.5, 15.0, 23.0]), [1.0, 0.0, 1.0])
    # t1 <= t0 disables the episode entirely
    off = M.PriceProcess()
    assert not M.crunch_profile(off, np.linspace(0.0, 48.0, 97)).any()


def test_price_trace_rejects_degenerate_inputs():
    with pytest.raises(ValueError, match="empty grid"):
        M.price_trace(M.PriceProcess(), horizon=0.01, dt=0.1)
    with pytest.raises(ValueError, match="positive"):
        M.price_trace(M.PriceProcess(p0=0.0))


# ---------------------------------------------------------------------------
# leading-axis convention: stack/unstack round-trip
# ---------------------------------------------------------------------------

def test_price_process_stack_unstack_roundtrip():
    procs = [M.spot_price_process(z, crunch_t0=float(i),
                                  crunch_t1=float(i) + 2.0)
             for i, z in enumerate(ZONES)]
    stacked = D.stack(procs)
    assert type(stacked) is M.PriceProcess
    for leaf in jax.tree_util.tree_leaves(stacked):
        assert leaf.shape[:1] == (len(procs),)
    back = D.unstack(stacked)
    assert len(back) == len(procs)
    for orig, b in zip(procs, back):
        for f in dataclasses.fields(M.PriceProcess):
            assert float(getattr(b, f.name)) == pytest.approx(
                float(np.float64(getattr(orig, f.name)))), f.name


# ---------------------------------------------------------------------------
# crunch -> Eq. 1 coupling through the shared properness cap
# ---------------------------------------------------------------------------

def test_crunch_effective_proper_and_never_below_base():
    """Mirror of the DiurnalConstrained A-cap test: even an extreme crunch
    boost keeps the raw Eq. 1 CDF proper up to the deadline for every
    shipped fit, and never pushes A below the base fit."""
    proc = M.PriceProcess(crunch_t0=0.0, crunch_t1=48.0, crunch_A=4.0,
                          crunch_tau1=0.1)
    for vm_type in D.VM_TYPE_PARAMS:
        base = D.constrained_for(vm_type)
        eff = M.crunch_effective(base, proc, t_launch=1.0)
        assert type(eff) is D.Constrained, vm_type
        assert float(eff.A) >= float(base.A) - 1e-9, vm_type
        raw = float(eff.cdf_raw(float(base.L) - 0.1))
        assert raw <= 1.0 + 1e-6, (vm_type, raw)


def test_crunch_effective_zero_intensity_is_identity():
    """Outside the crunch window the coupling must pass the launch-phase-
    resolved base model through with its parameters unchanged — what makes
    calm-regime tables equal plain per-scenario tables."""
    proc = M.PriceProcess(crunch_t0=8.0, crunch_t1=16.0, crunch_A=3.0)
    d = D.diurnal_for("n1-highcpu-16", launch_clock=20.0)
    eff = M.crunch_effective(d, proc, t_launch=0.0)       # c = 0
    ref = d.effective()
    for f in ("tau1", "tau2", "b", "A", "L"):
        assert float(getattr(eff, f)) == float(getattr(ref, f)), f
    # inside the window the early hazard is strictly harsher
    boosted = M.crunch_effective(d, proc, t_launch=9.0)   # c = 1
    assert float(boosted.tau1) < float(ref.tau1)
    assert float(boosted.cdf(1.0)) > float(ref.cdf(1.0))


# ---------------------------------------------------------------------------
# price grid + the serial dollar reference
# ---------------------------------------------------------------------------

def test_price_grid_cum_shift_and_price_at():
    rows = np.stack([M.price_trace(M.spot_price_process(z), horizon=2.0,
                                   dt=0.5, seed=0, leaf=i)
                     for i, z in enumerate(ZONES[:2])])
    g = M.PriceGrid.from_prices(rows, 0.5)
    assert len(g) == 2 and g.horizon == 2.0
    assert np.all(g.cum[:, 0] == 0.0)
    np.testing.assert_allclose(g.cum[:, -1], rows.sum(axis=1) * 0.5,
                               rtol=1e-12)
    sh = g.shift(0.5)
    np.testing.assert_array_equal(sh.prices[:, :-1], g.prices[:, 1:])
    np.testing.assert_array_equal(sh.prices[:, -1], g.prices[:, -1])
    np.testing.assert_array_equal(g.price_at(0.6), g.prices[:, 1])
    np.testing.assert_array_equal(g.price_at(99.0), g.prices[:, -1])
    with pytest.raises(ValueError, match="strictly positive"):
        M.PriceGrid.from_prices(np.array([[1.0, 0.0]]), 0.5)


def test_integrate_cost_ref_closed_form_tail_and_nan():
    g = M.PriceGrid.from_prices([[2.0, 4.0]], 1.0)

    def f(m):
        return M.integrate_cost_ref(g.prices[0], g.cum[0], g.dt, m)

    assert f(0.0) == 0.0
    assert f(0.5) == 1.0                    # inside the first cell
    assert f(1.5) == 2.0 + 4.0 * 0.5        # straddles the boundary
    assert f(2.0) == 6.0                    # exactly the horizon
    assert f(3.5) == 2.0 + 4.0 * 2.5        # tail billed at the last price
    assert np.isnan(f(float("nan")))


def test_price_grid_shift_at_and_beyond_horizon_boundary():
    """``shift`` clamps to the LAST cell, never reads past the trace: a
    launch exactly at the horizon (or beyond it) yields a constant grid at
    the final price, and a pre-launch (negative) anchor clamps to 0."""
    rows = np.array([[1.0, 2.0, 3.0, 4.0]])
    g = M.PriceGrid.from_prices(rows, 0.5)
    at = g.shift(g.horizon)                   # t0 == horizon: k0 == T
    np.testing.assert_array_equal(at.prices, np.full((1, 4), 4.0))
    beyond = g.shift(g.horizon + 7.25)
    np.testing.assert_array_equal(beyond.prices, np.full((1, 4), 4.0))
    np.testing.assert_array_equal(g.shift(-3.0).prices, g.prices)
    # the last cell BEFORE the horizon still sees its own price first
    last = g.shift(g.horizon - g.dt)
    np.testing.assert_array_equal(last.prices, np.full((1, 4), 4.0))
    # shifted grids re-derive cum, so the integral convention is preserved
    np.testing.assert_allclose(at.cum[0], np.arange(5) * 4.0 * 0.5,
                               rtol=1e-12)


def test_integrate_cost_ref_makespan_exactly_on_grid_edges():
    """A makespan landing exactly on a cell edge bills zero fraction of the
    next cell: ``f(k*dt) == cum[k]`` bit-for-bit, including the horizon
    edge where the clamped last cell takes over."""
    g = M.PriceGrid.from_prices([[2.0, 4.0, 8.0]], 0.5)

    def f(m):
        return M.integrate_cost_ref(g.prices[0], g.cum[0], g.dt, m)

    for k in range(3):
        assert f(k * 0.5) == g.cum[0, k]
    # horizon edge: k clamps to the last cell, frac covers exactly one dt
    assert f(3 * 0.5) == g.cum[0, 3]
    assert f(3 * 0.5) == f(1.5)


def test_price_feed_grid_tracks_the_market_clock():
    """``PriceFeed.grid`` snapshots the ticker from the CURRENT clock cell
    forward — the forecast the dollar-objective runtime solve prices
    against — without disturbing the feed's determinism."""
    feed = M.PriceFeed(seed=11, dt=0.5, tick_hours=0.25)
    g0 = feed.grid(3.0)
    assert len(g0) == 1 and g0.dt == 0.5
    assert g0.prices.shape == (1, 6)          # ceil(3.0 / 0.5)
    np.testing.assert_array_equal(g0.prices[0], feed._trace[:6])
    # advance the clock past two price cells; the snapshot re-anchors
    for _ in range(5):                        # 5 x 0.25h -> clock 1.25h
        feed.advance()
    g1 = feed.grid(1.0)
    np.testing.assert_array_equal(g1.prices[0], feed._trace[2:4])
    # same seed, fresh feed: identical snapshot (determinism preserved)
    np.testing.assert_array_equal(M.PriceFeed(seed=11, dt=0.5).grid(3.0)
                                  .prices, g0.prices)


# ---------------------------------------------------------------------------
# batched gather == serial reference, bit-for-bit under x64
# ---------------------------------------------------------------------------

def _market3(seed=5, horizon=12.0):
    return M.MarketModel(processes=[M.spot_price_process(z) for z in ZONES],
                         horizon=horizon, seed=seed)


def test_accumulate_price_cost_bitexact_x64():
    g = _market3().grid()
    rng = np.random.default_rng(0)
    m = rng.uniform(0.0, 15.0, size=(3, 200))   # includes the tail beyond 12h
    m[rng.uniform(size=m.shape) < 0.1] = np.nan
    with jax.enable_x64(True):
        out = E.accumulate_price_cost(g, m)
    assert out.shape == m.shape
    for s in range(3):
        for j in range(m.shape[1]):
            ref = M.integrate_cost_ref(g.prices[s], g.cum[s], g.dt, m[s, j])
            if np.isnan(ref):
                assert np.isnan(out[s, j]), (s, j)
            else:
                assert out[s, j] == ref, (s, j)


def test_accumulate_price_cost_index_shapes_and_validation():
    g = _market3().grid()
    rng = np.random.default_rng(1)
    m = rng.uniform(0.0, 10.0, size=(4, 16))
    idx = np.array([2, 0, 1, 2], np.int32)      # lanes share grid rows
    with jax.enable_x64(True):
        out = E.accumulate_price_cost(g, m, price_index=idx)
        row = E.accumulate_price_cost(g, m[0], price_index=2)
    assert row.shape == (16,)                   # 1-D in, 1-D out
    np.testing.assert_array_equal(row, out[0])
    for b in range(4):
        for j in range(16):
            assert out[b, j] == M.integrate_cost_ref(
                g.prices[idx[b]], g.cum[idx[b]], g.dt, m[b, j]), (b, j)
    with pytest.raises(ValueError, match="out of range"):
        E.accumulate_price_cost(g, m, price_index=[0, 1, 2, 3])


# ---------------------------------------------------------------------------
# market sweep: cost-path equivalence, tables= reuse, validation
# ---------------------------------------------------------------------------

_SWEEP_SCS = None


def _sweep_scenarios():
    global _SWEEP_SCS
    if _SWEEP_SCS is None:
        _SWEEP_SCS = SC.default_grid(vm_types=("n1-highcpu-16",),
                                     phases=("day",))
    return _SWEEP_SCS


_SWEEP_KW = dict(seeds=(0,), job_steps=24, n_trials=24, max_restarts=8)


def _assert_rows_identical(a_rows, b_rows):
    assert len(a_rows) == len(b_rows)
    for ra, rb in zip(a_rows, b_rows):
        assert set(ra) == set(rb)
        for k, va in ra.items():
            vb = rb[k]
            if isinstance(va, float) and np.isnan(va):
                assert isinstance(vb, float) and np.isnan(vb), k
            else:
                assert va == vb, (k, va, vb)


def test_sweep_market_cost_paths_identical_x64():
    """cost_path='kernel' (the batched gather) and 'reference' (the serial
    per-trial loop) must label every row with identical dollars under x64 —
    the sweep-level form of the bit-exactness contract."""
    scs = _sweep_scenarios()
    mkt = M.MarketModel.for_scenarios(scs)
    with jax.enable_x64(True):
        rk = SC.sweep_market(scs, market=mkt, cost_path="kernel",
                             **_SWEEP_KW)
        rr = SC.sweep_market(scs, market=mkt, cost_path="reference",
                             **_SWEEP_KW)
    assert len(rk) == len(scs) * 2 * 3          # regimes x policies
    _assert_rows_identical(rk, rr)


def test_sweep_market_tables_reuse_and_validation():
    scs = _sweep_scenarios()
    mkt = M.MarketModel.for_scenarios(scs)
    tables = SC.solve_market_tables(scs, mkt,
                                    job_steps=_SWEEP_KW["job_steps"])
    _assert_rows_identical(
        SC.sweep_market(scs, market=mkt, tables=tables, **_SWEEP_KW),
        SC.sweep_market(scs, market=mkt, **_SWEEP_KW))
    with pytest.raises(ValueError):
        SC.sweep_market(scs, market=mkt, tables=tables,
                        **dict(_SWEEP_KW, job_steps=30))
    with pytest.raises(ValueError):
        SC.sweep_market(scs, market=mkt, regimes=("stormy",), **_SWEEP_KW)
    with pytest.raises(ValueError):
        SC.sweep_market(scs, market=mkt, policies=("greedy",), **_SWEEP_KW)


def test_sweep_market_dollar_objective_end_to_end():
    """``dp_objective='dollars'`` threads the regime-anchored price grid
    into the DP solve: tables come back dollar-denominated, ``tables=``
    reuse matches the self-solving sweep row-for-row, and mixing table
    objectives raises before any trial is simulated."""
    scs = _sweep_scenarios()
    mkt = M.MarketModel.for_scenarios(scs)
    tabs = SC.solve_market_tables(scs, mkt,
                                  job_steps=_SWEEP_KW["job_steps"],
                                  dp_objective="dollars")
    for b in tabs.values():
        assert b.objective == "dollars"
        b.validate()
    _assert_rows_identical(
        SC.sweep_market(scs, market=mkt, tables=tabs,
                        dp_objective="dollars", **_SWEEP_KW),
        SC.sweep_market(scs, market=mkt, dp_objective="dollars",
                        **_SWEEP_KW))
    mk_tabs = SC.solve_market_tables(scs, mkt,
                                     job_steps=_SWEEP_KW["job_steps"])
    with pytest.raises(ValueError, match="objective"):
        SC.sweep_market(scs, market=mkt, tables=mk_tabs,
                        dp_objective="dollars", **_SWEEP_KW)
    with pytest.raises(ValueError, match="objective"):
        SC.sweep_market(scs, market=mkt, tables=tabs, **_SWEEP_KW)


# ---------------------------------------------------------------------------
# compile-once regression: trace-count spies (satellite 3)
# ---------------------------------------------------------------------------

def _retrace_spy(monkeypatch, name):
    """Replace a module-level jitted kernel with a fresh jit whose Python
    body counts executions: jax only runs the Python function when TRACING,
    so the list length is the number of compilations."""
    calls = []
    inner = getattr(E, name).__wrapped__

    def counting(*a, **k):
        calls.append(name)
        return inner(*a, **k)

    monkeypatch.setattr(E, name, jax.jit(counting))
    return calls


def test_sweep_market_compiles_each_kernel_once(monkeypatch):
    """One market sweep traces ``_price_cost_kernel`` exactly once (every
    regime/policy/seed billing reuses the cached executable) and
    ``_capped_icdf_kernel`` once per draw-site shape (the pool block and
    the conditioned first draw); repeat sweeps — fresh seeds included —
    never retrace either."""
    icdf = _retrace_spy(monkeypatch, "_capped_icdf_kernel")
    cost = _retrace_spy(monkeypatch, "_price_cost_kernel")
    scs = _sweep_scenarios()
    mkt = M.MarketModel.for_scenarios(scs)
    SC.sweep_market(scs, market=mkt, **_SWEEP_KW)
    first = (len(icdf), len(cost))
    assert first == (2, 1), first
    SC.sweep_market(scs, market=mkt,
                    **dict(_SWEEP_KW, seeds=(1, 2)))
    assert (len(icdf), len(cost)) == first      # zero retraces


# ---------------------------------------------------------------------------
# closed-loop price feed
# ---------------------------------------------------------------------------

def test_price_feed_deterministic_and_extends_without_rewrites():
    feed = M.PriceFeed(seed=4, tick_hours=0.5, block=16)
    seq = [feed.advance() for _ in range(64)]   # 32 h: several lazy blocks
    replay = M.PriceFeed(seed=4, tick_hours=0.5, block=16)
    assert seq == [replay.advance() for _ in range(64)]
    # the lazily-extended trace is a prefix of one long deterministic draw
    long = M.price_trace(feed.process, horizon=64.0, dt=feed.dt,
                         seed=4, leaf=0)
    for i, p in enumerate(seq):
        k = int(np.floor(i * 0.5 / feed.dt))
        assert p == long[k], i
    assert all(p > 0.0 for p in seq)


# ---------------------------------------------------------------------------
# property tests (hypothesis)
# ---------------------------------------------------------------------------

try:
    import hypothesis.strategies as st
    from hypothesis import given, settings
except ImportError:  # pragma: no cover - hypothesis is a test dependency
    st = None

if st is not None:
    _trace_cases = st.fixed_dictionaries({
        "seed": st.integers(0, 2**31 - 1),
        "leaf": st.integers(0, 63),
        "mu": st.floats(-4.0, 1.0),
        "sigma": st.floats(0.0, 0.6),
        "theta": st.floats(0.0, 2.0),
        "p0": st.floats(0.01, 2.0),
        "crunch": st.booleans(),
        "crunch_amp": st.floats(-1.0, 2.0),
    })

    @settings(max_examples=25, deadline=None)
    @given(_trace_cases)
    def test_price_trace_positive_deterministic_property(case):
        """Property: for ANY OU parameterization (crunch lift included,
        negative discounts too) the trace is strictly positive, finite,
        and bit-identical across two draws."""
        kw = dict(mu=case["mu"], sigma=case["sigma"], theta=case["theta"],
                  p0=case["p0"])
        if case["crunch"]:
            kw.update(crunch_t0=1.0, crunch_t1=4.0,
                      crunch_amp=case["crunch_amp"])
        proc = M.PriceProcess(**kw)
        a = M.price_trace(proc, horizon=6.0, dt=0.25,
                          seed=case["seed"], leaf=case["leaf"])
        assert a.shape == (24,)
        assert np.all(a > 0.0) and np.all(np.isfinite(a))
        np.testing.assert_array_equal(
            a, M.price_trace(proc, horizon=6.0, dt=0.25,
                             seed=case["seed"], leaf=case["leaf"]))

    @settings(max_examples=25, deadline=None)
    @given(st.sampled_from(sorted(D.VM_TYPE_PARAMS)),
           st.floats(1.0, 8.0), st.floats(0.05, 1.5), st.booleans())
    def test_crunch_effective_always_proper_property(vm_type, crunch_A,
                                                     crunch_tau1, inside):
        """Property: NO crunch boost — however extreme, launch inside or
        outside the window — yields an improper Eq. 1 fit or an A below
        the base fit (the shared capped_constrained guarantee)."""
        proc = M.PriceProcess(crunch_t0=0.0, crunch_t1=24.0,
                              crunch_A=crunch_A, crunch_tau1=crunch_tau1)
        base = D.constrained_for(vm_type)
        eff = M.crunch_effective(base, proc,
                                 t_launch=1.0 if inside else 30.0)
        assert float(eff.tau1) >= 0.05 - 1e-9
        # the primitive computes in float32: hold it to the base fit as
        # float32 represents it, not to the float64 literal
        assert float(eff.A) >= float(np.float32(base.A)) - 1e-9
        assert float(eff.cdf_raw(float(base.L) - 0.1)) <= 1.0 + 1e-6

    @settings(max_examples=10, deadline=None)
    @given(st.lists(st.floats(0.02, 1.0), min_size=1, max_size=5),
           st.floats(0.0, 1.5))
    def test_price_process_stack_roundtrip_property(p0s, amp):
        """Property: stack/unstack round-trips ANY PriceProcess list —
        the (S,) leading-axis convention holds for the market family."""
        procs = [M.PriceProcess(p0=p, crunch_amp=amp, crunch_t0=float(i),
                                crunch_t1=float(i) + 2.0)
                 for i, p in enumerate(p0s)]
        stacked = D.stack(procs)
        for leaf in jax.tree_util.tree_leaves(stacked):
            assert leaf.shape[:1] == (len(procs),)
        for orig, b in zip(procs, D.unstack(stacked)):
            for f in dataclasses.fields(M.PriceProcess):
                assert float(getattr(b, f.name)) == pytest.approx(
                    float(np.float64(getattr(orig, f.name))),
                    rel=1e-6, abs=1e-6), f.name
else:  # pragma: no cover
    @pytest.mark.skip(reason="property tests need hypothesis installed")
    def test_price_trace_positive_deterministic_property():
        pass
