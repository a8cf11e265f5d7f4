"""Solver backend equivalence: the bit-exactness contract across the
pluggable backends (reference vs xla), Pallas interpret tolerance, backend
selection/env-override rules, the sweep runners' ``solver_backend``
pass-through, scenario sharding transparency, and the FleetRuntime
mid-sweep backend swap pinning the ``v_init`` warm-start semantics."""
import dataclasses
import json
import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest

from repro.core import distributions as D
from repro.core import market as M
from repro.core import runtime as rt
from repro.core import scenarios as SC
from repro.core.policies import checkpointing as C
from repro.core.policies import solver_backends as SB
from repro.kernels import dp_recurrence as DP

GRID = 1.0 / 12.0
JOB = 60
RO = 0.3          # restart overhead (hours) — exercises launch-priced R_j


@pytest.fixture(scope="module")
def dists():
    # mixed hazards on one deadline: constrained (the paper's family),
    # memoryless, and a decreasing-hazard Weibull whose argmins often run
    # the whole remaining job in one segment
    return [D.constrained_for("n1-highcpu-16"), D.Exponential(mttf=8.0),
            D.Weibull(lam=0.12, k=0.8)]


@pytest.fixture(scope="module")
def plain(dists):
    return C.solve_batch(dists, JOB, grid_dt=GRID)


# ---------------------------------------------------------------------------
# bit-identity: reference vs xla (x64 session dtype), warm-start chains
# ---------------------------------------------------------------------------

def test_reference_vs_xla_bit_identical_x64(dists):
    """The heart of the contract: per scenario slice the batched XLA kernel
    reproduces the serial reference bit-for-bit — under an x64 session
    dtype, because the solver pins its own f32 arithmetic either way.  (The
    CDF grids themselves are built in session dtype, so the comparison is
    within-session, not across dtypes.)"""
    with jax.enable_x64(True):
        ref = C.solve_batch(dists, JOB, grid_dt=GRID, backend="reference")
        xla = C.solve_batch(dists, JOB, grid_dt=GRID, backend="xla")
    assert ref.backend == "reference" and xla.backend == "xla"
    assert np.array_equal(ref.V, xla.V)
    assert np.array_equal(ref.K, xla.K)


@pytest.mark.parametrize("objective", ["makespan", "dollars"])
def test_warm_start_chain(dists, price, objective):
    """Warm starts stay inside one objective's fixed-point chain: 2 warm
    sweeps from a 3-sweep cold V == 5-sweep cold solve."""
    kw = dict(grid_dt=GRID, restart_overhead=RO, objective=objective,
              price=price if objective == "dollars" else None)
    cold3 = C.solve_batch(dists, JOB, n_sweeps=3, **kw)
    warm = C.solve_batch(dists, JOB, n_sweeps=2, v_init=cold3.V, **kw)
    cold5 = C.solve_batch(dists, JOB, n_sweeps=5, **kw)
    assert warm.objective == objective
    assert np.array_equal(warm.V, cold5.V)
    assert np.array_equal(warm.K, cold5.K)


# ---------------------------------------------------------------------------
# dollar objective: the same bit-exactness contract, in a new currency
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def price():
    # flat / crunch spike / ramp — one row per scenario in `dists`, 15-min
    # price cells over 16h (ages beyond the trace bill at the last cell)
    n = 64
    flat = np.full(n, 0.12)
    spike = np.full(n, 0.10)
    spike[12:28] = 0.55
    ramp = np.linspace(0.08, 0.40, n)
    return M.PriceGrid.from_prices(np.stack([flat, spike, ramp]), 0.25)


def test_dollar_reference_vs_xla_bit_identical_x64(dists, price):
    """The tentpole contract: the dollar objective rides the same operand
    set (host-precomputed Pc/Elp grids) through both backends, so per
    scenario slice the batched XLA kernel reproduces the serial reference
    bit-for-bit under an x64 session dtype too."""
    with jax.enable_x64(True):
        ref = C.solve_batch(dists, JOB, grid_dt=GRID, restart_overhead=RO,
                            objective="dollars", price=price,
                            backend="reference")
        xla = C.solve_batch(dists, JOB, grid_dt=GRID, restart_overhead=RO,
                            objective="dollars", price=price, backend="xla")
    assert ref.objective == "dollars" and xla.objective == "dollars"
    assert np.array_equal(ref.V, xla.V)
    assert np.array_equal(ref.K, xla.K)
    ref.validate()


def test_dollar_flat_price_reduces_to_makespan(dists):
    """On a constant price grid the dollar recurrence is the makespan
    recurrence scaled by the rate — dollar V must equal rate x makespan V
    up to float32 rounding (allclose, not bitwise: the scaled arithmetic
    rounds at different points)."""
    rate = 0.17
    flat = M.PriceGrid.from_prices(np.full((1, 8), rate), 4.0)
    mk = C.solve_batch(dists, JOB, grid_dt=GRID, restart_overhead=RO)
    dl = C.solve_batch(dists, JOB, grid_dt=GRID, restart_overhead=RO,
                       objective="dollars", price=flat)
    np.testing.assert_allclose(np.asarray(dl.V), rate * np.asarray(mk.V),
                               rtol=1e-4, atol=1e-6)
    # the scaled arithmetic rounds near-ties differently, so argmin flips
    # are more common than across backends — demand bulk agreement only
    assert (np.asarray(dl.K) == np.asarray(mk.K)).mean() > 0.99


def test_dollar_solve_single_scenario_unwraps_batch(dists, price):
    """solve(objective='dollars') routes through the batched machinery with
    S=1 and must equal the matching solve_batch slice bit-for-bit."""
    one = M.PriceGrid.from_prices(np.asarray(price.prices)[1:2], price.dt)
    tab = C.solve(dists[1], 30, grid_dt=GRID, restart_overhead=RO,
                  objective="dollars", price=one)
    bat = C.solve_batch(dists[1:2], 30, grid_dt=GRID, restart_overhead=RO,
                        objective="dollars", price=one,
                        backend="reference")
    assert tab.objective == "dollars"
    assert np.array_equal(tab.V, bat.V[0])
    assert np.array_equal(tab.K, bat.K[0])


def test_dollar_objective_validation_errors(dists, price):
    with pytest.raises(ValueError, match="expected one of"):
        C.solve_batch(dists, JOB, grid_dt=GRID, objective="euros")
    with pytest.raises(ValueError, match="requires price"):
        C.solve_batch(dists, JOB, grid_dt=GRID, objective="dollars")
    with pytest.raises(ValueError, match="only meaningful"):
        C.solve_batch(dists, JOB, grid_dt=GRID, price=price)
    two = M.PriceGrid.from_prices(np.asarray(price.prices)[:2], price.dt)
    with pytest.raises(ValueError, match="rows"):
        C.solve_batch(dists, JOB, grid_dt=GRID, objective="dollars",
                      price=two)


@pytest.mark.pallas
def test_dollar_pallas_interpret_within_tolerance(dists, price):
    """The Pallas kernel recomputes the expected-lost-dollars term in-lane
    (it ignores the host Elp grids), so the dollar objective keeps it under
    the tolerance contract, not the bit-identity one."""
    job, grid = 24, 1.0 / 6.0
    kw = dict(grid_dt=grid, n_sweeps=2, restart_overhead=RO,
              objective="dollars", price=price)
    ref = C.solve_batch(dists, job, backend="reference", **kw)
    pal = C.solve_batch(dists, job, backend="pallas", **kw)
    assert pal.backend == "pallas"
    np.testing.assert_allclose(pal.V, ref.V, rtol=1e-5, atol=1e-5)
    # in-lane recompute flips a few more argmin near-ties than makespan's
    # hoisted grids do; the contract for dollar-K agreement is 99.5%
    assert (pal.K == ref.K).mean() > 0.995


def test_dollar_sharding_single_device_mesh_transparent(dists, price):
    """The dollar operands (Pc, Elp, per-scenario overhead) ride the sharded
    scenario axis: a 1-device mesh must not change a bit."""
    import jax
    from jax.sharding import Mesh
    from repro import sharding as sh
    kw = dict(grid_dt=GRID, restart_overhead=RO, objective="dollars",
              price=price, backend="xla")
    plain = C.solve_batch(dists, JOB, **kw)
    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    with jax.set_mesh(mesh), sh.use(mesh):
        shd = C.solve_batch(dists, JOB, **kw)
    assert np.array_equal(plain.V, shd.V)
    assert np.array_equal(plain.K, shd.K)


# ---------------------------------------------------------------------------
# backend selection
# ---------------------------------------------------------------------------

def test_resolve_env_override_applies_only_to_auto(monkeypatch):
    monkeypatch.delenv(SB.ENV_VAR, raising=False)
    assert SB.resolve("auto") == "xla"           # CPU container
    assert SB.resolve("reference") == "reference"
    monkeypatch.setenv(SB.ENV_VAR, "reference")
    assert SB.resolve("auto") == "reference"
    assert SB.resolve("xla") == "xla"            # explicit name wins
    monkeypatch.setenv(SB.ENV_VAR, "bogus")
    with pytest.raises(ValueError, match="unknown solver backend"):
        SB.resolve("auto")
    with pytest.raises(ValueError, match="unknown solver backend"):
        SB.resolve("bogus")


def test_solve_single_scenario_explicit_backends(dists):
    """solve(backend=...) routes through the batched machinery with S=1 and
    unwraps to the same tables as the reference path."""
    d = dists[0]
    ref = C.solve(d, 30, grid_dt=GRID)
    via_xla = C.solve(d, 30, grid_dt=GRID, backend="xla")
    assert np.array_equal(ref.V, via_xla.V)
    assert np.array_equal(ref.K, via_xla.K)


# ---------------------------------------------------------------------------
# Pallas backend (interpret mode on CPU)
# ---------------------------------------------------------------------------

@pytest.mark.pallas
def test_pallas_interpret_within_tolerance(dists):
    """The VMEM-resident kernel recomputes the probability grids on the fly,
    so it is tolerance-tested (not bit-pinned) against the reference."""
    job, grid = 24, 1.0 / 6.0
    ref = C.solve_batch(dists, job, grid_dt=grid, n_sweeps=2,
                        backend="reference")
    pal = C.solve_batch(dists, job, grid_dt=grid, n_sweeps=2,
                        backend="pallas")
    assert pal.backend == "pallas"
    np.testing.assert_allclose(pal.V, ref.V, rtol=1e-5, atol=1e-5)
    # argmin ties may flip at ulp scale; demand near-total agreement
    assert (pal.K == ref.K).mean() > 0.999


@pytest.mark.pallas
def test_pallas_warm_start_column_seed(dists):
    """The kernel's warm start is the seed column V[:, :, 0] — sweeps couple
    only through column 0, so one warm sweep from a 2-sweep V must land on
    the 3-sweep solve (within kernel tolerance)."""
    job, grid = 24, 1.0 / 6.0
    cold2 = C.solve_batch(dists, job, grid_dt=grid, n_sweeps=2)
    warm = C.solve_batch(dists, job, grid_dt=grid, n_sweeps=1,
                         v_init=cold2.V, backend="pallas")
    cold3 = C.solve_batch(dists, job, grid_dt=grid, n_sweeps=3)
    np.testing.assert_allclose(warm.V, cold3.V, rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# Pallas backend: one trace and lowering per (shape, statics)
# ---------------------------------------------------------------------------

def _pallas(dists, job, **kw):
    return C.solve_batch(dists, job, grid_dt=1.0 / 6.0, n_sweeps=2,
                         restart_overhead=RO, backend="pallas", **kw)


@pytest.mark.pallas
def test_pallas_same_shape_traces_once(dists):
    """A second solve at the same shapes, with other distributions, reuses
    the compiled call: the traced body runs once for both."""
    job = 11                  # a shape no other test in this file solves
    n0 = DP.trace_count()
    _pallas(dists, job)
    _pallas(dists[::-1], job)
    assert DP.trace_count() == n0 + 1


@pytest.mark.pallas
@pytest.mark.parametrize("change", ["job_steps", "cold_after_warm",
                                    "dollars"])
def test_pallas_new_shape_or_structure_traces_once(dists, price, change):
    """Each new shape or operand structure adds exactly one trace, and a
    repeat of it adds none."""
    job = {"job_steps": 13, "cold_after_warm": 14, "dollars": 15}[change]
    warm = dict(v_init=C.solve_batch(dists, job, grid_dt=1.0 / 6.0,
                                     n_sweeps=1, backend="xla").V)
    base, (vjob, vkw) = {
        "job_steps": ({}, (16, {})),
        "cold_after_warm": (warm, (job, {})),
        "dollars": ({}, (job, dict(objective="dollars", price=price))),
    }[change]
    n0 = DP.trace_count()
    _pallas(dists, job, **base)
    assert DP.trace_count() == n0 + 1
    _pallas(dists, vjob, **vkw)
    assert DP.trace_count() == n0 + 2
    _pallas(dists[::-1], vjob, **vkw)
    assert DP.trace_count() == n0 + 2


@pytest.mark.pallas
def test_pallas_cached_call_matches_fresh_compile(dists):
    """The cache holds programs, not state: a cache hit's tables are
    bit-identical to the same inputs solved after ``jax.clear_caches()``."""
    job = 12
    _pallas(dists, job)
    hit = _pallas(dists[::-1], job)
    n = DP.trace_count()
    jax.clear_caches()
    fresh = _pallas(dists[::-1], job)
    assert DP.trace_count() == n + 1
    assert np.array_equal(hit.V, fresh.V)
    assert np.array_equal(hit.K, fresh.K)


@pytest.mark.pallas
def test_pallas_refresh_loop_traces_twice():
    """The closed loop's refresh (``FleetRuntime._try_swap`` on a new live
    model, from the same published tables each time) lowers the kernel
    twice in all: the cold bootstrap and the warm re-solve shape."""
    from repro.core import scenarios as SC
    n0 = DP.trace_count()
    fr = rt.FleetRuntime(rt.RuntimeConfig(
        base_scenarios=tuple(s.name for s in SC.default_grid()),
        job_steps=10, grid_dt=0.5, restart_overhead=RO,
        solver_backend="pallas"))
    base = fr.live_tables
    for tau1 in (0.8, 1.0, 1.2, 1.4):
        fr.live_tables = base
        fr.tracker.model = D.Constrained(tau1=tau1, tau2=0.8, b=23.9,
                                         A=0.45, L=24.0)
        fr._try_swap("initial-fit")
        assert fr.live_tables is not base and fr._last_solve_warm
    assert fr.retries["solve"] == 0
    assert DP.trace_count() == n0 + 2


# ---------------------------------------------------------------------------
# sweep runners: solver_backend passes through to solve_batch
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("runner", ["sweep_checkpointing",
                                    "solve_market_tables", "sweep_market"])
def test_sweep_runner_backend_reference_equals_xla_x64(runner, monkeypatch):
    """Each sweep runner hands ``solver_backend`` to every DP solve it
    makes, and under x64 the reference and xla backends give the same
    tables, hence the same rows, bit for bit."""
    scs = SC.default_grid(vm_types=("n1-highcpu-16",), phases=("day",))
    solved = []
    solve_batch = C.solve_batch

    def spy(*a, **kw):
        tab = solve_batch(*a, **kw)
        solved.append(tab.backend)
        return tab

    monkeypatch.setattr(C, "solve_batch", spy)
    kw = dict(job_steps=30, grid_dt=GRID)
    sim = dict(seeds=(0,), n_trials=16, max_restarts=8)

    def run(backend):
        solved.clear()
        if runner == "sweep_checkpointing":
            out = SC.sweep_checkpointing(scs, solver_backend=backend,
                                         **kw, **sim)
        elif runner == "solve_market_tables":
            out = SC.solve_market_tables(
                scs, M.MarketModel.for_scenarios(scs),
                solver_backend=backend, **kw)
        else:
            out = SC.sweep_market(
                scs, market=M.MarketModel.for_scenarios(scs),
                solver_backend=backend, **kw, **sim)
        assert solved and set(solved) == {backend}
        return out

    with jax.enable_x64(True):
        ref, xla = run("reference"), run("xla")
    if runner == "solve_market_tables":
        assert ref.keys() == xla.keys() == {"calm", "crunch"}
        for regime in ref:
            assert np.array_equal(ref[regime].V, xla[regime].V)
            assert np.array_equal(ref[regime].K, xla[regime].K)
    else:
        assert len(ref) == len(xla) > 0
        for a, b in zip(ref, xla):
            assert a.keys() == b.keys()
            for k, va in a.items():
                if isinstance(va, float) and np.isnan(va):
                    assert np.isnan(b[k]), k
                else:
                    assert va == b[k], (k, va, b[k])


# ---------------------------------------------------------------------------
# scenario sharding
# ---------------------------------------------------------------------------

def test_sharding_single_device_mesh_transparent(dists, plain):
    """An active 1-device mesh context engages the shard_map wrapper (the
    'scenario' rule maps, S divides 1) without changing a bit."""
    import jax
    from jax.sharding import Mesh
    from repro import sharding as sh
    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    with jax.set_mesh(mesh), sh.use(mesh):
        shd = C.solve_batch(dists, JOB, grid_dt=GRID)
    assert np.array_equal(plain.V, shd.V)
    assert np.array_equal(plain.K, shd.K)


def test_sharding_no_mesh_returns_unwrapped():
    fn = lambda x: (x,)
    out, sharded = SB.shard_scenarios(fn, 8, 1, 1)
    assert out is fn and not sharded


@pytest.mark.slow
def test_sharding_two_devices_bit_identical():
    """Real shard_map over 2 forced host devices: the sharded S=4 solve
    must equal the unsharded single-device tables bit-for-bit; an indivisible S=3 falls back transparently; the CDF grids
    are built on the host device, not on the caller's mesh."""
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                                   + " --xla_force_host_platform_device_count=2")
        import json
        import jax
        import numpy as np
        from jax.sharding import Mesh
        from repro import sharding as sh
        from repro.core import distributions as D
        from repro.core.policies import checkpointing as C
        dists = [D.Exponential(mttf=8.0), D.Weibull(lam=0.12, k=0.8),
                 D.constrained_for("n1-highcpu-16"), D.Exponential(mttf=16.0)]
        plain = C.solve_batch(dists, 30, grid_dt=1.0 / 6.0, n_sweeps=2)
        # the CDF grids are built on the host even under the caller's mesh
        grid_devices = []
        cdf = D.Exponential.cdf
        D.Exponential.cdf = lambda self, t: (
            grid_devices.append(sorted(d.id for d in t.devices()))
            or cdf(self, t))
        mesh = Mesh(np.array(jax.devices()).reshape(2), ("data",))
        with jax.set_mesh(mesh), sh.use(mesh):
            shd = C.solve_batch(dists, 30, grid_dt=1.0 / 6.0, n_sweeps=2)
            p3 = C.solve_batch(dists[:3], 30, grid_dt=1.0 / 6.0, n_sweeps=2)
        D.Exponential.cdf = cdf
        u3 = C.solve_batch(dists[:3], 30, grid_dt=1.0 / 6.0, n_sweeps=2)
        print(json.dumps({
            "devices": jax.device_count(),
            "plain_eq": bool(np.array_equal(plain.V, shd.V)
                             and np.array_equal(plain.K, shd.K)),
            "indivisible_eq": bool(np.array_equal(p3.V, u3.V)),
            "grids_on_host": bool(grid_devices) and all(
                ids == [0] for ids in grid_devices),
        }))
    """)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    result = json.loads(out.stdout.strip().splitlines()[-1])
    assert result == {"devices": 2, "plain_eq": True,
                      "indivisible_eq": True, "grids_on_host": True}


# ---------------------------------------------------------------------------
# FleetRuntime mid-sweep backend swap
# ---------------------------------------------------------------------------

def test_runtime_mid_sweep_backend_swap_pins_v_init(monkeypatch):
    """Swapping the solver backend between refits must not disturb the
    warm-start chain: the fixed point couples backends only through V, so
    warm sweeps on a DIFFERENT backend continue the cold sweep sequence
    bit-exactly (reference and xla are interchangeable mid-loop)."""
    cfg = dict(job_steps=40, grid_dt=0.25, window=128, refit_every=32,
               min_samples=48, stream_block=128, regret_trials=32,
               stream_vm_types=("n1-highcpu-2",), solver_backend="xla")
    fr = rt.FleetRuntime(rt.RuntimeConfig(**cfg))
    dists = fr._dists()
    cold = fr.live_tables                      # n_sweeps=3 cold solve, xla
    want = C.solve_batch(dists, cfg["job_steps"], grid_dt=cfg["grid_dt"],
                         n_sweeps=3 + fr.cfg.warm_sweeps)
    fr.cfg = dataclasses.replace(fr.cfg, solver_backend="reference")
    tab = fr._solve(warm=True)                 # warm_sweeps=2 from cold.V
    assert fr._last_solve_warm
    assert np.array_equal(tab.V, want.V)
    assert np.array_equal(tab.K, want.K)
    assert np.array_equal(cold.V, fr.live_tables.V)  # swap did not publish
