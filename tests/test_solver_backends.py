"""Solver backend equivalence: the bit-exactness contract across the
pluggable backends (reference vs xla vs coarse-to-fine), Pallas interpret
tolerance, backend selection/env-override rules, scenario sharding
transparency, and the FleetRuntime mid-sweep backend swap pinning the
``v_init`` warm-start semantics."""
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
from repro.core.policies import checkpointing as C
from repro.core.policies import solver_backends as SB
from repro.core.policies.solver_backends import refine as R

GRID = 1.0 / 12.0
JOB = 60
RO = 0.3          # restart overhead (hours) — exercises launch-priced R_j


@pytest.fixture(scope="module")
def dists():
    # mixed hazards on one deadline: constrained (the paper's family),
    # memoryless, and a decreasing-hazard Weibull whose run-to-completion
    # argmins exercise the refine caps' graceful degradation
    return [D.constrained_for("n1-highcpu-16"), D.Exponential(mttf=8.0),
            D.Weibull(lam=0.12, k=0.8)]


@pytest.fixture(scope="module")
def plain(dists):
    return C.solve_batch(dists, JOB, grid_dt=GRID)


# ---------------------------------------------------------------------------
# bit-identity: reference vs xla vs coarse-to-fine (x64 session dtype)
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


def test_refined_verified_tables_bit_identical_x64(dists):
    """Coarse-to-fine with a passing verification is the plain solve: same
    V, same K, to the bit."""
    with jax.enable_x64(True):
        plain = C.solve_batch(dists, JOB, grid_dt=GRID)
        ctf = C.solve_batch(dists, JOB, grid_dt=GRID, refine=True,
                            refine_check="full")
    info = ctf.refine_info
    assert info["applied"] and info["verified_col0"]
    assert not info["fallback"]
    assert info["full_check_match"]
    assert ctf.backend == "xla+refine"
    assert np.array_equal(plain.V, ctf.V)
    assert np.array_equal(plain.K, ctf.K)


def test_refined_warm_start_chain(dists, plain):
    """Refined pre-sweeps reproduce the warm-start fixed-point chain too:
    2 warm sweeps (refined) from a 3-sweep cold V == 5-sweep cold solve."""
    warm = C.solve_batch(dists, JOB, grid_dt=GRID, n_sweeps=2,
                         v_init=plain.V, refine=True)
    cold5 = C.solve_batch(dists, JOB, grid_dt=GRID, n_sweeps=5)
    assert warm.refine_info["applied"]
    assert not warm.refine_info["fallback"]
    assert np.array_equal(warm.V, cold5.V)
    assert np.array_equal(warm.K, cold5.K)


def test_refined_fallback_on_sabotaged_caps(dists, plain, monkeypatch):
    """Force every candidate cap to 1 so the pre-sweeps must miss argmins:
    the column-0 verification has to catch it and the dispatcher has to
    serve the plain solve."""
    monkeypatch.setattr(R, "candidate_caps",
                        lambda Kc, segs, **kw: (1,) * len(segs))
    ctf = C.solve_batch(dists, JOB, grid_dt=GRID, refine=True)
    assert not ctf.refine_info["verified_col0"]
    assert ctf.refine_info["fallback"]
    assert np.array_equal(plain.V, ctf.V)
    assert np.array_equal(plain.K, ctf.K)


def test_refine_plan_degenerate_and_bad_backend(dists):
    small = C.solve_batch(dists, 6, grid_dt=1.0, refine=True)
    assert small.refine_info == {"applied": False, "reason": "degenerate"}
    assert R.plan(300, 1440, 1, 1, 4, None) is None     # no pre-sweeps
    with pytest.raises(ValueError, match="contradictory"):
        C.solve_batch(dists, JOB, grid_dt=GRID, refine=True,
                      backend="pallas")


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


def test_dollar_refined_verified_bit_identical_x64(dists, price):
    """Coarse-to-fine under the dollar objective (the coarse hint solve runs
    dollars too) with a passing full check equals the plain dollar solve."""
    with jax.enable_x64(True):
        # refine always runs on the XLA machinery, so compare against an
        # explicit xla plain solve (env-robust under the backend matrix)
        plain = C.solve_batch(dists, JOB, grid_dt=GRID, restart_overhead=RO,
                              objective="dollars", price=price,
                              backend="xla")
        ctf = C.solve_batch(dists, JOB, grid_dt=GRID, restart_overhead=RO,
                            objective="dollars", price=price, refine=True,
                            refine_check="full")
    assert ctf.refine_info["applied"] and not ctf.refine_info["fallback"]
    assert ctf.refine_info["full_check_match"]
    assert np.array_equal(plain.V, ctf.V)
    assert np.array_equal(plain.K, ctf.K)


def test_dollar_warm_start_chain(dists, price):
    """Warm starts stay inside one objective's fixed-point chain: 2 warm
    sweeps from a 3-sweep dollar V == 5-sweep cold dollar solve."""
    kw = dict(grid_dt=GRID, restart_overhead=RO, objective="dollars",
              price=price)
    cold3 = C.solve_batch(dists, JOB, n_sweeps=3, **kw)
    warm = C.solve_batch(dists, JOB, n_sweeps=2, v_init=cold3.V, **kw)
    cold5 = C.solve_batch(dists, JOB, n_sweeps=5, **kw)
    assert np.array_equal(warm.V, cold5.V)
    assert np.array_equal(warm.K, cold5.K)


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
        ctf = C.solve_batch(dists, JOB, refine=True,
                            **{**kw, "backend": "auto"})
    assert np.array_equal(plain.V, shd.V)
    assert np.array_equal(plain.K, shd.K)
    assert not ctf.refine_info["fallback"]
    assert np.array_equal(plain.V, ctf.V)


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
        ctf = C.solve_batch(dists, JOB, grid_dt=GRID, refine=True)
    assert np.array_equal(plain.V, shd.V)
    assert np.array_equal(plain.K, shd.K)
    assert not ctf.refine_info["fallback"]
    assert np.array_equal(plain.V, ctf.V)


def test_sharding_no_mesh_returns_unwrapped():
    fn = lambda x: (x,)
    out, sharded = SB.shard_scenarios(fn, 8, 1, 1)
    assert out is fn and not sharded


@pytest.mark.slow
def test_sharding_two_devices_bit_identical():
    """Real shard_map over 2 forced host devices: the sharded S=4 solve
    (plain and refined) must equal the unsharded single-device tables
    bit-for-bit; an indivisible S=3 falls back transparently; the CDF grids
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
            ctf = C.solve_batch(dists, 30, grid_dt=1.0 / 6.0, n_sweeps=2,
                                refine=True)
            p3 = C.solve_batch(dists[:3], 30, grid_dt=1.0 / 6.0, n_sweeps=2)
        D.Exponential.cdf = cdf
        u3 = C.solve_batch(dists[:3], 30, grid_dt=1.0 / 6.0, n_sweeps=2)
        print(json.dumps({
            "devices": jax.device_count(),
            "plain_eq": bool(np.array_equal(plain.V, shd.V)
                             and np.array_equal(plain.K, shd.K)),
            "refine_eq": bool(np.array_equal(plain.V, ctf.V)),
            "refine_ok": bool(not ctf.refine_info["fallback"]),
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
    assert result == {"devices": 2, "plain_eq": True, "refine_eq": True,
                      "refine_ok": True, "indivisible_eq": True,
                      "grids_on_host": True}


# ---------------------------------------------------------------------------
# FleetRuntime mid-sweep backend swap
# ---------------------------------------------------------------------------

def test_runtime_mid_sweep_backend_swap_pins_v_init(monkeypatch):
    """Swapping the solver backend between refits must not disturb the
    warm-start chain: the fixed point couples backends only through V, so
    warm sweeps on a DIFFERENT backend continue the cold sweep sequence
    bit-exactly (reference/xla/refined are interchangeable mid-loop)."""
    cfg = dict(job_steps=40, grid_dt=0.25, window=128, refit_every=32,
               min_samples=48, stream_block=128, regret_trials=32,
               stream_vm_types=("n1-highcpu-2",), solver_backend="xla")
    fr = rt.FleetRuntime(rt.RuntimeConfig(**cfg))
    dists = fr._dists()
    cold = fr.live_tables                      # n_sweeps=3 cold solve, xla
    want = C.solve_batch(dists, cfg["job_steps"], grid_dt=cfg["grid_dt"],
                         n_sweeps=3 + fr.cfg.warm_sweeps)
    for swap in ({"solver_backend": "reference"},
                 {"solver_backend": "auto", "solver_refine": True}):
        fr.cfg = dataclasses.replace(fr.cfg, **swap)
        tab = fr._solve(warm=True)             # warm_sweeps=2 from cold.V
        assert fr._last_solve_warm, swap
        assert np.array_equal(tab.V, want.V), swap
        assert np.array_equal(tab.K, want.K), swap
    assert np.array_equal(cold.V, fr.live_tables.V)  # swap did not publish
