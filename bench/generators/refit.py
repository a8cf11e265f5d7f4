"""Closed-loop table refresh through ``FleetRuntime``'s solve-and-swap path.

Set-up builds the runtime (its bootstrap cold solve of the catalog plus the
prior live model), draws the mix's live Eq. 1 refits from the seed and warms
the refresh once.  In the window one caller refreshes back to back: it
hands the runtime a new refit and times the warm re-solve, validation and
publish.  Every refresh starts from the tables published at set-up, so each
is the same work and any of them can be checked without replaying a chain.
The check re-solves a seeded sample of the window's refreshes with the
configuration's plain reference.
"""
from __future__ import annotations

import time

import numpy as np

from bench import common


def live_fits(cfg: dict, mix: dict, seed: int) -> list:
    """Eq. 1 refits of a drifting fleet window: a fleet type's fit with
    log-normal jitter on tau1, tau2 and A."""
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(mix["fits"]):
        base = cfg["vm_type_params"][rng.choice(cfg["fleet_vm_types"])]
        jit = {k: float(np.exp(rng.normal(0.0, s)))
               for k, s in mix["fit_jitter"].items()}
        out.append(dict(tau1=base["tau1"] * jit["tau1"],
                        tau2=base["tau2"] * jit["tau2"], b=base["b"],
                        A=min(base["A"] * jit["A"], mix["fit_A_max"]),
                        L=cfg["deadline_hours"]))
    return out


class Generator:
    def __init__(self, spec: dict, *, seed: int, reference):
        self.cfg, self.mix, self.ref = spec["config"], spec["mix"], reference
        self.seed = seed
        self.kept: list = []

    def setup(self):
        from repro.core import distributions as D
        from repro.core import runtime as rt
        from repro.core import scenarios as SC

        c, m = self.cfg, self.mix
        grid = SC.default_grid(vm_types=tuple(c["vm_types"]),
                               phases=tuple(c["phases"]),
                               zones=tuple(c["zones"]))
        self.fr = rt.FleetRuntime(rt.RuntimeConfig(
            base_scenarios=tuple(s.name for s in grid),
            job_steps=m["job_steps"], grid_dt=c["grid_dt_hours"],
            delta_steps=c["delta_steps"],
            restart_overhead=c["restart_overhead_hours"],
            n_sweeps=m["n_sweeps"], warm_sweeps=m["warm_sweeps"],
            solver_backend=m["backend"], dp_objective=c["objective"]))
        self.base = self.fr.live_tables
        self.n_scenarios = len(self.base)
        self.fits = live_fits(c, m, common.subseed(self.seed, "fits"))
        self.dists = [D.Constrained(**{k: np.float32(v) for k, v in p.items()})
                      for p in self.fits]
        self.refresh(self.dists[-1])          # warm the refresh's programs

    def refresh(self, dist) -> bool:
        """One refresh: a new live model to a validated, published table."""
        fr = self.fr
        fr.live_tables = self.base
        failures = fr.retries["solve"]
        fr.tracker.model = dist
        fr._try_swap("initial-fit")
        return fr.retries["solve"] == failures and fr.live_tables is not self.base

    def window(self, seconds: float) -> dict:
        import jax

        k = self.mix["check_sample"]
        rng = np.random.default_rng(common.subseed(self.seed, "sample"))
        lat, failed, n = [], 0, 0
        end = time.perf_counter() + seconds
        while time.perf_counter() < end:
            with jax.profiler.TraceAnnotation("bench.refresh"):
                t0 = time.perf_counter()
                ok = self.refresh(self.dists[n % len(self.dists)])
                lat.append(time.perf_counter() - t0)
            failed += not ok
            tab = self.fr.live_tables if ok else None
            # a seeded reservoir: every refresh equally likely to be checked
            if len(self.kept) < k:
                self.kept.append((n, tab))
            else:
                r = int(rng.integers(0, n + 1))
                if r < k:
                    self.kept[r] = (n, tab)
            n += 1
        return dict(attempted=n, failed=failed, latencies=lat, refreshes=n)

    def work(self) -> dict:
        return {}

    def free(self):
        self.fr = self.base = None

    def check(self) -> dict:
        return compare(self.cfg, self.mix, self.fits, self.kept, self.ref)

    def readings(self) -> dict:
        """After a window: the program's numbers and the control's, the
        reference in bfloat16 put in the program's place."""
        import jax.numpy as jnp
        c, m = self.cfg, self.mix
        solve = lambda F, H, c0: self.ref.dp_solve(
            F, H, col0=c0, grid_dt=c["grid_dt_hours"], j_max=m["job_steps"],
            delta=c["delta_steps"], n_sweeps=m["warm_sweeps"],
            dtype=jnp.bfloat16)
        return dict(program=self.check(),
                    control=compare(c, m, self.fits, self.kept, self.ref,
                                    solve=solve))


def compare(cfg, mix, fits, kept, ref, *, solve=None) -> dict:
    """The numbers ``correct`` compares: the widest gap of V (hours) and the
    largest relative excess cost of the served policy K over the
    reference's, by the float64 evaluator, for every job length on a fresh
    VM.  ``solve`` replaces the reference's solve of the served tables (the
    control puts a lower-precision reference in the program's place)."""
    dt, delta, J = cfg["grid_dt_hours"], cfg["delta_steps"], mix["job_steps"]
    kw = dict(grid_dt=dt, j_max=J, delta=delta)
    base = [ref.grids(p, dt) for p in ref.scenario_params(cfg)]
    boot = base + [ref.grids(ref.prior_params(cfg), dt)]
    f32 = lambda gs, i: np.stack([g[i] for g in gs]).astype(np.float32)
    V0, _ = ref.dp_solve(f32(boot, 0), f32(boot, 1), n_sweeps=mix["n_sweeps"],
                         **kw)
    v_gap, k_gap = -np.inf, -np.inf
    if not kept or any(tab is None for _, tab in kept):
        return dict(v_gap_h=float("nan"), k_cost_gap=float("nan"))
    for n, tab in kept:
        gs = base + [ref.grids(fits[n % len(fits)], dt)]
        F64, H64 = np.stack([g[0] for g in gs]), np.stack([g[1] for g in gs])
        Vr, Kr = ref.dp_solve(f32(gs, 0), f32(gs, 1), col0=V0[:, :, 0],
                              n_sweeps=mix["warm_sweeps"], **kw)
        if solve is not None:
            Vp, Kp = solve(f32(gs, 0), f32(gs, 1), V0[:, :, 0])
        else:
            Vp, Kp = np.asarray(tab.V), np.asarray(tab.K)
        v_gap = max(v_gap, float(np.max(np.abs(Vp - Vr))))
        ev = dict(grid_dt=dt, delta=delta, n_sweeps=mix["check_eval_sweeps"])
        cp = ref.evaluate(Kp, F64, H64, **ev)[:, 1:, 0]
        cr = ref.evaluate(Kr, F64, H64, **ev)[:, 1:, 0]
        k_gap = max(k_gap, float(np.max((cp - cr) / cr)))
    return dict(v_gap_h=v_gap, k_cost_gap=k_gap)
