"""Plain reference of the ``gcp-fleet`` configuration.

Written from the paper and the configuration file alone; it imports nothing
of the system under test.

* Lifetimes: Eq. 1, ``F(t) = A (1 - exp(-t/tau1) + exp((t - b)/tau2))`` on
  [0, L], the mass above F(L) preempted at L.  Catalog scenarios modulate
  ``A`` and ``tau1`` by the launch clock (``m = cos(2 pi (c - peak)/24)``,
  ``A (1 + amp_A m)``, ``tau1 (1 - amp_tau1 m)``) after the zone's scaling,
  with ``A`` capped so Eq. 1 stays proper up to L.
* The checkpointing DP, Eqs. 11-15 (``V[j, t] = min_i P_succ (w dt +
  V[j-i, t+w]) + P_fail (E_lost + R_j)``, ``w = i + delta`` except on the
  last segment, ``R_j = V[j, 0]`` of the previous sweep), as a plain jnp
  loop over rows.  The shifted reads ``V[j-i, min(t+w, t_max)]`` come from a
  table whose row r is stored shifted right by r, so each row reads one
  contiguous window; nothing else is restructured.
* The policy evaluator: the same recurrence in float64 numpy with the min
  replaced by a given K, i.e. the expected hours a table's policy costs.
"""
from __future__ import annotations

import functools
import itertools
import math

import jax
import jax.numpy as jnp
import numpy as np

EPS = 1e-9        # guard of the conditional forms (zero survival / failure)
DEAD = 1e-6       # survival below which a VM of that age is dead


# -- lifetimes ----------------------------------------------------------------

def _exp(x):
    return np.exp(np.clip(x, -60.0, 60.0))


def eq1_cdf(p: dict, t):
    t = np.asarray(t, np.float64)
    raw = p["A"] * (1.0 - _exp(-t / p["tau1"]) + _exp((t - p["b"]) / p["tau2"]))
    return np.clip(raw, 0.0, 1.0)


def eq1_partial_expectation(p: dict, t):
    """int_0^t x f(x) dx (Eq. 3's closed form)."""
    def G(x):
        return p["A"] * (-(x + p["tau1"]) * _exp(-x / p["tau1"])
                         + (x - p["tau2"]) * _exp((x - p["b"]) / p["tau2"]))
    t = np.asarray(t, np.float64)
    return G(t) - G(np.zeros_like(t))


def scenario_params(cfg: dict) -> list:
    """Effective Eq. 1 parameters of the catalog scenarios, in the order
    zone x phase x VM type."""
    L, dcfg = cfg["deadline_hours"], cfg["diurnal"]
    out = []
    for zone, phase, vm in itertools.product(cfg["zones"], cfg["phases"],
                                             cfg["vm_types"]):
        base = dict(cfg["vm_type_params"][vm])
        z = cfg["zone_params"][zone]
        A0, tau10 = base["A"] * z["A_scale"], base["tau1"] * z["tau1_scale"]
        m = math.cos(2 * math.pi * (cfg["phase_clocks"][phase]
                                    - dcfg["peak_clock"]) / 24.0)
        tau1 = max(tau10 * (1.0 - dcfg["amp_tau1"] * m), dcfg["tau1_floor"])
        cap = (1.0 - dcfg["proper_margin"]) / (
            1.0 - _exp(-L / tau1) + _exp((L - base["b"]) / base["tau2"]))
        A = min(max(A0 * (1.0 + dcfg["amp_A"] * m), dcfg["A_floor"]),
                max(cap, A0))
        out.append(dict(tau1=tau1, tau2=base["tau2"], b=base["b"], A=A, L=L))
    return out


def prior_params(cfg: dict) -> dict:
    return dict(cfg["vm_type_params"][cfg["live_prior"]],
                L=cfg["deadline_hours"])


def grids(p: dict, grid_dt: float):
    """float64 (F, H) on the age grid, the deadline atom in the last cell."""
    t_max = int(round(p["L"] / grid_dt))
    t = np.arange(t_max + 1) * grid_dt
    F = eq1_cdf(p, t)
    atom = max(1.0 - F[-1], 0.0)
    F[-1] = 1.0
    H = eq1_partial_expectation(p, t)
    H[-1] += atom * p["L"]
    return F, H


# -- the DP -------------------------------------------------------------------

def _conditional(F, H, w, dt):
    """P_fail and E_lost for segments of w steps starting at every age:
    (len(w), T) each."""
    T = F.shape[0]
    t = jnp.arange(T)
    end = jnp.minimum(t[None, :] + w[:, None], T - 1)
    Ft, Fe = F[None, :], F[end]
    p_fail = jnp.clip((Fe - Ft) / jnp.maximum(1 - Ft, EPS), 0, 1)
    dF = jnp.maximum(Fe - Ft, EPS)
    t_dt = t.astype(F.dtype) * dt
    e_lost = (H[end] - H[None, :]) / dF - t_dt[None, :]
    e_lost = jnp.clip(e_lost, 0, (w.astype(F.dtype) * dt)[:, None])
    return p_fail, e_lost


def _solve_one(F, H, col0, dt, *, j_max, delta, n_sweeps):
    J, T, dtype = j_max, F.shape[0], F.dtype
    i = jnp.arange(1, J + 1)
    pf_n, el_n = _conditional(F, H, i + delta, dt)    # checkpointed segment
    pf_f, el_f = _conditional(F, H, i, dt)            # last segment
    w_dt = ((i + delta).astype(dtype) * dt)[:, None]
    dead = (1 - F) < DEAD
    W = T + J + delta                                  # extended row width
    inf = jnp.asarray(jnp.inf, dtype)

    def sweep(col, _):
        R = col                                        # R_j = V[j, 0] before

        def row(j, st):
            Z, V, K = st
            # Z row (J - r) holds V[r, min(u, t_max)] at column r + u
            Vc = jax.lax.dynamic_slice(Z, (J - j + 1, j + delta), (J, T))
            cost = (1 - pf_n) * (w_dt + Vc) + pf_n * (el_n + R[j])
            cost = jnp.where((i < j)[:, None], cost, inf)
            m, k = jnp.min(cost, axis=0), jnp.argmin(cost, axis=0) + 1
            jdt = j.astype(dtype) * dt
            last = (1 - pf_f[j - 1]) * jdt + pf_f[j - 1] * (el_f[j - 1] + R[j])
            take = last < m
            m, k = jnp.where(take, last, m), jnp.where(take, j, k)
            vj = jnp.where(dead, R[j], m)
            kj = jnp.where(dead, j, k)
            ext = jnp.concatenate([vj, jnp.broadcast_to(vj[-1:], (W - T,))])
            Z = jax.lax.dynamic_update_slice(Z, ext[None], (J - j, j))
            return Z, V.at[j].set(vj), K.at[j].set(kj.astype(jnp.int32))

        Z0 = jnp.zeros((2 * J + 1, J + W), dtype)
        V0 = jnp.zeros((J + 1, T), dtype)
        K0 = jnp.zeros((J + 1, T), jnp.int32)
        _, V, K = jax.lax.fori_loop(1, J + 1, row, (Z0, V0, K0))
        return V[:, 0], (V, K)

    _, (V, K) = jax.lax.scan(sweep, col0, None, length=n_sweeps)
    return V[-1], K[-1]


@functools.partial(jax.jit, static_argnames=("j_max", "delta", "n_sweeps"))
def _solve(F, H, col0, dt, *, j_max, delta, n_sweeps):
    return jax.vmap(lambda f, h, c: _solve_one(
        f, h, c, dt, j_max=j_max, delta=delta, n_sweeps=n_sweeps))(F, H, col0)


def dp_solve(F, H, *, grid_dt, j_max, delta, n_sweeps, col0=None,
             dtype=jnp.float32):
    """Solve the DP for stacked (S, T) grids in ``dtype``.  ``col0`` seeds
    the restart costs (a warm start's ``V[:, :, 0]``); cold is ``j dt``.
    Returns float32 V and int32 K, (S, j_max + 1, T), on the host."""
    S = F.shape[0]
    if col0 is None:
        col0 = np.broadcast_to(np.arange(j_max + 1) * grid_dt, (S, j_max + 1))
    with jax.default_matmul_precision("highest"):
        V, K = _solve(jnp.asarray(F, dtype), jnp.asarray(H, dtype),
                      jnp.asarray(col0, dtype), jnp.asarray(grid_dt, dtype),
                      j_max=j_max, delta=delta, n_sweeps=n_sweeps)
    return np.asarray(V, np.float32), np.asarray(K)


# -- the policy evaluator -----------------------------------------------------

def evaluate(K, F, H, *, grid_dt, delta, n_sweeps):
    """Expected hours of following policy tables K (S, J+1, T) on lifetimes
    with float64 grids F, H (S, T); a cold start and ``n_sweeps`` restart
    sweeps.  Returns (S, J+1, T)."""
    K = np.asarray(K)
    S, J1, T = K.shape
    t = np.arange(T)
    s_ix = np.arange(S)[:, None]
    dead = (1.0 - F) < DEAD
    V = np.broadcast_to((np.arange(J1) * grid_dt)[None, :, None],
                        (S, J1, T)).copy()
    for _ in range(n_sweeps):
        R = V[:, :, 0].copy()
        Vn = np.zeros_like(V)
        for j in range(1, J1):
            i = np.clip(K[:, j], 1, j)
            w = np.where(i == j, i, i + delta)
            end = np.minimum(t[None, :] + w, T - 1)
            Fe = np.take_along_axis(F, end, 1)
            He = np.take_along_axis(H, end, 1)
            p_fail = np.clip((Fe - F) / np.maximum(1.0 - F, EPS), 0.0, 1.0)
            dF = np.maximum(Fe - F, EPS)
            e_lost = np.clip((He - H) / dF - t * grid_dt, 0.0, w * grid_dt)
            v_succ = w * grid_dt + Vn[s_ix, j - i, end]
            vj = (1 - p_fail) * v_succ + p_fail * (e_lost + R[:, j:j + 1])
            Vn[:, j] = np.where(dead, R[:, j:j + 1], vj)
        V = Vn
    return V
