"""Shared DP grid construction: the discretized lifetime CDF and its
partial-expectation companion.

Every solver backend consumes the same pair of per-scenario grids:

  ``Fc[t]``  the lifetime CDF on the age grid, with the provider-kill atom
             at the deadline ``L`` folded into the last cell (``Fc[-1] = 1``);
  ``Hc[t]``  the partial expectation ``H(t) = int_0^t x dF~(x)`` including
             the same atom (``Hc[-1] += atom * L``) — the numerator of the
             conditional expected-loss term E[x - t | fail in (t, t+w]].

This module is the single source of those grids (PR 7 deduplicated the
copies that ``solve`` and ``solve_batch`` used to carry): the eager op
sequence below is the bit-exactness anchor — every backend receives float32
grids built by exactly these ops at any session dtype, which is what lets
the batched/XLA/Pallas kernels be compared table-for-table against the
serial reference.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

# Shared guard against zero survival/failure mass in the conditional forms;
# all backends must use this same constant so their per-element arithmetic
# stays comparable.
_EPS = 1e-9


def cdf_grids(dist, grid_dt: float):
    """Build the (Fc, Hc) solver grids for one distribution.

    Returns ``(Fc, Hc, t_max)`` where the grids are host numpy arrays with
    ``t_max + 1`` cells
    (``t_max = round(L / grid_dt)``) and are pinned to the solver's native
    float32: a python-float scalar would trace as weak f64 under x64 and
    shift parts of the DP arithmetic to f64, where the reference and batched
    kernels round differently — pinning keeps every backend bit-comparable
    at any session dtype.

    The grids are built on the host CPU whatever the default device.  The
    DP is ill-conditioned in them: ``e_lost`` divides a difference of
    partial expectations by a small CDF difference, so a 1-ulp change in
    ``Fc``/``Hc`` moves V by ~3e-4 and flips ~4 % of K at J = 720,
    T = 1441.  Accelerators evaluate ``exp``/``log`` to a few ulps, so
    building the grids on the host is what makes tables solved on any
    device comparable to the reference.
    """
    host = jax.devices("cpu")[0]
    dist = jax.tree_util.tree_map(
        lambda x: jax.device_put(x, host) if isinstance(x, jax.Array) else x,
        dist)
    # a one-device host mesh, not ``jax.default_device``: a caller's
    # ``jax.set_mesh`` would place the arrays created here on its mesh
    with jax.set_mesh(Mesh(np.array([host]), ("host",))):
        L = float(dist.L)
        t_max = int(round(L / grid_dt))
        tk = jnp.arange(t_max + 1) * grid_dt
        F_raw = jnp.clip(dist.cdf(tk), 0.0, 1.0)
        atom = jnp.maximum(1.0 - F_raw[-1], 0.0)         # provider kill at L
        Fc = F_raw.at[-1].set(1.0).astype(jnp.float32)
        H_raw = dist.partial_expectation(jnp.zeros_like(tk), tk)
        Hc = H_raw.at[-1].add(atom * L).astype(jnp.float32)
    # host numpy, so the solve that consumes them runs on the default device
    return np.asarray(Fc), np.asarray(Hc), t_max


def price_cum_grids(prices, cum, price_dt: float, grid_dt: float,
                    t_max: int, ext: int):
    """Cumulative-dollar grid on the DP age axis, for the dollar objective.

    ``prices``/``cum``/``price_dt`` are a ``market.PriceGrid``'s fields (duck
    typed so this module stays free of a market import): ``prices`` is
    ``(S, T_price)`` $/hour cells, ``cum[s, k]`` the dollars accrued through
    the first ``k`` cells.  Returns ``(Pc, P0)`` where

      ``Pc[s, m]``  float32 ``(S, t_max + 1 + ext)`` — dollars accrued by a VM
                    of age ``m * grid_dt`` hours, evaluated with exactly the
                    semantics of ``market.integrate_cost_ref`` (piecewise
                    linear between cell edges; ages beyond the price horizon
                    billed at the final cell's price);
      ``P0[s]``     float64 ``(S,)`` — the launch-cell price, used to bill the
                    restart overhead.

    The ``ext`` extra cells extend the axis past the DP horizon so the
    recurrence's ``t + w`` segment-cost gathers never clip: segment dollars
    are ``Pc[t + w] - Pc[t]`` with ``t <= t_max`` and ``w <= ext``.  All
    arithmetic is host float64 (matching ``integrate_cost_ref``) with one
    final float32 cast — the bit-exactness anchor for the dollar objective,
    mirroring the float32 pin in :func:`cdf_grids`.
    """
    prices = np.asarray(prices, np.float64)
    cum = np.asarray(cum, np.float64)
    pdt = float(price_dt)
    tau = np.arange(t_max + 1 + ext, dtype=np.float64) * float(grid_dt)
    k = np.clip(np.floor(tau / pdt).astype(np.int64), 0, prices.shape[1] - 1)
    Pc = cum[:, k] + prices[:, k] * (tau[None, :] - k[None, :] * pdt)
    return Pc.astype(np.float32), prices[:, 0].copy()


def dollar_loss_grids(Fc, Hc, Pc, grid_dt: float, *, j_max: int, t_max: int,
                      delta_steps: int):
    """Expected-lost-dollars grids for the dollar objective, computed on the
    host in plain float32 numpy and fed to every backend as an OPERAND.

    Returns ``Elp`` of shape ``(S, 2, t_max + 1, j_max)``: ``Elp[:, 0]`` the
    non-final-segment variant (``w = i + delta``) and ``Elp[:, 1]`` the
    final-segment variant (``w = i``) of

        E[lost $ | fail in (t, t+w]] = e_lost(t, w) * dP(t, w) / (w * dt)

    (the conditional expected lost hours times the window's average $/hour).
    This is deliberately NOT computed inside the kernels: the expression
    contains mul-feeding-add/sub pairs that XLA:CPU FMA-contracts at the
    LLVM level, and its contraction choices differ between the serial
    reference program (one fused loop body) and the batched kernels (hoisted
    grid fusions) — 1-ulp divergences that ``jax.lax.optimization_barrier``
    does not survive to prevent (the barrier is elided before codegen).
    A single host-side numpy evaluation, consumed bit-for-bit by all
    backends, removes the compiler from the equation entirely — the same
    pattern as ``Pc`` itself.  Every op below is float32 with explicit
    casts so NumPy's promotion rules cannot silently widen to f64.
    """
    Fc = np.asarray(Fc, np.float32)
    Hc = np.asarray(Hc, np.float32)
    Pc = np.asarray(Pc, np.float32)
    dt = np.float32(grid_dt)
    eps = np.float32(_EPS)
    t = np.arange(t_max + 1)
    i = np.arange(1, j_max + 1)
    tdt = t.astype(np.float32) * dt                       # (T,)
    out = []
    for w in (i + delta_steps, i):
        end = np.clip(t[:, None] + w[None, :], 0, t_max)  # (T, I)
        endx = t[:, None] + w[None, :]                    # unclipped
        wdt = w.astype(np.float32) * dt                   # (I,)
        Ft = Fc[:, t][:, :, None]
        Fe = Fc[:, end]
        dF = np.maximum(Fe - Ft, eps)
        el = (Hc[:, end] - Hc[:, t][:, :, None]) / dF - tdt[None, :, None]
        el = np.clip(el, np.float32(0.0), wdt[None, None, :])
        dP = Pc[:, endx] - Pc[:, t][:, :, None]
        pb = dP / wdt[None, None, :]
        out.append(el * pb)
    return np.stack(out, axis=1)
