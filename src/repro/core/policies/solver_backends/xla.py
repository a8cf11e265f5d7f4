"""Batched XLA DP kernel — the CPU/GPU production backend.

The kernel is built from module-private pieces: ``_candidate_grids``
hoists the j-invariant grids, ``_seg_plan``/``_seg_views`` split the j loop
into segments over column prefixes of those grids, ``_body_factory`` is the
one-row update and ``_sweep_from_R`` one full sweep.

Per scenario slice the solve is BIT-IDENTICAL to the serial reference kernel
(``reference.solve_tables``) — the per-candidate arithmetic keeps the
reference expression tree so XLA's FMA contraction matches — while
restructuring the loop body for throughput:

  * the (VM age x candidate interval) grids ``p_fail``/``e_lost`` are
    j-invariant, so they are hoisted out of the 900-iteration loop (the
    reference recomputes them, with two ``(T, I)`` gathers and three
    divisions, every iteration);
  * only the final-segment candidate ``i == j`` (no trailing checkpoint,
    ``w = i``) differs per j, so it is patched as a single column instead
    of re-selecting full ``w``/``end`` grids;
  * ``argmin`` is computed as a min-reduce plus a first-match max-reduce
    (XLA CPU's variadic argmin reduce was half the body's wall-clock);
  * the j loop runs in three segments (thirds of the remaining-work axis)
    so early rows do not scan the full candidate axis; all segments share
    column-prefix views of one precomputed grid set.

The dollar objective (``Pc`` a cumulative-dollar grid, see
``grids.price_cum_grids``) rides the same structure: the per-segment dollar
cost ``dP`` and average price ``pb`` are j-invariant too, so they join the
hoisted grid set (7-tuple -> 11-tuple) and the loop body swaps the two cost
expressions — same gathers, same argmin, bit-identical to the reference's
dollar branch per scenario slice.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .grids import _EPS


def _seg_plan(j_max: int):
    """The j-axis segmentation: thirds of the remaining-work axis when wide
    enough to keep every segment SIMD-wide (a very narrow cost matrix
    compiles to different, ULP-shifting, scalar codegen)."""
    if j_max >= 24:
        j1 = (j_max + 1) // 3
        j2 = 2 * (j_max + 1) // 3
        return [(j1, 1, j1 + 1), (j2, j1 + 1, j2 + 1),
                (j_max, j2 + 1, j_max + 1)]
    return [(j_max, 1, j_max + 1)]


def _candidate_grids(Fc, Hc, dt, *, j_max, t_max, delta_steps, Pc=None,
                    Elp=None):
    """Hoist the j-invariant (VM age x candidate) grids, vmapped over the
    scenario axis.  Identical per-element arithmetic to the reference body.

    Returns ``(pf_nf_f, el_nf_f, end_nf_f, pf_fd_f, el_fd_f, end_fd_f,
    i_full)`` — the non-final (``w = i + delta``) and final-segment
    (``w = i``) probability/loss/end grids plus the full candidate axis.
    With ``Pc``/``Elp`` (dollar objective) the tuple gains ``(dp_nf_f,
    elp_nf_f, dp_fd_f, elp_fd_f)`` — segment dollars ``dP`` (gathered on
    the extended, unclipped price axis; a contraction-free sub of gathers)
    and the host-precomputed expected-lost-dollars grids from
    ``grids.dollar_loss_grids``, passed through untouched so the reference
    kernel consumes the very same bits.
    """
    t_idx = jnp.arange(t_max + 1)
    i_full = jnp.arange(1, j_max + 1)

    def grids(Fc1, Hc1, w):
        end = jnp.clip(t_idx[:, None] + w[None, :], 0, t_max)
        Ft = Fc1[t_idx][:, None]
        Fe = Fc1[end]
        St = jnp.maximum(1.0 - Ft, _EPS)
        p_fail = jnp.clip((Fe - Ft) / St, 0.0, 1.0)
        dF = jnp.maximum(Fe - Ft, _EPS)
        e_lost = (Hc1[end] - Hc1[t_idx][:, None]) / dF - t_idx[:, None] * dt
        e_lost = jnp.clip(e_lost, 0.0, w[None, :] * dt)
        return p_fail, e_lost, end

    pf_nf_f, el_nf_f, end_nf_f = jax.vmap(
        lambda f, h: grids(f, h, i_full + delta_steps))(Fc, Hc)
    pf_fd_f, el_fd_f, end_fd_f = jax.vmap(
        lambda f, h: grids(f, h, i_full))(Fc, Hc)
    base = (pf_nf_f, el_nf_f, end_nf_f, pf_fd_f, el_fd_f, end_fd_f, i_full)
    if Pc is None:
        return base

    def dgrids(Pc1, w):
        endx = t_idx[:, None] + w[None, :]                # unclipped
        return Pc1[endx] - Pc1[t_idx][:, None]

    dp_nf_f = jax.vmap(lambda p: dgrids(p, i_full + delta_steps))(Pc)
    dp_fd_f = jax.vmap(lambda p: dgrids(p, i_full))(Pc)
    return base + (dp_nf_f, Elp[:, 0], dp_fd_f, Elp[:, 1])


def _seg_views(gp, delta_steps, I_len):
    """A shorter candidate axis is a column prefix of the full grids (column
    i's values depend only on i), so segments share one precomputed set;
    end grids are parameter-independent (one copy)."""
    pf_nf_f, el_nf_f, end_nf_f, pf_fd_f, el_fd_f, end_fd_f, i_full = gp[:7]
    sd = (i_full[:I_len], i_full[:I_len] + delta_steps,
          pf_nf_f[:, :, :I_len], el_nf_f[:, :, :I_len],
          pf_fd_f[:, :, :I_len], el_fd_f[:, :, :I_len],
          end_nf_f[0][:, :I_len], end_fd_f[0][:, :I_len])
    if len(gp) > 7:
        dp_nf_f, elp_nf_f, dp_fd_f, elp_fd_f = gp[7:]
        sd = sd + (dp_nf_f[:, :, :I_len], elp_nf_f[:, :, :I_len],
                   dp_fd_f[:, :, :I_len], elp_fd_f[:, :, :I_len])
    return sd


def _body_factory(sd, R, dead, dt, j_max):
    """One j-row update over a segment's candidate prefix (see module
    docstring for the restructurings vs the reference body)."""
    dollar = len(sd) > 8
    if dollar:
        (i_ax, w_nf, pf_nf, el_nf, pf_fd, el_fd, end_nf, end_fd,
         dp_nf, elp_nf, dp_fd, elp_fd) = sd
    else:
        i_ax, w_nf, pf_nf, el_nf, pf_fd, el_fd, end_nf, end_fd = sd
    I_len = int(i_ax.shape[0])

    def _minimize(cost, valid):
        costm = jnp.where(valid[None, :], cost, jnp.inf)
        vj = jnp.min(costm, axis=1)
        # first-match argmin: maximize (I_len - idx) over the minima
        eq = (costm == vj[:, None]) & valid[None, :]
        payload = jnp.where(eq, I_len - jnp.arange(I_len)[None, :], 0)
        kj = (I_len + 1 - jnp.max(payload, axis=1)).astype(jnp.int32)
        return vj, kj

    def body(j, VK):
        V, K = VK
        valid = i_ax <= j

        def one(V1, pf1, el1, pffd1, elfd1, Rj1):
            Vg = V1[(j - i_ax)[None, :], end_nf]
            v_succ = w_nf[None, :] * dt + Vg
            v_fail = el1 + Rj1
            cost = (1.0 - pf1) * v_succ + pf1 * v_fail
            # final-segment candidate i == j: w = i, V[j-i] == V[0]
            colV = V1[0, end_fd[:, j - 1]]
            vs_f = jnp.asarray(j, cost.dtype) * dt + colV
            cost_f = (1.0 - pffd1[:, j - 1]) * vs_f \
                + pffd1[:, j - 1] * (elfd1[:, j - 1] + Rj1)
            cost = jax.lax.dynamic_update_slice(cost, cost_f[:, None],
                                                (0, j - 1))
            return _minimize(cost, valid)

        def one_dollar(V1, pf1, pffd1, dp1, elp1, dpfd1, elpfd1, Rj1):
            Vg = V1[(j - i_ax)[None, :], end_nf]
            v_succ = dp1 + Vg
            v_fail = elp1 + Rj1
            cost = (1.0 - pf1) * v_succ + pf1 * v_fail
            # final-segment candidate i == j: w = i, V[j-i] == V[0]
            colV = V1[0, end_fd[:, j - 1]]
            vs_f = dpfd1[:, j - 1] + colV
            cost_f = (1.0 - pffd1[:, j - 1]) * vs_f \
                + pffd1[:, j - 1] * (elpfd1[:, j - 1] + Rj1)
            cost = jax.lax.dynamic_update_slice(cost, cost_f[:, None],
                                                (0, j - 1))
            return _minimize(cost, valid)

        if dollar:
            vj, kj = jax.vmap(one_dollar)(V, pf_nf, pf_fd,
                                          dp_nf, elp_nf, dp_fd, elp_fd,
                                          R[:, j][:, None])
        else:
            vj, kj = jax.vmap(one)(V, pf_nf, el_nf, pf_fd, el_fd,
                                   R[:, j][:, None])
        vj = jnp.where(dead, R[:, j][:, None], vj)
        kj = jnp.where(dead, jnp.minimum(j, j_max), kj)
        V = jax.vmap(lambda V1, r: jax.lax.dynamic_update_slice(
            V1, r[None, :], (j, 0)))(V, vj.astype(V.dtype))
        K = jax.vmap(lambda K1, r: jax.lax.dynamic_update_slice(
            K1, r[None, :], (j, 0)))(K, kj)
        return V, K

    return body


def _sweep_from_R(gp, seg_data, segs, R, dead, dt, *, j_max, t_max):
    """One full-resolution DP sweep from a given restart-cost vector
    ``R`` of shape ``(S, j_max+1)``.  Returns fresh ``(V, K)``."""
    S = R.shape[0]
    V0 = jnp.zeros((S, j_max + 1, t_max + 1), jnp.float32)
    K0 = jnp.zeros((S, j_max + 1, t_max + 1), jnp.int32)
    VK = (V0, K0)
    for sd, (_, lo, hi) in zip(seg_data, segs):
        VK = jax.lax.fori_loop(lo, hi,
                               _body_factory(sd, R, dead, dt, j_max), VK)
    return VK


def _impl(Fc, Hc, grid_dt, restart_overhead, v_init=None, Pc=None, Elp=None,
          *, j_max: int, t_max: int, delta_steps: int, n_sweeps: int):
    dt = grid_dt
    T = t_max + 1
    S = Fc.shape[0]
    Sc = 1.0 - Fc
    dead = Sc < 1e-6                                      # (S, T)
    segs = _seg_plan(j_max)
    gp = _candidate_grids(Fc, Hc, dt, j_max=j_max, t_max=t_max,
                          delta_steps=delta_steps, Pc=Pc, Elp=Elp)
    seg_data = [_seg_views(gp, delta_steps, I) for I, _, _ in segs]

    def one_sweep(carry, _):
        V_prev, _ = carry
        if Pc is None:
            R = restart_overhead + V_prev[:, :, 0]        # (S, j_max+1)
        else:
            # dollar mode: restart_overhead is the per-scenario (S,) dollar
            # overhead (hours x launch price, folded by the dispatcher)
            R = restart_overhead[:, None] + V_prev[:, :, 0]
        VK = _sweep_from_R(gp, seg_data, segs, R, dead, dt,
                           j_max=j_max, t_max=t_max)
        return VK, None

    if v_init is None:
        if Pc is None:
            # cold start: optimistic j*dt (built inside the jit, exactly as
            # the reference does — the None-vs-array pytree structure gives
            # the warm path its own trace, so this cold graph stays
            # byte-identical to the pre-warm-start kernel and the
            # solve/solve_batch bit contract holds)
            v0 = (jnp.arange(j_max + 1) * dt)[None, :, None]
        else:
            # dollar seed: Pc prefix gather, bit-identical across backends
            v0 = Pc[:, :j_max + 1, None]
        V_init = jnp.broadcast_to(v0, (S, j_max + 1, T)).astype(jnp.float32)
    else:
        # warm start: seed the restart-cost fixed point with a previously
        # converged V (the closed-loop runtime hands in the last-good tables
        # after a drift refit — fewer sweeps reach the same fixed point)
        V_init = v_init.astype(jnp.float32)
    (V, K), _ = jax.lax.scan(one_sweep,
                             (V_init, jnp.zeros((S, j_max + 1, T), jnp.int32)),
                             None, length=n_sweeps)
    return V, K


solve_tables_batch = jax.jit(
    _impl, static_argnames=("j_max", "t_max", "delta_steps", "n_sweeps"))
