"""Serial reference DP kernel — the bit-exactness anchor.

This is the original per-scenario ``_solve_tables`` kernel, retained forever
per the contract in ``checkpointing.py``: every production backend (XLA,
Pallas) is measured against the tables this kernel produces.
It is deliberately unclever — the (age x candidate) grids are recomputed in
every j iteration and the batch path is a plain Python loop over scenarios —
because its job is to be obviously faithful to Eqs. 11-15, not fast.

Objectives.  With ``Pc=None`` the recurrence minimizes expected *makespan*
(hours); with a cumulative-dollar grid ``Pc`` (see ``grids.price_cum_grids``)
it minimizes expected *dollars-to-completion*: segment work is billed at the
integrated price over the VM's age window (``dP = Pc[t+w] - Pc[t]``), lost
work on failure at the window's average price, and the restart overhead at
the launch-cell price (folded into ``restart_overhead`` by the dispatcher,
which passes the per-scenario dollar overhead in dollar mode).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .grids import _EPS


@functools.partial(jax.jit, static_argnames=("j_max", "t_max", "delta_steps",
                                             "n_sweeps"))
def solve_tables(Fc, Hc, grid_dt, restart_overhead, v_init=None, Pc=None,
                 Elp=None, *, j_max: int, t_max: int, delta_steps: int,
                 n_sweeps: int):
    """Returns (V, K) of shapes (j_max+1, t_max+1) for ONE scenario.

    ``v_init`` optionally seeds the restart-cost fixed point (same warm-start
    semantics as the batched kernels, one scenario at a time); the cold path
    (``v_init=None``) builds the optimistic ``j*dt`` seed inside the jit and
    stays byte-identical to the pre-refactor kernel.

    ``Pc`` (``(t_max + 1 + j_max + delta_steps,)`` float32) switches the
    recurrence to the dollar objective.  ``restart_overhead`` must then
    already be dollar-denominated (hours x launch price), and ``Elp``
    (``(2, t_max + 1, j_max)`` float32, ``grids.dollar_loss_grids``) carries
    the expected-lost-dollars grids — precomputed on the host because XLA:CPU
    FMA-contracts that expression differently in this fused loop body than
    in the batched kernels' hoisted grids (see ``dollar_loss_grids``).  The
    extended ``Pc`` tail lets the ``t + w`` segment-cost gathers run past the
    horizon unclipped, which is what makes a flat price reduce exactly to
    ``p x makespan``.
    """
    dt = grid_dt
    t_idx = jnp.arange(t_max + 1)
    i_ax = jnp.arange(1, j_max + 1)                      # candidate intervals
    Sc = 1.0 - Fc
    dead = Sc < 1e-6

    def one_sweep(carry, _):
        V_prev, _ = carry
        # restart cost per remaining length j (uses previous sweep's V[:, 0])
        R = restart_overhead + V_prev[:, 0]              # (j_max+1,)

        def body(j, VK):
            V, K = VK
            valid = i_ax <= j                             # (I,)
            final = i_ax == j                             # no checkpoint on last segment
            w = jnp.where(final, i_ax, i_ax + delta_steps)  # (I,)
            end = jnp.clip(t_idx[:, None] + w[None, :], 0, t_max)  # (T, I)
            Ft = Fc[t_idx][:, None]
            Fe = Fc[end]
            St = jnp.maximum(1.0 - Ft, _EPS)
            p_fail = jnp.clip((Fe - Ft) / St, 0.0, 1.0)
            p_succ = 1.0 - p_fail
            if Pc is None:
                # E[x-t | fail in (t, te]] via H(t) = int_0^t x dF~ (atom
                # incl.)
                dF = jnp.maximum(Fe - Ft, _EPS)
                e_lost = (Hc[end] - Hc[t_idx][:, None]) / dF \
                    - t_idx[:, None] * dt
                e_lost = jnp.clip(e_lost, 0.0, w[None, :] * dt)
                v_succ = w[None, :] * dt + V[j - i_ax[None, :], end]
                v_fail = e_lost + R[j]
            else:
                # dollars: segment billed at integrated price over the age
                # window (unclipped gather on the extended Pc axis); the
                # expected lost dollars come from the host-precomputed Elp
                # grids — in-kernel only gathers, adds and subs remain, all
                # FMA-contraction-free (see grids.dollar_loss_grids)
                endx = t_idx[:, None] + w[None, :]        # (T, I), unclipped
                dP = Pc[endx] - Pc[t_idx][:, None]
                elp = jnp.where(final[None, :], Elp[1], Elp[0])
                v_succ = dP + V[j - i_ax[None, :], end]
                v_fail = elp + R[j]
            cost = p_succ * v_succ + p_fail * v_fail
            cost = jnp.where(valid[None, :], cost, jnp.inf)
            vj = jnp.min(cost, axis=1)
            kj = jnp.argmin(cost, axis=1) + 1
            # dead VM (age >= horizon): must restart
            vj = jnp.where(dead, R[j], vj)
            kj = jnp.where(dead, jnp.minimum(j, j_max), kj)
            V = V.at[j].set(vj.astype(V.dtype))
            K = K.at[j].set(kj.astype(K.dtype))
            return V, K

        V0 = jnp.zeros((j_max + 1, t_max + 1), jnp.float32)
        K0 = jnp.zeros((j_max + 1, t_max + 1), jnp.int32)
        V, K = jax.lax.fori_loop(1, j_max + 1, body, (V0, K0))
        return (V, K), None

    if v_init is None:
        if Pc is None:
            # sweep 0 restart estimate: optimistic j*dt
            seed_col = (jnp.arange(j_max + 1) * dt).astype(jnp.float32)
        else:
            # dollar seed: dollars to run j steps from launch, a pure gather
            # (no arithmetic) so every backend's cold seed is bit-identical
            seed_col = Pc[:j_max + 1]
        V_init = jnp.broadcast_to(seed_col[:, None],
                                  (j_max + 1, t_max + 1)).astype(jnp.float32)
    else:
        V_init = v_init.astype(jnp.float32)
    (V, K), _ = jax.lax.scan(one_sweep,
                             (V_init, jnp.zeros_like(V_init, jnp.int32)),
                             None, length=n_sweeps)
    return V, K


def solve_tables_batch(Fc, Hc, grid_dt, restart_overhead, v_init=None,
                       Pc=None, Elp=None, *, j_max: int, t_max: int,
                       delta_steps: int, n_sweeps: int):
    """Batch adapter for the reference kernel: a plain Python loop over the
    scenario axis (one compiled per-scenario solve, S dispatches).  This is
    the ``backend="reference"`` path of ``solve_batch`` — slow on purpose,
    and the yardstick the equivalence tests hold the fast backends to.

    In dollar mode (``Pc`` an ``(S, TX)`` batch, ``Elp`` an ``(S, 2, T, I)``
    batch) ``restart_overhead`` is the per-scenario ``(S,)`` dollar
    overhead; each scenario gets its own slice.
    """
    outs = []
    for s in range(Fc.shape[0]):
        vi = None if v_init is None else v_init[s]
        pcs = None if Pc is None else Pc[s]
        eps = None if Elp is None else Elp[s]
        ro = restart_overhead if Pc is None else restart_overhead[s]
        outs.append(solve_tables(Fc[s], Hc[s], grid_dt, ro, vi, pcs, eps,
                                 j_max=j_max, t_max=t_max,
                                 delta_steps=delta_steps, n_sweeps=n_sweeps))
    V = jnp.stack([o[0] for o in outs])
    K = jnp.stack([o[1] for o in outs])
    return V, K
