"""Checkpointing-DP inner recurrence (Eqs. 11-15) as a Pallas TPU kernel.

Grid = ``(S_pad / 8,)``: one program per block of eight scenarios, with the
whole DP of the block — ``n_sweeps`` restart-cost fixed-point sweeps x
``j_max`` rows — run inside the program so the value table never leaves
VMEM.  Layout per program:

  * scenarios on the 8 sublanes and the VM-age axis on the lanes: every row
    of the value table is one ``(8, Tp)`` f32 slab (``Tp`` = ``t_max + 1``
    rounded up to 128), so one candidate evaluation is a handful of
    full-vreg VPU multiply-adds;
  * the j-loop's min-reduce over candidate intervals is a sequential scan:
    candidates ``i = 1..j`` stream one at a time, each updating a running
    ``(8, Tp)`` min (strict ``<`` on an ascending scan keeps the
    reference's first-match argmin for ``K``);
  * the value table is a persistent ``(j_max+1, 8, TB)`` VMEM scratch whose
    lanes past ``Tp`` hold each row's horizon value, so the reference's
    ``clip(t + w, 0, t_max)`` age gather becomes a shifted row read.  A
    shift by ``w`` is a 128-aligned dynamic lane window plus a lane rotate
    by ``w mod 128`` (``pltpu.roll``): Mosaic loads only at lane offsets it
    can prove aligned.

V and K rows of the last sweep go straight to HBM by DMA, so only the
value table and the per-row restart costs occupy VMEM
(:func:`vmem_bytes`).

Unlike the XLA backend — which hoists ``(T, I)`` probability/loss grids per
scenario — this kernel recomputes ``p_fail``/``e_lost`` on the fly from the
CDF rows as shifted-slab arithmetic: nothing larger than the value table is
ever materialized.  The trade is bit-exactness: recomputation under a
different fusion schedule rounds differently at ULP scale, so this backend
is tolerance-tested against the reference, not bit-pinned (see
``docs/solver.md``).

The dollar objective adds one more row per program — the cumulative-dollar
grid ``Pc`` (``grids.price_cum_grids``): segment dollars
``dP = Pc[t+w] - Pc[t]`` are the same shifted-slab pattern as the CDF
deltas.  The cumulative row is built host-side on the extended age axis, so
edge padding beyond it only ever feeds dead lanes (whose values are
overwritten with ``Rj``).

Oracle: ``solver_backends.reference``.  Off-TPU the kernel runs with
``interpret=True`` (tests/test_solver_backends.py, marker ``pallas``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_EPS = 1e-9
SUB = 8            # scenarios per program: one per sublane
LANE = 128


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _widths(j_max: int, t_max: int, delta_steps: int):
    """(Tp, TB): compute width and value-table row width in lanes.  Reads
    shift by at most ``j_max + delta_steps`` and load a ``Tp + 128`` window
    from a 128-aligned base at or below the shift."""
    tp = _round_up(t_max + 1, LANE)
    return tp, tp + _round_up(j_max + delta_steps + 1, LANE)


def vmem_bytes(j_max: int, t_max: int, delta_steps: int) -> int:
    """VMEM the kernel's scratch and double-buffered input rows take."""
    tp, tb = _widths(j_max, t_max, delta_steps)
    table = (j_max + 1) * SUB * tb * 4
    restart = (j_max + 1) * SUB * LANE * 4
    rows = 2 * (3 * SUB * tb + SUB * LANE) * 4
    stage = SUB * tp * 4
    return table + restart + rows + stage


def _dp_kernel(dt_ref, fc_ref, hc_ref, pc_ref, ro_ref, c0_hbm, v_hbm, k_hbm,
               v_scr, r_scr, k_stage, *, j_max: int, t_max: int,
               delta_steps: int, n_sweeps: int, Tp: int, TB: int,
               price: bool):
    W = Tp + LANE                                     # loaded window width
    s0 = pl.program_id(0) * SUB
    dtf = dt_ref[0]

    def shifted(load, w):
        """``row[:, w:w+Tp]`` of an (8, TB) row, from an aligned window."""
        base = pl.multiple_of((w // LANE) * LANE, LANE)
        win = load(pl.ds(base, W))
        return pltpu.roll(win, (W - (w - base)) % W, 1)[:, :Tp]

    # row 0 (job done): V = 0 at every age, including the horizon lanes
    v_scr[0] = jnp.zeros((SUB, TB), jnp.float32)
    # restart-cost column seed (cold j*dt / Pc, or a warm start's column 0)
    pltpu.sync_copy(c0_hbm.at[:, pl.ds(s0, SUB), :], r_scr)

    def sweep(s, carry):
        ro = ro_ref[...]                              # (8, 128) overhead

        def restart(m, c):
            # R[m] = overhead + V[m, 0] of the previous sweep (or the seed)
            prev = jnp.where(s == 0, r_scr[m],
                             jnp.broadcast_to(v_scr[m, :, 0:1], (SUB, LANE)))
            r_scr[m] = ro + prev
            return c

        jax.lax.fori_loop(0, j_max + 1, restart, 0)

        def row(j, carry):
            Rj = r_scr[j][:, 0:1]                     # (8, 1)
            Ft = fc_ref[:, :Tp]
            St = jnp.maximum(1.0 - Ft, _EPS)
            dead = (1.0 - Ft) < 1e-6                  # padded lanes: Fc = 1

            def cand(i, mk):
                m, k = mk
                w = jnp.where(i == j, i, i + delta_steps)
                wdt = w.astype(jnp.float32) * dtf
                Ft = fc_ref[:, :Tp]
                Ht = hc_ref[:, :Tp]
                t_dt = jax.lax.broadcasted_iota(
                    jnp.int32, (SUB, Tp), 1).astype(jnp.float32) * dtf
                Fe = shifted(lambda sl: fc_ref[:, sl], w)
                He = shifted(lambda sl: hc_ref[:, sl], w)
                vrow = shifted(lambda sl: v_scr[j - i, :, sl], w)
                p_fail = jnp.clip((Fe - Ft) / St, 0.0, 1.0)
                dF = jnp.maximum(Fe - Ft, _EPS)
                e_lost = (He - Ht) / dF - t_dt
                e_lost = jnp.clip(e_lost, 0.0, wdt)
                if price:
                    dP = shifted(lambda sl: pc_ref[:, sl], w) - pc_ref[:, :Tp]
                    pb = dP / wdt
                    v_succ = dP + vrow
                    cost = (1.0 - p_fail) * v_succ \
                        + p_fail * (e_lost * pb + Rj)
                else:
                    v_succ = wdt + vrow
                    cost = (1.0 - p_fail) * v_succ + p_fail * (e_lost + Rj)
                upd = cost < m
                return jnp.where(upd, cost, m), jnp.where(upd, i, k)

            m0 = jnp.full((SUB, Tp), jnp.inf, jnp.float32)
            k0 = jnp.zeros((SUB, Tp), jnp.int32)
            m, k = jax.lax.fori_loop(1, j + 1, cand, (m0, k0))
            vj = jnp.where(dead, Rj, m)
            # persist the row: computed lanes, then horizon lanes (age >=
            # t_max means a dead VM, whose value is exactly Rj)
            v_scr[j, :, :Tp] = vj
            v_scr[j, :, Tp:] = jnp.broadcast_to(Rj, (SUB, TB - Tp))

            @pl.when(s == n_sweeps - 1)
            def _():
                k_stage[...] = jnp.where(dead, jnp.minimum(j, j_max), k)
                pltpu.sync_copy(v_scr.at[j, :, pl.ds(0, Tp)],
                                v_hbm.at[j, pl.ds(s0, SUB), :])
                pltpu.sync_copy(k_stage, k_hbm.at[j, pl.ds(s0, SUB), :])

            return carry

        return jax.lax.fori_loop(1, j_max + 1, row, carry)

    jax.lax.fori_loop(0, n_sweeps, sweep, 0)
    k_stage[...] = jnp.zeros((SUB, Tp), jnp.int32)
    pltpu.sync_copy(v_scr.at[0, :, pl.ds(0, Tp)], v_hbm.at[0, pl.ds(s0, SUB), :])
    pltpu.sync_copy(k_stage, k_hbm.at[0, pl.ds(s0, SUB), :])


def dp_recurrence(Fc, Hc, col0, Ro, grid_dt, *, j_max: int, t_max: int,
                  delta_steps: int, n_sweeps: int, interpret: bool = False,
                  Pc=None):
    """Solve the batched checkpointing DP.

    Fc, Hc: (S, t_max+1) f32 CDF / partial-expectation grids (see
    ``solver_backends.grids``); col0: (S, j_max+1) f32 seed for the
    restart-cost column (cold ``j*dt`` or a warm start's ``V[:, :, 0]``);
    Ro: (S,) f32 restart overhead in the objective's unit; grid_dt: the
    age-grid step in hours (a scalar, traced or not).
    Returns (V, K) of shapes (S, j_max+1, t_max+1).

    Dollar objective: ``Pc`` is the (S, t_max+1+j_max+delta_steps) f32
    cumulative-dollar grid.  ``col0`` must be the dollar seed
    (``Pc[:, :j_max+1]`` cold, or a warm dollar table's column 0).
    """
    S, T = Fc.shape
    if T != t_max + 1:
        raise ValueError(f"grid width {T} != t_max + 1 = {t_max + 1}")
    price = Pc is not None
    Tp, TB = _widths(j_max, t_max, delta_steps)
    S_pad = _round_up(S, SUB)

    def rows(x):          # (S, n) -> (S_pad, TB), edge-padded both ways
        x = jnp.asarray(x, jnp.float32)
        return jnp.pad(x, ((0, S_pad - S), (0, TB - x.shape[1])), mode="edge")

    fc, hc = rows(Fc), rows(Hc)
    pc = rows(Pc) if price else jnp.zeros((S_pad, TB), jnp.float32)
    ro = jnp.broadcast_to(rows(jnp.reshape(Ro, (S, 1)))[:, :1], (S_pad, LANE))
    c0 = jnp.pad(jnp.asarray(col0, jnp.float32), ((0, S_pad - S), (0, 0)),
                 mode="edge")
    c0 = jnp.broadcast_to(c0.T[:, :, None], (j_max + 1, S_pad, LANE))
    dt = jnp.reshape(jnp.asarray(grid_dt, jnp.float32), (1,))
    kernel = functools.partial(
        _dp_kernel, j_max=j_max, t_max=t_max, delta_steps=delta_steps,
        n_sweeps=n_sweeps, Tp=Tp, TB=TB, price=price)
    row_spec = pl.BlockSpec((SUB, TB), lambda b: (b, 0))
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    V, K = pl.pallas_call(
        kernel,
        grid=(S_pad // SUB,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                  row_spec, row_spec, row_spec,
                  pl.BlockSpec((SUB, LANE), lambda b: (b, 0)), hbm],
        out_specs=[hbm, hbm],
        out_shape=[
            jax.ShapeDtypeStruct((j_max + 1, S_pad, Tp), jnp.float32),
            jax.ShapeDtypeStruct((j_max + 1, S_pad, Tp), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((j_max + 1, SUB, TB), jnp.float32),
            pltpu.VMEM((j_max + 1, SUB, LANE), jnp.float32),
            pltpu.VMEM((SUB, Tp), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=vmem_bytes(j_max, t_max, delta_steps) + (8 << 20)),
        interpret=interpret,
    )(dt, fc, hc, pc, ro, c0)
    # rows were written j-major so each DMA is one aligned (8, Tp) tile run
    return (jnp.transpose(V[:, :S, :T], (1, 0, 2)),
            jnp.transpose(K[:, :S, :T], (1, 0, 2)))
