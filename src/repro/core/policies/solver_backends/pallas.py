"""Pallas DP backend: adapter from the backend contract to the
``repro.kernels.dp_recurrence`` kernel.

Selected by ``backend="auto"`` on TPU; on CPU it runs the kernel in
interpret mode (``backend="pallas"`` explicitly, or the
``REPRO_SOLVER_BACKEND=pallas`` env override), which is how the CI matrix
validates it without TPU hardware.  Tolerance-tested against the reference —
the kernel recomputes the probability grids on the fly under a different
fusion schedule, so it is NOT part of the bit-exactness contract (see
``docs/solver.md``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ....kernels.dp_recurrence import dp_call


def _interpret_default() -> bool:
    # lower natively on TPU; emulate anywhere else
    return jax.default_backend() != "tpu"


def _impl(Fc, Hc, grid_dt, restart_overhead, v_init=None, Pc=None, *,
          j_max: int, t_max: int, delta_steps: int, n_sweeps: int,
          interpret: bool):
    S = Fc.shape[0]
    if v_init is None:
        if Pc is None:
            col0 = jnp.broadcast_to(
                (jnp.arange(j_max + 1) * grid_dt)[None, :],
                (S, j_max + 1)).astype(jnp.float32)
        else:
            col0 = jnp.asarray(Pc, jnp.float32)[:, :j_max + 1]
    else:
        col0 = v_init[:, :, 0].astype(jnp.float32)
    Ro = jnp.broadcast_to(jnp.asarray(restart_overhead, jnp.float32), (S,))
    return dp_call(Fc, Hc, col0, Ro, grid_dt, Pc, j_max=j_max, t_max=t_max,
                   delta_steps=delta_steps, n_sweeps=n_sweeps,
                   interpret=interpret)


# the seed column, the kernel's pads, the kernel and the transposes: one
# compiled call per (shapes, dtypes, v_init/Pc structure, statics)
_solve = jax.jit(_impl, static_argnames=(
    "j_max", "t_max", "delta_steps", "n_sweeps", "interpret"))


def solve_tables_batch(Fc, Hc, grid_dt, restart_overhead, v_init=None,
                       Pc=None, Elp=None, *, j_max: int, t_max: int,
                       delta_steps: int, n_sweeps: int, interpret=None):
    """Backend contract entry (see ``solver_backends.__init__``): stacked
    ``(S, t_max+1)`` grids in, ``(S, j_max+1, t_max+1)`` tables out.

    The kernel carries the restart-cost fixed point through a column-0 VMEM
    scratch, so the warm start enters as the seed column ``v_init[:, :, 0]``
    — same semantics as the full-array seed of the other backends, because
    sweeps couple only through that column.

    Dollar objective: ``Pc`` is the ``(S, TX)`` cumulative-dollar grid and
    ``restart_overhead`` the per-scenario ``(S,)`` dollar overhead, both
    forwarded to the kernel's price mode.  The host-precomputed ``Elp``
    loss grids are accepted for contract uniformity but IGNORED: the Pallas
    kernel recomputes the expected-lost-dollars term in-lane, which is
    exactly why this backend sits under the tolerance contract rather than
    the bit-identity one.

    The whole adapter is one jitted call: it is traced and lowered once per
    shape (``dp_recurrence.trace_count``) and only dispatched afterwards.
    """
    if interpret is None:
        interpret = _interpret_default()
    return _solve(Fc, Hc, grid_dt, restart_overhead, v_init, Pc,
                  j_max=j_max, t_max=t_max, delta_steps=delta_steps,
                  n_sweeps=n_sweeps, interpret=bool(interpret))
