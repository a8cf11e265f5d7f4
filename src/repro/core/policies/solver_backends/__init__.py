"""Pluggable DP solver backends for the checkpointing DP (Eqs. 11-15).

``checkpointing.solve`` / ``solve_batch`` dispatch here.  Every backend
module implements one contract:

    solve_tables_batch(Fc, Hc, grid_dt, restart_overhead, v_init=None,
                       Pc=None, *, j_max, t_max, delta_steps, n_sweeps)
        -> (V, K)

with stacked ``(S, t_max+1)`` float32 grids (built once by
``grids.cdf_grids``) in and ``(S, j_max+1, t_max+1)`` tables out, and the
``v_init`` warm-start seeding the restart-cost fixed point.  ``Pc=None``
selects the makespan objective; a stacked ``(S, TX)`` cumulative-dollar
grid (``grids.price_cum_grids``, ``TX = t_max+1+j_max+delta_steps``)
selects the dollar objective, in which case ``restart_overhead`` is the
per-scenario ``(S,)`` dollar overhead (hours x launch-cell price, folded
by the dispatcher) so sharding can split it with the other operands.
Backends:

  reference  the retained serial kernel — the bit-exactness anchor;
             batch = a Python loop over scenarios.
  xla        the batched production kernel (hoisted grids, segmented j
             loop); bit-identical to the reference per scenario slice.
  pallas     ``repro.kernels.dp_recurrence`` — VMEM-resident blocked scan;
             tolerance-tested, interpret mode off-TPU.

plus ``refine`` (coarse-to-fine pruning around the coarse argmin), which is
an orchestration over the ``xla`` machinery rather than a fourth contract
implementation — ``checkpointing.solve_batch(refine=True)`` drives it.

Selection: an explicit ``backend=`` name always wins; ``"auto"`` consults
the ``REPRO_SOLVER_BACKEND`` env var and otherwise picks Pallas on TPU and
XLA everywhere else.

Scenario sharding: ``shard_scenarios`` wraps a backend call in ``shard_map``
over the ``"scenario"`` logical axis when a ``repro.sharding`` mesh context
is active and its rules map that axis onto mesh axes dividing ``S``; in
every other case the call runs unwrapped — the exact single-device path, so
sharding is transparent (identical tables, enforced by
``tests/test_solver_backends.py``).
"""
from __future__ import annotations

import os

import jax
from jax.sharding import PartitionSpec

from .... import sharding as _sharding
from . import grids, reference, refine, xla
from . import pallas as pallas_backend

BACKENDS = ("reference", "xla", "pallas")
ENV_VAR = "REPRO_SOLVER_BACKEND"

_MODULES = {"reference": reference, "xla": xla, "pallas": pallas_backend}


def resolve(backend: str = "auto") -> str:
    """Resolve a ``backend=`` argument to a concrete backend name.

    The ``REPRO_SOLVER_BACKEND`` env override applies ONLY to ``"auto"`` —
    code that asks for a backend by name gets that backend (the CI matrix
    steers default-selection tests without silently rewiring the
    bit-contract tests, which pin their backends explicitly).
    """
    if backend == "auto":
        env = os.environ.get(ENV_VAR, "").strip().lower()
        if env:
            backend = env
        else:
            backend = "pallas" if jax.default_backend() == "tpu" else "xla"
    if backend not in BACKENDS:
        raise ValueError(
            f"unknown solver backend {backend!r}; expected one of "
            f"{('auto',) + BACKENDS} (or REPRO_SOLVER_BACKEND in {BACKENDS})")
    return backend


def get(name: str):
    """The backend module for a resolved name."""
    return _MODULES[name]


def scenario_partition(n_scenarios: int):
    """(mesh, PartitionSpec) for the ``scenario`` logical axis under the
    active ``repro.sharding`` context, or ``(None, None)`` when there is no
    mesh, no rule maps the axis, or the mapped axes do not divide S —
    every such case takes the unwrapped single-device path."""
    mesh = _sharding.active_mesh()
    if mesh is None:
        return None, None
    spec = _sharding.spec_for(("scenario",), (int(n_scenarios),))
    if len(spec) == 0 or spec[0] is None:
        return None, None
    return mesh, PartitionSpec(spec[0])


def shard_scenarios(fn, n_scenarios: int, n_args: int, n_out: int):
    """Wrap ``fn(*arrays) -> tuple`` (all inputs and outputs carrying a
    leading ``(S,)`` axis) in ``shard_map`` over the scenario axis.

    Returns ``(wrapped_fn, sharded)``; when no mesh/rule applies the
    original ``fn`` comes back untouched (``sharded=False``) so the
    single-device call path stays byte-identical to the unsharded one.
    Per-scenario DP solves are independent, so the sharded tables match the
    unsharded ones bit-for-bit.
    """
    mesh, pspec = scenario_partition(n_scenarios)
    if mesh is None:
        return fn, False
    wrapped = jax.shard_map(fn, mesh=mesh, in_specs=(pspec,) * n_args,
                            out_specs=(pspec,) * n_out, check_vma=False)
    return wrapped, True
