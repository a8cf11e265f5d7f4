"""Production mesh construction.

A function (never a module-level constant) so importing this module does not
touch jax device state - the dry-run must set XLA_FLAGS before first init.

Every mesh here has *Auto* axes: the trainer places parameters and batches
with ``NamedSharding`` and lets GSPMD propagate the rest, which is the
sharding mode its logical-axis rules (``repro.sharding``) are written for.
"""
from __future__ import annotations

import jax


def make_mesh(shape: tuple, axes: tuple):
    """``jax.make_mesh`` with Auto axes (its default is Explicit)."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: (16, 16) = 256 chips ("data", "model").
    Multi-pod: (2, 16, 16) = 512 chips ("pod", "data", "model") - the pod
    axis is the fault domain (pure DP over DCN)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh():
    """Whatever this host actually has (tests / examples)."""
    n = len(jax.devices())
    return make_mesh((n, 1), ("data", "model"))
