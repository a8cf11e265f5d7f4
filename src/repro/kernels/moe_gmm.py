"""Grouped matrix products of the dropless expert layer, named for traces.

``gmm(lhs, rhs, group_sizes)``: the rows of ``lhs`` (m, k) are sorted by
group; group g's ``group_sizes[g]`` rows are multiplied by ``rhs[g]``
(k, n).  Rows past ``sum(group_sizes)`` are not computed and their output
is undefined: callers mask them.

``impl="pallas"``: megablox's Pallas TPU kernels
(``jax.experimental.pallas.ops.tpu.megablox``), each called inside a
``jax.jit`` named ``moe_gmm`` (forward and the input gradient) or
``moe_tgmm`` (the weight gradient).  XLA names a Pallas custom call after
its enclosing jit, so the device ops read ``moe_gmm.<n>`` and
``moe_tgmm.<n>`` in a trace.  The VJP is megablox's: dlhs = gmm(dy, rhs^T),
drhs = tgmm(lhs^T, dy).  ``interpret=True`` runs the same kernels in the
Pallas interpreter (CPU tests).
``impl="ragged_dot"``: ``jax.lax.ragged_dot``, the XLA op; the oracle, and
what non-TPU backends lower (the dry-run).
``impl="auto"``: ``pallas`` on a TPU backend, else ``ragged_dot``.
"""
from __future__ import annotations

import functools
import importlib

import jax
import jax.numpy as jnp

# the module, not the package's same-named custom_vjp export
_mb = importlib.import_module("jax.experimental.pallas.ops.tpu.megablox.gmm")

TM = 256          # rows per tile: the padding each group pays at most
_MAX_TILE = 512   # k and n tiles (tgmm holds a (tk, tn) f32 block in VMEM)


def _tile(dim: int) -> int:
    """Largest multiple of 128 up to _MAX_TILE dividing ``dim``, else the
    whole dimension (a block equal to the array dimension is legal)."""
    for t in range(_MAX_TILE, 127, -128):
        if dim % t == 0:
            return t
    return dim


def row_tile(m: int) -> int:
    """The row tile for an m-row operand (m is padded to a multiple)."""
    return min(TM, m)


def _tiling(m, k, n):
    return (row_tile(m), _tile(k), _tile(n))


@functools.partial(jax.jit, static_argnames=("transpose_rhs", "interpret"))
def moe_gmm(lhs, rhs, group_sizes, transpose_rhs=False, interpret=False):
    k = lhs.shape[1]
    n = rhs.shape[1] if transpose_rhs else rhs.shape[2]
    return _mb.gmm.__wrapped__(
        lhs, rhs, group_sizes, lhs.dtype, _tiling(lhs.shape[0], k, n),
        None, None, transpose_rhs, interpret)


@functools.partial(jax.jit, static_argnames=("interpret",))
def moe_tgmm(lhs, dy, group_sizes, interpret=False):
    """(G, k, n) = per group lhs[rows]^T @ dy[rows], f32."""
    m, k = lhs.shape
    n = dy.shape[1]
    return _mb.tgmm.__wrapped__(
        lhs.swapaxes(0, 1), dy, group_sizes, jnp.float32, _tiling(m, k, n),
        None, group_sizes.shape[0], None, interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _gmm_pallas(lhs, rhs, group_sizes, interpret):
    return moe_gmm(lhs, rhs, group_sizes, interpret=interpret)


def _fwd(lhs, rhs, group_sizes, interpret):
    return (moe_gmm(lhs, rhs, group_sizes, interpret=interpret),
            (lhs, rhs, group_sizes))


def _bwd(interpret, res, dy):
    lhs, rhs, group_sizes = res
    dlhs = moe_gmm(dy.astype(lhs.dtype), rhs, group_sizes, transpose_rhs=True,
                   interpret=interpret)
    drhs = moe_tgmm(lhs, dy.astype(lhs.dtype), group_sizes,
                    interpret=interpret)
    return dlhs, drhs.astype(rhs.dtype), None


_gmm_pallas.defvjp(_fwd, _bwd)


def gmm(lhs, rhs, group_sizes, *, impl: str = "auto",
        interpret: bool = False):
    """lhs (m, k) with m a multiple of ``row_tile(m)``; rhs (G, k, n);
    group_sizes (G,) int32.  Returns (m, n) in lhs's dtype."""
    if impl == "auto":
        impl = "pallas" if jax.default_backend() == "tpu" else "ragged_dot"
    if impl == "pallas":
        return _gmm_pallas(lhs, rhs, group_sizes, interpret)
    if impl == "ragged_dot":
        return jax.lax.ragged_dot(lhs, rhs, group_sizes)
    raise ValueError(f"unknown grouped matmul impl {impl!r}")
