"""Compile rehearsals for a TPU v5e chip, at the sizes the chip runs.

Nothing here executes: each test lowers and compiles one main-path kernel for
a v5e that is described, not attached, and fails where the chip's compiler
would refuse it (an unaligned slice, too much VMEM, a missing lowering).
The topology is described inside a fixture, never at import, so every test
worker collects the same tests and only the worker that runs this file loads
the TPU compiler.
"""
import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import engine
from repro.core import service_kernel as SK
from repro.kernels import dp_recurrence as DP

T_MAX = 1440                  # 24 h deadline on a 1 min grid


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_fits_hbm(compiled, hbm_bytes=16e9):
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert 0 < used < hbm_bytes


@pytest.mark.pallas
@pytest.mark.parametrize("objective", ["makespan", "dollars"])
@pytest.mark.parametrize("j_max", [300, 720])
def test_dp_recurrence_compiles_for_v5e(one_chip, j_max, objective):
    S, T = 8, T_MAX + 1
    f32 = functools.partial(_spec, one_chip, dtype=jnp.float32)
    args = [f32((S, T)), f32((S, T)), f32((S, j_max + 1)), f32((S,)),
            f32(())]
    kw = dict(j_max=j_max, t_max=T_MAX, delta_steps=1, n_sweeps=3)
    if objective == "dollars":
        args.append(f32((S, T + j_max + 1)))
        fn = lambda fc, hc, c0, ro, dt, pc: DP.dp_recurrence(
            fc, hc, c0, ro, dt, Pc=pc, **kw)
    else:
        fn = lambda fc, hc, c0, ro, dt: DP.dp_recurrence(
            fc, hc, c0, ro, dt, **kw)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    # the value table must fit the chip's VMEM with the limit the call asks
    assert DP.vmem_bytes(j_max, T_MAX, 1) < 100 << 20


def test_indexed_makespan_executor_compiles_for_v5e(one_chip):
    """The one-kernel sweep fold at the Fig. 7 size: 8 scenarios x 3
    policies, 5000 trials, J = 720 on the 1441-cell age grid."""
    B, U, Q, n, restarts, J = 24, 17, 8, 5000, 64, 720
    spec = functools.partial(_spec, one_chip)
    args = (spec((U, J + 1, T_MAX + 1), jnp.int32), spec((B,), jnp.int32),
            spec((Q, n, restarts + 2), jnp.float32), spec((B,), jnp.int32),
            spec((B, n), jnp.float32)) \
        + tuple(spec((), jnp.int32) for _ in range(5))
    compiled = engine._makespan_kernel_indexed.lower(*args).compile()
    _assert_fits_hbm(compiled)


def test_service_kernel_compiles_for_v5e(one_chip):
    """The event-synchronous service loop at 10^4 jobs x 50 lanes."""
    B, R, J, Q, P, U, TV, A, slots = 50, 5, 10_000, 5, 1 << 16, 1, 512, 64, 256
    spec = functools.partial(_spec, one_chip)
    f32, i32 = jnp.float32, jnp.int32
    lane = dict(bag_index=spec((B,), i32), pool_index=spec((B,), i32),
                table_index=spec((B,), i32), policy=spec((B,), i32),
                cluster_size=spec((B,), i32), deflate=spec((B,), jnp.bool_),
                deflate_factor=spec((B,), f32), price=spec((B, 1), f32))
    shared = dict(lengths=spec((R, J), f32), deadlines=spec((R, J), f32),
                  pools=spec((Q, P), f32), tables=spec((U, TV, A), jnp.bool_),
                  T_values=spec((TV,), f32), reuse_L=spec((), f32),
                  relaunch_overhead=spec((), f32),
                  hot_spare_hours=spec((), f32), ckpt_on=spec((), jnp.bool_),
                  ckpt_interval=spec((), f32), ckpt_cost=spec((), f32),
                  price_dt=spec((), f32), max_steps=spec((), i32))
    compiled = SK._service_kernel.lower(lane, shared, slots).compile()
    _assert_fits_hbm(compiled)


@pytest.mark.pallas
@pytest.mark.parametrize("k, n", [(2048, 2 * 1408), (1408, 2048)])
def test_moe_gmm_compiles_for_v5e(one_chip, k, n):
    """The expert layer's grouped matmuls (``moe_gmm`` forward and input
    gradient, ``moe_tgmm`` weight gradient) at Moonlight's widths: the
    gate|up and down products of 8 held experts over 8192 x 6 pair rows."""
    from repro.kernels import moe_gmm
    m, G = 8192 * 6, 8
    bf16 = functools.partial(_spec, one_chip, dtype=jnp.bfloat16)
    sizes = _spec(one_chip, (G,), jnp.int32)

    def fwd_bwd(lhs, rhs, sizes):
        return jax.grad(lambda a, b: jnp.sum(moe_gmm.gmm(
            a, b, sizes, impl="pallas").astype(jnp.float32)),
            argnums=(0, 1))(lhs, rhs)

    compiled = jax.jit(fwd_bwd).lower(bf16((m, k)), bf16((G, k, n)),
                                      sizes).compile()
    _assert_fits_hbm(compiled)
    names = compiled.as_text()
    assert "moe_gmm" in names and "moe_tgmm" in names
