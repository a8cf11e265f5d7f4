"""Kernel micro-benchmarks (XLA production paths on CPU; Pallas kernels are
TPU-targeted and validated in interpret mode, so their CPU timings are not
meaningful - we time the XLA flash/assoc implementations the dry-run lowers,
against the naive references)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import ops, ref

from .common import emit, timed


def _bench(fn, *args, reps=3):
    out = fn(*args)
    jax.block_until_ready(out)
    (out), us = timed(lambda: jax.block_until_ready(fn(*args)), reps=reps)
    return us


def run():
    key = jax.random.PRNGKey(0)
    ks = jax.random.split(key, 3)
    B, S, H, KV, Dh = 1, 2048, 8, 2, 64
    q = jax.random.normal(ks[0], (B, S, H, Dh), jnp.float32)
    k = jax.random.normal(ks[1], (B, S, KV, Dh), jnp.float32)
    v = jax.random.normal(ks[2], (B, S, KV, Dh), jnp.float32)

    naive = jax.jit(lambda q, k, v: ref.attention(q, k, v, causal=True))
    flash = jax.jit(lambda q, k, v: ops.flash_attention_xla(q, k, v, True, 0,
                                                            None, 512, 512))
    us_n = _bench(naive, q, k, v)
    us_f = _bench(flash, q, k, v)
    flops = 4 * B * S * S / 2 * H * Dh
    emit("kernels/attn_naive_2k", us_n, f"gflops={flops/us_n/1e3:.1f}")
    emit("kernels/attn_xla_flash_2k", us_f,
         f"gflops={flops/us_f/1e3:.1f};vs_naive={us_n/us_f:.2f}x")

    a = jax.random.uniform(ks[0], (4, 4096, 256), minval=0.9, maxval=0.999)
    b = 0.1 * jax.random.normal(ks[1], (4, 4096, 256))
    seq = jax.jit(lambda a, b: ref.linear_recurrence(a, b))
    assoc = jax.jit(lambda a, b: ops.linear_recurrence(a, b, impl="assoc"))
    us_s = _bench(seq, a, b)
    us_a = _bench(assoc, a, b)
    emit("kernels/linrec_scan_4k", us_s, "impl=lax.scan")
    emit("kernels/linrec_assoc_4k", us_a,
         f"impl=associative_scan;vs_scan={us_s/us_a:.2f}x")

    qd = jax.random.normal(ks[0], (8, 16, 64), jnp.float32)
    kc = jax.random.normal(ks[1], (8, 8192, 4, 64), jnp.float32)
    vc = jax.random.normal(ks[2], (8, 8192, 4, 64), jnp.float32)
    ln = jnp.full((8,), 8192, jnp.int32)
    dec = jax.jit(lambda q, k, v, l: ref.decode_attention(q, k, v, l))
    us_d = _bench(dec, qd, kc, vc, ln)
    bytes_read = kc.size * 4 * 2
    emit("kernels/decode_8k_cache", us_d,
         f"GBps={bytes_read/us_d/1e3:.1f}")

    _bench_dp_recurrence()


def _bench_dp_recurrence():
    """The checkpointing-DP inner recurrence on one small workload: the
    XLA production kernel and the Pallas kernel in interpret mode (CPU
    emulation — timing is a smoke number, not a device number)."""
    from repro.core import distributions as D
    from repro.core.policies import checkpointing as ckpt

    dists = [D.constrained_for("n1-highcpu-16"), D.Exponential(mttf=8.0),
             D.Weibull(lam=0.12, k=0.8)]
    wl = dict(grid_dt=1.0 / 6.0, n_sweeps=2)
    job = 24
    us_x = _bench(lambda: ckpt.solve_batch(dists, job, backend="xla", **wl))
    us_p = _bench(lambda: ckpt.solve_batch(dists, job, backend="pallas",
                                           **wl))
    emit("kernels/dp_recurrence_xla_S3_J24", us_x, "backend=xla")
    emit("kernels/dp_recurrence_pallas_S3_J24", us_p,
         f"backend=pallas;interpret=True(cpu_smoke);vs_xla={us_x/us_p:.2f}x")


if __name__ == "__main__":
    run()
