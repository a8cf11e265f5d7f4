"""Service-kernel benchmark: the Python heap event loop vs the batched
event-synchronous JAX kernel (repro.core.service_kernel).

Three blocks, written into ``BENCH_simulation.json`` under
``"service_kernel"`` (payload schema 2 — see docs/bench_schemas.md):

  * ``fig8`` — the paper's Fig. 8 workload (100-job bags, cluster 32,
    model + memoryless policies over seeds): wall-clock for the whole grid
    through ``run_bag_grid`` in both modes, plus the number of rows that
    are bit-identical when the comparison is repeated under x64;
  * ``scale`` — the kernel's design point (10^4-job bags, where the serial
    loop's per-event O(J) bookkeeping dominates): events/sec measured
    directly for ONE serial lane and for a 50-lane kernel dispatch of the
    same workload, and their ratio (the headline speedup);
  * ``one_dispatch`` — a >=10^5-job batch completing in ONE jitted
    dispatch: jobs/sec, events/sec and the step count.

Serial event counts are taken from the kernel lane that replays the same
(bag, pool, policy) — the trajectories are identical by construction (and
bit-identical under x64; see tests/test_service_kernel.py), and the serial
loop does not count events itself.

``run(quick=True)`` shrinks every block (2,000-job bags, fewer seeds) so a
CI smoke pass finishes in tens of seconds; standalone runs (``--only
service``) update only the ``service_kernel`` block of an existing
``BENCH_simulation.json`` and leave the sibling blocks in place.
"""
from __future__ import annotations

import json
import os
import time

import jax
import numpy as np

from repro.core import distributions as D
from repro.core import service as SV
from repro.core import service_kernel as K

from .common import REPO_ROOT, emit, write_bench_json

VM_TYPE = "n1-highcpu-32"


def _grid_kw(quick: bool) -> dict:
    return dict(vm_types=(VM_TYPE,),
                policies=("model", "memoryless"),
                cluster_sizes=(32,),
                seeds=tuple(range(2 if quick else 10)),
                n_jobs=40 if quick else 100,
                job_hours=2.0)


def _rows_identical(rows_a, rows_b) -> int:
    n = 0
    for a, b in zip(rows_a, rows_b):
        x, y = a["result"], b["result"]
        n += (x.makespan == y.makespan and x.vm_hours == y.vm_hours
              and x.n_preemptions == y.n_preemptions
              and x.n_job_failures == y.n_job_failures)
    return n


def _bench_fig8(quick: bool) -> dict:
    kw = _grid_kw(quick)
    SV.run_bag_grid(mode="batched", **kw)  # jit warm-up
    t0 = time.perf_counter()
    rows_s = SV.run_bag_grid(mode="serial", **kw)
    t_serial = time.perf_counter() - t0
    t0 = time.perf_counter()
    rows_b = SV.run_bag_grid(mode="batched", **kw)
    t_batched = time.perf_counter() - t0

    # repeat the comparison under x64, where the contract is bit-identity
    with jax.enable_x64(True):
        bitexact = _rows_identical(SV.run_bag_grid(mode="serial", **kw),
                                   SV.run_bag_grid(mode="batched", **kw))
    emit(f"service/fig8_grid_n{kw['n_jobs']}", t_batched * 1e6,
         f"serial_s={t_serial:.3f};batched_s={t_batched:.3f};"
         f"speedup={t_serial / t_batched:.1f}x;"
         f"bitexact_x64={bitexact}/{len(rows_s)}")
    return dict(n_jobs=kw["n_jobs"], grid_rows=len(rows_s),
                serial_s=t_serial, batched_s=t_batched,
                speedup_wall=t_serial / t_batched,
                rows_bitexact_x64=bitexact)


def _kernel_dispatch(n_jobs: int, lanes: int, n_bags: int,
                     pool_size: int) -> tuple:
    """Warm up then time one B-lane memoryless dispatch; returns timings."""
    dist = D.constrained_for(VM_TYPE)
    seeds = list(range(n_bags))
    bags = np.stack([SV._bag_lengths(n_jobs, 2.0, 0.1, s) for s in seeds])
    pools = K.draw_service_pool_batch([dist] * n_bags, seeds, size=pool_size)
    kw = dict(lengths=bags, pools=pools,
              bag_index=[i % n_bags for i in range(lanes)],
              pool_index=[i % n_bags for i in range(lanes)],
              policy=["memoryless"] * lanes, cluster_size=[32] * lanes)
    K.simulate_service_batch(**kw)  # compile warm-up
    t0 = time.perf_counter()
    res = K.simulate_service_batch(**kw)
    return res, time.perf_counter() - t0


def _bench_scale(quick: bool) -> dict:
    n_jobs = 2_000 if quick else 10_000
    lanes = 50
    pool_size = 4 * n_jobs

    res, t_kernel = _kernel_dispatch(n_jobs, lanes, 2, pool_size)
    ev_kernel = int(res.n_events.sum())

    # ONE serial lane of the same workload (same bag, same pooled stream)
    t0 = time.perf_counter()
    SV.run_bag_grid(mode="serial", vm_types=(VM_TYPE,),
                    policies=("memoryless",), cluster_sizes=(32,),
                    seeds=(0,), n_jobs=n_jobs, job_hours=2.0,
                    pool_size=pool_size)
    t_serial = time.perf_counter() - t0
    ev_serial = int(res.n_events[0])  # lane 0 replays the serial trajectory

    eps_serial = ev_serial / t_serial
    eps_kernel = ev_kernel / t_kernel
    speedup = eps_kernel / eps_serial
    emit(f"service/scale_n{n_jobs}_B{lanes}", t_kernel * 1e6,
         f"serial_ev_s={eps_serial:.0f};kernel_ev_s={eps_kernel:.0f};"
         f"speedup_events_per_sec={speedup:.0f}x")
    return dict(
        n_jobs=n_jobs,
        serial=dict(events=ev_serial, wall_s=t_serial,
                    events_per_s=eps_serial),
        kernel=dict(lanes=lanes, jobs_total=lanes * n_jobs,
                    events=ev_kernel, wall_s=t_kernel,
                    events_per_s=eps_kernel,
                    jobs_per_s=lanes * n_jobs / t_kernel),
        speedup_events_per_sec=speedup)


def _bench_one_dispatch(quick: bool) -> dict:
    n_jobs = 2_000 if quick else 100_000
    lanes = 50 if quick else 10
    res, t = _kernel_dispatch(n_jobs, lanes, 2, 4 * n_jobs)
    jobs_total = lanes * n_jobs
    ev = int(res.n_events.sum())
    emit(f"service/one_dispatch_{jobs_total}jobs", t * 1e6,
         f"jobs_per_s={jobs_total / t:.0f};events_per_s={ev / t:.0f};"
         f"steps_max={int(res.steps.max())}")
    return dict(n_jobs_per_lane=n_jobs, lanes=lanes, jobs_total=jobs_total,
                events=ev, wall_s=t, events_per_s=ev / t,
                jobs_per_s=jobs_total / t, steps_max=int(res.steps.max()))


def bench_block(quick: bool = False) -> dict:
    """The ``service_kernel`` block embedded in ``BENCH_simulation.json``."""
    return {
        "fig8": _bench_fig8(quick),
        "scale": _bench_scale(quick),
        "one_dispatch": _bench_one_dispatch(quick),
    }


def run(quick: bool = False):
    block = bench_block(quick)
    # standalone runs patch the existing artifact in place so the sibling
    # blocks (written by sim_engine_bench) keep their numbers
    path = os.path.join(REPO_ROOT, "BENCH_simulation.json")
    payload = {"schema": 2, "mode": "quick" if quick else "full"}
    if os.path.exists(path):
        with open(path) as f:
            payload = json.load(f)
        payload["schema"] = max(2, int(payload.get("schema", 0)))
    payload["service_kernel"] = block
    payload["generated_unix"] = time.time()
    write_bench_json("BENCH_simulation.json", payload,
                     emit_as="service/json")


if __name__ == "__main__":
    import sys

    run(quick="--quick" in sys.argv)
