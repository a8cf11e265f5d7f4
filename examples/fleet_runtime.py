"""Closed-loop fleet runtime demo: drift, faults, and graceful degradation.

Streams fleet lifetimes through ``runtime.FleetRuntime`` under the
deterministic default fault schedule (a regime drift, a preemption storm,
injected fit divergences and a solve timeout — see `docs/runtime.md`).  The
runtime refits Eq. 1 on a confirmed change point, re-solves the DP
(warm-started from the previous value table) and hot-swaps validated tables
into the standing sweep; every injected fault degrades to the last-good
model/tables instead of crashing.

Run: PYTHONPATH=src python examples/fleet_runtime.py [--quick]

``--quick`` shrinks the stream so the example (and the CI smoke that
executes it) finishes in seconds; the printed structure is identical.
"""
import sys

from repro import fault
from repro.core import runtime as rt
from repro.kernels import dp_recurrence

QUICK = "--quick" in sys.argv
n_obs = 320 if QUICK else 800

cfg = rt.RuntimeConfig(
    job_steps=40, grid_dt=0.25, window=128, refit_every=32, min_samples=48,
    stream_block=128, stream_vm_types=("n1-highcpu-2",),
    regret_trials=64 if QUICK else 256, retry_backoff_obs=8, max_retries=3)
schedule = fault.default_schedule(n_obs)
print(f"fault schedule ({n_obs} observations):")
for ev in schedule:
    print(f"  obs {ev.at_obs:4d}: {ev.kind:15s} duration={ev.duration}"
          + (f"  param={ev.param}" if ev.param else ""))

runtime = rt.FleetRuntime(cfg, injector=fault.FaultInjector(schedule, seed=0))
report = runtime.run(n_obs)

print("\nevent log (stream -> track -> refit -> re-solve -> swap):")
for obs, kind, detail in report.events:
    print(f"  obs {obs:4d}: {kind:22s} {detail}")

print(f"\nswaps ({len(report.swaps)}; Pallas DP traces: "
      f"{dp_recurrence.trace_count()}):")
for s in report.swaps:
    regret = ("" if s.regret_frac is None
              else f"  stale-K regret {s.regret_hours:+.2f}h "
                   f"({s.regret_frac:+.1%})")
    print(f"  obs {s.obs:4d}: {s.reason:12s} warm={s.warm!s:5s} "
          f"solve {s.solve_seconds:.2f}s  stale for {s.stale_obs} obs{regret}")

print(f"\nheadline: {report.change_points} change point(s), "
      f"{report.n_refits} refits, retries fit={report.retries['fit']} "
      f"solve={report.retries['solve']}, degraded={report.degraded}")
if report.adaptation_lag_obs is not None:
    print(f"adaptation lag: {report.adaptation_lag_obs} observations from "
          f"injected drift to the answering table swap")

print("\nthe fleet keeps serving: re-evaluating the standing sweep from the "
      "CURRENT live tables (no re-solve)")
rows = runtime.evaluate(n_trials=64 if QUICK else 256)
for r in rows:
    if r["scenario"] == cfg.live_name:
        print(f"  {r['scenario']:12s} {r['policy']:5s}: "
              f"mean {r['makespan_mean']:5.2f}h  p95 {r['makespan_p95']:5.2f}h")
