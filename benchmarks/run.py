"""Benchmark harness - one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows.  The dry-run/roofline numbers
come from ``repro.launch.dryrun`` (512-device placeholder mesh); this harness
covers the paper's own tables/figures plus kernel and end-to-end
microbenches.  Its timings are host-clock rehearsals, not chip
measurements.

Usage::

    python -m benchmarks.run [--quick] [--only MODULE[,MODULE...]]

``--quick`` shrinks the workloads of modules that support it (the
simulation-engine benchmark among them) so a full-harness smoke run
finishes in seconds and still refreshes ``BENCH_simulation.json`` at the
repo root.
"""
from __future__ import annotations

import argparse
import inspect
import sys
import traceback

from repro import compile_cache

from . import (e2e_train, fig1_fit, fig5_wasted_work, fig6_scheduling,
               fig7_checkpointing, fig8_service, kernels_bench, market_bench,
               runtime_bench, service_bench, sim_engine_bench, tonks_lemma)

MODULES = [
    ("fig1_fit", fig1_fit),
    ("fig5_wasted_work", fig5_wasted_work),
    ("fig6_scheduling", fig6_scheduling),
    ("fig7_checkpointing", fig7_checkpointing),
    ("fig8_service", fig8_service),
    ("sim_engine_bench", sim_engine_bench),
    ("service", service_bench),
    ("market", market_bench),
    ("runtime", runtime_bench),
    ("tonks_lemma", tonks_lemma),
    ("kernels_bench", kernels_bench),
    ("e2e_train", e2e_train),
]


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--quick", action="store_true",
                    help="shrink workloads where supported (seconds, not "
                         "minutes); still writes BENCH_simulation.json")
    ap.add_argument("--only", default=None,
                    help="comma-separated module names to run")
    args = ap.parse_args(argv)
    compile_cache.enable()
    if args.only is None:
        selected = MODULES
    else:
        names = args.only.split(",")
        unknown = sorted(set(names) - {n for n, _ in MODULES})
        if unknown:
            ap.error(f"unknown module(s) {unknown}; "
                     f"choose from {[n for n, _ in MODULES]}")
        selected = [(n, m) for n, m in MODULES if n in names]

    print("name,us_per_call,derived")
    failed = []
    for name, mod in selected:
        try:
            if "quick" in inspect.signature(mod.run).parameters:
                mod.run(quick=args.quick)
            else:
                mod.run()
        except Exception as e:  # keep the harness going; report at the end
            failed.append(name)
            print(f"{name}/ERROR,0.0,{type(e).__name__}:{e}",
                  file=sys.stdout)
            traceback.print_exc(file=sys.stderr)
    if failed:
        raise SystemExit(f"benchmarks failed: {failed}")


if __name__ == '__main__':
    main()
