"""Run one cell of the chip benchmark once.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout.  The run builds its inputs and weights from
``--seed``, warms up the cell's own shapes (set-up), measures for
``--seconds``, then checks what the timed path produced against the plain
reference of the cell's configuration.  The last line of standard output is
one JSON object: ``correct``, ``attempted``, ``failed``, ``metrics`` (the
cell's end-to-end metrics with ``--trace 0``, its per-layer metrics with
``--trace 1``), ``device``, with ``--trace 1`` a ``breakdown``, and last the
numbers compared with their limits (also the last lines of standard error).
With no TPU, or fewer chips than the cell asks for, it exits non-zero and
prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()   # set-up is timed from here

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import traceback  # noqa: E402
import types  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import common  # noqa: E402


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def peaks_for(kind: str) -> dict:
    table = common.load_json(common.BENCH / "peaks.json")["devices"]
    if kind not in table:
        raise KeyError(f"device kind {kind!r} has no entry in "
                       f"bench/peaks.json (have {sorted(table)})")
    return table[kind]


def enable_compile_cache() -> str:
    """JAX's persistent cache at the program's fixed directory (or
    ``$JAX_COMPILATION_CACHE_DIR``), holding every program however fast it
    compiled, so that only a checkout's first run compiles."""
    import jax
    from repro import compile_cache

    path = compile_cache.enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def main(argv=None, *, spec=None, require_tpu: bool = True) -> int:
    """``spec`` and ``require_tpu=False`` are for the harness's own tests,
    which drive a run on the CPU at test sizes."""
    args = parse(argv)
    try:
        spec = spec or common.resolve(args.workload)
        sys.path.insert(0, str(common.ROOT / "src"))
        import repro  # noqa: F401  (the system under test)
    except (KeyError, FileNotFoundError, ImportError) as e:
        log(f"bench: cannot resolve workload {args.workload!r}: {e!r}")
        return 2
    import jax

    devices = jax.devices()
    chips = int(spec["cell"]["chips"])
    if require_tpu and devices[0].platform != "tpu":
        log(f"bench: no TPU (JAX found {devices[0].platform}); the benchmark "
            f"has no CPU path")
        return 1
    if len(devices) < chips:
        log(f"bench: the cell asks for {chips} chip(s), JAX sees "
            f"{len(devices)}")
        return 1
    used = devices[:chips]
    kind = used[0].device_kind
    peaks = peaks_for(kind) if require_tpu else {}
    cache = enable_compile_cache()
    log(f"bench: {args.workload} seed={args.seed} seconds={args.seconds} "
        f"trace={args.trace} on {chips} x {kind}; compile cache {cache}")

    from bench import trace as tr

    generator = common.load_module(spec["generator"]).Generator(
        spec, seed=args.seed,
        reference=common.load_module(spec["reference"]))
    generator.setup()
    setup_s = time.perf_counter() - T_START
    log(f"bench: set-up {setup_s:.3f} s")

    tdir = tempfile.mkdtemp(prefix="bench_trace_") if args.trace else None
    if tdir:
        jax.profiler.start_trace(tdir)
    w0 = time.perf_counter()
    with jax.profiler.TraceAnnotation("bench.window"):
        samples = generator.window(args.seconds)
    window_s = time.perf_counter() - w0
    reduced = None
    if tdir:
        jax.profiler.stop_trace()
        reduced = tr.reduce(tr.load_xplane(tdir), n_devices=chips)
        shutil.rmtree(tdir, ignore_errors=True)
    log(f"bench: window {window_s:.3f} s")

    stats = [d.memory_stats() or {} for d in used]
    memory_peak = max(int(s.get("peak_bytes_in_use", 0)) for s in stats)
    generator.free()
    checks = generator.check()

    ctx = types.SimpleNamespace(
        samples=samples, setup_s=setup_s, window_s=window_s, trace=reduced,
        peaks=peaks, config=spec["config"], mix=spec["mix"],
        work=generator.work(), workload=args.workload)
    wanted = spec["per_layer"] if args.trace else spec["end_to_end"]
    metrics = {}
    for m in wanted:
        value = common.metric_reader(m["name"]).read(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    compared = {}
    for name, limit in spec["limits"].items():
        value = checks.get(name, math.nan)
        # no reading (nan) is not correct, and prints as null
        compared[name] = {"value": value if math.isfinite(value) else None,
                          "limit": limit}
    correct = bool(compared) and all(
        c["value"] is not None and c["value"] <= c["limit"]
        for c in compared.values())
    result = {
        "correct": correct,
        "attempted": int(samples["attempted"]),
        "failed": int(samples["failed"]),
        "metrics": metrics,
        "device": {"platform": used[0].platform, "kind": kind,
                   "count": len(used), "memory_peak_bytes": memory_peak},
    }
    if reduced is not None:
        result["device"]["busy_s"] = reduced["busy_s"]
        result["device"]["window_s"] = reduced["window_s"]
        result["breakdown"] = reduced["breakdown"]
    result["checks"] = compared
    for name, c in compared.items():
        ok = "ok" if c["value"] is not None and c["value"] <= c["limit"] \
            else "FAIL"
        log(f"check {name} {c['value']!r} limit {c['limit']!r} {ok}")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except Exception:
        traceback.print_exc()
        sys.exit(1)
