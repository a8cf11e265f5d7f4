import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

# --- everything below may touch jax ----------------------------------------
import argparse      # noqa: E402
import json          # noqa: E402
import math          # noqa: E402
import time          # noqa: E402
import traceback     # noqa: E402

import jax           # noqa: E402

from .. import analytics, configs, sharding   # noqa: E402
from ..configs import SHAPES                  # noqa: E402
from ..configs.base import TrainConfig        # noqa: E402
from . import hlo_stats, steps                # noqa: E402
from .mesh import make_production_mesh        # noqa: E402

"""Multi-pod dry-run: prove every (architecture x input shape x mesh) cell
lowers, GSPMD-partitions, and compiles on the production meshes - 16x16
("data","model") single pod and 2x16x16 ("pod","data","model") multi-pod -
and extract the memory / FLOP / collective numbers of the analytic roofline
estimate (``repro.analytics``).

Run:  PYTHONPATH=src python -m repro.launch.dryrun [--arch A] [--shape S]
          [--multi-pod] [--rules fsdp] [--out results.json]
Defaults to the full 40-cell grid on both meshes with the baseline rules.
"""


def _mem_summary(compiled):
    out = {}
    try:
        ma = compiled.memory_analysis()
    except Exception as e:      # backend without memory analysis
        return {"error": repr(e)}
    for attr in ("argument_size_in_bytes", "output_size_in_bytes",
                 "temp_size_in_bytes", "alias_size_in_bytes",
                 "generated_code_size_in_bytes"):
        v = getattr(ma, attr, None)
        if v is not None:
            out[attr] = int(v)
    if not out and isinstance(ma, dict):
        out = {k: int(v) for k, v in ma.items()}
    return out


def _cost_summary(compiled):
    try:
        ca = compiled.cost_analysis()
    except Exception as e:
        return {"error": repr(e)}
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    keep = {}
    for k, v in (ca or {}).items():
        if k in ("flops", "bytes accessed", "transcendentals") or \
                k.startswith("bytes accessed"):
            keep[k] = float(v)
    return keep


# per-arch microbatch accumulation for train_4k: picked so activation peak
# fits 16 GB/chip HBM under the fsdp rule set
DEFAULT_ACCUM = {
    "deepseek-coder-33b": 4, "yi-34b": 4, "recurrentgemma-2b": 4,
    "xlstm-1.3b": 8, "moonshot-v1-16b-a3b": 2, "qwen2-vl-2b": 2,
    "llama3.2-1b": 2, "musicgen-medium": 2, "phi3.5-moe-42b-a6.6b": 2,
}


def run_cell(arch: str, shape_name: str, *, multi_pod: bool,
             rules: str = "baseline", accum: int = 0,
             serve_bf16: bool = False, verbose: bool = True) -> dict:
    import dataclasses
    cfg = configs.get(arch)
    shape = SHAPES[shape_name]
    if rules == "production":
        # per-workload layouts (§Perf): FSDP+seq-parallel for training,
        # TP-only weights + bf16 for serving (no per-token weight gathers)
        rules = "fsdp" if shape.kind == "train" else "baseline"
        serve_bf16 = True
    if serve_bf16 and shape.kind != "train":
        cfg = dataclasses.replace(cfg, param_dtype="bfloat16")
    chips = 512 if multi_pod else 256
    if accum <= 0:
        accum = DEFAULT_ACCUM.get(arch, 1) if rules in ("fsdp",) else 1
    rec = {"arch": arch, "shape": shape_name, "rules": rules,
           "mesh": "2x16x16" if multi_pod else "16x16", "chips": chips,
           "grad_accum": accum, "serve_bf16": serve_bf16}

    if shape_name == "long_500k" and not cfg.is_subquadratic:
        rec["status"] = "skip"
        rec["reason"] = ("pure full-attention arch: 524k dense-KV decode is "
                         "inherently quadratic; see DESIGN.md "
                         "§Arch-applicability")
        return rec

    t0 = time.time()
    try:
        mesh = make_production_mesh(multi_pod=multi_pod)
        with jax.set_mesh(mesh), sharding.use(mesh, rules):
            in_sh, out_sh, args, _ = steps.shardings_for_cell(
                cfg, shape, mesh, rules)
            if shape.kind == "train":
                _, p_axes = steps.abstract_init(cfg)
                fn = steps.make_train_step(cfg, TrainConfig(grad_accum=accum),
                                           param_axes=p_axes)
                donate = (0, 1)        # params, opt_state update in place
            elif shape.kind == "prefill":
                fn = steps.make_prefill_step(cfg)
                donate = (1,)          # cache
            else:
                fn = steps.make_decode_step(cfg)
                donate = (1,)          # cache
            jitted = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh,
                             donate_argnums=donate)
            lowered = jitted.lower(*args)
            t_lower = time.time() - t0
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower

            rec["status"] = "ok"
            rec["lower_s"] = round(t_lower, 1)
            rec["compile_s"] = round(t_compile, 1)
            rec["memory"] = _mem_summary(compiled)
            rec["hlo_cost"] = _cost_summary(compiled)
            coll = hlo_stats.collective_bytes(compiled.as_text())
            rec["collectives"] = {"bytes_by_kind": coll.bytes_by_kind,
                                  "count_by_kind": coll.count_by_kind,
                                  "total_bytes": coll.total_bytes}
    except Exception as e:
        rec["status"] = "fail"
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-2000:]
        return rec

    # analytic roofline terms (HLO while-bodies are counted once by XLA's
    # cost analysis; the analytic model is the reconciled source - §Roofline)
    cost = analytics.cell_cost(cfg, shape, chips=chips,
                               pods=2 if multi_pod else 1, rules=rules)
    rec["analytic"] = {
        "flops": cost.flops, "hbm_bytes": cost.hbm_bytes,
        "ici_bytes_per_chip": cost.ici_bytes,
        "dcn_bytes_per_chip": cost.dcn_bytes,
        "model_flops": cost.model_flops,
        "params_bytes": cost.params_bytes, "notes": cost.notes,
    }
    rec["roofline"] = analytics.roofline(cost, chips=chips)
    # secondary collective term from the HLO parse: the compiled module is
    # post-SPMD-partitioning, so operand shapes (and hence bytes) are already
    # per-chip local
    rec["roofline"]["t_collective_hlo"] = \
        rec["collectives"]["total_bytes"] / analytics.ICI_BW

    if verbose:
        r = rec["roofline"]
        mem = rec["memory"]
        arg_gb = mem.get("argument_size_in_bytes", 0) / 1e9
        tmp_gb = mem.get("temp_size_in_bytes", 0) / 1e9
        print(f"  ok lower={rec['lower_s']}s compile={rec['compile_s']}s "
              f"args={arg_gb:.2f}GB temp={tmp_gb:.2f}GB "
              f"| t_comp={r['t_compute']*1e3:.1f}ms t_mem={r['t_memory']*1e3:.1f}ms "
              f"t_coll={r['t_collective']*1e3:.1f}ms -> {r['dominant']}"
              f" (roofline {r['roofline_fraction']*100:.0f}%)", flush=True)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, help="one arch id (default: all)")
    ap.add_argument("--shape", default=None, help="one shape (default: all)")
    ap.add_argument("--multi-pod", action="store_true",
                    help="only the 2x16x16 mesh")
    ap.add_argument("--single-pod", action="store_true",
                    help="only the 16x16 mesh")
    ap.add_argument("--rules", default="baseline",
                    choices=sorted(sharding.RULE_SETS) + ["production"])
    ap.add_argument("--accum", type=int, default=0,
                    help="grad accumulation (0 = per-arch default)")
    ap.add_argument("--serve-bf16", action="store_true",
                    help="bf16 weights for prefill/decode cells")
    ap.add_argument("--out", default=None, help="write JSON records here")
    args = ap.parse_args()

    archs = [args.arch] if args.arch else list(configs.ARCHS)
    shapes = [args.shape] if args.shape else list(SHAPES)
    meshes = []
    if not args.multi_pod:
        meshes.append(False)
    if not args.single_pod:
        meshes.append(True)

    records = []
    n_fail = 0
    for multi in meshes:
        for arch in archs:
            for shp in shapes:
                label = f"[{'2x16x16' if multi else '16x16'}] {arch} x {shp}"
                print(label, flush=True)
                rec = run_cell(arch, shp, multi_pod=multi, rules=args.rules,
                               accum=args.accum, serve_bf16=args.serve_bf16)
                records.append(rec)
                if rec["status"] == "fail":
                    n_fail += 1
                    print("  FAIL:", rec["error"], flush=True)
                elif rec["status"] == "skip":
                    print("  skip:", rec["reason"].split(";")[0], flush=True)
    if args.out:
        with open(args.out, "w") as f:
            json.dump(records, f, indent=1)
        print(f"wrote {len(records)} records to {args.out}")
    ok = sum(r["status"] == "ok" for r in records)
    skip = sum(r["status"] == "skip" for r in records)
    print(f"dry-run: {ok} ok, {skip} skip, {n_fail} fail "
          f"/ {len(records)} cells")
    if n_fail:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
