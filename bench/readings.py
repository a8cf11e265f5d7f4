"""Readings that the limits of ``bench/limits/<workload>.json`` are set from.

    python3 bench/readings.py --workload <name> --seeds 1,2,3 [--seconds 3]

For each seed, in one process: the cell's set-up, for a cell whose generator
needs one a short window, then the numbers ``correct`` compares for the
program (sound runs give the lower reading) and for the control, the
reference one precision below the configuration's in the program's place
(the upper reading), and for training a planted fault.  One JSON line per
seed on standard output.  Needs the chip, as a run does.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

from bench import common, run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    args = ap.parse_args(argv)
    import jax

    if jax.devices()[0].platform != "tpu":
        print("readings: no TPU", file=sys.stderr)
        return 1
    run.enable_compile_cache()
    spec = common.resolve(args.workload)
    mod = common.load_module(spec["generator"])
    ref = common.load_module(spec["reference"])
    for seed in (int(s) for s in args.seeds.split(",")):
        d = mod.Generator(spec, seed=seed, reference=ref)
        d.setup()
        if spec["mix"]["generator"] != "train":
            d.window(args.seconds)
            d.free()
        print(json.dumps(dict(seed=seed, **d.readings())), flush=True)
        d.free()
    return 0


if __name__ == "__main__":
    sys.exit(main())
