"""Cell resolution and the pieces every run shares.

A cell of ``BENCHMARK.json`` names a configuration and a traffic mix; each
is found by name under ``bench/``:

  configs/<config>.json            the sizes as run (and ``reduced``/``assumed``)
  configs/<config>_reference.py    the plain reference of that configuration
  traffic/<mix>.json               the mix's parameters; ``generator`` names the
                                   general generator in ``generators/<generator>.py``
  metrics/<metric>.py              one reader per metric, ``read(ctx)``
  limits/<workload>.json           the limit of each number ``correct`` compares

Adding a cell, a mix of an existing generator or a metric therefore adds files
and entries only.
"""
from __future__ import annotations

import hashlib
import importlib.util
import json
import pathlib

ROOT = pathlib.Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"


def load_json(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: pathlib.Path, name: str | None = None):
    """Import a file by path (file names may hold ``-``)."""
    if not path.is_file():
        raise FileNotFoundError(path)
    spec = importlib.util.spec_from_file_location(
        name or "bench_" + path.stem.replace("-", "_").replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def benchmark() -> dict:
    return load_json(ROOT / "BENCHMARK.json")


def metric_applies(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def resolve(workload: str, bench: dict | None = None) -> dict:
    """Everything a run of ``workload`` needs, found by name."""
    bench = bench or benchmark()
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    cell = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    config = configs[cell["config"]]
    mix = load_json(BENCH / "traffic" / f"{cell['traffic']}.json")
    return dict(
        cell=cell,
        config_entry=config,
        config=load_json(ROOT / config["file"]),
        reference=BENCH / "configs" / f"{cell['config']}_reference.py",
        mix=mix,
        generator=BENCH / "generators" / f"{mix['generator']}.py",
        limits=load_json(BENCH / "limits" / f"{workload}.json"),
        end_to_end=[m for m in bench["end_to_end"]
                    if metric_applies(m, workload)],
        per_layer=[m for m in bench["per_layer"]
                   if metric_applies(m, workload)],
    )


def metric_reader(name: str):
    return load_module(BENCH / "metrics" / f"{name}.py")


def subseed(seed: int, tag: str) -> int:
    """A 31-bit seed for one purpose, drawn from the run's ``--seed`` (any
    size: seeds may exceed 32 bits, and JAX keys keep 32)."""
    h = hashlib.sha256(f"{int(seed)}/{tag}".encode()).digest()
    return int.from_bytes(h[:4], "little") & 0x7FFFFFFF

