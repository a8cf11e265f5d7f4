"""BENCHMARK.json resolves to its files by name, keeps to the contract's
shape, and a run without a TPU, or outside a checkout, prints no result."""
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

from bench import common

BENCH = common.benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_every_cell_resolves(cell):
    spec = common.resolve(cell)
    assert spec["generator"].is_file() and spec["reference"].is_file()
    common.load_module(spec["generator"]).Generator
    assert spec["limits"], "a cell compares at least one number"
    names = {m["name"] for m in spec["end_to_end"]}
    assert "setup_s" in names and len(names) >= 2
    assert spec["per_layer"], "a cell reports at least one per-layer metric"


@pytest.mark.parametrize("metric", [m["name"] for m in BENCH["end_to_end"]
                                    + BENCH["per_layer"]])
def test_every_metric_has_a_reader(metric):
    assert callable(common.metric_reader(metric).read)


def test_contract_shape():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    layers = {}
    for c in BENCH["configs"]:
        assert NAME.match(c["name"]) and 1 <= len(c["why"]) <= 200
        assert c["file"].startswith("bench/")
        cfg = common.load_json(common.ROOT / c["file"])
        assert cfg["reduced"] == c["reduced"]
    for w in BENCH["workloads"]:
        assert NAME.match(w["name"]) and 1 <= len(w["why"]) <= 200
        assert w["chips"] in (1, 4)
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e and "\n" not in m["layer"]
        layers.setdefault(m["layer"].lower(), m["layer"])
        assert layers[m["layer"].lower()] == m["layer"]
    assert len(json.dumps(BENCH)) < 64 * 1024


def _run(cwd, env_extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **env_extra)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         BENCH["workloads"][0]["name"], "--seed", "5000000000", "--seconds",
         "1", "--trace", "0"], cwd=cwd, env=env, capture_output=True,
        text=True, timeout=300)


def test_no_tpu_exits_nonzero_without_a_result():
    r = _run(common.ROOT, {})
    assert r.returncode != 0 and r.stdout.strip() == ""
    assert "no TPU" in r.stderr


def test_outside_a_checkout_exits_nonzero(tmp_path):
    shutil.copy(common.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(common.BENCH, tmp_path / "bench")
    r = _run(tmp_path, {"PYTHONPATH": ""})
    assert r.returncode != 0 and r.stdout.strip() == ""
