"""Each mode driver at a quick size on the CPU: the result line carries the
keys the contract names, and exactly the metrics BENCHMARK.json declares
for the cell."""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from bench import run as bench_run
from bench.tests import quick

ROOT = Path(__file__).resolve().parents[2]
CELLS = sorted(quick.OVERRIDES)
KEYS = ["correct", "attempted", "failed", "metrics", "device", "checks"]


@pytest.mark.parametrize("name", CELLS)
def test_untraced_run_reports_end_to_end_metrics(name):
    result = quick.run(name)
    spec = bench_run.load_cell(name)
    assert [k for k in result if k in KEYS] == KEYS      # checks come last
    assert list(result)[-1] == "checks"
    assert result["correct"] is True, result["checks"]
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert set(result["metrics"]) == {m["name"] for m in spec["end_to_end"]}
    for m in spec["end_to_end"]:
        got = result["metrics"][m["name"]]
        assert got["unit"] == m["unit"] and got["value"] > 0
    assert set(result["device"]) >= {"platform", "kind", "count",
                                      "memory_peak_bytes"}
    assert set(result["checks"]) == set(spec["limits"])
    json.dumps(result)


@pytest.mark.parametrize("name", ["e3sm-compress", "e3sm-decompress"])
def test_traced_run_reports_per_layer_metrics(name):
    result = quick.run(name, trace=True)
    spec = bench_run.load_cell(name)
    declared = {m["name"]: m for m in spec["per_layer"]}
    # a CPU trace has no device plane: the device readers find nothing
    device_only = {n for n, m in declared.items() if m["layer"] in
                   ("device", "whole step")}
    assert set(result["metrics"]) == set(declared) - device_only
    assert {"busy_s", "window_s"} <= set(result["device"])
    assert result["correct"] is True, result["checks"]


def test_refuses_a_backend_without_tpu(capsys, monkeypatch):
    # main() points JAX's compile cache into the checkout; undo it afterwards
    for var in ("JAX_COMPILATION_CACHE_DIR",
                "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"):
        monkeypatch.delenv(var, raising=False)
    rc = bench_run.main(["--workload", "e3sm-compress", "--seed", "1",
                         "--seconds", "1"])
    out, err = capsys.readouterr()
    assert rc != 0 and not out.strip() and "TPU" in err


def test_fails_without_the_program(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "e3sm-compress",
         "--seed", "1", "--seconds", "1"], cwd=tmp_path,
        env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True,
        text=True, timeout=120)
    assert proc.returncode != 0 and not proc.stdout.strip()
