"""BENCHMARK.json against the benchmark contract's rules of form, and every
entry's files present where the harness looks for them."""
from __future__ import annotations

import json
import re
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())


def test_top_level_keys_and_command():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["paths"] == ["bench"]
    assert SPEC["command"] == ["python3", "bench/run.py"]
    assert 1 <= SPEC["run_seconds"] <= 51
    assert (ROOT / "BENCHMARK.json").stat().st_size <= 64 * 1024


def test_configs_and_cells_have_their_files():
    configs = {c["name"]: c for c in SPEC["configs"]}
    for c in SPEC["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["file"].startswith("bench/")
        body = json.loads((ROOT / c["file"]).read_text())
        assert set(c["reduced"]) == set(body["reduced"])
        assert all(NAME.match(k) and k in body for k in c["reduced"])
        assert (ROOT / body["reference"]).is_file()
    used = set()
    pairs = set()
    for w in SPEC["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and w["chips"] in (1, 4)
        assert w["config"] in configs and 1 <= len(w["why"]) <= 200
        traffic = json.loads(
            (ROOT / "bench/traffic" / f"{w['traffic']}.json").read_text())
        assert (ROOT / "bench/modes" / f"{traffic['mode']}.py").is_file()
        assert json.loads((ROOT / "bench/workloads" / f"{w['name']}.json")
                          .read_text())["limits"]
        used.add(w["config"])
        pairs.add((w["config"], w["traffic"]))
    assert used == set(configs)
    assert len(pairs) == len(SPEC["workloads"])


def test_metrics_form_and_readers():
    cells = {w["name"] for w in SPEC["workloads"]}
    names = [m["name"] for m in SPEC["end_to_end"] + SPEC["per_layer"]]
    assert len(names) == len(set(names))
    reported = {c: set() for c in cells}
    for m in SPEC["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
        for c in m.get("workloads", cells):
            reported[c].add(m["name"])
    assert all("setup_s" in r and len(r) >= 2 for r in reported.values())
    has_layer = set()
    for m in SPEC["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert (ROOT / "bench/metrics" / f"{m['name']}.py").is_file()
        for c in m["workloads"]:
            assert m["moves"] in reported[c], (m["name"], c)
            has_layer.add(c)
    assert has_layer == cells
