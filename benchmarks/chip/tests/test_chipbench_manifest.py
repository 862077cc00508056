"""BENCHMARK.json against the benchmark's contract and the files it names."""

import importlib
import json
import re
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parents[1]
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(BENCH))

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def manifest():
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def test_top_level_keys(manifest):
    assert set(manifest) == {"command", "paths", "run_seconds", "configs", "workloads",
                             "end_to_end", "per_layer"}
    assert 1 <= manifest["run_seconds"] <= 51
    assert manifest["paths"] == ["benchmarks/chip"]
    assert all("\n" not in w and len(w) <= 200 for w in manifest["command"])


def test_names_and_units(manifest):
    names = [c["name"] for c in manifest["configs"]] + [w["name"] for w in manifest["workloads"]]
    names += [m["name"] for m in manifest["end_to_end"] + manifest["per_layer"]]
    for n in names:
        assert NAME.match(n), n
    for w in manifest["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert len(w["why"]) <= 200 and "\n" not in w["why"] and "\t" not in w["why"]
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    assert len(set(names)) == len(names)


def test_end_to_end_bounds(manifest):
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    assert "setup_s" in e2e
    for m in e2e.values():
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")


def test_every_workload_has_its_files(manifest):
    configs = {c["name"]: c for c in manifest["configs"]}
    for w in manifest["workloads"]:
        assert w["config"] in configs
        assert w["chips"] in (1, 4)
        cell = json.loads((BENCH / "workloads" / f"{w['name']}.json").read_text())
        assert cell["config"] == w["config"] and cell["chips"] == w["chips"]
        assert cell["traffic_name"] == w["traffic"]
        assert (BENCH / "traffic" / f"{cell['traffic']['kind']}.py").is_file()
        importlib.import_module(f"reference.{cell['reference']}").check_spec(cell["spec"])
        import run
        run.load_cell(w["name"])  # its chips and its spec's mesh agree
        from compare import NUMBERS
        assert cell["limits"] and set(cell["limits"]) <= set(NUMBERS)
        cfg = json.loads((ROOT / configs[w["config"]]["file"]).read_text())
        assert cfg["reduced"] == configs[w["config"]]["reduced"]
        importlib.import_module(f"reference.{cfg['reference']}")
        importlib.import_module(f"flops.{cfg['flops']}")
    used = {w["config"] for w in manifest["workloads"]}
    assert used == set(configs)


def test_per_layer_metrics_have_readers_and_moves(manifest):
    e2e = {m["name"] for m in manifest["end_to_end"]}
    cells = {w["name"] for w in manifest["workloads"]}
    for m in manifest["per_layer"]:
        assert m["moves"] in e2e and m["moves"] != "setup_s"
        # Each cell the metric names reports the metric it moves.
        for c in m.get("workloads", cells):
            assert c in cells
        assert callable(importlib.import_module(f"metrics.{m['name']}").read)
        assert 1 <= len(m["layer"]) <= 200 and "\n" not in m["layer"]
