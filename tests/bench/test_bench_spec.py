"""BENCHMARK.json and the files it names: everything a cell needs is
found by name, and each per-layer metric's cells report the end-to-end
metric it moves."""
import fnmatch
import json
import re
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
CELLS = [c["name"] for c in SPEC["workloads"]]


def _reports(metric, cell):
    return cell in metric.get("workloads", CELLS)


@pytest.mark.parametrize("cell", SPEC["workloads"], ids=CELLS)
def test_cell_files_exist(cell):
    from bench import harness
    spec, c, cfg, mix, limits = harness.load_cell(cell["name"])
    assert cfg["name"] == cell["config"]
    assert mix["kind"] in ("train", "serve_open_loop")
    assert limits and all(v > 0 for v in limits.values())
    e2e = [m for m in SPEC["end_to_end"] if _reports(m, cell["name"])]
    assert "setup_s" in [m["name"] for m in e2e] and len(e2e) >= 2
    assert any(_reports(m, cell["name"]) for m in SPEC["per_layer"])


@pytest.mark.parametrize("metric", SPEC["end_to_end"] + SPEC["per_layer"],
                         ids=lambda m: m["name"])
def test_metric_has_reader_and_valid_name(metric):
    assert NAME.match(metric["name"])
    assert (ROOT / "bench" / "metrics" / f"{metric['name']}.py").is_file()
    assert set(metric.get("workloads", CELLS)) <= set(CELLS)
    if "moves" in metric:
        moved = {m["name"]: m for m in SPEC["end_to_end"]}[metric["moves"]]
        for cell in metric["workloads"]:
            assert _reports(moved, cell), (metric["name"], cell)
    else:
        assert 0.01 <= metric["bound"] <= 0.25


def test_configs_named_once_and_used():
    files = [c["file"] for c in SPEC["configs"]]
    assert len(set(files)) == len(files)
    used = {c["config"] for c in SPEC["workloads"]}
    assert used == {c["name"] for c in SPEC["configs"]}
    for c in SPEC["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert cfg["source"] == c["source"] and cfg["reduced"] == c["reduced"]


def test_no_benchmark_file_matches_an_ignore_rule():
    # the root .gitignore ignores bench*.json (older analysis artifacts)
    for d in SPEC["paths"]:
        for p in (ROOT / d).rglob("*"):
            assert not fnmatch.fnmatch(p.name, "bench*.json"), p
