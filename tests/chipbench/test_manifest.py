"""BENCHMARK.json against the contract's shape, and against the files it
names: everything a cell needs is found by name."""

from __future__ import annotations

import json
import re
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
MANIFEST = json.loads((REPO / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def test_top_level_keys():
    assert set(MANIFEST) == {
        "command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer",
    }
    assert MANIFEST["paths"] == ["chipbench", "tests/chipbench"]
    assert 1 <= MANIFEST["run_seconds"] <= 51
    assert (REPO / MANIFEST["command"][1]).is_file()
    assert len((REPO / "BENCHMARK.json").read_bytes()) <= 64 * 1024


@pytest.mark.parametrize(
    "entry",
    MANIFEST["configs"] + MANIFEST["workloads"] + MANIFEST["end_to_end"]
    + MANIFEST["per_layer"],
    ids=lambda e: e["name"],
)
def test_names_units_and_lines(entry):
    assert NAME.match(entry["name"])
    for key in ("config", "traffic", "moves"):
        if key in entry:
            assert NAME.match(entry[key])
    if "unit" in entry:
        assert UNIT.match(entry["unit"])
        assert entry["better"] in ("lower", "higher")
        assert entry["source"] in SOURCES
    for key in ("why", "layer", "source"):
        if key in entry:
            text = entry[key]
            assert 1 <= len(text) <= 200
            assert "\n" not in text and "\t" not in text
    for key in entry.get("reduced", []):
        assert NAME.match(key)


def test_entries_have_just_the_contract_keys():
    for c in MANIFEST["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
    for w in MANIFEST["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4)
    for m in MANIFEST["end_to_end"]:
        assert set(m) - {"workloads"} == {
            "name", "unit", "better", "bound", "source"}
        assert 0 < m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    for m in MANIFEST["per_layer"]:
        assert set(m) - {"workloads"} == {
            "name", "unit", "better", "source", "layer", "moves"}


def test_names_are_unique_and_cross_references_hold():
    cells = [w["name"] for w in MANIFEST["workloads"]]
    configs = [c["name"] for c in MANIFEST["configs"]]
    metrics = [m["name"] for m in
               MANIFEST["end_to_end"] + MANIFEST["per_layer"]]
    for names in (cells, configs, metrics):
        assert len(names) == len(set(names))
    pairs = [(w["config"], w["traffic"]) for w in MANIFEST["workloads"]]
    assert len(pairs) == len(set(pairs))
    assert {w["config"] for w in MANIFEST["workloads"]} == set(configs)
    end = {m["name"]: m for m in MANIFEST["end_to_end"]}
    assert "setup_s" in end and "workloads" not in end["setup_s"]
    for m in MANIFEST["end_to_end"] + MANIFEST["per_layer"]:
        for cell in m.get("workloads", []):
            assert cell in cells
    for m in MANIFEST["per_layer"]:
        moved = end[m["moves"]]
        for cell in m.get("workloads", cells):
            assert cell in moved.get("workloads", cells)
    four = sum(1 for w in MANIFEST["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(cells) // 4)


@pytest.mark.parametrize("cell", MANIFEST["workloads"],
                         ids=lambda w: w["name"])
def test_every_cell_reports_enough_and_finds_its_files(cell):
    name = cell["name"]
    has = lambda m: "workloads" not in m or name in m["workloads"]
    assert sum(1 for m in MANIFEST["end_to_end"] if has(m)) >= 2
    assert sum(1 for m in MANIFEST["per_layer"] if has(m)) >= 1
    config = next(c for c in MANIFEST["configs"]
                  if c["name"] == cell["config"])
    body = json.loads((REPO / config["file"]).read_text())
    assert config["file"].startswith("chipbench/")
    assert (REPO / "chipbench" / "runners" / f"{body['kind']}.py").is_file()
    assert (REPO / "chipbench" / "references"
            / f"{body['reference']}.py").is_file()
    assert (REPO / "chipbench" / "traffic"
            / f"{cell['traffic']}.json").is_file()
    assert "limits" in body


@pytest.mark.parametrize("metric", MANIFEST["per_layer"],
                         ids=lambda m: m["name"])
def test_every_per_layer_metric_has_a_reader_of_its_own(metric):
    path = REPO / "chipbench" / "metrics" / f"{metric['name']}.py"
    assert path.is_file()
    assert "def read(" in path.read_text() or " as read" in path.read_text()


def test_files_under_paths_are_named_from_name_characters():
    for base in MANIFEST["paths"]:
        for path in (REPO / base).rglob("*"):
            if "__pycache__" in path.parts:
                continue
            rel = path.relative_to(REPO).as_posix()
            assert re.match(r"^[A-Za-z0-9_.\-/]+$", rel), rel
