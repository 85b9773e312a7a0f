"""BENCHMARK.json against the contract's shape, and against the files it
names: everything a cell needs is found by name. Every check runs on
the committed benchmark and on the guard's copy with one configuration,
one serving mix, one cell and one per-layer metric appended at the ends
of their lists (tests/chipbench/_tiny.py, conftest.py)."""

from __future__ import annotations

import json
import re
from pathlib import Path

import pytest

import _tiny

REPO = Path(__file__).resolve().parents[2]
MANIFESTS = {"committed": json.loads((REPO / "BENCHMARK.json").read_text())}
MANIFESTS["with_additions"] = _tiny.with_additions(MANIFESTS["committed"])
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def each(*groups):
    """(checkout, entry) for every entry of the named lists, in both
    manifests: the ids a parametrised check runs under."""
    return [pytest.param(which, e, id=f"{which}-{e['name']}")
            for which, m in MANIFESTS.items()
            for g in groups for e in m[g]]


@pytest.fixture
def at(checkout):
    """(root, manifest as that checkout's file has it)."""
    return checkout, json.loads((checkout / "BENCHMARK.json").read_text())


def test_the_copy_holds_what_the_parametrised_checks_assume(root_of):
    for which, manifest in MANIFESTS.items():
        on_disk = json.loads(
            (root_of(which) / "BENCHMARK.json").read_text())
        assert on_disk == manifest
    added = MANIFESTS["with_additions"]
    names = lambda m, group: [e["name"] for e in m[group]]
    for group in ("configs", "workloads", "per_layer"):
        assert names(added, group)[:-1] == names(MANIFESTS["committed"],
                                                 group)
        assert names(added, group)[-1] in _tiny.GUARD.values()


def test_top_level_keys(at):
    REPO, MANIFEST = at
    assert set(MANIFEST) == {
        "command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer",
    }
    assert MANIFEST["paths"] == ["chipbench", "tests/chipbench"]
    assert 1 <= MANIFEST["run_seconds"] <= 51
    assert (REPO / MANIFEST["command"][1]).is_file()
    assert len((REPO / "BENCHMARK.json").read_bytes()) <= 64 * 1024


@pytest.mark.parametrize(
    "which,entry", each("configs", "workloads", "end_to_end", "per_layer"))
def test_names_units_and_lines(which, entry):
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


def test_entries_have_just_the_contract_keys(at):
    _, MANIFEST = at
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


def test_names_are_unique_and_cross_references_hold(at):
    _, MANIFEST = at
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


@pytest.mark.parametrize("which,cell", each("workloads"))
def test_every_cell_reports_enough_and_finds_its_files(which, cell, root_of):
    REPO, MANIFEST = root_of(which), MANIFESTS[which]
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


@pytest.mark.parametrize("which,cell", each("workloads"))
def test_a_serving_cells_program_holds_its_mix(which, cell, root_of):
    """A cell that names a mix its program cannot hold fails here and
    not after set-up on the chip: no class's longest prompt is past
    ``max_prompt``, and none's longest prompt with its longest answer
    (and a drafting tick's rows past a request's end, where the program
    drafts) is past ``max_context``, itself whole pages."""
    root, manifest = root_of(which), MANIFESTS[which]
    traffic = json.loads((root / "chipbench" / "traffic"
                          / f"{cell['traffic']}.json").read_text())
    if "round" not in traffic:
        return  # not a serving mix: nothing to hold
    config = next(c for c in manifest["configs"]
                  if c["name"] == cell["config"])
    program = json.loads((root / config["file"]).read_text())["program"]
    spare = 2 * program["n_inner"] if program.get("draft") else 0
    for c in traffic["classes"]:
        assert c["prompt"][1] <= program["max_prompt"], c["name"]
        if "max_context" in program:
            assert (c["prompt"][1] + c["output"][1] + spare
                    <= program["max_context"]), c["name"]
    if "max_context" in program:
        assert program["max_context"] % program["page_tokens"] == 0
    assert program["prompt_chunk"] <= program["max_prompt"]


@pytest.mark.parametrize("which,metric", each("per_layer"))
def test_every_per_layer_metric_has_a_reader_of_its_own(which, metric,
                                                        root_of):
    path = root_of(which) / "chipbench" / "metrics" / f"{metric['name']}.py"
    assert path.is_file()
    assert "def read(" in path.read_text() or " as read" in path.read_text()


def test_files_under_paths_are_named_from_name_characters(at):
    REPO, MANIFEST = at
    for base in MANIFEST["paths"]:
        for path in (REPO / base).rglob("*"):
            if "__pycache__" in path.parts:
                continue
            rel = path.relative_to(REPO).as_posix()
            assert re.match(r"^[A-Za-z0-9_.\-/]+$", rel), rel
