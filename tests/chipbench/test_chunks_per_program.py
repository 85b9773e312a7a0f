"""The reader of ``chunks_per_prefill_program``: on the trace recorded
from a scheduler that ran every chunk as a program of its own (no
``chunk_programs`` on its ticks) it reads 1.0; on hand-made ticks it
reads chunks over programs; where the program wrote no ticks, nothing."""

from __future__ import annotations

import types

import pytest

from chipbench.metrics import _program_spans as ps
from test_program_spans import (  # noqa: F401
    REPO, WINDOW, reader, span, summary)
from test_recorded_serving_trace import run as recorded  # noqa: F401

NAME = "chunks_per_prefill_program"


def _run(summary, ticks):
    spans = [
        s for i, args in enumerate(ticks) for s in (
            span("serving.tick", 10 + 100 * i, 90 + 100 * i, tick=i, **args),
            span("serving.decode_dispatch", 20 + 100 * i, 25 + 100 * i),
        )
    ]
    return types.SimpleNamespace(
        summary=summary, trace_dir=None,
        info={"slots": 4, ps.CACHE_KEY: ps.build(summary, WINDOW, spans)})


def test_a_recorded_trace_without_the_counter_reads_one(recorded):
    ticks = ps.load(recorded).named("serving.tick")
    assert ticks and all("chunk_programs" not in t.args for t in ticks)
    assert reader(NAME).read(recorded) == 1.0


# (chunks, programs) tick by tick -> chunks a program over the window
CASES = {
    "alone": ([(1, 1), (0, 0), (2, 2)], 1.0),
    "grouped": ([(4, 1), (3, 1), (0, 0), (7, 3), (1, 1)], 15 / 6),
    "mixed_window": ([(5, 2), (2, 1)], 7 / 3),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_chunks_over_programs(summary, case):  # noqa: F811
    ticks, want = CASES[case]
    run = _run(summary, [
        {"admitting": max(0, c - 1), "chunks": c, "chunk_programs": p}
        for c, p in ticks])
    assert reader(NAME).read(run) == pytest.approx(want)


def test_nothing_to_read(summary):  # noqa: F811
    # no device trace (a CPU run); ticks that ran no chunk at all
    cpu = types.SimpleNamespace(summary=None, trace_dir=None, info={})
    assert reader(NAME).read(cpu) is None
    idle = _run(summary, [{"admitting": 0, "chunks": 0, "chunk_programs": 0}])
    assert reader(NAME).read(idle) is None


# The entry is looked up by name: later metrics and later serving cells
# are appended behind it, as the guard's copy does (_tiny.py).
@pytest.mark.parametrize("holds", ["the_entry", "every_serving_cell"])
def test_the_manifest_lists_the_metric_by_name(checkout, holds):
    import json

    import _tiny

    manifest = json.loads((checkout / "BENCHMARK.json").read_text())
    (entry,) = [m for m in manifest["per_layer"] if m["name"] == NAME]
    if holds == "the_entry":
        assert entry["moves"] == "serve_tok_s"
        assert entry["layer"] == "server" and entry["unit"] == "chunks"
        # behind the metrics that were accepted before it (PR 32's)
        names = [m["name"] for m in manifest["per_layer"]]
        assert _tiny.stands_after(names, NAME, "experts_local_pct")
    else:
        assert entry["workloads"] == _tiny.serving_cells(checkout, manifest)
