"""The readers of the program's own spans on a real trace file:
chipbench/testdata/small_serving_tpu.xplane.pb.gz was recorded on one
v5e chip by chipbench/testdata/record_serving_trace.py (a tiny paged
scheduler, six requests over four slots, nine ticks; the numbers below
are the ones that script printed)."""

from __future__ import annotations

import gzip
import shutil
import types
from pathlib import Path

import pytest

from chipbench import run as bench
from chipbench import trace_reduce as tr
from chipbench.metrics import _program_spans as ps

REPO = Path(__file__).resolve().parents[2]
PACKED = REPO / "chipbench/testdata/small_serving_tpu.xplane.pb.gz"


def reader(name):
    return bench.load_from(REPO, "metrics", name)


@pytest.fixture(scope="module")
def run(tmp_path_factory):
    """What the harness hands a reader: the reduced trace, and the
    directory that still holds the file."""
    trace_dir = tmp_path_factory.mktemp("serving_trace")
    with gzip.open(PACKED, "rb") as f, open(
            trace_dir / "small_serving_tpu.xplane.pb", "wb") as g:
        shutil.copyfileobj(f, g)
    summary = tr.reduce_events(tr.load_xplane(tr.find_xplane(str(trace_dir))))
    return types.SimpleNamespace(
        summary=summary, trace_dir=str(trace_dir), info={"slots": 4})


def test_programs_have_their_names_in_the_trace(run):
    runs = {
        name.rsplit("_", 1)[0]: len(v)
        for name, v in run.summary.modules.items() if "serving" in name
    }
    assert runs == {
        "jit_serving_prefill_chunk": 11, "jit_serving_first_token": 6,
        "jit_serving_place_pages": 6, "jit_serving_tick_paged": 8,
    }
    assert run.summary.window_s == pytest.approx(0.0944, abs=1e-4)
    # a model this small leaves the chip waiting for the host
    assert 100 * run.summary.idle_share == pytest.approx(99.097, abs=1e-3)


def test_spans_and_their_arguments_are_read(run):
    spans = ps.load(run)
    count = {n: len(spans.named(n)) for n in {s.name for s in spans.spans}}
    assert count == {
        "serving.tick": 9, "serving.admit": 9, "serving.admit_new": 6,
        "serving.prefill_chunk": 11, "serving.first_token": 6,
        "serving.first_token_wait": 6, "serving.decode": 8,
        "serving.decode_dispatch": 8, "serving.decode_wait": 8,
        "serving.harvest": 8,
    }
    ticks = spans.named("serving.tick")
    assert ticks[1].args == {"tick": 11, "queue": 2, "decoding": 2,
                             "admitting": 2, "free": 0}
    assert [t.args["admitting"] for t in ticks] == [0, 2, 1, 0, 0, 1, 1, 0, 0]
    new = spans.named("serving.admit_new")
    assert [s.args["prompt_tokens"] for s in new] == [40, 150, 20, 90, 64, 130]
    assert [s.args["chunks"] for s in new] == [1, 3, 1, 2, 1, 3]
    harvest = spans.named("serving.harvest")
    assert sum(s.args["tokens"] for s in harvest) == 68 - 6  # less firsts
    assert sum(s.args["retired"] for s in harvest) == 6
    # the spans of one request share its id
    for s in new:
        mine = [x.name for x in spans.spans if x.args.get("req") == s.args["req"]]
        assert mine.count("serving.prefill_chunk") == s.args["chunks"]
        assert mine.count("serving.first_token_wait") == 1


def test_the_clock_offset_is_measured_and_applied(run):
    spans = ps.load(run)
    # one of the eight tick programs appears to start 0.63 ms BEFORE the
    # span that dispatched it began: the device's stamps run ahead
    assert spans.clock_offset_ns == pytest.approx(-0.6334e6, rel=1e-3)


def test_idle_shares_add_up_to_the_devices(run):
    shares = {
        n: reader(n).read(run) for n in (
            "idle_in_admit_pct", "idle_in_decode_pct",
            "idle_in_harvest_pct", "idle_outside_step_pct")
    }
    assert shares["idle_in_admit_pct"] == pytest.approx(70.904, abs=1e-2)
    assert shares["idle_in_decode_pct"] == pytest.approx(14.791, abs=1e-2)
    assert shares["idle_in_harvest_pct"] == pytest.approx(12.720, abs=1e-2)
    assert shares["idle_outside_step_pct"] == pytest.approx(0.554, abs=1e-2)
    whole = reader("serve_device_idle_pct").read(run)
    assert abs(sum(shares.values()) - whole) < 0.5
    assert ps.idle_pct(run, "tick_self") < 0.2


def test_span_counter_and_scope_readers(run):
    assert reader("first_token_wait_ms").read(run) == pytest.approx(
        0.3409, abs=1e-3)
    # five of the nine ticks' 36 slots began a tick still in prefill
    assert reader("admitting_slots_pct").read(run) == pytest.approx(
        100 * 5 / 36)
    assert reader("tick_gather_share_pct").read(run) == pytest.approx(
        29.68, abs=0.01)


def test_scopes_of_the_tick_are_in_the_file(run):
    scopes = ps.op_scopes(tr.find_xplane(run.trace_dir))
    found = {p for v in scopes.values() for p in ps.scope_parts(v)}
    assert set(ps.TICK_SCOPES) <= found
    # the four serving programs and the eager ones beside them
    assert len({program for program, _ in scopes}) >= 4
