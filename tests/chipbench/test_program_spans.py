"""The readers of the program's own spans (chipbench/metrics/
_program_spans.py and the metrics of the layer ``scheduler (host)``):
their arithmetic on hand-made host and device events, with the device's
clock 1.2 ms ahead of the host's, and what they return where there is
nothing to read."""

from __future__ import annotations

import shutil
import types
from pathlib import Path

import pytest

from chipbench import run as bench
from chipbench import trace_reduce as tr
from chipbench.metrics import _program_spans as ps

REPO = Path(__file__).resolve().parents[2]
MS = 1e6  # nanoseconds
SKEW = 1.2 * MS
READERS = [
    "idle_in_admit_pct", "idle_in_decode_pct", "idle_in_harvest_pct",
    "idle_outside_step_pct", "first_token_wait_ms", "admitting_slots_pct",
    "tick_gather_share_pct",
]
TICK_PROGRAM = "jit_serving_tick_paged(111)"


def reader(name):
    return bench.load_from(REPO, "metrics", name)


def span(name, a, b, **args):
    return ps.HostSpan(name, a * MS, b * MS, args)


# Two ticks in a window of one second, times in ms on the host's clock.
# The device truly ran [14, 99] (a prefill chunk, then the placing of
# its pages), [118, 388] and [430, 788] (the two tick programs, the
# second started the instant its dispatch span began); its events are
# stamped 1.2 ms earlier.
WINDOW = (0.0, 1000 * MS)
SPANS = [
    span("serving.tick", 10, 400, tick=5, queue=9, decoding=3,
         admitting=1, free=0),
    span("serving.admit", 10, 110),
    span("serving.prefill_chunk", 12, 20, req=7, slot=2, chunk=0, of=1),
    span("serving.first_token", 20, 30, req=7, slot=2),
    span("serving.first_token_wait", 30, 100, req=7),
    span("serving.decode", 110.2, 390, slots=4),
    span("serving.decode_dispatch", 115, 120),
    span("serving.decode_wait", 120, 390),
    span("serving.harvest", 390, 399.9, tokens=32, retired=1),
    span("serving.tick", 420, 800, tick=6, queue=8, decoding=1,
         admitting=3, free=0),
    span("serving.admit", 420, 425),
    span("serving.decode", 425.1, 790, slots=1),
    span("serving.decode_dispatch", 430, 436),
    span("serving.decode_wait", 436, 790),
    span("serving.harvest", 790, 799.8, tokens=8, retired=0),
]
TRUE_BUSY_MS = (99 - 14) + (388 - 118) + (788 - 430)


def device_events():
    def at(a, b):  # true ms -> (stamped start ns, duration ns)
        return a * MS - SKEW, (b - a) * MS

    modules = [
        ("jit_serving_prefill_chunk(222)", *at(14, 90)),
        ("jit_serving_place_pages(333)", *at(90, 99)),
        (TICK_PROGRAM, *at(118, 388)),
        (TICK_PROGRAM, *at(430, 788)),
    ]
    ops = [
        ("%fusion.1 = f32[] fusion()", *at(14, 90)),
        ("%scatter.2 = s8[] scatter()", *at(90, 99)),
    ]
    for a, b in ((118, 388), (430, 788)):
        ops += [
            ("%gather.3 = s8[] gather()", *at(a, a + 60)),
            ("%while.4 = () while()", *at(a + 60, b - 40)),
            ("%fusion.5 = bf16[] fusion()", *at(a + 61, b - 41)),
            ("%scatter.6 = s8[] scatter()", *at(b - 40, b)),
        ]
    return {"device": {0: {"ops": ops, "modules": modules}},
            "host": [(tr.WINDOW_SPAN, WINDOW[0], WINDOW[1] - WINDOW[0])]}


@pytest.fixture(scope="module")
def summary():
    return tr.reduce_events(device_events())


@pytest.fixture()
def run(summary):
    built = ps.build(summary, WINDOW, SPANS)
    return types.SimpleNamespace(
        summary=summary, trace_dir=None,
        info={"slots": 4, ps.CACHE_KEY: built},
    )


def test_clock_offset_is_measured_from_the_dispatch_spans(summary):
    built = ps.build(summary, WINDOW, SPANS)
    # tick 1 started 3 ms after its dispatch span began, tick 2 at once:
    # the smallest difference is the skew alone
    assert built.clock_offset_ns == pytest.approx(-SKEW)
    assert ps.clock_offset_ns([117 * MS], [115 * MS, 430 * MS]) == 2 * MS
    # a program queued behind admission still pairs with its own span
    assert ps.clock_offset_ns(
        [215 * MS, 428.8 * MS], [115 * MS, 430 * MS]) == -1.2 * MS
    assert ps.clock_offset_ns([], [1.0]) is None
    assert ps.clock_offset_ns([1.0], []) is None


def test_idle_time_is_laid_against_the_phase_the_host_was_in(run):
    idle = {k: v / MS for k, v in run.info[ps.CACHE_KEY].idle_ns.items()}
    # admit: [10,14] and [99,110] of tick 1, all of [420,425] of tick 2
    assert idle["serving.admit"] == pytest.approx(4 + 11 + 5)
    # decode: launch [110.2,118] and return [388,390]; [425.1,430], [788,790]
    assert idle["serving.decode"] == pytest.approx(7.8 + 2 + 4.9 + 2)
    assert idle["serving.harvest"] == pytest.approx(9.9 + 9.8)
    assert idle["tick_self"] == pytest.approx(0.2 + 0.1 + 0.1 + 0.2)
    assert idle["outside"] == pytest.approx(10 + 20 + 200)
    # the spans inside a phase are laid out too
    assert idle["serving.first_token_wait"] == pytest.approx(1)  # [99,100]
    assert idle["serving.decode_dispatch"] == pytest.approx(3)   # [115,118]
    assert idle["serving.decode_wait"] == pytest.approx(4)
    total = run.info[ps.CACHE_KEY].total_idle_ns / MS
    assert total == pytest.approx(1000 - TRUE_BUSY_MS)


def test_without_the_shift_the_same_events_read_otherwise(summary):
    """What the measured offset is for: laid out on the device's own
    stamps, the placing program seems to end 1.2 ms earlier, and the
    wait for the first token to hold the host idle that much longer."""
    no_dispatch = [s for s in SPANS if s.name != ps.DISPATCH]
    unshifted = ps.build(summary, WINDOW, no_dispatch)
    assert unshifted.clock_offset_ns is None
    assert unshifted.idle_ns[ps.FIRST_TOKEN_WAIT] / MS == pytest.approx(2.2)


def test_the_four_idle_shares_add_up_to_the_devices_idle_share(run):
    four = sum(reader(n).read(run) for n in READERS[:4])
    whole = reader("serve_device_idle_pct").read(run)
    assert whole == pytest.approx(100 - TRUE_BUSY_MS / 10)
    assert abs(four - whole) < 0.5
    assert four == pytest.approx(whole - 0.06)  # the tick's self time
    assert reader("idle_in_admit_pct").read(run) == pytest.approx(2.0)
    assert reader("idle_in_decode_pct").read(run) == pytest.approx(1.67)
    assert reader("idle_in_harvest_pct").read(run) == pytest.approx(1.97)
    assert reader("idle_outside_step_pct").read(run) == pytest.approx(23.0)


def test_span_and_counter_readers(run):
    assert reader("first_token_wait_ms").read(run) == pytest.approx(70.0)
    # admitting: 1 of 4 slots in tick 5, 3 of 4 in tick 6
    assert reader("admitting_slots_pct").read(run) == pytest.approx(50.0)
    run.info[ps.CACHE_KEY].spans.append(
        span("serving.first_token_wait", 421, 424, req=8))
    assert reader("first_token_wait_ms").read(run) == pytest.approx(36.5)


def test_spans_outside_the_window_are_left_out(summary):
    early = span("serving.tick", -50, -10, tick=4, queue=9, decoding=4,
                 admitting=4, free=0)
    built = ps.build(summary, WINDOW, [early] + SPANS)
    assert len(built.named("serving.tick")) == 2
    assert ps.build(summary, WINDOW, [early]) is None


@pytest.mark.parametrize("name", READERS)
def test_no_device_trace_no_number(name):
    """A CPU run has no device plane: nothing from it may stand under
    these names, the span and counter readers' neither."""
    cpu = types.SimpleNamespace(summary=None, trace_dir="/nonexistent",
                                info={"slots": 4})
    assert reader(name).read(cpu) is None


@pytest.mark.parametrize("name", READERS)
def test_a_program_without_the_spans_gives_nothing_and_does_not_raise(
        name, tmp_path):
    """The parent commit's scheduler writes no ``serving.*`` span and
    no scope: on its trace every reader returns None. The recorded
    trace of chipbench/testdata/small_tpu.xplane.pb is such a trace (a
    program holding a ``while``, host spans of the benchmark only)."""
    shutil.copy(REPO / "chipbench/testdata/small_tpu.xplane.pb", tmp_path)
    path = tr.find_xplane(str(tmp_path))
    old = types.SimpleNamespace(
        summary=tr.reduce_events(tr.load_xplane(path)),
        trace_dir=str(tmp_path), info={"slots": 4},
    )
    assert reader(name).read(old) is None


def test_span_names_survive_the_reductions_cleaning():
    # trace_reduce.clean_name strips trailing digits and foreign
    # characters from what the ledger prints under idle_gaps
    for name in (ps.TICK, ps.DISPATCH, ps.FIRST_TOKEN_WAIT) + ps.PHASES:
        assert tr.clean_name(name) == name


# -- the one map read from the file's wire format --------------------------


def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _field(number: int, value) -> bytes:
    if isinstance(value, int):
        return _varint(number << 3) + _varint(value)
    return _varint((number << 3) | 2) + _varint(len(value)) + value


def _stat_meta(i, name):
    return _field(5, _field(1, i) + _field(2, _field(1, i) + _field(2, name)))


def _event_meta(i, name, stats=()):
    body = _field(1, i) + _field(2, name) + b"".join(
        _field(5, s) for s in stats)
    return _field(4, _field(1, i) + _field(2, body))


def _line(name, events, timestamp_ns=1000):
    """One XLine: events are (metadata id, start us, duration us)."""
    return _field(3, _field(2, name) + _field(3, timestamp_ns) + b"".join(
        _field(4, _field(1, md) + _field(2, int(a * 1e6))
               + _field(3, int(d * 1e6)))
        for md, a, d in events))


PROGRAM = _field(1, 2) + _field(3, 111)  # the stat program_id, a uint64


def _tf_op(text):
    return _field(1, 1) + _field(5, text)


def test_scopes_are_read_from_the_operations_metadata(tmp_path):
    """A hand-encoded XSpace: one device plane with two operations, one
    whose ``tf_op`` is a string and one whose ``tf_op`` refers to a
    stat metadata's name, and a host plane that is passed over."""
    device = (
        _field(1, 0) + _field(2, b"/device:TPU:0")
        + _stat_meta(1, b"tf_op") + _stat_meta(2, b"program_id")
        + _stat_meta(9, b"jit(f)/decode_attn/mul:")
        + _event_meta(1, b"%gather.3 = s8[] gather()", [
            _tf_op(b"jit(f)/kv_page_gather/gather:"), PROGRAM])
        + _event_meta(2, b"%fusion.5 = bf16[] fusion()", [
            _field(1, 1) + _field(7, 9), PROGRAM,
            _field(1, 3) + bytes([(2 << 3) | 1]) + bytes(8)])  # a double
        + _event_meta(3, b"%copy.9 = s8[] copy()", [PROGRAM])
    )
    host = _field(2, b"/host:CPU") + _event_meta(
        1, b"x", [_tf_op(b"jit(f)/other:"), PROGRAM])
    path = tmp_path / "hand.xplane.pb"
    path.write_bytes(_field(1, device) + _field(1, host))
    assert ps.op_scopes(str(path)) == {
        (111, "%gather.3 = s8[] gather()"): "jit(f)/kv_page_gather/gather:",
        (111, "%fusion.5 = bf16[] fusion()"): "jit(f)/decode_attn/mul:",
    }
    assert ps.scope_parts("jit(f)/kv_page_gather/gather:") == [
        "jit(f)", "kv_page_gather", "gather"]


def test_gather_share_counts_unscoped_copies_beside_the_scan(tmp_path):
    """A hand-encoded tick program of 100 us: a gather (10 us, scoped),
    a copy the compiler put in after it (20 us, no ``tf_op``), the scan
    (50 us: attention under its scope, then a copy without one), a
    scatter (20 us, scoped). The gather's and the scatter's stages are
    the scoped 30 us and the unscoped 20 us outside the scan; the
    unscoped copy inside the scan is the scan's."""
    names = [
        (1, b"jit_serving_tick_paged(111)", []),
        (2, b"%gather.3 = s8[] gather()",
         [_tf_op(b"jit(f)/kv_page_gather/gather:"), PROGRAM]),
        (3, b"%copy.9 = s8[] copy()", [PROGRAM]),
        (4, b"%while.4 = () while()", [PROGRAM]),
        (5, b"%fusion.5 = bf16[] fusion()",
         [_tf_op(b"jit(f)/while/body/decode_attn/mul:"), PROGRAM]),
        (6, b"%copy.10 = s8[] copy()", [PROGRAM]),
        (7, b"%scatter.6 = s8[] scatter()",
         [_tf_op(b"jit(f)/kv_page_scatter/scatter:"), PROGRAM]),
    ]
    device = (
        _field(1, 0) + _field(2, b"/device:TPU:0")
        + _stat_meta(1, b"tf_op") + _stat_meta(2, b"program_id")
        + b"".join(_event_meta(*n) for n in names)
        + _line(b"XLA Modules", [(1, 0, 100)])
        + _line(b"XLA Ops", [(2, 0, 10), (3, 10, 20), (4, 30, 50),
                             (5, 30, 30), (6, 60, 20), (7, 80, 20)])
    )
    host = (
        _field(2, b"/host:CPU")
        + _event_meta(1, tr.WINDOW_SPAN.encode())
        + _line(b"python3", [(1, 0, 100)])
    )
    (tmp_path / "hand.xplane.pb").write_bytes(
        _field(1, device) + _field(1, host))
    raw = tr.load_xplane(tr.find_xplane(str(tmp_path)))
    assert len(raw["device"][0]["ops"]) == 6 and len(raw["host"]) == 1
    run = types.SimpleNamespace(
        summary=tr.reduce_events(raw), trace_dir=str(tmp_path), info={})
    assert run.summary.busy_s == pytest.approx(100e-6)
    assert reader("tick_gather_share_pct").read(run) == pytest.approx(50.0)
