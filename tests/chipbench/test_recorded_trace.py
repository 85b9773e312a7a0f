"""The reading of a real trace file: chipbench/testdata/small_tpu.xplane.pb
was recorded on one v5e chip by chipbench/testdata/record_small_trace.py
(six runs of one small program, a 20 ms host sleep after each)."""

from __future__ import annotations

from collections import Counter
from pathlib import Path

import pytest

from chipbench import trace_reduce as tr

TRACE = Path(__file__).resolve().parents[2] / "chipbench/testdata/small_tpu.xplane.pb"


@pytest.fixture(scope="module")
def raw():
    return tr.load_xplane(str(TRACE))


def test_planes_and_lines_are_found(raw):
    assert list(raw["device"]) == [0]
    assert len(raw["device"][0]["modules"]) == 6
    assert len(raw["device"][0]["ops"]) == 84
    names = Counter(n for n, _, _ in raw["host"])
    assert names[tr.WINDOW_SPAN] == 1
    assert names["chipbench:step"] == 6 and names["chipbench:sleep"] == 6


def test_window_busy_and_programs(raw):
    s = tr.reduce_events(raw)
    assert s.chips == 1
    # six steps of about 1 ms and six sleeps of 20 ms
    assert s.window_s == pytest.approx(0.1294, abs=1e-4)
    # The device's clock runs about 1.2 ms ahead of the host's in this
    # file (every program starts that long before the call that
    # launched it), so the first of the six runs falls before the
    # window's host mark: five are inside, 67.5 us each.
    seconds, n = s.module_seconds(lambda name: name.startswith("jit_small_step"))
    assert n == 5
    assert seconds == pytest.approx(5 * 67.5e-6, rel=0.01)
    assert s.busy_s == pytest.approx(seconds, rel=1e-3)
    assert s.idle_share == pytest.approx(1 - seconds / s.window_s, rel=1e-6)


def test_operations_by_name_and_self_time(raw):
    s = tr.reduce_events(raw)
    count = Counter(o.name for o in s.ops)
    # per run: the first product, and a while of four loop bodies
    assert count["fusion"] == 5 and count["while"] == 5
    assert count["convolution_tanh_fusion"] == 20
    by = s.op_seconds()
    assert sum(by.values()) == pytest.approx(s.busy_s, rel=1e-3)
    loop = [k for k in by if k.endswith("/while")][0]
    body = [k for k in by if k.endswith("/convolution_tanh_fusion")][0]
    # the loop's own time is what its bodies leave: next to nothing
    assert by[loop] < 0.01 * by[body]
    assert by[body] == pytest.approx(231.4e-6, rel=0.01)


def test_idle_time_is_named_by_the_host_span(raw):
    s = tr.reduce_events(raw)
    assert sum(s.gaps.values()) == pytest.approx(s.window_s - s.busy_s,
                                                 rel=1e-6)
    # six sleeps of 20-21 ms; with the clocks 1.2 ms apart the short
    # gaps around each run fall into the sleep before it as well
    assert s.gaps["chipbench_sleep"] == pytest.approx(0.129, abs=2e-3)
    b = s.breakdown()
    assert b["idle_gaps"][0][0] == "chipbench_sleep"
    assert b["device_ops"][0][0].endswith("/convolution_tanh_fusion")

