"""The reduction from trace events to numbers, on hand-made events whose
answers can be worked out on paper (the recorded trace is in
test_recorded_trace.py)."""

from __future__ import annotations

import pytest

from chipbench import trace_reduce as tr

US = 1000.0  # nanoseconds


def raw_events():
    """One chip, window 0..1000 us. Program jit_step(1) runs 100..500:
    a fusion 100..200, a while 200..500 holding two kernels 220..300 and
    320..480. Program jit_other(2) runs 700..800: one copy 700..800.
    The host is in 'chipbench:step' 0..600 and inside it in
    'DevicePut' 510..590; then in 'chipbench:admit' 600..1000."""
    ops = [
        ("%fusion.12", 100 * US, 100 * US),
        ("%while.3", 200 * US, 300 * US),
        ("%jvp__.7", 220 * US, 80 * US),
        ("%jvp__.8", 320 * US, 160 * US),
        ("%copy.1", 700 * US, 100 * US),
    ]
    modules = [("jit_step(1)", 100 * US, 400 * US),
               ("jit_other(2)", 700 * US, 100 * US)]
    host = [
        (tr.WINDOW_SPAN, 0.0, 1000 * US),
        ("chipbench:step", 0.0, 600 * US),
        ("DevicePut", 510 * US, 80 * US),
        ("chipbench:admit", 600 * US, 400 * US),
    ]
    return {"device": {0: {"ops": ops, "modules": modules}}, "host": host}


def test_busy_idle_and_window():
    s = tr.reduce_events(raw_events())
    assert s.window_s == pytest.approx(1000e-6)
    # busy: 100..500 and 700..800
    assert s.busy_s == pytest.approx(500e-6)
    assert s.idle_share == pytest.approx(0.5)


def test_self_time_by_name_excludes_children():
    s = tr.reduce_events(raw_events())
    by = s.op_seconds()
    assert by["jit_step_1/fusion"] == pytest.approx(100e-6)
    assert by["jit_step_1/jvp__"] == pytest.approx(240e-6)
    # the while lasts 300 us, 240 of them inside its kernels
    assert by["jit_step_1/while"] == pytest.approx(60e-6)
    assert by["jit_other_2/copy"] == pytest.approx(100e-6)
    assert sum(by.values()) == pytest.approx(s.busy_s)
    assert s.seconds_where(lambda o: o.name.startswith("jvp")) == \
        pytest.approx(240e-6)


def test_programs_by_name():
    s = tr.reduce_events(raw_events())
    seconds, n = s.module_seconds(lambda name: name.startswith("jit_step"))
    assert (seconds, n) == (pytest.approx(400e-6), 1)
    seconds, n = s.module_seconds(lambda name: True)
    assert (seconds, n) == (pytest.approx(500e-6), 2)


def test_gaps_are_named_by_the_innermost_host_span():
    s = tr.reduce_events(raw_events())
    # 0..100 step; 500..700 midpoint 600 -> admit (shorter than step at
    # the boundary); 800..1000 admit
    assert s.gaps["chipbench_step"] == pytest.approx(100e-6)
    assert s.gaps["chipbench_admit"] == pytest.approx(400e-6)
    assert sum(s.gaps.values()) == pytest.approx(s.window_s - s.busy_s)
    b = s.breakdown()
    assert b["device_ops"][0][0] == "jit_step_1/jvp__"
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10


def test_short_gaps_are_launch_spacing():
    raw = raw_events()
    raw["device"][0]["ops"].append(("%tiny.1", 803 * US, 50 * US))
    s = tr.reduce_events(raw)
    assert s.gaps["device_between_ops"] == pytest.approx(3e-6)


def test_two_chips_are_averaged():
    raw = raw_events()
    raw["device"][1] = {"ops": [("%fusion.1", 0.0, 1000 * US)],
                        "modules": [("jit_step(1)", 0.0, 1000 * US)]}
    s = tr.reduce_events(raw)
    assert s.chips == 2
    assert s.busy_s_per_chip == [pytest.approx(500e-6),
                                 pytest.approx(1000e-6)]
    assert s.busy_s == pytest.approx(750e-6)
    assert s.idle_share == pytest.approx(0.25)


def test_events_are_clipped_to_the_window():
    raw = raw_events()
    s = tr.reduce_events(raw, window=(150 * US, 750 * US))
    # 150..500 and 700..750
    assert s.busy_s == pytest.approx(400e-6)
    assert s.window_s == pytest.approx(600e-6)


def test_names_are_cleaned():
    assert tr.clean_name("%fusion.123 = f32[] fusion(...)") == "fusion"
    assert tr.clean_name("transpose(jvp())") == "transpose_jvp___"
    assert tr.clean_module("jit_step(123)") == "jit_step_123"
