"""From a profiler trace (``.xplane.pb``) to numbers: the busy and idle
share of the traced window on each chip, device time by operation and by
program, and the idle gaps named by what the host was doing in them.

Two stages, so that the arithmetic can be checked on hand-made events
(tests/chipbench) and the reading on a small recorded trace
(chipbench/testdata):

* :func:`load_xplane` reads the file with nothing but JAX into plain
  tuples;
* :func:`reduce_events` does the arithmetic.

Device planes are those named ``/device:TPU:<n>``; on them the line
``XLA Ops`` holds one event per executed operation (a ``while`` holds
its body's operations nested inside it) and ``XLA Modules`` one event
per executed program. Host planes are ``/host:...``; their events are
JAX's own TraceMe spans and the benchmark's ``chipbench:...`` spans.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re

WINDOW_SPAN = "chipbench:traced_window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
BETWEEN_OPS_S = 5e-6  # a gap shorter than this is launch spacing


@dataclasses.dataclass
class Op:
    chip: int
    module: str
    name: str
    start: float  # seconds
    dur: float    # seconds, children included
    self_dur: float  # seconds, children excluded


@dataclasses.dataclass
class TraceSummary:
    window_s: float
    chips: int
    busy_s_per_chip: list[float]
    ops: list[Op]
    modules: dict[str, list[tuple[int, float, float]]]  # name -> (chip, start, dur)
    gaps: dict[str, float]  # host span -> idle seconds, mean over chips

    @property
    def busy_s(self) -> float:
        return sum(self.busy_s_per_chip) / max(1, self.chips)

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def op_seconds(self) -> dict[str, float]:
        """Self time by 'program/operation', mean over the chips."""
        out: dict[str, float] = {}
        for op in self.ops:
            key = f"{op.module}/{op.name}"
            out[key] = out.get(key, 0.0) + op.self_dur / self.chips
        return out

    def seconds_where(self, pred) -> float:
        """Self time of the operations for which ``pred(op)`` holds,
        mean over the chips."""
        return sum(o.self_dur for o in self.ops if pred(o)) / self.chips

    def module_seconds(self, pred) -> tuple[float, int]:
        """Device time and number of executions of the programs whose
        name satisfies ``pred``, mean over the chips."""
        tot, n = 0.0, 0
        for name, runs in self.modules.items():
            if pred(name):
                tot += sum(d for _, _, d in runs)
                n += len(runs)
        return tot / self.chips, n

    def breakdown(self, top: int = 10) -> dict:
        ops = sorted(self.op_seconds().items(), key=lambda kv: -kv[1])
        gaps = sorted(self.gaps.items(), key=lambda kv: -kv[1])
        return {
            "device_ops": [[k, v] for k, v in ops[:top]],
            "idle_gaps": [[k, v] for k, v in gaps[:top]],
        }


def clean_name(name: str) -> str:
    """'%fusion.123 = ...' -> 'fusion'; anything outside the characters
    of a metric name becomes '_'."""
    name = name.split(" = ")[0].lstrip("%")
    name = re.sub(r"[.\-_]?\d+$", "", name)
    name = re.sub(r"\(\d+\)$", "", name)
    return re.sub(r"[^A-Za-z0-9_.\-]", "_", name) or "_"


def clean_module(name: str) -> str:
    """'jit_run(123)' -> 'jit_run_123': programs of one name are told
    apart by the identifier the runtime gives them."""
    return re.sub(r"[^A-Za-z0-9_.\-]", "_", name).strip("_") or "_"


def find_xplane(log_dir: str) -> str:
    paths = sorted(
        glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True),
        key=os.path.getmtime,
    )
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def load_xplane(path: str) -> dict:
    """{'device': {chip: {'ops': [(name, start_ns, dur_ns)], 'modules':
    [...]}}, 'host': [(name, start_ns, dur_ns)]}"""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    device: dict[int, dict[str, list]] = {}
    host: list[tuple[str, float, float]] = []
    for plane in data.planes:
        m = re.match(r"^/device:TPU:(\d+)$", plane.name)
        if m:
            chip = device.setdefault(
                int(m.group(1)), {"ops": [], "modules": []}
            )
            for line in plane.lines:
                if line.name == OPS_LINE:
                    dst = chip["ops"]
                elif line.name == MODULES_LINE:
                    dst = chip["modules"]
                else:
                    continue
                for ev in line.events:
                    dst.append((ev.name, ev.start_ns, ev.duration_ns))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    host.append((ev.name, ev.start_ns, ev.duration_ns))
    return {"device": device, "host": host}


def _union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _self_times(events: list[tuple[str, float, float]]) -> list[float]:
    """Exclusive duration of each event of one line, where events may
    nest (a ``while`` around its body): its duration less that of its
    direct children. Returned in the order of ``events``."""
    order = sorted(range(len(events)),
                   key=lambda i: (events[i][1], -events[i][2]))
    self_d = [events[i][2] for i in range(len(events))]
    stack: list[int] = []
    for i in order:
        _, s, d = events[i]
        while stack and s >= events[stack[-1]][1] + events[stack[-1]][2]:
            stack.pop()
        if stack:
            self_d[stack[-1]] -= d
        stack.append(i)
    return [max(0.0, x) for x in self_d]


def _host_span_at(host_sorted, t: float) -> str:
    """The shortest host span that covers time ``t``."""
    best, best_d = None, None
    for name, s, d in host_sorted:
        if s > t:
            break
        if s + d >= t and (best_d is None or d < best_d):
            best, best_d = name, d
    return best if best is not None else "host:unattributed"


def reduce_events(raw: dict, window: tuple[float, float] | None = None
                  ) -> TraceSummary:
    """``window`` is (start_ns, end_ns); by default the benchmark's
    ``chipbench:traced_window`` host span, else the span of the device
    events."""
    host = [(n, float(s), float(d)) for n, s, d in raw["host"]]
    if window is None:
        marks = [(s, s + d) for n, s, d in host if n == WINDOW_SPAN]
        if marks:
            window = max(marks, key=lambda w: w[1] - w[0])
    device = raw["device"]
    if window is None:
        starts = [s for c in device.values() for _, s, _ in c["ops"]]
        ends = [s + d for c in device.values() for _, s, d in c["ops"]]
        if not starts:
            raise ValueError("no device operation in the trace")
        window = (min(starts), max(ends))
    w0, w1 = window
    # host spans that can explain a gap: drop the window span itself
    host_sorted = sorted(
        (h for h in host if h[0] != WINDOW_SPAN and h[1] < w1
         and h[1] + h[2] > w0),
        key=lambda h: h[1],
    )
    ops: list[Op] = []
    modules: dict[str, list] = {}
    busy: list[float] = []
    gaps: dict[str, float] = {}
    chips = sorted(device)
    for ci, chip in enumerate(chips):
        mods = sorted(
            (s, s + d, clean_module(n)) for n, s, d in device[chip]["modules"]
        )
        for s, e, n in mods:
            if e > w0 and s < w1:
                modules.setdefault(n, []).append(
                    (ci, (s - w0) * 1e-9, (min(e, w1) - max(s, w0)) * 1e-9)
                )
        evs = [
            (n, float(s), float(d)) for n, s, d in device[chip]["ops"]
            if s + d > w0 and s < w1
        ]
        selfs = _self_times(evs)
        mi = 0
        order = sorted(range(len(evs)), key=lambda i: evs[i][1])
        for i in order:
            n, s, d = evs[i]
            while mi < len(mods) and mods[mi][1] <= s:
                mi += 1
            module = (
                mods[mi][2] if mi < len(mods) and mods[mi][0] <= s
                else "no_module"
            )
            ops.append(Op(ci, module, clean_name(n), (s - w0) * 1e-9,
                          d * 1e-9, selfs[i] * 1e-9))
        merged = _union(
            [(max(s, w0), min(s + d, w1)) for _, s, d in evs]
        )
        busy.append(sum(b - a for a, b in merged) * 1e-9)
        edge = w0
        for a, b in merged + [(w1, w1)]:
            gap = a - edge
            if gap > 0:
                if gap * 1e-9 < BETWEEN_OPS_S:
                    name = "device:between_ops"
                else:
                    name = _host_span_at(host_sorted, edge + gap / 2)
                name = clean_name(name)
                gaps[name] = gaps.get(name, 0.0) + gap * 1e-9
            edge = max(edge, b)
    n = max(1, len(chips))
    return TraceSummary(
        window_s=(w1 - w0) * 1e-9,
        chips=n,
        busy_s_per_chip=busy,
        ops=ops,
        modules=modules,
        gaps={k: v / n for k, v in gaps.items()},
    )

