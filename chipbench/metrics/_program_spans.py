"""The program's own spans, counters and scopes in a traced run: what
the readers of the layer ``scheduler (host)`` share.

What a reader is offered. While the per-layer readers run,
``run.trace_dir`` still holds the profiler's ``.xplane.pb`` of the
traced window (``run.py`` deletes it after the last reader), and
``run.summary`` its reduction. The reduction keeps names, starts and
durations only; the file holds more, and this module reads two things
out of it:

* the host events that the program under test wrote itself. The
  serving scheduler enters one ``jax.profiler.TraceAnnotation`` per
  phase of a tick (``serving.tick`` around ``serving.admit`` /
  ``serving.decode`` / ``serving.harvest``, and the spans inside them:
  docs/API.md has the table), with what varies in the arguments
  (``req``, ``slot``, ``admitting`` ...). They are written by the
  profiler, on the clock the device's operations are stamped with, so a
  gap on the device can be laid against the phase the host was in.
  :func:`read_host_spans` keeps them with their arguments.
* the scope of each device operation (``jax.named_scope`` in the
  program: ``kv_page_gather``, ``decode_attn`` ...). The profiler
  stores it as the ``tf_op`` statistic of the operation's *metadata*,
  which ``jax.profiler.ProfileData`` does not hand out (it gives an
  event's own statistics only), so :func:`op_scopes` reads that one map
  from the file's wire format.

:func:`load` does the arithmetic once per run and caches it in
``run.info``; it prints ``note clock_offset_ms`` and the idle time by
span. Every function here returns None (or an empty result) where the
program wrote no such span, as a parent commit without them does:
the reader then leaves its metric out.

The clock. In a trace the device's stamps run ahead of the host's (a
program appears to start before the call that launched it, by about a
millisecond). No tick program can start before the
``serving.decode_dispatch`` span that launched it began, so the smallest
(device start of a tick program - start of its dispatch span) bounds
the skew; where it is negative, the device's intervals are moved later
by it before any gap is laid against a phase.
"""

from __future__ import annotations

import bisect
import dataclasses
import json
import re
import statistics

from chipbench import trace_reduce
from chipbench.metrics._util import decode_tick_module

PREFIX = "serving."
TICK = "serving.tick"
PHASES = ("serving.admit", "serving.decode", "serving.harvest")
DISPATCH = "serving.decode_dispatch"
FIRST_TOKEN_WAIT = "serving.first_token_wait"
GATHER_SCOPES = frozenset({"kv_page_gather", "kv_page_scatter"})
TICK_SCOPES = GATHER_SCOPES | {"decode_attn", "decode_mlp"}
CACHE_KEY = "program_spans"

Interval = tuple[float, float]


@dataclasses.dataclass
class HostSpan:
    name: str
    start: float  # ns on the host's clock
    end: float
    args: dict


@dataclasses.dataclass
class ProgramSpans:
    window: Interval                 # ns on the host's clock
    spans: list[HostSpan]            # serving.* that start in the window
    clock_offset_ns: float | None    # smallest (tick start - dispatch start)
    idle_ns: dict[str, float]        # span name, "outside", "tick_self"
    total_idle_ns: float

    @property
    def window_ns(self) -> float:
        return self.window[1] - self.window[0]

    def named(self, name: str) -> list[HostSpan]:
        return [s for s in self.spans if s.name == name]


# -- reading the file -------------------------------------------------------


def read_host_spans(path: str) -> tuple[Interval | None, list[HostSpan]]:
    """The traced window (the benchmark's own mark) and every
    ``serving.*`` host event of the file with its arguments, by start."""
    from jax.profiler import ProfileData

    marks: list[Interval] = []
    spans: list[HostSpan] = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name == trace_reduce.WINDOW_SPAN:
                    marks.append((ev.start_ns, ev.start_ns + ev.duration_ns))
                elif ev.name.startswith(PREFIX):
                    spans.append(HostSpan(
                        ev.name, ev.start_ns,
                        ev.start_ns + ev.duration_ns, dict(ev.stats),
                    ))
    window = max(marks, key=lambda w: w[1] - w[0]) if marks else None
    return window, sorted(spans, key=lambda s: s.start)


def _varint(buf, i: int) -> tuple[int, int]:
    value = shift = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """(field number, value) for each field of one protobuf message:
    an int for a varint, a memoryview for a length-delimited field;
    fixed-width fields are skipped."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
            yield key >> 3, value
        elif wire == 2:
            size, i = _varint(buf, i)
            yield key >> 3, buf[i:i + size]
            i += size
        elif wire in (1, 5):
            i += 8 if wire == 1 else 4
        else:
            raise ValueError(f"wire type {wire} in a trace file")


def op_scopes(path: str) -> dict[tuple[int, str], str]:
    """{(program id, operation's name): its ``tf_op``} over the device
    planes of an ``.xplane.pb``: 'jit(f)/kv_page_gather/gather' for an
    operation traced under ``jax.named_scope("kv_page_gather")``. The
    name is the one ``ProfileData`` gives the operation's events; the
    program id is the number in an "XLA Modules" event's name.

    Field numbers (tsl/profiler/protobuf/xplane.proto): XSpace.planes 1;
    XPlane.name 2, .event_metadata 4, .stat_metadata 5 (maps: key 1,
    value 2); XEventMetadata.name 2, .stats 5; XStatMetadata.name 2;
    XStat.metadata_id 1, .uint64_value 3, .int64_value 4, .str_value 5,
    .ref_value 7 (the id of a stat metadata whose name is the string).
    """
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out: dict[tuple[int, str], str] = {}
    for field, plane in _fields(space):
        if field != 1:
            continue
        name, events, stat_names = "", [], {}
        for f_no, value in _fields(plane):
            if f_no == 2:
                name = bytes(value).decode()
            elif f_no == 4:
                events.append(dict(_fields(value)).get(2))
            elif f_no == 5:
                entry = dict(_fields(value))
                meta = dict(_fields(entry.get(2, b"")))
                stat_names[entry.get(1, 0)] = bytes(
                    meta.get(2, b"")).decode()
        if not re.match(r"^/device:TPU:\d+$", name):
            continue
        for meta in events:
            if meta is None:
                continue
            op_name, stats = "", {}
            for f_no, value in _fields(meta):
                if f_no == 2:
                    op_name = bytes(value).decode()
                elif f_no == 5:
                    stat = dict(_fields(value))
                    stats[stat_names.get(stat.get(1))] = stat
            scope, program = stats.get("tf_op"), stats.get("program_id")
            if scope is None or program is None:
                continue
            if 5 in scope:
                text = bytes(scope[5]).decode()
            else:
                text = stat_names.get(scope.get(7), "")
            out[(program.get(3, program.get(4, 0)), op_name)] = text
    return out


def scope_parts(tf_op: str) -> list[str]:
    """'jit(f)/kv_page_gather/gather:' -> ['jit(f)', 'kv_page_gather',
    'gather']."""
    return [p.rstrip(":") for p in tf_op.split("/")]


# -- arithmetic on plain intervals -----------------------------------------


def clock_offset_ns(tick_starts: list[float],
                    dispatch_starts: list[float]) -> float | None:
    """The smallest (device start of a tick program - start of the
    dispatch span that launched it), each program paired with the
    dispatch span whose start is nearest. Negative: the device's clock
    runs ahead by at least that much."""
    if not tick_starts or not dispatch_starts:
        return None
    starts = sorted(dispatch_starts)
    best = None
    for t in tick_starts:
        i = bisect.bisect_left(starts, t)
        near = min(starts[max(0, i - 1): i + 1], key=lambda d: abs(t - d))
        best = t - near if best is None else min(best, t - near)
    return best


def _clip(intervals: list[Interval], window: Interval) -> list[Interval]:
    w0, w1 = window
    return trace_reduce._union(
        [(max(a, w0), min(b, w1)) for a, b in intervals
         if b > w0 and a < w1]
    )


def _overlap_ns(a: list[Interval], b: list[Interval]) -> float:
    """Length of the intersection of two sorted disjoint interval
    lists."""
    i = j = 0
    total = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] <= b[j][1]:
            i += 1
        else:
            j += 1
    return total


def idle_by_span(busy: list[Interval], spans: list[HostSpan],
                 window: Interval) -> tuple[dict[str, float], float]:
    """Idle nanoseconds of the window (no operation running: the
    complement of ``busy``) that fall inside the spans of each name,
    and the window's whole idle time. Two more keys: ``outside``, idle
    time outside every ``serving.tick``; ``tick_self``, idle time inside
    a tick and outside its three phases. ``outside`` + ``tick_self`` +
    the three phases add up to the whole."""
    w0, w1 = window
    idle, edge = [], w0
    for a, b in _clip(busy, window) + [(w1, w1)]:
        if a > edge:
            idle.append((edge, a))
        edge = max(edge, b)
    total = sum(b - a for a, b in idle)
    by_name: dict[str, list[Interval]] = {}
    for s in spans:
        by_name.setdefault(s.name, []).append((s.start, s.end))
    out = {
        name: _overlap_ns(idle, _clip(iv, window))
        for name, iv in by_name.items()
    }
    in_tick = out.get(TICK, 0.0)
    out["outside"] = total - in_tick
    out["tick_self"] = in_tick - sum(out.get(p, 0.0) for p in PHASES)
    return out, total


def build(summary, window: Interval, spans: list[HostSpan]
          ) -> ProgramSpans | None:
    """The arithmetic, on a reduced trace and the program's host spans.
    None where the program wrote no ``serving.tick`` in the window."""
    w0, w1 = window
    inside = [s for s in spans if w0 <= s.start < w1]
    if not any(s.name == TICK for s in inside):
        return None
    tick = decode_tick_module(summary)
    tick_starts = [
        w0 + start * 1e9 for _, start, _ in summary.modules.get(tick, ())
    ]
    offset = clock_offset_ns(
        tick_starts, [s.start for s in spans if s.name == DISPATCH])
    shift = max(0.0, -offset) if offset is not None else 0.0
    idle = {}
    total = 0.0
    chips = sorted({op.chip for op in summary.ops})
    for chip in chips:
        busy = [
            (w0 + op.start * 1e9 + shift,
             w0 + (op.start + op.dur) * 1e9 + shift)
            for op in summary.ops if op.chip == chip
        ]
        by, t = idle_by_span(busy, inside, window)
        for k, v in by.items():
            idle[k] = idle.get(k, 0.0) + v / len(chips)
        total += t / len(chips)
    return ProgramSpans(window, inside, offset, idle, total)


# -- what the readers call ----------------------------------------------------


def _host(run) -> tuple[Interval | None, list[HostSpan]]:
    """The file's window mark and ``serving.*`` events, read once."""
    key = CACHE_KEY + "_host"
    if key not in run.info:
        run.info[key] = read_host_spans(
            trace_reduce.find_xplane(run.trace_dir))
    return run.info[key]


def load(run) -> ProgramSpans | None:
    """The program's spans of this run's traced window, or None where
    there is no device trace (a CPU run) or the program wrote none.
    Built once, then found in ``run.info``."""
    if run.summary is None:
        return None
    if CACHE_KEY not in run.info:
        window, spans = _host(run)
        ps = None
        if window is not None:
            ps = build(run.summary, window, spans)
        run.info[CACHE_KEY] = ps
        if ps is not None:
            if ps.clock_offset_ns is not None:
                print(
                    f"note clock_offset_ms {ps.clock_offset_ns * 1e-6:.4f} "
                    "(smallest tick-program start less its dispatch "
                    "span's start; device intervals moved later by "
                    f"{max(0.0, -ps.clock_offset_ns) * 1e-6:.4f} ms)",
                    flush=True)
            print("note idle_ms_by_span " + json.dumps({
                k: round(v * 1e-6, 3)
                for k, v in sorted(ps.idle_ns.items(), key=lambda kv: -kv[1])
            }) + f" total {ps.total_idle_ns * 1e-6:.3f}", flush=True)
    return run.info[CACHE_KEY]


def idle_pct(run, key: str) -> float | None:
    """Share of the traced window that was idle on the device while the
    host was inside the spans named ``key``."""
    ps = load(run)
    if ps is None or ps.window_ns <= 0:
        return None
    return 100.0 * ps.idle_ns.get(key, 0.0) / ps.window_ns


def median_span_ms(run, name: str) -> float | None:
    ps = load(run)
    if ps is None:
        return None
    durations = [s.end - s.start for s in ps.named(name)]
    return 1e-6 * statistics.median(durations) if durations else None


def mean_tick_argument(run, key: str) -> float | None:
    """Mean over the window's ticks of one argument of
    ``serving.tick`` (the scheduler's own count when the tick began)."""
    ps = load(run)
    if ps is None:
        return None
    values = [s.args[key] for s in ps.named(TICK) if key in s.args]
    return statistics.fmean(values) if values else None


def gather_share_of_tick(run) -> float | None:
    """Share of the tick program's device time (its operations' self
    time) that belongs to the stages traced under ``GATHER_SCOPES``: the
    operations that carry one of them, and the operations that carry no
    ``tf_op`` at all and run before or after the tick's scan (its
    longest ``while``). The second kind are copies the compiler puts in
    beside a gather or a scatter (a change of layout); it gives them no
    metadata, and outside the scan the tick program does nothing but
    gather and scatter. None where no operation of the tick carries any
    of the program's scopes (``TICK_SCOPES``): a program without
    ``jax.named_scope``, as a parent commit is, or a profiler that does
    not record them."""
    if run.summary is None:
        return None
    tick = decode_tick_module(run.summary)
    if tick is None:
        return None
    path = trace_reduce.find_xplane(run.trace_dir)
    window, _ = _host(run)
    if window is None:
        return None
    w0, w1 = window
    parts_of = {k: frozenset(scope_parts(v))
                for k, v in op_scopes(path).items()}
    picked = beside = known = whole = 0.0
    for chip in trace_reduce.load_xplane(path)["device"].values():
        runs = sorted(
            (s, s + d, int(m.group(1)))
            for n, s, d in chip["modules"]
            if trace_reduce.clean_module(n) == tick
            and (m := re.search(r"\((\d+)\)$", n))
        )
        starts = [r[0] for r in runs]

        def run_of(t: float) -> int | None:
            i = bisect.bisect_right(starts, t) - 1
            return i if i >= 0 and t < runs[i][1] else None

        evs = [(n, float(s), float(d)) for n, s, d in chip["ops"]
               if s + d > w0 and s < w1]
        scans: dict[int, Interval] = {}  # run -> its longest while
        for n, s, d in evs:
            i = run_of(s)
            if (i is not None and trace_reduce.clean_name(n) == "while"
                    and (i not in scans or d > scans[i][1] - scans[i][0])):
                scans[i] = (s, s + d)
        for (name, s, _), self_ns in zip(evs, trace_reduce._self_times(evs)):
            i = run_of(s)
            if i is None:
                continue
            whole += self_ns
            parts = parts_of.get((runs[i][2], name))
            if parts is None:  # no tf_op at all
                if i in scans and not scans[i][0] <= s < scans[i][1]:
                    beside += self_ns
                continue
            if parts & TICK_SCOPES:
                known += self_ns
            if parts & GATHER_SCOPES:
                picked += self_ns
    if whole <= 0 or known <= 0:
        return None
    print("note tick_time_by_scope_pct under_a_gather_or_scatter_scope "
          f"{100.0 * picked / whole:.2f} unscoped_outside_the_scan "
          f"{100.0 * beside / whole:.2f} under_any_program_scope "
          f"{100.0 * known / whole:.2f}", flush=True)
    return 100.0 * (picked + beside) / whole
