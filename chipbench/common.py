"""What every runner shares: the clock and spans, quantile points and
percentiles, the device look-up, the compile cache and its meter, the
profiler window and the ``check`` lines.

Nothing here imports the program under test.
"""

from __future__ import annotations

import json
import math
import os
import time
from contextlib import contextmanager
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent

now = time.perf_counter


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


class Spans:
    """The benchmark's own spans around calls into a layer, on the host
    clock, kept in memory. With ``annotate`` on (the traced run) each
    span is also a ``jax.profiler.TraceAnnotation``, so that the trace
    reduction can say what the host was doing in a device gap."""

    def __init__(self, annotate: bool = False):
        self.annotate = annotate
        self.spans: dict[str, list[tuple[float, float]]] = {}

    @contextmanager
    def span(self, name: str):
        ann = None
        if self.annotate:
            from jax.profiler import TraceAnnotation

            ann = TraceAnnotation("chipbench:" + name)
            ann.__enter__()
        t0 = now()
        try:
            yield
        finally:
            t1 = now()
            if ann is not None:
                ann.__exit__(None, None, None)
            self.spans.setdefault(name, []).append((t0, t1))

    def durations(self, name: str) -> list[float]:
        return [b - a for a, b in self.spans.get(name, ())]


def quantile_points(lo: float, hi: float, n: int, dist: str) -> list[int]:
    """The n mid-quantile points of a distribution on [lo, hi], as whole
    numbers: the fixed multiset that every seed shuffles."""
    out = []
    for i in range(n):
        u = (i + 0.5) / n
        if dist != "log_uniform":
            raise ValueError(f"unknown distribution {dist!r}")
        x = math.exp(math.log(lo) + u * (math.log(hi) - math.log(lo)))
        out.append(int(round(x)))
    return out


def seeded_rng(seed: int, salt: int = 0):
    import numpy as np

    return np.random.default_rng([int(seed), int(salt)])


def weighted_percentile(values, weights, q: float) -> float:
    """Percentile of a multiset given as (value, count) pairs."""
    pairs = sorted(zip(values, weights))
    total = sum(w for _, w in pairs)
    if total <= 0:
        raise ValueError("no readings")
    want = q / 100.0 * total
    acc = 0.0
    for v, w in pairs:
        acc += w
        if acc >= want:
            return float(v)
    return float(pairs[-1][0])


def compact(values, digits: int = 3) -> str:
    return "[" + ",".join(f"{v:.{digits}f}" for v in values) + "]"


def load_json(path) -> dict:
    with open(path) as f:
        return json.load(f)


def wire_compile_cache() -> str:
    """JAX's persistent compilation cache at JAX_COMPILATION_CACHE_DIR if
    the machine sets it, else at one fixed path inside the checkout.
    Every program is persisted, so a second run compiles nothing."""
    import jax

    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir:
        cache_dir = str(ROOT / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return cache_dir


def find_devices(chips: int, require_chip: bool = True):
    """The devices a cell runs on. Without a TPU, or with fewer chips
    than the cell asks for, this is an error: no number of this
    benchmark comes from a CPU."""
    import jax

    devices = jax.devices()
    if require_chip:
        if devices[0].platform != "tpu":
            raise NoChip(
                f"needs a TPU, found platform {devices[0].platform!r}"
            )
        if len(devices) < chips:
            raise NoChip(
                f"cell asks for {chips} chips, found {len(devices)}"
            )
    return list(devices[:chips])


def peaks_for(device_kind: str) -> dict:
    table = load_json(HERE / "peaks.json")
    if device_kind not in table or device_kind == "source":
        raise KeyError(
            f"no published peaks for device kind {device_kind!r} in "
            "chipbench/peaks.json; add them with their source"
        )
    return table[device_kind]


def memory_peak_bytes(devices) -> int:
    """Peak on the fullest chip, read when the window has just closed.
    The runtime counts array buffers (``peak_bytes_in_use``) apart from
    the scratch it holds for the loaded programs (``bytes_reserved``:
    12.4 GiB of the trainer's 14.2), so the peak is the larger of the
    buffers' own peak and buffers plus scratch as they stand now."""
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(
            peak, int(stats.get("peak_bytes_in_use", 0)),
            int(stats.get("bytes_in_use", 0))
            + int(stats.get("bytes_reserved", 0)),
        )
    return peak


class CompileMeter:
    """Backend compilations and persistent-cache look-ups, from JAX's
    own monitoring events: set-up reports them, and the window must add
    none."""

    def __init__(self):
        import threading

        from jax import monitoring

        self._lock = threading.Lock()
        self.compile_s = 0.0
        self.compiles = 0
        self.requests = 0
        self.hits = 0
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            with self._lock:
                self.compile_s += secs
                self.compiles += 1

    def _event(self, event: str, **_kw) -> None:
        with self._lock:
            if event == "/jax/compilation_cache/compile_requests_use_cache":
                self.requests += 1
            elif event == "/jax/compilation_cache/cache_hits":
                self.hits += 1

    def read(self) -> dict:
        with self._lock:
            return {"programs": self.compiles,
                    "compile_or_load_s": round(self.compile_s, 2),
                    "cache_requests": self.requests,
                    "cache_hits": self.hits}


def print_setup(run) -> None:
    """The set-up's phases, so that a slow one can be found."""
    parts = " ".join(
        f"{name[6:]}={sum(b - a for a, b in spans):.2f}"
        for name, spans in run.spans.spans.items()
        if name.startswith("setup_")
    )
    print(f"note setup_s {run.end_to_end['setup_s']:.2f} of which "
          f"{parts}", flush=True)
    if run.meter is not None:
        run.info["programs_at_window_open"] = run.meter.read()["programs"]
        print(f"note set-up programs {json.dumps(run.meter.read())}",
              flush=True)


def window_compiled_nothing(run) -> None:
    """A check: no program was compiled or loaded inside the window."""
    if run.meter is None:
        return
    added = run.meter.read()["programs"] - run.info.get(
        "programs_at_window_open", 0)
    run.check.at_most("programs_compiled_in_window", added, 0)


def print_memory(devices) -> None:
    """The runtime's own counters, so that ``memory_peak_bytes`` can be
    split into array buffers and the scratch reserved for programs."""
    for d in devices:
        stats = d.memory_stats() or {}
        keep = {k: v for k, v in stats.items()
                if k in ("bytes_in_use", "peak_bytes_in_use",
                         "largest_alloc_size", "bytes_limit",
                         "bytes_reserved", "peak_bytes_reserved")}
        print(f"note memory device {d.id} {json.dumps(keep)}", flush=True)


def device_record(devices) -> dict:
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }


class Check:
    """The numbers that decide ``correct``, each beside its limit."""

    def __init__(self):
        self.rows: list[tuple[str, float, float, bool]] = []

    def at_most(self, name: str, value: float, limit: float) -> None:
        ok = bool(value <= limit) and math.isfinite(value)
        self.rows.append((name, float(value), float(limit), ok))

    def require(self, name: str, ok: bool) -> None:
        self.rows.append((name, 0.0 if ok else 1.0, 0.0, bool(ok)))

    @property
    def correct(self) -> bool:
        return bool(self.rows) and all(r[3] for r in self.rows)

    def print(self) -> None:
        for name, value, limit, ok in self.rows:
            print(
                f"check {name}: {value:.6g} limit {limit:.6g} "
                f"{'ok' if ok else 'FAILED'}", flush=True,
            )


class WindowTrace:
    """The profiler around the first ``trace_seconds`` of a traced
    run's window. Host spans of the JAX runtime and the benchmark's own
    are recorded; the Python tracer is off."""

    def __init__(self, run, trace_seconds: float):
        self.run = run
        self.limit = min(float(trace_seconds), run.seconds)
        self.t_open = None
        self.mark = None

    def start(self) -> None:
        if not self.run.trace:
            return
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        os.makedirs(self.run.trace_dir, exist_ok=True)
        jax.profiler.start_trace(self.run.trace_dir, profiler_options=opts)
        self.mark = jax.profiler.TraceAnnotation("chipbench:traced_window")
        self.mark.__enter__()
        self.t_open = now()

    def stop_if_due(self, force: bool = False) -> None:
        if self.t_open is None:
            return
        if not force and now() - self.t_open < self.limit:
            return
        import jax

        self.mark.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.t_open = None

    def reduce(self) -> None:
        """After the window: the trace as numbers, if it holds a device."""
        if not self.run.trace:
            return
        self.stop_if_due(force=True)
        from chipbench import trace_reduce

        raw = trace_reduce.load_xplane(
            trace_reduce.find_xplane(self.run.trace_dir)
        )
        if raw["device"]:
            self.run.summary = trace_reduce.reduce_events(raw)
