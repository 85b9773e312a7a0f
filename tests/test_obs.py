"""Observability subsystem (mpistragglers_jl_tpu/obs).

Contracts under test:

* the registry — get-or-create identity, thread-safe counts, fixed
  log-bucket histograms (edge buckets, concurrent get-or-create,
  +Inf round-trips), and a Prometheus text exposition that parses
  LINE BY LINE (a scrape either reads every line or the export is
  broken);
* the unified timeline — a serving-scheduler run and a pool asyncmap
  loop merge into ONE Chrome trace-event JSON (valid JSON, non-negative
  span durations, worker/coordinator AND scheduler-tick tracks) with
  the summary()'s waitall-drain accounting alongside;
* the opt-in contract — a dark scheduler allocates no registry objects
  and its tick path's residual guard cost is bounded far below the 5%
  budget (the no-op fast path the tracer established for the pool,
  extended to every instrumented layer);
* the live telemetry plane — cross-process aggregation (worker-local
  registries piggybacked on result frames, counter-delta semantics
  across respawns, clock-aligned spans), the flight recorder's bounded
  postmortem ring + watchdog, and the HTTP exporter's /metrics,
  /healthz, /trace, /flight round-trips against a real straggling
  ProcessBackend pool and an instrumented scheduler.
"""

import json
import math
import re
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from mpistragglers_jl_tpu import AsyncPool, LocalBackend, asyncmap, waitall
from mpistragglers_jl_tpu.backends.base import DeadWorkerError
from mpistragglers_jl_tpu.backends.process import ProcessBackend
from mpistragglers_jl_tpu.obs import (
    DEFAULT_BUCKETS,
    FlightRecorder,
    MetricsRegistry,
    ObsServer,
    SpanRecorder,
    TelemetryAggregator,
    WorkerTelemetry,
    annotate,
    dump_merged_chrome_trace,
)
from mpistragglers_jl_tpu.utils import (
    EpochTracer,
    HedgedServer,
    PoolLatencyModel,
    faults,
)


def echo_work(i, payload, epoch):
    return payload * (i + 1)


def _get(url, timeout=10.0):
    """(status, body bytes) for a GET, 4xx/5xx included."""
    try:
        with urllib.request.urlopen(url, timeout=timeout) as r:
            return r.status, r.read()
    except urllib.error.HTTPError as e:
        return e.code, e.read()


class PerWorkerDelay:
    """Picklable per-worker delay (spawned process workers need a
    module-level class; faults.per_worker closes over a lambda)."""

    def __init__(self, delays):
        self.delays = list(delays)

    def __call__(self, i, epoch):
        return self.delays[i]


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------


class TestRegistry:
    def test_get_or_create_identity_and_kinds(self):
        reg = MetricsRegistry()
        c = reg.counter("a_total", help="h")
        assert reg.counter("a_total") is c
        assert reg.counter("a_total", route="x") is not c
        with pytest.raises(ValueError):
            reg.gauge("a_total")
        with pytest.raises(ValueError):
            reg.counter("bad name!")
        c.inc()
        c.inc(2.5)
        with pytest.raises(ValueError):
            c.inc(-1)
        assert c.value == 3.5
        # names are exactly the Prometheus grammar: a wider registry
        # grammar would need a lossy export mapping under which two
        # families ("a.b", "a_b") collide into one invalid exposition
        with pytest.raises(ValueError):
            reg.counter("a.b")
        with pytest.raises(ValueError):
            reg.counter("1ab")
        with pytest.raises(ValueError):
            reg.counter("latência_total")  # unicode isalnum trap

    def test_gauge_and_histogram(self):
        reg = MetricsRegistry()
        g = reg.gauge("depth")
        g.set(4)
        g.inc()
        g.dec(2)
        assert g.value == 3
        h = reg.histogram("lat_seconds")
        assert h.bounds == DEFAULT_BUCKETS
        for v in (1e-5, 2e-3, 2e-3, 0.5, 1e9):
            h.observe(v)
        assert h.count == 5 and h.sum == pytest.approx(1e9 + 0.504012)
        assert h.quantile(0.5) <= h.quantile(0.95)
        assert h.quantile(1.0) == math.inf  # overflow bucket
        assert reg.histogram("empty").quantile(0.5) is None
        with pytest.raises(ValueError):
            reg.histogram("bad", buckets=(2.0, 1.0))
        # re-registration: same grid (or None = don't care) returns the
        # instrument, a conflicting grid raises instead of silently
        # routing out-of-range observes into +Inf
        w = reg.histogram("width", buckets=(1.0, 2.0, 4.0))
        assert reg.histogram("width") is w
        assert reg.histogram("width", buckets=(1, 2, 4)) is w
        with pytest.raises(ValueError):
            reg.histogram("width", buckets=(1.0, 2.0))
        # the grid is per FAMILY: a new labeled series inherits it
        # (disjoint le sets would misaggregate sum-by-le quantiles),
        # and a conflicting grid on any series of the family raises
        w2 = reg.histogram("width", worker="1")
        assert w2.bounds == (1.0, 2.0, 4.0)
        with pytest.raises(ValueError):
            reg.histogram("width", worker="2", buckets=(8.0,))

    def test_label_names_validated(self):
        reg = MetricsRegistry()
        with pytest.raises(ValueError):
            reg.counter("a_total", **{"região": "eu"})  # unicode kwarg
        with pytest.raises(ValueError):
            reg.counter("a_total", __reserved="x")
        with pytest.raises(ValueError):
            reg.histogram("h_seconds", le="0.1")  # bucket-label clash
        reg.gauge("g", le="ok")  # reserved only where it collides

    def test_thread_safety_exact_counts(self):
        """Writers off the coordinator thread (the native transport's
        harvest thread case) must not lose increments."""
        reg = MetricsRegistry()
        c = reg.counter("c_total")
        h = reg.histogram("h_seconds")

        def w():
            for _ in range(5000):
                c.inc()
                h.observe(0.001)

        ts = [threading.Thread(target=w) for _ in range(8)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        assert c.value == 40000
        assert h.count == 40000

    def test_prometheus_parses_line_by_line(self):
        reg = MetricsRegistry()
        reg.counter("serving_tokens_total", help="tokens").inc(7)
        reg.counter("route_total", route="kernel").inc()
        reg.counter("route_total", route="einsum").inc(3)
        reg.gauge("queue_depth").set(2)
        h = reg.histogram("ttft_seconds")
        h.observe(0.01)
        h.observe(3.0)
        text = reg.to_prometheus()
        line_re = re.compile(
            r"^(# (HELP|TYPE) [a-zA-Z_:][a-zA-Z0-9_:]*( .*)?"
            r"|[a-zA-Z_:][a-zA-Z0-9_:]*"
            r"(\{[a-zA-Z_][a-zA-Z0-9_]*=\"[^\"]*\""
            r"(,[a-zA-Z_][a-zA-Z0-9_]*=\"[^\"]*\")*\})?"
            r" (-?[0-9]+(\.[0-9]+)?([eE][+-]?[0-9]+)?|\+Inf|-Inf))$"
        )
        lines = text.splitlines()
        assert lines, "empty exposition"
        for line in lines:
            assert line_re.match(line), f"unparseable line: {line!r}"
        # histogram expansion: cumulative buckets end at count
        bucket = [ln for ln in lines if ln.startswith("ttft_seconds_bucket")]
        assert bucket[-1].startswith('ttft_seconds_bucket{le="+Inf"}')
        assert bucket[-1].endswith(" 2")
        assert "ttft_seconds_count 2" in lines
        # both labeled series of one family export under one TYPE
        assert sum(1 for ln in lines if ln.startswith("# TYPE route_total")) == 1

    def test_prometheus_help_escaping_roundtrips(self):
        """Exposition 0.0.4 conformance (round 22): HELP text escapes
        backslash as ``\\\\`` and newline as ``\\n`` — byte-exact
        round-trip through the spec's unescaping, not the old
        newline->space flattening. Label values were already
        conformant; pinned here beside the HELP arm."""
        reg = MetricsRegistry()
        help_text = 'rate in req\\s\nsecond line with "quotes"'
        reg.counter("tricky_total", help=help_text).inc()
        reg.counter(
            "labeled_total", path='a\\b\n"c"'
        ).inc()
        text = reg.to_prometheus()
        lines = text.splitlines()
        # every line is still single-line (no raw newline leaked)
        help_line = next(
            ln for ln in lines if ln.startswith("# HELP tricky_total")
        )
        escaped = help_line[len("# HELP tricky_total "):]
        assert "\n" not in escaped
        assert escaped == (
            'rate in req\\\\s\\nsecond line with "quotes"'
        )

        # the spec's unescaping recovers the original exactly
        def unescape_help(s):
            out, i = [], 0
            while i < len(s):
                if s[i] == "\\" and i + 1 < len(s):
                    out.append(
                        {"\\": "\\", "n": "\n"}[s[i + 1]]
                    )
                    i += 2
                else:
                    out.append(s[i])
                    i += 1
            return "".join(out)

        assert unescape_help(escaped) == help_text
        # label values: backslash, quote, and newline all escaped
        sample = next(
            ln for ln in lines if ln.startswith("labeled_total{")
        )
        assert 'path="a\\\\b\\n\\"c\\""' in sample
        assert "\n" not in sample

    def test_json_snapshot_roundtrips(self):
        reg = MetricsRegistry()
        reg.counter("a_total").inc(2)
        reg.histogram("b_seconds").observe(0.1)
        snap = json.loads(reg.to_json())
        assert snap["a_total"]["series"][0]["value"] == 2
        hist = snap["b_seconds"]["series"][0]["value"]
        assert hist["count"] == 1 and hist["p50"] > 0


# ---------------------------------------------------------------------------
# tracer summary: waitall drains no longer vanish
# ---------------------------------------------------------------------------


class TestSummaryWaitall:
    def test_waitall_drains_counted(self):
        """A straggler whose results only ever land in waitall used to
        vanish: dispatched but never counted as an arrival. Now every
        dispatch is accounted (delivered_rate == 1 after a full
        drain) and the drain shows up in n_waitall_arrivals."""
        backend = LocalBackend(
            echo_work, 3, delay_fn=faults.per_worker([0.002, 0.002, 0.08])
        )
        tracer = EpochTracer()
        try:
            pool = AsyncPool(3)
            for _ in range(3):
                asyncmap(pool, np.zeros(1), backend, nwait=2,
                         tracer=tracer)
                waitall(pool, backend, tracer=tracer)
        finally:
            backend.shutdown()
        s = tracer.summary()
        arrivals = s["n_fresh"] + s["n_stale"]
        assert s["n_dispatched"] == arrivals == 9
        assert s["delivered_rate"] == 1.0
        assert s["n_waitall_arrivals"] >= 3  # the straggler's drains
        # straggler_rate keeps its asyncmap-only meaning: worker 2
        # never made the nwait=2 cut inside its own epoch
        assert s["straggler_rate"] == pytest.approx(1 / 3)

    def test_waitall_only_trace_still_accounts(self):
        """A tracer attached only to a shutdown drain (the
        CodedGradTrainer.fit pattern with an untraced loop) must not
        collapse to a bare {'epochs': 0} — the drains ARE the data."""
        backend = LocalBackend(echo_work, 2)
        tracer = EpochTracer()
        try:
            pool = AsyncPool(2)
            asyncmap(pool, np.zeros(1), backend, nwait=0)  # untraced
            waitall(pool, backend, tracer=tracer)
        finally:
            backend.shutdown()
        s = tracer.summary()
        assert s["epochs"] == 0 and s["wall_mean_s"] is None
        assert s["n_waitall_arrivals"] == 2
        assert s["n_fresh"] + s["n_stale"] == 2
        assert s["straggler_rate"] == 0.0  # no in-trace dispatches

    def test_asyncmap_only_run_unchanged(self):
        backend = LocalBackend(echo_work, 2)
        tracer = EpochTracer()
        try:
            pool = AsyncPool(2)
            for _ in range(4):
                asyncmap(pool, np.zeros(1), backend, nwait=2,
                         tracer=tracer)
        finally:
            backend.shutdown()
        s = tracer.summary()
        assert s["epochs"] == 4
        assert s["n_waitall_arrivals"] == 0
        assert s["straggler_rate"] == 0.0
        assert s["delivered_rate"] == 1.0


# ---------------------------------------------------------------------------
# hedge / latency-model registry export
# ---------------------------------------------------------------------------


class TestRegistryExports:
    def test_hedge_metrics(self):
        reg = MetricsRegistry()
        backend = LocalBackend(
            echo_work, 4,
            delay_fn=faults.per_worker([0.001, 0.001, 0.001, 0.001]),
        )
        srv = HedgedServer(backend, registry=reg)
        try:
            for _ in range(5):
                srv.request(np.ones(2), hedge=2)
            srv.drain()
        finally:
            backend.shutdown()
        assert reg.counter("hedge_requests_total").value == 5
        assert reg.counter("hedge_dispatches_total").value >= 5
        assert reg.histogram("hedge_width").count == 5
        assert reg.histogram("hedge_winner_latency_seconds").count == 5
        wins = sum(
            reg.counter("hedge_wins_total", rank=str(r)).value
            for r in range(4)
        )
        assert wins == 5
        assert "hedge_width_bucket" in reg.to_prometheus()

    def test_latency_model_publish(self):
        reg = MetricsRegistry()
        model = PoolLatencyModel(2)
        for _ in range(6):
            model.observe(0, 0.01)
            model.observe(1, 0.05)
        model.publish(reg)
        m0 = reg.gauge("pool_worker_latency_mean_seconds", worker="0")
        m1 = reg.gauge("pool_worker_latency_mean_seconds", worker="1")
        assert m0.value == pytest.approx(0.01)
        assert m1.value == pytest.approx(0.05)
        assert reg.gauge(
            "pool_worker_latency_samples", worker="1"
        ).value == 6
        # re-publish overwrites, never duplicates series
        n = len(reg)
        model.observe(0, 0.02)
        model.publish(reg)
        assert len(reg) == n


# ---------------------------------------------------------------------------
# merged timeline: scheduler + pool in one trace
# ---------------------------------------------------------------------------


def _pool_traced_run():
    backend = LocalBackend(
        echo_work, 3, delay_fn=faults.per_worker([0.03, 0.002, 0.002])
    )
    tracer = EpochTracer()
    try:
        pool = AsyncPool(3)
        for _ in range(3):
            asyncmap(pool, np.zeros(1), backend, nwait=2, tracer=tracer)
        waitall(pool, backend, tracer=tracer)
    finally:
        backend.shutdown()
    return tracer


class TestMergedTimeline:
    def test_span_recorder_chrome_shape(self, tmp_path):
        rec = SpanRecorder("demo")
        with rec.span("outer", track="t", x=1):
            time.sleep(0.002)
        rec.add("retro", 1.0, 0.5, track="t")
        rec.add("clamped", 1.0, -0.5, track="t")  # clock hiccup
        rec.count("depth", 3)
        path = tmp_path / "one.json"
        n = rec.dump_chrome_trace(path)
        doc = json.loads(path.read_text())
        evs = doc["traceEvents"]
        assert n == 4
        assert all(e["dur"] >= 0 for e in evs if e["ph"] == "X")
        assert any(
            e["ph"] == "M" and e["name"] == "process_name"
            and e["args"]["name"] == "demo"
            for e in evs
        )
        assert any(e["ph"] == "C" for e in evs)

    def test_pool_and_recorder_merge(self, tmp_path):
        """A pool tracer and a host recorder land in one valid trace
        under distinct pids, every span non-negative, pool worker /
        coordinator track metadata intact."""
        tracer = _pool_traced_run()
        rec = SpanRecorder("train")
        with rec.span("step 1", track="train"):
            time.sleep(0.001)
        path = tmp_path / "merged.json"
        n = dump_merged_chrome_trace(
            path, tracers=[tracer], recorders=[rec]
        )
        doc = json.loads(path.read_text())
        evs = doc["traceEvents"]
        spans = [e for e in evs if e["ph"] == "X"]
        assert len(spans) == n
        assert all(e["dur"] >= 0 and e["ts"] >= 0 for e in spans)
        names = {
            (e["pid"], e["args"]["name"])
            for e in evs
            if e["ph"] == "M" and e["name"] == "thread_name"
        }
        assert (0, "coordinator") in names
        assert any(nm.startswith("worker") for p, nm in names if p == 0)
        procs = {
            e["pid"]: e["args"]["name"]
            for e in evs
            if e["ph"] == "M" and e["name"] == "process_name"
        }
        assert procs == {0: "pool", 1: "train"}
        # both sources contributed spans, on their own processes
        assert {e["pid"] for e in spans} == {0, 1}

    def test_recorder_cap_is_visible_not_silent(self, tmp_path):
        """A long-lived writer hits max_events: new events drop, the
        drop is counted and surfaces as a truncation marker in the
        exported trace (never a silent end-of-run)."""
        rec = SpanRecorder("s", max_events=3)
        for i in range(5):
            rec.add(f"e{i}", float(i), 0.5)
        rec.count("q", 1)
        assert len(rec) == 3 and rec.dropped == 3
        assert "3 dropped" in repr(rec)
        path = tmp_path / "capped.json"
        rec.dump_chrome_trace(path)
        doc = json.loads(path.read_text())
        assert any(
            e["ph"] == "I" and "3 events dropped" in e["name"]
            for e in doc["traceEvents"]
        )

    def test_annotate_is_safe_everywhere(self):
        with annotate("anything"):
            pass


# ---------------------------------------------------------------------------
# serving scheduler instrumentation (jax; tiny config)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def tiny_serving():
    from mpistragglers_jl_tpu.models.transformer import (
        TransformerConfig,
        init_params,
    )

    cfg = TransformerConfig(
        vocab=37, d_model=32, n_heads=4, n_kv_heads=2, n_layers=2,
        d_ff=64, attn_window=6,
    )
    return cfg, init_params(cfg, seed=3)


def _sched(cfg, params, **kw):
    from mpistragglers_jl_tpu.models.serving import ServingScheduler

    return ServingScheduler(
        params, cfg, slots=2, n_inner=4, prompt_chunk=8, max_prompt=32,
        page_tokens=3, **kw,
    )


class TestServingObservability:
    def test_instrumented_run_exports_everything(
        self, tiny_serving, tmp_path
    ):
        """The acceptance run: >= 3 requests submit->retire through an
        instrumented scheduler + one traced pool loop -> ONE merged
        Chrome trace with scheduler-tick and pool-worker tracks, and a
        Prometheus dump carrying queue depth, tokens/s, the TTFT
        histogram, and kernel-route counters."""
        cfg, params = tiny_serving
        reg = MetricsRegistry()
        rec = SpanRecorder("serving")
        sched = _sched(cfg, params, registry=reg, spans=rec)
        rng = np.random.default_rng(0)
        reqs = [
            sched.submit(rng.integers(1, cfg.vocab, size=p), max_new=m)
            for p, m in [(5, 6), (11, 4), (3, 8), (7, 5)]
        ]
        sched.run()
        assert all(r.finished for r in reqs)

        # series
        assert reg.counter("serving_ticks_total").value >= 2
        # the counter records DELIVERED tokens (the EOS-clamped tail
        # the retirement trim strips is never counted), so after full
        # drain it equals the streams exactly
        assert reg.counter("serving_tokens_total").value == sum(
            len(r.tokens) for r in reqs
        )
        # the per-tick span token counts cover the same population
        # (admission first-tokens included), so they cross-check
        assert sum(
            args["tokens"] for _, name, _, _, args in rec.spans
            if name.startswith("tick ")
        ) == reg.counter("serving_tokens_total").value
        assert reg.histogram("serving_ttft_seconds").count == len(reqs)
        assert reg.histogram("serving_intertoken_seconds").count > 0
        assert reg.counter("serving_admitted_total").value == len(reqs)
        assert (
            reg.counter("serving_retired_total", reason="length").value
            == len(reqs)
        )
        assert reg.counter("serving_prefill_chunks_total").value >= 5
        prom = reg.to_prometheus()
        for want in (
            "serving_queue_depth",
            "serving_tokens_per_s",
            "serving_ttft_seconds_bucket",
            "serving_kernel_route_total",
        ):
            assert want in prom, want

        # merged timeline with a pool run
        tracer = _pool_traced_run()
        path = tmp_path / "unified.json"
        dump_merged_chrome_trace(
            path, tracers=[tracer], recorders=[rec]
        )
        doc = json.loads(path.read_text())
        evs = doc["traceEvents"]
        spans = [e for e in evs if e["ph"] == "X"]
        assert all(e["dur"] >= 0 for e in spans)
        names = [e["name"] for e in spans]
        assert any(n.startswith("tick ") for n in names)
        assert any(n.startswith("asyncmap") for n in names)
        assert {"admit", "decode", "retire"} <= set(names)
        threads = {
            e["args"]["name"]
            for e in evs
            if e["ph"] == "M" and e["name"] == "thread_name"
        }
        assert "scheduler" in threads and "coordinator" in threads

    def test_greedy_stream_unchanged_by_instrumentation(
        self, tiny_serving
    ):
        cfg, params = tiny_serving
        rng = np.random.default_rng(5)
        p = rng.integers(1, cfg.vocab, size=9)
        dark = _sched(cfg, params)
        r1 = dark.submit(p, max_new=7)
        dark.run()
        lit = _sched(
            cfg, params, registry=MetricsRegistry(),
            spans=SpanRecorder(),
        )
        r2 = lit.submit(p, max_new=7)
        lit.run()
        assert r1.tokens == r2.tokens

    def test_dark_tick_does_no_observability_work(
        self, tiny_serving, monkeypatch
    ):
        """With nothing attached the tick path must allocate no
        registry objects and read no clocks: every metric constructor
        AND the serving module's perf_counter are boobytrapped, then a
        full submit->retire run executes."""
        from mpistragglers_jl_tpu.obs import metrics as m
        from mpistragglers_jl_tpu.models import serving

        def boom(*a, **k):
            raise AssertionError(
                "dark scheduler touched the observability layer"
            )

        for cls in (m.Counter, m.Gauge, m.Histogram, m.MetricsRegistry):
            monkeypatch.setattr(cls, "__init__", boom)

        class NoClock:
            perf_counter = staticmethod(boom)

            def __getattr__(self, name):  # anything else: real time
                return getattr(time, name)

        monkeypatch.setattr(serving, "time", NoClock())
        cfg, params = tiny_serving
        sched = _sched(cfg, params)
        r = sched.submit(np.arange(1, 6, dtype=np.int32), max_new=6)
        sched.run()
        assert r.finished and len(r.tokens) == 6

    def test_noop_overhead_under_budget(self, tiny_serving):
        """The no-op fast-path benchmark (acceptance: instrumentation
        disabled costs <= 5% of a scheduler tick). The dark tick's
        entire observability residue is a handful of ``obs is not
        None`` guards (the raising-clock test above proves nothing
        else runs); measure that guard bundle directly against a
        measured decode tick — nanoseconds vs milliseconds, so the
        bound holds with orders of magnitude to spare and no timing
        flake."""
        cfg, params = tiny_serving
        sched = _sched(cfg, params)
        sched.submit(np.arange(1, 4, dtype=np.int32), max_new=10_000)
        sched.step()  # admits + compiles the decode scan
        t0 = time.perf_counter()
        for _ in range(5):
            sched.step()
        tick_s = (time.perf_counter() - t0) / 5

        def guards(s):
            # the exact per-tick residue: the obs-None checks step()
            # and its admission/first-token/prefill hooks perform
            obs = s._obs
            if obs is not None:
                pass
            if s._obs is not None:
                pass
            if s._obs is not None:
                pass
            if obs is not None:
                pass
            if obs is not None:
                pass
            return obs

        reps = 20_000
        t0 = time.perf_counter()
        for _ in range(reps):
            guards(sched)
        guard_s = (time.perf_counter() - t0) / reps
        assert guard_s <= 0.05 * tick_s, (
            f"disabled-path guards cost {guard_s * 1e6:.2f} µs vs tick "
            f"{tick_s * 1e3:.2f} ms — no-op fast path regressed"
        )


# ---------------------------------------------------------------------------
# coded training instrumentation
# ---------------------------------------------------------------------------


class TestCodedTrainObservability:
    def test_step_metrics_and_tracer_bridge(self):
        import jax.numpy as jnp

        from mpistragglers_jl_tpu.models.coded_train import (
            CodedGradTrainer,
        )

        def loss(params, batch):
            x, y = batch
            return jnp.mean((x @ params["w"] - y) ** 2)

        rng = np.random.default_rng(0)
        chunks = [
            (
                jnp.asarray(rng.standard_normal((4, 3)), jnp.float32),
                jnp.asarray(rng.standard_normal((4,)), jnp.float32),
            )
            for _ in range(6)
        ]
        reg = MetricsRegistry()
        rec = SpanRecorder("train")
        tracer = EpochTracer()
        tr = CodedGradTrainer(
            loss,
            {"w": jnp.zeros((3,), jnp.float32)},
            lambda j: chunks[j],
            n_workers=6,
            s=2,
            tracer=tracer,
            registry=reg,
            spans=rec,
        )
        params, hist = tr.fit(epochs=3, lr=0.1, eval_every=None)
        assert reg.counter("train_steps_total").value == 3
        assert reg.histogram("train_step_seconds").count == 3
        assert reg.gauge("train_decode_fresh_k").value >= 4
        recovered = sum(
            reg.counter(
                "train_worker_recovered_total", worker=str(i)
            ).value
            for i in range(6)
        )
        assert recovered == 3 * 4  # k = n - s shards per step
        assert len(tracer.records) >= 3
        assert len(rec.spans) == 3
        assert all(nm.startswith("coded step") for _, nm, *_ in rec.spans)
        assert tr.last_fresh.size >= 4
        tr.backend.shutdown()


# ---------------------------------------------------------------------------
# histogram edge cases (fixed log grid)
# ---------------------------------------------------------------------------


class TestHistogramEdges:
    def test_extreme_values_land_in_edge_buckets(self):
        """Below the first bound -> first bucket; above the last ->
        the +Inf overflow bucket; neither is dropped or misfiled."""
        reg = MetricsRegistry()
        h = reg.histogram("edge_seconds")
        lo, hi = DEFAULT_BUCKETS[0], DEFAULT_BUCKETS[-1]
        h.observe(lo / 1e3)     # far below the first bound
        h.observe(0.0)          # degenerate zero
        h.observe(hi * 1e3)     # far above the last bound
        counts = h.bucket_counts()
        assert counts[0] == 2           # both sub-bound values
        assert counts[-1] == 1          # the overflow
        assert h.count == 3
        assert h.quantile(0.5) == lo    # covered by the first bucket
        assert h.quantile(1.0) == math.inf
        # exact-bound values are cumulative-<= (le semantics)
        h.observe(lo)
        assert h.bucket_counts()[0] == 3

    def test_concurrent_get_or_create_same_labeled_series(self):
        """Eight threads racing get-or-create of ONE labeled series
        must all receive the same instrument and lose no increments
        (the registry's lock covers creation; the instrument's lock
        covers counts)."""
        reg = MetricsRegistry()
        got = []

        def w():
            for _ in range(1000):
                c = reg.counter("race_total", worker="7")
                c.inc()
            got.append(reg.counter("race_total", worker="7"))

        ts = [threading.Thread(target=w) for _ in range(8)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        assert len(reg) == 1
        assert all(g is got[0] for g in got)
        assert got[0].value == 8000

    def test_prometheus_inf_roundtrip_and_le_cumulativity(self):
        """The exposition's bucket lines are CUMULATIVE, ordered by
        ``le``, end at the ``+Inf`` bucket, and ``+Inf`` == ``_count``
        — including when samples land below the first and above the
        last bound; every ``le`` value (incl. +Inf) parses back to the
        float grid."""
        reg = MetricsRegistry()
        h = reg.histogram("rt_seconds")
        for v in (1e-9, 2e-3, 0.5, 1e9, 1e9):
            h.observe(v)
        lines = reg.to_prometheus().splitlines()
        brx = re.compile(r'rt_seconds_bucket\{le="([^"]+)"\} (\d+)')
        buckets = [
            (m.group(1), int(m.group(2)))
            for m in map(brx.match, lines) if m
        ]
        assert len(buckets) == len(DEFAULT_BUCKETS) + 1
        les = [float(le) for le, _ in buckets]   # "+Inf" -> inf
        assert les == sorted(les) and les[-1] == math.inf
        assert les[:-1] == [pytest.approx(b) for b in DEFAULT_BUCKETS]
        cums = [c for _, c in buckets]
        assert cums == sorted(cums)              # cumulative
        assert cums[0] == 1                      # the below-first value
        assert cums[-1] == 5 == h.count          # +Inf == _count
        assert cums[-2] == 3                     # the two overflows
        assert "rt_seconds_count 5" in lines

    def test_merge_deltas_validation(self):
        """Cross-process merge rejects grid mismatches and negative
        deltas (a shrinking histogram is an upstream protocol bug)."""
        reg = MetricsRegistry()
        h = reg.histogram("m_seconds")
        n = len(DEFAULT_BUCKETS) + 1
        h.merge_deltas([1] * n, 2.5, n)
        assert h.count == n and h.sum == 2.5
        with pytest.raises(ValueError, match="grid"):
            h.merge_deltas([1] * (n - 1), 0.0, 1)
        with pytest.raises(ValueError, match=">= 0"):
            h.merge_deltas([-1] + [0] * (n - 1), 0.0, 0)


# ---------------------------------------------------------------------------
# flight recorder: the bounded postmortem ring
# ---------------------------------------------------------------------------


class TestFlightRecorder:
    def test_ring_keeps_recent_and_marks_eviction(self, tmp_path):
        fr = FlightRecorder(capacity=4)
        for i in range(9):
            fr.span(f"s{i}", float(i), 0.5)
        assert len(fr) == 4 and fr.evicted == 5
        doc = fr.dump(tmp_path / "f.json")
        names = [
            e["name"] for e in doc["traceEvents"] if e.get("ph") == "X"
        ]
        assert names == ["s5", "s6", "s7", "s8"]  # the RECENT past
        assert any(
            "5 older entries evicted" in e["name"]
            for e in doc["traceEvents"] if e.get("ph") == "I"
        )
        # the file round-trips as the same valid JSON
        assert json.loads((tmp_path / "f.json").read_text()) == doc

    def test_counter_records_deltas(self):
        fr = FlightRecorder()
        fr.counter("tok_total", 10)
        fr.counter("tok_total", 25)
        fr.counter("tok_total", 25)
        evs = [
            e for e in fr.snapshot()["traceEvents"]
            if e.get("ph") == "C"
        ]
        assert [e["args"]["delta"] for e in evs] == [10, 15, 0]
        assert [e["args"]["tok_total"] for e in evs] == [10, 25, 25]

    def test_one_pid_per_src(self):
        fr = FlightRecorder()
        fr.span("a", 0.0, 1.0, src="coordinator")
        fr.span("b", 0.0, 1.0, src="worker 0")
        fr.span("c", 0.5, 1.0, src="worker 1")
        doc = fr.snapshot()
        procs = {
            e["args"]["name"]: e["pid"]
            for e in doc["traceEvents"]
            if e.get("ph") == "M" and e["name"] == "process_name"
        }
        assert set(procs) == {"coordinator", "worker 0", "worker 1"}
        assert len(set(procs.values())) == 3

    def test_trip_dumps_to_armed_path(self, tmp_path):
        path = tmp_path / "trip.json"
        fr = FlightRecorder().arm(str(path))
        fr.span("work", 0.0, 1.0)
        fr.trip("pool wait past deadline")
        assert path.exists() and fr.dumps == [str(path)]
        doc = json.loads(path.read_text())
        assert any(
            "pool wait past deadline" in e["name"]
            for e in doc["traceEvents"] if e.get("ph") == "I"
        )

    def test_watchdog_fires_once_per_stall_episode(self, tmp_path):
        fr = FlightRecorder()
        stamp = [time.perf_counter()]
        wd = fr.watchdog(
            "probe", lambda: stamp[0], stall_s=0.1,
            path=str(tmp_path / "wd.json"),
        )
        try:
            deadline = time.perf_counter() + 5.0
            while wd.fired == 0 and time.perf_counter() < deadline:
                time.sleep(0.02)
            assert wd.fired == 1
            time.sleep(0.3)           # still stalled: must NOT re-fire
            assert wd.fired == 1
            # activity resumes; wait until a poll OBSERVED it (re-arm
            # is the poll thread's doing, so loop instead of sleeping
            # a fixed margin a loaded box could miss)
            deadline = time.perf_counter() + 5.0
            while not wd._armed and time.perf_counter() < deadline:
                stamp[0] = time.perf_counter()
                time.sleep(0.02)
            assert wd._armed
            stamp[0] -= 10.0                 # ...then stalls again
            deadline = time.perf_counter() + 5.0
            while wd.fired == 1 and time.perf_counter() < deadline:
                stamp[0] = time.perf_counter() - 10.0  # stay stalled
                time.sleep(0.02)
            assert wd.fired == 2
        finally:
            fr.close()
        assert (tmp_path / "wd.json").exists()

    def test_pool_deadline_expiry_trips_flight(self, tmp_path):
        """asyncmap with flight= attached: a wait past the deadline
        dumps the ring BEFORE DeadWorkerError propagates — the hang
        artifact exists even though nothing after the raise runs."""
        path = tmp_path / "deadline.json"
        fr = FlightRecorder().arm(str(path))
        backend = LocalBackend(
            echo_work, 2, delay_fn=faults.per_worker([0.5, 0.5])
        )
        try:
            pool = AsyncPool(2)
            with pytest.raises(DeadWorkerError):
                asyncmap(pool, np.ones(2), backend, nwait=2,
                         timeout=0.05, flight=fr)
            assert path.exists()
            doc = json.loads(path.read_text())
            assert any(
                "wait past deadline" in e["name"]
                for e in doc["traceEvents"] if e.get("ph") == "I"
            )
            # the pool stays usable: drain the tardy workers
            waitall(pool, backend, flight=fr)
        finally:
            backend.shutdown()
        names = [
            e["name"] for e in fr.snapshot()["traceEvents"]
            if e.get("ph") == "X"
        ]
        assert any(n.startswith("asyncmap") for n in names)
        assert any(n.startswith("waitall") for n in names)


# ---------------------------------------------------------------------------
# cross-process aggregation (unit level)
# ---------------------------------------------------------------------------


class TestAggregation:
    def test_counter_deltas_across_respawns(self):
        """Counters stay monotonic across worker restarts: same-boot
        frames add deltas, a new boot's full value adds on top (never
        double-counted, never reset)."""
        reg = MetricsRegistry()
        agg = TelemetryAggregator(reg)
        w = WorkerTelemetry(3)
        w.registry.counter("worker_tasks_total").inc(3)
        agg.merge(3, w.snapshot())
        w.registry.counter("worker_tasks_total").inc(2)
        agg.merge(3, w.snapshot())      # cumulative 5 -> delta 2
        merged = reg.counter("worker_tasks_total", worker="3")
        assert merged.value == 5
        w2 = WorkerTelemetry(3)         # the respawn: fresh boot id
        assert w2.boot != w.boot
        w2.registry.counter("worker_tasks_total").inc(4)
        agg.merge(3, w2.snapshot())
        assert merged.value == 9        # 5 + 4, not 4, not 5
        # replayed cumulative value adds nothing
        agg.merge(3, w2.snapshot())
        assert merged.value == 9

    def test_histogram_merges_bucketwise_without_double_count(self):
        reg = MetricsRegistry()
        agg = TelemetryAggregator(reg)
        w = WorkerTelemetry(0)
        for v in (1e-4, 2e-3, 0.3):
            w.registry.histogram("worker_task_seconds").observe(v)
        agg.merge(0, w.snapshot())
        agg.merge(0, w.snapshot())      # same cumulative state: no-op
        h = reg.histogram("worker_task_seconds", worker="0")
        assert h.count == 3
        assert h.sum == pytest.approx(0.3021)
        w.registry.histogram("worker_task_seconds").observe(0.5)
        agg.merge(0, w.snapshot())
        assert h.count == 4

    def test_clock_offset_translates_worker_spans(self):
        """A worker whose clock runs 5 s ahead: the min-delay offset
        estimate recovers the skew and its spans land on the
        coordinator axis in the merged recorder."""
        reg = MetricsRegistry()
        agg = TelemetryAggregator(reg)
        skew = 5.0
        w = WorkerTelemetry(1)
        # coordinator dispatches at t=10 (its clock)
        agg.note_dispatch(1, seq=7, t=10.0)
        # worker: receives at 15.001, computes, sends at 15.021
        w.span("task e1", 15.002, 0.018)
        frame = w.snapshot(pair=(7, 10.001 + skew, 10.021 + skew))
        # coordinator receives at 10.022
        agg.merge(1, frame, t_recv_c=10.022)
        off = agg.clock_offset(1)
        assert off == pytest.approx(skew, abs=2e-3)
        (rec,) = agg.recorders()
        assert rec.process == "worker 1"
        (span,) = rec.spans
        _, name, t0, dur, _ = span
        assert name == "task e1"
        assert t0 == pytest.approx(10.002, abs=5e-3)  # coord axis
        assert dur == pytest.approx(0.018)
        # a respawn kills the offset with the incarnation, even when
        # the new boot's FIRST frame carries no pair sample (e.g. a
        # drain frame): reusing the dead clock's offset would scatter
        # the new process's spans far off-axis (review finding)
        w2 = WorkerTelemetry(1)
        agg.merge(1, w2.snapshot())
        assert agg.clock_offset(1) is None

    def test_malformed_frames_are_dropped(self):
        agg = TelemetryAggregator(MetricsRegistry())
        agg.merge(0, {"v": 999})        # wrong version
        agg.merge(0, "not a dict")
        agg.merge(0, {"v": 1, "boot": "b", "spans": [("bad",)]})
        assert agg.frames_merged == 1   # only the version-1 frame
        assert agg.recorders() == []


# ---------------------------------------------------------------------------
# the live telemetry plane: HTTP round-trips against real processes
# ---------------------------------------------------------------------------


class TestLiveTelemetryPlane:
    def test_live_scrape_roundtrip(self, tiny_serving, tmp_path):
        """The acceptance run, all on CPU: an ObsServer on port 0 over
        an instrumented ServingScheduler + a straggling ProcessBackend
        pool. /metrics mid-run carries worker-labeled series that
        ORIGINATED in the worker processes (cross-process aggregation);
        /healthz flips 503 when a worker process is killed and recovers
        after respawn; /trace and the watchdog-triggered /flight dump
        load as valid Chrome/Perfetto JSON with one pid per worker
        process."""
        cfg, params = tiny_serving
        reg = MetricsRegistry()
        rec = SpanRecorder("serving")
        fl = FlightRecorder()
        srv = ObsServer(reg, flight=fl).start()
        backend = ProcessBackend(
            echo_work, 3,
            delay_fn=PerWorkerDelay([0.001, 0.001, 0.05]),
            registry=reg, flight=fl, exporter=srv,
        )
        sched = _sched(cfg, params, registry=reg, spans=rec,
                       flight=fl, exporter=srv)
        try:
            assert srv.port != 0  # port 0 bind resolved
            # -- instrumented scheduler serves while the pool loops
            r = sched.submit(
                np.arange(1, 6, dtype=np.int32), max_new=6
            )
            sched.run()
            assert r.finished
            pool = AsyncPool(3)
            for _ in range(4):
                asyncmap(pool, np.ones(4), backend, nwait=2,
                         flight=fl)
            # -- /metrics MID-RUN: the straggler is still grinding its
            # last dispatch, yet the fast workers' frames are merged
            status, body = _get(srv.url + "/metrics")
            assert status == 200
            prom = body.decode()
            by_worker = {
                m.group(1): float(m.group(2))
                for m in re.finditer(
                    r'worker_tasks_total\{worker="(\d)"\} '
                    r'([0-9.]+)', prom
                )
            }
            assert set(by_worker) >= {"0", "1"}  # originated in-process
            assert all(v >= 1 for v in by_worker.values())
            assert "serving_ticks_total" in prom  # coordinator series
            waitall(pool, backend, flight=fl)
            status, body = _get(srv.url + "/metrics")
            by_worker = {
                m.group(1): float(m.group(2))
                for m in re.finditer(
                    r'worker_tasks_total\{worker="(\d)"\} '
                    r'([0-9.]+)', body.decode()
                )
            }
            assert set(by_worker) == {"0", "1", "2"}
            # /metrics.json mirrors the same families
            status, body = _get(srv.url + "/metrics.json")
            assert status == 200
            snap = json.loads(body)
            assert "worker_tasks_total" in snap

            # -- /healthz: healthy -> kill -> 503 -> respawn -> healthy
            status, body = _get(srv.url + "/healthz")
            assert status == 200 and json.loads(body)["ok"]
            backend._procs[1].terminate()
            deadline = time.perf_counter() + 30.0
            while (
                1 not in backend.dead_workers()
                and time.perf_counter() < deadline
            ):
                time.sleep(0.02)
            # assert the waited-for condition itself: a timed-out wait
            # falling through to the healthz assert would fail with a
            # misleading message on a loaded box
            assert 1 in backend.dead_workers(), (
                "worker 1 death not detected within 30s"
            )
            status, body = _get(srv.url + "/healthz")
            doc = json.loads(body)
            assert status == 503 and not doc["ok"]
            assert "1" in doc["checks"]["pool"]["detail"]
            assert doc["checks"]["pool"]["age_s"] >= 0
            backend.respawn(1)
            status, body = _get(srv.url + "/healthz")
            assert status == 200 and json.loads(body)["ok"]
            # the respawned rank computes again (fresh boot id merges
            # without double-counting — TestAggregation pins the math)
            asyncmap(pool, np.ones(4), backend, nwait=3)
            waitall(pool, backend)

            # -- /trace: valid Chrome JSON, one pid per worker process
            status, body = _get(srv.url + "/trace")
            assert status == 200
            trace = json.loads(body)
            procs = {
                e["args"]["name"]: e["pid"]
                for e in trace["traceEvents"]
                if e.get("ph") == "M" and e["name"] == "process_name"
            }
            workers = {n for n in procs if n.startswith("worker ")}
            assert workers == {"worker 0", "worker 1", "worker 2"}
            assert len({procs[n] for n in workers}) == 3  # one pid each
            assert "serving" in procs  # the scheduler's recorder too
            spans = [
                e for e in trace["traceEvents"] if e.get("ph") == "X"
            ]
            assert all(e["dur"] >= 0 for e in spans)
            assert any(
                e["name"].startswith("task e") for e in spans
            )  # spans recorded INSIDE worker processes

            # -- watchdog-triggered /flight dump: the scheduler goes
            # quiet; the liveness probe trips an automatic ring dump
            dump_path = tmp_path / "flight.json"
            wd = fl.watchdog(
                "scheduler", lambda: sched.last_tick_at,
                stall_s=0.15, path=str(dump_path),
            )
            deadline = time.perf_counter() + 30.0
            while (
                wd.fired == 0 and time.perf_counter() < deadline
            ):
                time.sleep(0.02)
            assert wd.fired >= 1, (
                "flight watchdog did not fire within 30s of the "
                "scheduler going quiet"
            )
            fdoc = json.loads(dump_path.read_text())
            fprocs = {
                e["args"]["name"]: e["pid"]
                for e in fdoc["traceEvents"]
                if e.get("ph") == "M" and e["name"] == "process_name"
            }
            fworkers = {
                n for n in fprocs if n.startswith("worker ")
            }
            assert len(fworkers) == 3  # one pid per worker process
            assert len({fprocs[n] for n in fworkers}) == 3
            assert any(
                "watchdog" in e["name"]
                for e in fdoc["traceEvents"] if e.get("ph") == "I"
            )
            # the live endpoint serves the same ring
            status, body = _get(srv.url + "/flight")
            assert status == 200
            assert json.loads(body)["traceEvents"]
        finally:
            fl.close()
            backend.shutdown()
            srv.close()

    def test_exporter_only_scheduler_stamps_tick_liveness(
        self, tiny_serving
    ):
        """A scheduler built with ONLY exporter= (no registry/spans/
        flight) must still stamp last_tick_at — its registered
        /healthz tick-freshness check reads it, and a never-set stamp
        would report an actively-ticking scheduler as stuck forever
        (review finding)."""
        cfg, params = tiny_serving
        srv = ObsServer().start()
        sched = _sched(cfg, params, exporter=srv)
        try:
            sched.submit(np.arange(1, 5, dtype=np.int32), max_new=4)
            sched.step()
            assert sched.last_tick_at is not None
            status, body = _get(srv.url + "/healthz")
            assert status == 200, body
            sched.run()
            status, _ = _get(srv.url + "/healthz")
            assert status == 200
            # same mechanism through the PUBLIC registration API: a
            # dark scheduler registered later must start stamping too
            dark = _sched(cfg, params)
            assert not dark._stamp_ticks
            srv.register_scheduler(dark, name="late")
            dark.submit(np.arange(1, 4, dtype=np.int32), max_new=3)
            dark.step()
            assert dark.last_tick_at is not None
            status, _ = _get(srv.url + "/healthz")
            assert status == 200
        finally:
            srv.close()

    def test_hedge_health_and_unknown_routes(self):
        reg = MetricsRegistry()
        srv = ObsServer(reg).start()
        backend = LocalBackend(echo_work, 2)
        hedge = HedgedServer(backend, registry=reg, exporter=srv)
        try:
            status, body = _get(srv.url + "/healthz")
            assert status == 200
            hedge._dead.add(1)  # bench a replica
            status, body = _get(srv.url + "/healthz")
            doc = json.loads(body)
            assert status == 503
            assert "1" in doc["checks"]["hedge"]["detail"]
            hedge.reset_dead(1)
            status, _ = _get(srv.url + "/healthz")
            assert status == 200
            status, _ = _get(srv.url + "/nope")
            assert status == 404
            status, body = _get(srv.url + "/")
            assert "/metrics" in json.loads(body)["endpoints"]
        finally:
            backend.shutdown()
            srv.close()

    def test_server_without_registry_404s_metrics(self):
        srv = ObsServer().start()
        try:
            status, _ = _get(srv.url + "/metrics")
            assert status == 404
            status, _ = _get(srv.url + "/flight")
            assert status == 404
            # /trace works with zero sources: an empty valid trace
            status, body = _get(srv.url + "/trace")
            assert status == 200
            assert json.loads(body)["traceEvents"] == []
        finally:
            srv.close()
