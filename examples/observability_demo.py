"""Unified runtime observability: one registry, one timeline, live HTTP.

Four layers of the stack run instrumented and land in the SAME
telemetry artifacts:

1. a continuous-batching ``ServingScheduler`` (tiny transformer, CPU)
   serves four requests with a ``MetricsRegistry`` + ``SpanRecorder``
   attached — per-tick admit/decode/retire spans, queue-depth and
   slot-occupancy series, TTFT / inter-token histograms, and the int8
   kernel-route counter;
2. an async-pool ``asyncmap`` loop under an injected straggler runs
   with an ``EpochTracer`` and feeds a ``PoolLatencyModel`` whose
   per-worker fits publish into the same registry; a ``HedgedServer``
   on the same backend exports its fire rates beside them;
3. the LIVE telemetry plane: an ``ObsServer`` (loopback, port 0)
   serves the registry while a straggling ``ProcessBackend`` pool —
   real OS worker processes — runs with cross-process aggregation, and
   the demo scrapes its own ``/metrics`` and ``/healthz`` over real
   HTTP (``curl http://127.0.0.1:<printed port>/metrics`` works too
   while it runs), then trips a ``FlightRecorder`` dump — the bounded
   postmortem ring, with one Perfetto pid per worker process;
4. request-scoped causal tracing (round 22): a sim router day runs
   with a ``TraceBook`` armed — every request's life (submitted →
   prefill chunks → first token → migrate/adopt → retired) is one
   typed event list — the demo prints one served request's waterfall,
   fetches the SAME waterfall as JSON from ``GET /trace/<id>`` over
   real HTTP, and runs the conservation audit (``GET /audit``: every
   submitted id resolved exactly once, token/migration arithmetic
   closed);
5. the windowed SLO plane (round 24): a sim router day with a mid-day
   latency regression runs with a ``SeriesStore`` + ``SloPolicy``
   attached — the TTFT fast-burn alert fires during the regression
   and clears after the heal, the alert timeline and per-tenant cost
   ledger print, and ``GET /slo`` / ``GET /series`` serve the same
   state over real HTTP;
6. everything merges: ``dump_merged_chrome_trace`` writes ONE
   Chrome/Perfetto trace with the pool's worker/coordinator tracks,
   the scheduler's tick track, and the worker processes' own task
   spans (clock-aligned) side by side — open it at
   https://ui.perfetto.dev — and the registry dumps both Prometheus
   text exposition and JSON.

Run: ``python examples/observability_demo.py [outdir]`` (CPU-only,
seconds).
"""

import json
import os
import sys

import numpy as np

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)

from mpistragglers_jl_tpu import AsyncPool, LocalBackend, asyncmap, waitall
from mpistragglers_jl_tpu.backends.process import ProcessBackend
from mpistragglers_jl_tpu.obs import (
    FlightRecorder,
    MetricsRegistry,
    ObsServer,
    SpanRecorder,
    dump_merged_chrome_trace,
)
from mpistragglers_jl_tpu.utils import (
    EpochTracer,
    HedgedServer,
    PoolLatencyModel,
    faults,
)


def proc_work(i, payload, epoch):
    """Module-level so it pickles into spawned worker processes."""
    return payload * (i + 1)


class ProcDelay:
    """Picklable per-worker straggler injection for the process pool."""

    def __init__(self, delays):
        self.delays = list(delays)

    def __call__(self, i, epoch):
        return self.delays[i]


def serving_section(registry, spans):
    from mpistragglers_jl_tpu.models.serving import ServingScheduler
    from mpistragglers_jl_tpu.models.transformer import (
        TransformerConfig,
        init_params,
    )

    cfg = TransformerConfig(
        vocab=61, d_model=64, n_heads=8, n_kv_heads=2, n_layers=2,
        d_ff=128, attn_window=6,
    )
    params = init_params(cfg, seed=11)
    sched = ServingScheduler(
        params, cfg, slots=2, n_inner=4, prompt_chunk=8, max_prompt=64,
        page_tokens=3, registry=registry, spans=spans,
    )
    rng = np.random.default_rng(0)
    reqs = [
        sched.submit(rng.integers(1, cfg.vocab, size=p), max_new=m)
        for p, m in [(5, 8), (11, 6), (3, 10), (7, 5)]
    ]
    sched.run()
    assert all(r.finished for r in reqs)
    ttft = registry.histogram("serving_ttft_seconds")
    print(
        f"serving: {len(reqs)} requests over "
        f"{sched.tick_count} ticks, "
        f"{int(registry.counter('serving_tokens_total').value)} tokens "
        f"delivered, ttft p50 <= {ttft.quantile(0.5) * 1e3:.1f} ms"
    )


def pool_section(registry):
    def work(i, payload, epoch):
        return payload * (i + 1)

    n = 4
    backend = LocalBackend(
        work, n, delay_fn=faults.per_worker([0.004, 0.004, 0.004, 0.06])
    )
    tracer = EpochTracer()
    model = PoolLatencyModel(n)
    try:
        pool = AsyncPool(n)
        for _ in range(6):
            asyncmap(pool, np.ones(8), backend, nwait=3, tracer=tracer)
            model.observe_pool(pool)
        waitall(pool, backend, tracer=tracer)
        model.observe_pool(pool)
        model.publish(registry)

        srv = HedgedServer(backend, registry=registry)
        for q in range(5):
            srv.request(np.full(2, float(q)), hedge=2)
        srv.drain()
    finally:
        backend.shutdown()
    s = tracer.summary()
    print(
        f"pool: {s['epochs']} epochs, straggler_rate="
        f"{s['straggler_rate']:.2f}, delivered_rate="
        f"{s['delivered_rate']:.2f} "
        f"({s['n_waitall_arrivals']} waitall drains counted)"
    )
    print(
        "hedge: "
        f"{int(registry.counter('hedge_requests_total').value)} requests, "
        f"{int(registry.counter('hedge_dispatches_total').value)} "
        "replica dispatches"
    )
    return tracer


def live_section(registry, flight, outdir):
    """The telemetry plane: serve the registry over HTTP, run a real
    process pool with cross-process aggregation, scrape ourselves."""
    import urllib.request

    srv = ObsServer(registry, flight=flight).start()
    backend = ProcessBackend(
        proc_work, 3, delay_fn=ProcDelay([0.002, 0.002, 0.05]),
        registry=registry, flight=flight, exporter=srv,
    )
    try:
        print(
            f"live: ObsServer on {srv.url} — try "
            f"`curl {srv.url}/metrics` while this runs"
        )
        pool = AsyncPool(3)
        for _ in range(5):
            asyncmap(pool, np.ones(8), backend, nwait=2, flight=flight)
        waitall(pool, backend, flight=flight)

        prom = urllib.request.urlopen(srv.url + "/metrics").read()
        worker_lines = [
            ln for ln in prom.decode().splitlines()
            if ln.startswith("worker_tasks_total{")
        ]
        assert len(worker_lines) == 3, worker_lines  # one per process
        health = json.loads(
            urllib.request.urlopen(srv.url + "/healthz").read()
        )
        assert health["ok"] and "pool" in health["checks"]
        trace = json.loads(
            urllib.request.urlopen(srv.url + "/trace").read()
        )
        worker_pids = {
            e["pid"] for e in trace["traceEvents"]
            if e.get("ph") == "M" and e["name"] == "process_name"
            and e["args"]["name"].startswith("worker ")
        }
        flight_path = os.path.join(outdir, "flight.json")
        flight.arm(flight_path)
        flight.trip("demo: operator-requested postmortem dump")
        fdoc = json.load(open(flight_path))
        assert any(
            e.get("ph") == "I" and "postmortem" in e["name"]
            for e in fdoc["traceEvents"]
        )
        print(
            f"live: scraped {len(prom.splitlines())} exposition lines "
            f"over HTTP, healthz ok, {len(worker_pids)} worker pids "
            f"in /trace, flight ring ({len(flight)} entries) -> "
            f"{flight_path}"
        )
        return backend.aggregator.recorders()
    finally:
        backend.shutdown()
        srv.close()


def tracing_section():
    """Request-scoped causal tracing: arm a TraceBook on a two-tier
    sim router day (prefill tier hands streams to decode replicas at
    first token, so waterfalls cross a migration), print one request's
    waterfall, then serve it over real HTTP via /trace/<id> and run
    the conservation audit via /audit."""
    import urllib.request

    from mpistragglers_jl_tpu.models.router import RequestRouter
    from mpistragglers_jl_tpu.obs import TraceBook, audit
    from mpistragglers_jl_tpu.sim.clock import VirtualClock
    from mpistragglers_jl_tpu.sim.workload import (
        SimReplica,
        poisson_arrivals,
        run_router_day,
    )

    clock = VirtualClock()
    fleet = [
        SimReplica(clock, slots=4, n_inner=8, tick_s=0.02,
                   tier="prefill" if i < 1 else "decode",
                   chunk_s=0.005)
        for i in range(3)
    ]
    book = TraceBook("router-day")
    router = RequestRouter(fleet, policy="two_tier", clock=clock,
                           trace=book)
    rep = run_router_day(
        router,
        poisson_arrivals(30.0, n=120, seed=3,
                         prompt_len=64, max_new=8),
    )

    # one migrated-and-served request's waterfall, door-relative
    tid = next(
        t for t in book.ids() if book.cohort(t) == "migrated"
    )
    wf = book.waterfall(tid)
    print(
        f"tracing: {len(book)} traces on the day "
        f"(digest {rep.digest()}); request #{tid} waterfall:"
    )
    for ev in wf["events"]:
        attrs = ", ".join(
            f"{k}={v}" for k, v in ev["attrs"].items()
        )
        print(f"  +{ev['dt'] * 1e3:8.2f} ms  {ev['kind']:18s} {attrs}")
    print(
        f"  ttft {wf['ttft'] * 1e3:.2f} ms, latency "
        f"{wf['latency'] * 1e3:.2f} ms, outcome {wf['outcome']}"
    )

    # the same waterfall over real HTTP, plus the conservation audit
    with ObsServer() as srv:
        srv.add_tracebook(book)
        http_wf = json.loads(
            urllib.request.urlopen(
                f"{srv.url}/trace/{tid}"
            ).read()
        )
        assert http_wf["ttft"] == wf["ttft"]
        assert http_wf["latency"] == wf["latency"]
        adoc = json.loads(
            urllib.request.urlopen(srv.url + "/audit").read()
        )
    res = audit(book, rep)
    assert res.ok and adoc["ok"], (res.failures, adoc)
    print(
        f"tracing: GET /trace/{tid} reproduced ttft/latency exactly; "
        f"GET /audit ok ({len(res.checked)} invariants checked: "
        + ", ".join(res.checked) + ")"
    )


def slo_section():
    """The windowed SLO plane (round 24): a sim router day with a
    mid-day latency regression (two of three replicas partitioned
    under load) runs with a SeriesStore + SloPolicy attached — the
    TTFT fast-burn alert fires during the regression and clears after
    the heal; the demo prints the alert timeline and the per-tenant
    cost ledger, then re-fetches the SAME policy state as JSON from
    ``GET /slo`` over real HTTP."""
    import urllib.request

    from mpistragglers_jl_tpu.models.router import RequestRouter
    from mpistragglers_jl_tpu.obs import (
        SeriesStore,
        SloObjective,
        SloPolicy,
    )
    from mpistragglers_jl_tpu.sim.clock import VirtualClock
    from mpistragglers_jl_tpu.sim.workload import (
        ReplicaPartition,
        SimReplica,
        poisson_arrivals,
        run_router_day,
    )

    clock = VirtualClock()
    fleet = [
        SimReplica(clock, slots=2, n_inner=4, tick_s=0.02)
        for _ in range(3)
    ]
    reg = MetricsRegistry()
    router = RequestRouter(fleet, policy="least_loaded", clock=clock,
                           registry=reg)
    series = SeriesStore(reg, clock=clock, window_s=1.0,
                         max_windows=120)
    slo = SloPolicy(series, [SloObjective(
        "ttft-p99", "latency", 0.1, q=0.9,
        fast_s=2.0, slow_s=6.0, fire_burn=2.0,
    )])
    rep = run_router_day(
        router,
        poisson_arrivals(60.0, n=1200, seed=5, prompt_len=64,
                         max_new=8),
        events=[ReplicaPartition(4.0, (1, 2), 5.0)],
        series=series, slo=slo,
    )
    assert slo.timeline, "the regression must fire the alert"
    assert slo.fast_burn_firing() == [], "the heal must clear it"
    print(
        f"slo: {series.n_rolled} windows over a "
        f"{rep.virtual_s:.1f} s day, alert timeline:"
    )
    for ev in slo.timeline:
        print(
            f"  t={ev['t']:6.2f} s  {ev['phase']:5s} "
            f"{ev['objective']} (fast burn {ev['fast_burn']:.2f}x, "
            f"slow burn {ev['slow_burn']:.2f}x)"
        )
    busy = sum(
        v["busy_s"] for row in slo.ledger()
        for v in row["tenants"].values()
    )
    print(
        f"slo: cost ledger attributed {busy:.1f} busy chip-seconds "
        f"over {len(slo.ledger())} windows"
    )

    # the same policy state over real HTTP: /slo is the pageable
    # surface (503 while a fast-burn alert fires; 200 here — cleared)
    with ObsServer(reg) as srv:
        srv.add_slo(slo)
        doc = json.loads(
            urllib.request.urlopen(srv.url + "/slo").read()
        )
        sdoc = json.loads(
            urllib.request.urlopen(srv.url + "/series").read()
        )
    assert doc["ok"] and doc["policies"][0]["timeline"] == slo.timeline
    assert sdoc["stores"][0]["n_rolled"] == series.n_rolled
    obj = doc["policies"][0]["objectives"][0]
    print(
        f"slo: GET /slo ok={doc['ok']} (budget burned "
        f"{obj['budget']['burned_frac']:.2f}, "
        f"{len(doc['policies'][0]['timeline'])} transitions); "
        f"GET /series mirrors {sdoc['stores'][0]['n_rolled']} windows"
    )


def main():
    outdir = sys.argv[1] if len(sys.argv) > 1 else "."
    os.makedirs(outdir, exist_ok=True)
    registry = MetricsRegistry()
    spans = SpanRecorder("serving")
    flight = FlightRecorder()

    serving_section(registry, spans)
    tracer = pool_section(registry)
    worker_recorders = live_section(registry, flight, outdir)
    tracing_section()
    slo_section()

    trace_path = os.path.join(outdir, "unified_trace.json")
    n_events = dump_merged_chrome_trace(
        trace_path, tracers=[tracer],
        recorders=[spans] + worker_recorders,
    )
    doc = json.load(open(trace_path))  # round-trips as valid JSON
    assert all(
        e["dur"] >= 0 for e in doc["traceEvents"] if e.get("ph") == "X"
    )
    print(
        f"merged timeline: {n_events} events -> {trace_path} "
        "(open in ui.perfetto.dev)"
    )

    prom_path = os.path.join(outdir, "metrics.prom")
    registry.dump_prometheus(prom_path)
    json_path = os.path.join(outdir, "metrics.json")
    registry.dump_json(json_path)
    prom = open(prom_path).read()
    for want in (
        "serving_queue_depth",
        "serving_tokens_per_s",
        "serving_ttft_seconds_bucket",
        "serving_kernel_route_total",
        "pool_worker_latency_mean_seconds",
        "hedge_requests_total",
        "worker_tasks_total",  # originated inside worker processes
    ):
        assert want in prom, want
    print(
        f"prometheus exposition: {len(registry)} series -> {prom_path} "
        f"(+ JSON snapshot {json_path})"
    )
    print("observability demo ok")


if __name__ == "__main__":
    main()
