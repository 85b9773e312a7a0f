"""The serving loop, which every serving kind goes through: the
program's ``ServingScheduler`` fed from a backlog, one tick at a time.

All requests are submitted up front, so the slots stay full. A warm
phase on the same traffic runs for the mix's ``warm_rounds`` (every
program has then compiled or been found in the cache, every slot has
retired a request, and the schedule has settled). After each tick the
loop stamps the clock and reads how many tokens each request in a slot
was delivered.

**The window is a stretch of the schedule, not of the clock.** The
scheduler decides tick by tick from lengths alone
(chipbench/traffic_gen.py), so which tick admits which request is the
same in every run of one program. The window opens with the tick after
the one that admits request ``warm_rounds * round`` and closes with the
tick that admits request ``(warm_rounds + R) * round``, where
``R = max(1, round(window_rounds * seconds / run_seconds))``:
``window_rounds`` is the traffic file's, ``seconds`` the command
line's and ``run_seconds`` the manifest's, so at the driver's
``--seconds`` the window is the file's number of rounds and a shorter
call gets its share. No clock is asked when closing: every run of a
cell times the same ticks, the same tokens and the same token gaps,
and a faster program finishes them sooner. (Closed on the clock, 1% of
timing moved the close by a tick or two, and ``itl_p95_ms`` read which
tick that was: PERF.md section 6, PR 29.)

A kind's runner (``serve.py``, ``serve_moe.py``) builds the model and
its weights, says how many cached K/V rows a request of a given length
attends and how many bytes a step reads, and brings the reference;
everything else of a serving run is here.
"""

from __future__ import annotations

import dataclasses
import gc
import statistics

import numpy as np

from chipbench import common, traffic_gen


def window_rounds(traffic: dict, seconds: float, run_seconds: float) -> int:
    """R: the rounds of the schedule that a call of ``--seconds`` times."""
    return max(1, round(
        int(traffic["window_rounds"]) * float(seconds) / float(run_seconds)))


def schedule_marks(traffic: dict, seconds: float,
                   run_seconds: float) -> tuple[int, int, int]:
    """R, and the two requests whose admission bounds the window: the
    tick after the one that admits the first opens it, the tick that
    admits the second closes it. A traffic file that does not outlast
    the window by two rounds is refused."""
    size, warm = int(traffic["round"]), int(traffic["warm_rounds"])
    R = window_rounds(traffic, seconds, run_seconds)
    if warm + R + 2 > int(traffic["rounds"]):
        raise ValueError(
            f"the traffic file has {traffic['rounds']} rounds; "
            f"{warm} warm rounds, a window of {R} and two to spare need "
            f"{warm + R + 2}"
        )
    return R, warm * size, (warm + R) * size


def submit_backlog(run, params, model, vocab: int):
    """The program's scheduler as the configuration's ``program`` sets
    it up, with the whole backlog queued: ``(scheduler, requests)``."""
    from mpistragglers_jl_tpu.models.serving import ServingScheduler

    program = run.config["program"]
    with run.spans.span("setup_traffic"):
        requests = traffic_gen.ordered_requests(run.traffic)
        prompts = traffic_gen.prompts_for(requests, vocab, run.seed)
    with run.spans.span("setup_scheduler"):
        sched = ServingScheduler(
            params, model, slots=int(program["slots"]),
            n_inner=int(program["n_inner"]),
            quantize_kv=bool(program["quantize_kv"]),
            page_tokens=int(program["page_tokens"]),
            prompt_chunk=int(program["prompt_chunk"]),
            max_prompt=int(program["max_prompt"]),
        )
        reqs = [sched.submit(p, r[2]) for p, r in zip(prompts, requests)]
    print(f"note int8_decode_kernel_routed {bool(sched.use_kernel)}",
          flush=True)
    return sched, reqs


class Deliveries:
    """Per-request delivery bookkeeping, after every tick: what each
    request in a slot was delivered, and (recording) the window's
    ticks, token gaps and finished requests."""

    def __init__(self, sched, reqs, kv_rows):
        self.sched, self.reqs, self.kv_rows = sched, reqs, kv_rows
        self.seen = [0] * len(reqs)
        self.last_t = [0.0] * len(reqs)
        self.active: list[int] = []
        self.nxt = 0
        self.gaps: list[float] = []     # seconds per token
        self.gap_w: list[int] = []      # tokens that waited that long
        self.ticks: list[tuple[float, int, int]] = []  # (t_end, tokens, decoding)
        self.finished: list[int] = []
        self.kv_rows_sum = 0.0          # cached rows attended, summed
        self.rows_by_tick: list[int] = []  # and tick by tick
        self.hits: list[float] = []     # the tick's own experts_hit

    def after_tick(self, t: float, record: bool) -> None:
        reqs, seen = self.reqs, self.seen
        while (self.nxt < len(reqs)
               and reqs[self.nxt].admitted_tick is not None):
            self.active.append(self.nxt)
            self.nxt += 1
        delivered = decoding = rows = 0
        for i in list(self.active):
            r = reqs[i]
            n = len(r.tokens) - seen[i]
            if seen[i] > 0:
                decoding += 1
                rows += self.kv_rows(len(r.prompt) + seen[i])
            if n > 0:
                if record:
                    delivered += n
                    if seen[i] > 0:
                        self.gaps.append((t - self.last_t[i]) / n)
                        self.gap_w.append(n)
                seen[i] += n
                self.last_t[i] = t
            if r.finished:
                self.active.remove(i)
                if record:
                    self.finished.append(i)
        if record:
            self.ticks.append((t, delivered, decoding))
            self.kv_rows_sum += rows
            self.rows_by_tick.append(rows)
            hit = getattr(self.sched, "experts_hit", None)
            if decoding and hit is not None:
                self.hits.append(hit)


@dataclasses.dataclass
class Served:
    """What the window left for the kind's runner: the seeded sample of
    finished streams for the reference, and the mean of the ticks'
    ``experts_hit`` (0.0 where the model has no expert layer)."""

    streams: list
    experts_hit: float


def serve(run, sched, reqs, kv_rows) -> Served:
    """Warm phase, window, the end-to-end metrics and the run's lines.
    ``kv_rows(length)`` is the kind's count of cached K/V rows that a
    decoding request of that length attends in one step."""
    traffic, program = run.traffic, run.config["program"]
    slots = int(program["slots"])
    R, n_open, n_close = schedule_marks(traffic, run.seconds,
                                        run.run_seconds)
    book = Deliveries(sched, reqs, kv_rows)

    # -- warm phase: the same traffic, for warm_rounds rounds ------------
    with run.spans.span("setup_warm"):
        first = reqs[:slots]
        guard = 0
        while (reqs[n_open].admitted_tick is None
               or not all(r.finished for r in first)):
            sched.step()
            book.after_tick(common.now(), False)
            guard += 1
            if guard > 100000:
                raise RuntimeError("warm phase did not finish")
    run.end_to_end["setup_s"] = common.now() - run.t_start
    common.print_setup(run)

    # -- the window: until the tick that admits request n_close ----------
    tracer = common.WindowTrace(run, traffic.get("trace_seconds", 4))
    tracer.start()
    t_open = common.now()
    while True:
        with run.spans.span("tick"):
            sched.step()
        book.after_tick(common.now(), True)
        tracer.stop_if_due()
        if reqs[n_close].admitted_tick is not None:
            break
        if sched.pending == 0:
            raise RuntimeError(
                "backlog emptied inside the window; the traffic file "
                "needs more requests for this length of run"
            )
    ticks, gaps, gap_w = book.ticks, book.gaps, book.gap_w
    run.window = (t_open, ticks[-1][0])
    tracer.reduce()
    run.memory_peak_bytes = common.memory_peak_bytes(run.devices)
    common.print_memory(run.devices)
    common.window_compiled_nothing(run)

    t_first, t_last = ticks[0][0], ticks[-1][0]
    tokens_between = sum(n for _, n, _ in ticks[1:])
    run.end_to_end["serve_tok_s"] = tokens_between / (t_last - t_first)
    # the tail of all the window's token gaps: every output token after
    # a request's first waited (time since the request's previous
    # delivery) / (tokens in this delivery)
    run.end_to_end["itl_p95_ms"] = 1e3 * common.weighted_percentile(
        gaps, gap_w, 95.0)
    done = [reqs[i] for i in book.finished]
    run.attempted = len(done)
    run.failed = sum(
        1 for r in done
        if r.reason != "length" or len(r.tokens) != r.max_new
    )
    tick_ms = [1e3 * (b[0] - a[0]) for a, b in zip(ticks, ticks[1:])]
    experts_hit = statistics.fmean(book.hits) if book.hits else 0.0
    run.info.update(
        ticks=ticks, slots=slots, n_inner=int(program["n_inner"]),
        token_gaps=(gaps, gap_w),
        mean_kv_rows_per_tick=book.kv_rows_sum / max(1, len(ticks)),
        kv_rows_by_tick=book.rows_by_tick,
    )
    print("series tick_ms " + common.compact(tick_ms, 1), flush=True)
    print(f"note window rounds {R} ticks {len(ticks)} seconds "
          f"{t_last - t_open:.3f}", flush=True)
    print(
        f"note ticks {len(ticks)} tokens {tokens_between} requests_done "
        f"{len(done)} token_gaps {sum(gap_w)} itl_ms p50 "
        f"{1e3 * common.weighted_percentile(gaps, gap_w, 50.0):.3f} mean "
        f"{1e3 * sum(g * w for g, w in zip(gaps, gap_w)) / sum(gap_w):.3f}"
        f" p95 {run.end_to_end['itl_p95_ms']:.3f} p99 "
        f"{1e3 * common.weighted_percentile(gaps, gap_w, 99.0):.3f} "
        f"tick_median_ms {statistics.median(tick_ms):.3f}"
        + (f" experts_hit_mean {experts_hit:.2f}" if book.hits else ""),
        flush=True,
    )
    streams = [
        (np.asarray(reqs[i].prompt), np.asarray(reqs[i].tokens, np.int32))
        for i in sample_finished(reqs, book.finished, run.seed,
                                 int(traffic["check_requests"]))
    ]
    return Served(streams, experts_hit)


def sample_finished(reqs, finished, seed: int, n: int) -> list[int]:
    """A seeded sample of the requests that finished in the window,
    with the longest (prompt plus answer) among them."""
    if not finished:
        return []
    longest = max(
        finished, key=lambda i: len(reqs[i].prompt) + len(reqs[i].tokens)
    )
    rest = [i for i in finished if i != longest]
    rng = common.seeded_rng(seed, 13)
    pick = list(rng.permutation(len(rest))[: max(0, n - 1)])
    return [longest] + [rest[j] for j in pick]


def gap_numbers(ref_logits, tokens) -> tuple[float, float]:
    """How far the given tokens' logits lie below the reference's best,
    position by position: the widest gap, and the mean over all the
    positions (a token that is the reference's best counts 0)."""
    gaps = np.concatenate([
        lg.max(axis=-1) - lg[np.arange(len(tok)), tok]
        for lg, tok in zip(ref_logits, tokens)
    ])
    return float(gaps.max()), float(gaps.mean())


def judge(run, params, streams, reference_logits) -> None:
    """Once the caller has dropped the scheduler: free the program's
    state, run the plain reference over the sampled streams
    (``reference_logits(streams)``: its logits at the served positions,
    row by row) and set the numbers that decide ``correct``."""
    import jax

    gc.collect()
    jax.clear_caches()
    with run.spans.span("reference"):
        ref_logits = reference_logits(streams)
        worst, mean = gap_numbers(ref_logits, [s for _, s in streams])
    run.info["reference"] = (params, streams, ref_logits)
    print(
        f"note reference_s {run.spans.durations('reference')[0]:.2f} "
        f"streams {len(streams)} served_tokens "
        f"{sum(len(s) for _, s in streams)} longest "
        f"{max(len(p) + len(s) for p, s in streams)}", flush=True,
    )
    limits = run.config["limits"]
    run.check.at_most("served_token_logit_gap_worst", worst,
                      limits["logit_gap_worst"])
    run.check.at_most("served_token_logit_gap_mean", mean,
                      limits["logit_gap_mean"])
    run.check.require("requests_complete_as_asked",
                      run.failed == 0 and run.attempted > 0)
