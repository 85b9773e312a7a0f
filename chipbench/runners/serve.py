"""Runner of the serving cells: the program's ``ServingScheduler`` fed
from a backlog, one tick at a time.

All requests are submitted up front, so the slots stay full. A warm
phase on the same traffic runs for the mix's ``warm_rounds`` (every
program has then compiled or been found in the cache, every slot has
retired a request, and the schedule has settled); the window opens at a
tick boundary and closes at the first tick boundary after ``--seconds``.
After each tick the runner stamps the clock and reads how many tokens
each request in a slot was delivered. After the window the scheduler is
freed and the plain reference judges a seeded sample of the streams
that finished inside the window.
"""

from __future__ import annotations

import gc
import statistics

import numpy as np

from chipbench import common, counts, traffic_gen, weights
from chipbench.runners import _model


def _bucket(n: int, step: int = 256) -> int:
    return -(-n // step) * step


def reference_gaps(ref, params, streams, window, precision="float32"):
    """For each (prompt, served tokens): how far each served token's
    logit lies below the reference's best, and the reference's logits
    themselves row by row (for the control)."""
    import jax.numpy as jnp

    out = []
    for prompt, served in streams:
        tp, n = len(prompt), len(served)
        pad = _bucket(tp + n)
        seq = np.zeros((pad,), np.int32)
        seq[:tp] = prompt
        seq[tp:tp + n] = served
        rows = _bucket(n, 64)
        first = min(tp - 1, pad - rows)
        lg = np.asarray(ref.stream_logits(
            params, jnp.asarray(seq), first, rows, window=window,
            precision=precision,
        ))[tp - 1 - first: tp - 1 - first + n]
        out.append(lg)
    return out


def gap_numbers(ref_logits, tokens) -> tuple[float, float]:
    """How far the given tokens' logits lie below the reference's best,
    position by position: the widest gap, and the mean over all the
    positions (a token that is the reference's best counts 0)."""
    gaps = np.concatenate([
        lg.max(axis=-1) - lg[np.arange(len(tok)), tok]
        for lg, tok in zip(ref_logits, tokens)
    ])
    return float(gaps.max()), float(gaps.mean())


def control(run, precision: str) -> dict:
    """The reference in a lower precision, put in the program's place
    without decoding: at each position of the same prompts and served
    tokens, how far the token that the lower precision puts first lies
    below the float32 reference's best."""
    params, streams, ref_logits = run.info["reference"]
    ref = _model.reference_module(run)
    low = reference_gaps(ref, params, streams,
                         run.config["sliding_window"], precision)
    worst, mean = gap_numbers(ref_logits, [lo.argmax(axis=-1) for lo in low])
    return {"logit_gap_worst": worst, "logit_gap_mean": mean}


def run(run) -> None:
    import jax

    from mpistragglers_jl_tpu.models.serving import ServingScheduler

    cfg, traffic, program = run.config, run.traffic, run.config["program"]
    model = _model.transformer_config(cfg)
    sz = _model.sizes(cfg)
    shapes = _model.param_shapes(cfg)
    with run.spans.span("setup_weights"):
        params = weights.make_params(
            shapes, run.seed, d_model=sz["d_model"],
            n_layers=sz["n_layers"],
        )
        jax.block_until_ready(params)
    with run.spans.span("setup_traffic"):
        requests = traffic_gen.ordered_requests(traffic)
        prompts = traffic_gen.prompts_for(requests, sz["vocab"], run.seed)
    slots = int(program["slots"])
    with run.spans.span("setup_scheduler"):
        sched = ServingScheduler(
            params, model, slots=slots, n_inner=int(program["n_inner"]),
            quantize_kv=bool(program["quantize_kv"]),
            page_tokens=int(program["page_tokens"]),
            prompt_chunk=int(program["prompt_chunk"]),
            max_prompt=int(program["max_prompt"]),
        )
        reqs = [sched.submit(p, r[2]) for p, r in zip(prompts, requests)]
    print(f"note int8_decode_kernel_routed {bool(sched.use_kernel)}",
          flush=True)

    # per-request delivery bookkeeping, after every tick
    seen = [0] * len(reqs)
    last_t = [0.0] * len(reqs)
    active: list[int] = []
    nxt = 0
    gaps: list[float] = []      # seconds per token
    gap_w: list[int] = []       # tokens that waited that long
    ticks: list[tuple[float, int, int]] = []  # (t_end, tokens, decoding)
    finished_in_window: list[int] = []
    row_bytes_rows = 0.0
    W = cfg["sliding_window"]

    def after_tick(t: float, record: bool) -> None:
        nonlocal nxt, row_bytes_rows
        while nxt < len(reqs) and reqs[nxt].admitted_tick is not None:
            active.append(nxt)
            nxt += 1
        delivered = decoding = 0
        kv_rows = 0
        for i in list(active):
            r = reqs[i]
            n = len(r.tokens) - seen[i]
            if seen[i] > 0:
                decoding += 1
                kv_rows += min(W, len(r.prompt) + seen[i])
            if n > 0:
                if record:
                    delivered += n
                    if seen[i] > 0:
                        gaps.append((t - last_t[i]) / n)
                        gap_w.append(n)
                seen[i] += n
                last_t[i] = t
            if r.finished:
                active.remove(i)
                if record:
                    finished_in_window.append(i)
        if record:
            ticks.append((t, delivered, decoding))
            row_bytes_rows += kv_rows

    # -- warm phase: the same traffic, for warm_rounds rounds ------------
    # (every slot has then retired a request, every shape has been
    # seen, and the schedule has settled into its period)
    # The window opens in the tick that admits a round's first request.
    with run.spans.span("setup_warm"):
        n_warm = int(traffic["warm_rounds"]) * int(traffic["round"])
        first = reqs[:slots]
        guard = 0
        while (reqs[n_warm].admitted_tick is None
               or not all(r.finished for r in first)):
            sched.step()
            after_tick(common.now(), False)
            guard += 1
            if guard > 100000:
                raise RuntimeError("warm phase did not finish")
    run.end_to_end["setup_s"] = common.now() - run.t_start
    common.print_setup(run)

    # -- the window -------------------------------------------------------
    tracer = common.WindowTrace(run, traffic.get("trace_seconds", 4))
    tracer.start()
    t_open = common.now()
    while True:
        with run.spans.span("tick"):
            sched.step()
        t = common.now()
        after_tick(t, True)
        tracer.stop_if_due()
        if t - t_open >= run.seconds:
            break
        if sched.pending == 0:
            raise RuntimeError(
                "backlog emptied inside the window; the traffic file "
                "needs more requests for this length of run"
            )
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
    done = [reqs[i] for i in finished_in_window]
    run.attempted = len(done)
    run.failed = sum(
        1 for r in done
        if r.reason != "length" or len(r.tokens) != r.max_new
    )
    tick_ms = [1e3 * (b[0] - a[0]) for a, b in zip(ticks, ticks[1:])]
    n_inner = int(program["n_inner"])
    row_bytes = counts.kv_row_bytes(
        kv_heads=sz["kv_heads"], head_dim=sz["d_model"] // sz["n_heads"],
        n_layers=sz["n_layers"], quantized=bool(program["quantize_kv"]),
    )
    run.info.update(
        ticks=ticks, slots=slots, n_inner=n_inner,
        token_gaps=(gaps, gap_w),
        weight_bytes=counts.serving_weight_bytes(**sz),
        kv_row_bytes=row_bytes,
        mean_kv_rows_per_tick=row_bytes_rows / max(1, len(ticks)),
    )
    print("series tick_ms " + common.compact(tick_ms, 1), flush=True)
    print(
        f"note ticks {len(ticks)} tokens {tokens_between} requests_done "
        f"{len(done)} token_gaps {sum(gap_w)} itl_ms p50 "
        f"{1e3 * common.weighted_percentile(gaps, gap_w, 50.0):.3f} mean "
        f"{1e3 * sum(g * w for g, w in zip(gaps, gap_w)) / sum(gap_w):.3f}"
        f" p95 {run.end_to_end['itl_p95_ms']:.3f} p99 "
        f"{1e3 * common.weighted_percentile(gaps, gap_w, 99.0):.3f} "
        f"tick_median_ms {statistics.median(tick_ms):.3f}", flush=True,
    )

    # -- the plain reference on a seeded sample of finished streams ------
    streams = [
        (np.asarray(reqs[i].prompt), np.asarray(reqs[i].tokens, np.int32))
        for i in sample_finished(reqs, finished_in_window, run.seed,
                                 int(traffic["check_requests"]))
    ]
    del sched, reqs
    gc.collect()
    jax.clear_caches()
    ref = _model.reference_module(run)
    with run.spans.span("reference"):
        ref_logits = reference_gaps(ref, params, streams, W)
        worst, mean = gap_numbers(ref_logits, [s for _, s in streams])
    n_tok = sum(len(s) for _, s in streams)
    run.info["reference"] = (params, streams, ref_logits)
    print(
        f"note reference_s {run.spans.durations('reference')[0]:.2f} "
        f"streams {len(streams)} served_tokens {n_tok} longest "
        f"{max(len(p) + len(s) for p, s in streams)}", flush=True,
    )
    run.check.at_most("served_token_logit_gap_worst", worst,
                      cfg["limits"]["logit_gap_worst"])
    run.check.at_most("served_token_logit_gap_mean", mean,
                      cfg["limits"]["logit_gap_mean"])
    run.check.require("requests_complete_as_asked",
                      run.failed == 0 and run.attempted > 0)


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
