"""Runner of the serving cells whose model has expert layers and layers
of more than one attention span (the ``afmoe`` block): the program's
``ServingScheduler`` fed from a backlog, one tick at a time, as
chipbench/runners/serve.py does for the dense block. The configuration
file's keys are the published ``config.json``'s; this module turns them
into the program's ``TransformerConfig`` (the layer pattern as data) and
into the pytree of shapes the weights are made over.

What differs from runners/serve.py: how the model is built, the bytes a
step reads (chipbench/counts_moe.py: the experts that got a token, K/V
rows by layer kind) and the reference (chipbench/references/afmoe.py,
which takes each layer's span). The loop around ``sched.step()`` is the
same; the two are kept apart because runners/serve.py and
runners/_model.py read StarCoder2's keys and belong to the accepted
benchmark (PERF.md section 7).
"""

from __future__ import annotations

import gc
import math
import statistics

import numpy as np

from chipbench import common, counts_moe, traffic_gen, weights
from chipbench.runners import _model
from chipbench.runners.serve import gap_numbers, sample_finished

SLIDING = "sliding_attention"


def layer_windows(config: dict) -> tuple:
    """Each layer's attention span: the window, or None for a
    full-attention layer."""
    kinds = config["layer_types"]
    if len(kinds) != config["num_hidden_layers"]:
        raise ValueError("layer_types does not name every layer")
    return tuple(
        config["sliding_window"] if k == SLIDING else None for k in kinds
    )


def sizes(config: dict) -> dict:
    return {
        "d_model": config["hidden_size"],
        "n_heads": config["num_attention_heads"],
        "kv_heads": config["num_key_value_heads"],
        "head_dim": config["head_dim"],
        "d_ff": config["intermediate_size"],
        "d_expert": config["moe_intermediate_size"],
        "n_experts": config["num_experts"],
        "shared_experts": config["num_shared_experts"],
        "n_layers": config["num_hidden_layers"],
        "n_dense_layers": config["num_dense_layers"],
        "vocab": config["vocab_size"],
    }


def transformer_config(config: dict):
    import jax.numpy as jnp

    from mpistragglers_jl_tpu.models.transformer import TransformerConfig

    program = config["program"]
    n = config["num_hidden_layers"]
    return TransformerConfig(
        vocab=config["vocab_size"],
        d_model=config["hidden_size"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        d_head=config["head_dim"],
        n_layers=n,
        d_ff=config["intermediate_size"],
        attn=program.get("attn", "ulysses"),
        attn_impl=program.get("attn_impl", "flash"),
        dtype=jnp.dtype(config["torch_dtype"]),
        norm="rmsnorm",
        norm_eps=config["rms_norm_eps"],
        ffn="swiglu",
        tie_head=bool(config["tie_word_embeddings"]),
        qk_norm=True,
        attn_gate=True,
        post_norm=True,
        emb_scale=(math.sqrt(config["hidden_size"])
                   if config["mup_enabled"] else 1.0),
        layer_windows=layer_windows(config),
        rope_full=False,
        layer_experts=tuple(
            li >= config["num_dense_layers"] for li in range(n)),
        n_experts=config["num_experts"],
        experts_per_token=config["num_experts_per_tok"],
        d_expert=config["moe_intermediate_size"],
        shared_experts=config["num_shared_experts"],
        route_scale=config["route_scale"],
        max_context=int(program["max_context"]),
    )


def param_shapes(config: dict):
    """The pytree of shapes that the program's ``init_params`` returns
    for this configuration, written out for the reason
    ``weights.transformer_shapes`` gives (tests/chipbench holds the two
    against each other at a tiny size). The router and its bias are
    float32, as the program keeps them."""
    import jax
    import jax.numpy as jnp

    z = sizes(config)
    dtype = jnp.dtype(config["torch_dtype"])
    D, H, Hkv, Dh = z["d_model"], z["n_heads"], z["kv_heads"], z["head_dim"]
    E, F = z["n_experts"], z["d_expert"]
    s = lambda *shape: jax.ShapeDtypeStruct(shape, dtype)
    f32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32)

    def layer(li):
        out = {
            "ln1_s": s(D), "ln1p_s": s(D), "ln2_s": s(D), "ln2p_s": s(D),
            "qn_s": s(Dh), "kn_s": s(Dh),
            "wq": s(D, H, Dh), "wk": s(D, Hkv, Dh), "wv": s(D, Hkv, Dh),
            "wo": s(H, Dh, D), "wog": s(D, H, Dh),
        }
        if li < z["n_dense_layers"]:
            out.update({"w_gate": s(D, z["d_ff"]), "w_up": s(D, z["d_ff"]),
                        "w_down": s(z["d_ff"], D)})
        else:
            Fs = z["shared_experts"] * F
            out.update({
                "router": f32(D, E), "router_bias": f32(E),
                "we_gate": s(E, D, F), "we_up": s(E, D, F),
                "we_down": s(E, F, D),
                "ws_gate": s(D, Fs), "ws_up": s(D, Fs), "ws_down": s(Fs, D),
            })
        return out

    return {
        "emb": s(z["vocab"], D),
        "layers": [layer(li) for li in range(z["n_layers"])],
        "lnf_s": s(D),
        "head": s(z["vocab"], D),
    }


def make_params(config: dict, seed: int):
    """Seeded weights on the device (chipbench/weights.py), then every
    norm scale set to one: ``weights.make_params`` knows ``ln1_s``,
    ``ln2_s`` and ``lnf_s`` by name and would draw the others. The
    expert bias keeps its draw (normal, standard deviation
    1/sqrt(hidden_size)): small and not zero, so that "selects and does
    not weigh" is exercised."""
    import jax
    import jax.numpy as jnp

    z = sizes(config)
    params = weights.make_params(
        param_shapes(config), seed, d_model=z["d_model"],
        n_layers=z["n_layers"],
    )
    ones = jax.jit(lambda a: jnp.ones_like(a))
    return jax.tree_util.tree_map_with_path(
        lambda path, a: ones(a) if weights.leaf_name(path).endswith("_s")
        else a, params,
    )


def reference_gaps(ref, params, streams, windows, max_context: int,
                   precision="float32"):
    """For each (prompt, served tokens): the reference's logits at the
    served positions, row by row. What runners/serve.py's function of
    this name does, with one difference: every stream is padded to
    ``max_context`` and every read is 256 rows, so that one run, and
    every run, compiles each of the reference's programs once. (Padded
    by 256s as there, five streams of five lengths compiled twenty
    programs: 83 to 112 s where the arithmetic takes 6; my chip runs,
    PR 26.) A sliding-window or causal layer never looks ahead, so the
    padding changes no row that is read."""
    import jax.numpy as jnp

    out = []
    rows = min(256, max_context)
    for prompt, served in streams:
        tp, n = len(prompt), len(served)
        if n > rows or tp + n > max_context:
            raise ValueError("a stream outgrew the reference's one shape")
        seq = np.zeros((max_context,), np.int32)
        seq[:tp] = prompt
        seq[tp:tp + n] = served
        first = min(tp - 1, max_context - rows)
        lg = np.asarray(ref.stream_logits(
            params, jnp.asarray(seq), first, rows, window=windows,
            precision=precision,
        ))[tp - 1 - first: tp - 1 - first + n]
        out.append(lg)
    return out


def control(run, precision: str) -> dict:
    """The reference in a lower precision, put in the program's place
    without decoding: at each position of the same prompts and served
    tokens, how far the token that the lower precision puts first lies
    below the float32 reference's best."""
    params, streams, ref_logits = run.info["reference"]
    ref = _model.reference_module(run)
    low = reference_gaps(ref, params, streams, layer_windows(run.config),
                         int(run.config["program"]["max_context"]),
                         precision)
    worst, mean = gap_numbers(ref_logits, [lo.argmax(axis=-1) for lo in low])
    return {"logit_gap_worst": worst, "logit_gap_mean": mean}


def run(run) -> None:
    import jax

    from mpistragglers_jl_tpu.models.serving import ServingScheduler

    cfg, traffic, program = run.config, run.traffic, run.config["program"]
    # first of all: a program that cannot describe this block fails here,
    # before a weight is made
    model = transformer_config(cfg)
    sz = sizes(cfg)
    windows = layer_windows(cfg)
    with run.spans.span("setup_weights"):
        params = make_params(cfg, run.seed)
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
    layer_rows = 0.0            # cached rows attended, summed over layers
    hits: list[float] = []      # the tick's own experts_hit

    def after_tick(t: float, record: bool) -> None:
        nonlocal nxt, layer_rows
        while nxt < len(reqs) and reqs[nxt].admitted_tick is not None:
            active.append(nxt)
            nxt += 1
        delivered = decoding = 0
        rows = 0
        for i in list(active):
            r = reqs[i]
            n = len(r.tokens) - seen[i]
            if seen[i] > 0:
                decoding += 1
                rows += counts_moe.kv_layer_rows(
                    len(r.prompt) + seen[i], windows)
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
            layer_rows += rows
            if decoding and sched.experts_hit is not None:
                hits.append(sched.experts_hit)

    # -- warm phase: the same traffic, for warm_rounds rounds ------------
    # (every slot has then retired a request, every shape has been
    # seen, and the schedule has settled into its period)
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
    experts_hit = statistics.fmean(hits) if hits else 0.0
    # the existing readers multiply rows by bytes a row: here a row is
    # one position in ONE layer, and the rows are summed over the layers
    run.info.update(
        ticks=ticks, slots=slots, n_inner=n_inner,
        token_gaps=(gaps, gap_w),
        weight_bytes=counts_moe.step_weight_bytes(
            experts_hit=experts_hit, **sz),
        kv_row_bytes=counts_moe.kv_layer_row_bytes(
            kv_heads=sz["kv_heads"], head_dim=sz["head_dim"],
            quantized=bool(program["quantize_kv"])),
        mean_kv_rows_per_tick=layer_rows / max(1, len(ticks)),
        experts_hit=experts_hit,
    )
    pages = {k: p.n_pages - 1 for k, p in sched.pools.items()}
    print("series tick_ms " + common.compact(tick_ms, 1), flush=True)
    print(
        f"note ticks {len(ticks)} tokens {tokens_between} requests_done "
        f"{len(done)} token_gaps {sum(gap_w)} itl_ms p50 "
        f"{1e3 * common.weighted_percentile(gaps, gap_w, 50.0):.3f} mean "
        f"{1e3 * sum(g * w for g, w in zip(gaps, gap_w)) / sum(gap_w):.3f}"
        f" p95 {run.end_to_end['itl_p95_ms']:.3f} p99 "
        f"{1e3 * common.weighted_percentile(gaps, gap_w, 99.0):.3f} "
        f"tick_median_ms {statistics.median(tick_ms):.3f} "
        f"experts_hit_mean {experts_hit:.2f} pool_pages {pages}",
        flush=True,
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
        ref_logits = reference_gaps(ref, params, streams, windows,
                                    int(program["max_context"]))
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
