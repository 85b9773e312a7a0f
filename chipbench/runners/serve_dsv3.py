"""Runner of the serving cells whose model is the ``deepseek_v3`` block:
latent attention on one residual stream, a dense feed-forward in the
leading layers and group-limited sigmoid experts behind them, of which
this chip holds a share, served by a scheduler whose decode step yields
one or two tokens a slot, drafted by the model's own
multi-token-prediction module, under keyed sampling.

The configuration file's keys are the published ``config.json``'s; this
module turns them into the program's ``TransformerConfig`` and into the
pytree of shapes the weights are made over, builds the scheduler itself
(``_serve_loop.submit_backlog`` passes neither a temperature nor a
drafter nor keys), runs chipbench/runners/_serve_loop.py's window as it
is, counts the bytes a drafting step reads (chipbench/counts_dsv3.py)
and brings the reference (chipbench/references/deepseek_v3.py) with its
control.

``correct`` is decided by three comparisons, all on the timed path's
own streams (a seeded sample of the requests that finished in the
window), all under the noise the request's key gives at each position,
which the reference draws for itself:

1. the served tokens' gap under the reference's logits, worst and mean
   (``_serve_loop.judge``'s numbers, on ``ref / T + G``);
2. the same two numbers for the DRAFTED tokens under the reference's
   module's logits: no served token depends on the module, so (1) says
   nothing of it;
3. accepted / drafted over those streams, program against the
   reference's own drafts and tokens at the same positions.
"""

from __future__ import annotations

import numpy as np

from chipbench import counts_dsv3, traffic_gen, weights
from chipbench.counts_mla import latent_row_bytes
from chipbench.runners import _model, _serve_loop
from chipbench.runners.serve_mla import _mscale, yarn

KEY_SALT = 3  # weights.seed_key's salt for the requests' sampling keys
ORDER_SALT = 4  # and for the order of the vocabulary's rows


def sizes(config: dict) -> dict:
    return {
        "d_model": config["hidden_size"],
        "n_heads": config["num_attention_heads"],
        "q_rank": config["q_lora_rank"],
        "kv_rank": config["kv_lora_rank"],
        "nope": config["qk_nope_head_dim"],
        "rope": config["qk_rope_head_dim"],
        "v": config["v_head_dim"],
        "d_ff": config["intermediate_size"],
        "d_expert": config["moe_intermediate_size"],
        "router_experts": config["router_experts"],
        "held_experts": config["n_routed_experts"],
        "shared_experts": config["n_shared_experts"],
        "n_layers": config["num_hidden_layers"],
        "n_dense_layers": config["first_k_dense_replace"],
        "vocab": config["vocab_size"],
        "mtp_depth": config["num_nextn_predict_layers"],
    }


def transformer_config(config: dict):
    import jax.numpy as jnp

    from mpistragglers_jl_tpu.models.transformer import (
        TransformerConfig,
        yarn_rope_table,
    )

    held = tuple(config["experts_held"])
    if (config["moe_layer_freq"] != 1 or config["attention_bias"]
            or config["scoring_func"] != "sigmoid"
            or config["topk_method"] != "noaux_tc"
            or not config["norm_topk_prob"]
            or config["num_key_value_heads"]
            != config["num_attention_heads"]
            or held[1] - held[0] != config["n_routed_experts"]
            or config["num_experts"] != config["n_routed_experts"]
            or config["num_dense_layers"] != config["first_k_dense_replace"]):
        raise ValueError("a key this runner reads as published is another")
    theta, factor, original, fast, slow, ms, ms_all = yarn(config)
    if _mscale(ms, factor) != _mscale(ms_all, factor):
        raise ValueError("cos and sin would be scaled: the program's "
                         "rotary has no magnitude")
    program = config["program"]
    n, z = config["num_hidden_layers"], sizes(config)
    head = z["nope"] + z["rope"]
    return TransformerConfig(
        vocab=z["vocab"], d_model=z["d_model"], n_heads=z["n_heads"],
        d_head=head, n_layers=n, d_ff=z["d_ff"],
        attn=program.get("attn", "ulysses"),
        attn_impl=program.get("attn_impl", "flash"),
        dtype=jnp.dtype(config["torch_dtype"]),
        norm="rmsnorm", norm_eps=config["rms_norm_eps"], ffn="swiglu",
        tie_head=bool(config["tie_word_embeddings"]),
        layer_mixers=("mla",) * n,
        mla_q_rank=z["q_rank"], mla_kv_rank=z["kv_rank"],
        mla_nope_dim=z["nope"], mla_rope_dim=z["rope"], mla_v_dim=z["v"],
        rope_theta=theta,
        rope_table=yarn_rope_table(z["rope"], theta, factor, original,
                                   fast, slow),
        attn_scale=head ** -0.5 * _mscale(ms_all, factor) ** 2,
        layer_experts=tuple(li >= z["n_dense_layers"] for li in range(n)),
        n_experts=z["router_experts"], experts_held=held,
        experts_per_token=config["num_experts_per_tok"],
        d_expert=z["d_expert"], shared_experts=z["shared_experts"],
        route_scale=float(config["routed_scaling_factor"]),
        route_groups=config["n_group"],
        route_topk_groups=config["topk_group"],
        max_context=int(program["max_context"]),
        mtp_depth=z["mtp_depth"],
    )


def param_shapes(config: dict):
    """The pytree of shapes that the program's ``init_params`` returns
    for this configuration, written out for the reason
    ``weights.transformer_shapes`` gives (tests/chipbench holds the two
    against each other at a tiny size). The router scores ALL the
    experts, the matrices are the held ones'; the router and its bias
    are float32, as the program keeps them."""
    import jax
    import jax.numpy as jnp

    z = sizes(config)
    dtype = jnp.dtype(config["torch_dtype"])
    D, H, F = z["d_model"], z["n_heads"], z["d_expert"]
    E, Eh, R = z["router_experts"], z["held_experts"], z["kv_rank"]
    s = lambda *shape: jax.ShapeDtypeStruct(shape, dtype)
    f32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32)

    def layer(li):
        out = {
            "ln1_s": s(D), "ln2_s": s(D),
            "mla_wdq": s(D, z["q_rank"]), "mla_qn_s": s(z["q_rank"]),
            "mla_wuq": s(z["q_rank"], H, z["nope"] + z["rope"]),
            "mla_wdkv": s(D, R + z["rope"]), "mla_kvn_s": s(R),
            "mla_wukv": s(R, H, z["nope"] + z["v"]),
            "wo": s(H, z["v"], D),
        }
        if li < z["n_dense_layers"]:
            out.update({"w_gate": s(D, z["d_ff"]), "w_up": s(D, z["d_ff"]),
                        "w_down": s(z["d_ff"], D)})
        else:
            Fs = z["shared_experts"] * F
            out.update({
                "router": f32(D, E), "router_bias": f32(E),
                "we_gate": s(Eh, D, F), "we_up": s(Eh, D, F),
                "we_down": s(Eh, F, D),
                "ws_gate": s(D, Fs), "ws_up": s(D, Fs), "ws_down": s(Fs, D),
            })
        return out

    out = {
        "emb": s(z["vocab"], D),
        "layers": [layer(li) for li in range(z["n_layers"])],
        "lnf_s": s(D),
        "head": s(z["vocab"], D),
    }
    if z["mtp_depth"]:
        # the block is of the last layer's kind
        out["mtp"] = {"hn_s": s(D), "en_s": s(D), "eh_proj": s(2 * D, D),
                      "block": layer(z["n_layers"] - 1), "lnf_s": s(D)}
    return out


def make_params(config: dict, seed: int):
    """Seeded weights on the device (chipbench/weights.py), then every
    norm scale one (the module's three among them). The expert bias
    keeps its draw, small and not zero; the module's embedding and head
    are the model's arrays, so it has none of its own to draw. Made
    layer by layer, each from a seed of its own: the four expert layers
    and the module's block are one program's five runs, where one
    program over all 11 GB takes a minute to compile.

    Where the configuration has ``weights_draw``, every seed gets the
    SAME values, that draw's, in another order: the seed permutes the
    vocabulary's rows of embedding and head (and draws the prompts and
    the requests' keys, as everywhere). A draw of its own for every
    seed changes the work of this kind's step: which of the held
    experts the router likes is the weights', so the experts hit a
    step, and with them the bytes it reads, move with the seed (PERF.md
    section 2, ``serve_dsv3_chat``)."""
    import jax
    import jax.numpy as jnp

    z = sizes(config)
    shapes = param_shapes(config)
    draw = config.get("weights_draw")
    values = int(seed) if draw is None else int(draw["seed"])

    def make(tree, k: int):
        return weights.make_params(tree, values * 64 + k,
                                   d_model=z["d_model"],
                                   n_layers=z["n_layers"])

    params = make({k: v for k, v in shapes.items()
                   if k not in ("layers", "mtp")}, 0)
    params["layers"] = [make(lp, 1 + li)
                        for li, lp in enumerate(shapes["layers"])]
    if "mtp" in shapes:
        own = {k: v for k, v in shapes["mtp"].items() if k != "block"}
        params["mtp"] = {**make(own, 62),
                         "block": make(shapes["mtp"]["block"], 63)}

    def redraw(path, a):
        return (jnp.ones_like(a)
                if weights.leaf_name(path).endswith("_s") else a)

    def finish(p, key):
        p = jax.tree_util.tree_map_with_path(redraw, p)
        if draw is not None:
            order = jax.random.permutation(key, z["vocab"])
            p["emb"], p["head"] = p["emb"][order], p["head"][order]
        return p

    return jax.jit(finish, donate_argnums=(0,))(
        params, weights.seed_key(seed, ORDER_SALT))


def reference_sizes(config: dict) -> dict:
    """The reference's keywords for this configuration's sizes."""
    return dict(
        top_k=config["num_experts_per_tok"],
        route_scale=float(config["routed_scaling_factor"]),
        n_group=config["n_group"], topk_group=config["topk_group"],
        held_lo=config["experts_held"][0],
        kv_rank=config["kv_lora_rank"], nope=config["qk_nope_head_dim"],
        yarn=yarn(config),
    )


def request_key(seed: int, index: int):
    """The sampling key of the run's ``index``-th request."""
    import jax

    return jax.random.fold_in(weights.seed_key(seed, KEY_SALT), index)


def reference_rows(ref, config: dict, params, streams,
                   precision="float32"):
    """For each checked stream ``(prompt, served, key, drafts)``: ``(noisy,
    noisy_mtp)``, each (len(served), vocab). Row k of the first is the
    reference's logits for the served token k over the temperature plus
    the noise the request's key gives at that token's position; row k
    of the second the module's logits for the same token under the same
    noise (row 0, which nothing drafts, is zeros). Every stream is
    padded to ``max_context`` and every read is 256 rows, so each of
    the reference's programs compiles once."""
    import jax.numpy as jnp

    program = config["program"]
    max_context = int(program["max_context"])
    T = float(program["temperature"])
    rows = min(256, max_context)
    out = []
    for prompt, served, key, _ in streams:
        tp, n = len(prompt), len(served)
        if n > rows or tp + n > max_context:
            raise ValueError("a stream outgrew the reference's one shape")
        seq = np.zeros((max_context,), np.int32)
        seq[:tp] = prompt
        seq[tp:tp + n] = served
        first = min(tp - 1, max_context - rows)
        lg, mtp = ref.stream_logits(
            params, jnp.asarray(seq), first, rows, precision=precision,
            **reference_sizes(config))
        # the token at position tp + k is read from the model's row
        # tp + k - 1 and guessed by the module's row tp + k - 2
        at = tp - 1 - first
        scale, noise = 1.0, 0.0
        if T:
            scale = 1.0 / T
            noise = np.asarray(ref.gumbel_rows(key, tp - 1, n, lg.shape[-1]))
        noisy = np.asarray(lg)[at:at + n] * scale + noise
        noisy_mtp = np.zeros_like(noisy)
        if mtp is not None and n > 1:
            noisy_mtp[1:] = np.asarray(mtp)[at:at + n - 1] * scale
            if T:
                noisy_mtp[1:] += noise[1:]
        out.append((noisy, noisy_mtp))
    return out


def draft_numbers(streams, rows) -> dict:
    """The numbers of comparisons 2 and 3 over the checked streams:
    how far the drafted tokens lie below the best of the reference's
    module's noisy logits (worst, mean), and accepted / drafted of the
    program beside the reference's own at the same positions (its
    draft against its token)."""
    gaps, got, want = [], [], []
    for (_, _, _, drafts), (noisy, noisy_mtp) in zip(streams, rows):
        for at, tok, accepted in drafts:
            gaps.append(float(noisy_mtp[at].max() - noisy_mtp[at][tok]))
            got.append(bool(accepted))
            want.append(bool(noisy_mtp[at].argmax() == noisy[at].argmax()))
    if not gaps:
        return {"drafts": 0}
    return {"drafts": len(gaps), "draft_gap_worst": max(gaps),
            "draft_gap_mean": float(np.mean(gaps)),
            "accept_rate": float(np.mean(got)),
            "accept_rate_reference": float(np.mean(want))}


def control(run, precision: str) -> dict:
    """The reference in a lower precision, put in the program's place
    without decoding: at each position of the same prompts and served
    tokens, under the same noise, how far the token that the lower
    precision puts first lies below the float32 reference's best, for
    the model's logits and for the module's."""
    params, streams, rows = run.info["reference_dsv3"]
    ref = _model.reference_module(run)
    low = reference_rows(ref, run.config, params, streams, precision)
    worst, mean = _serve_loop.gap_numbers(
        [r[0] for r in rows], [lo[0].argmax(axis=-1) for lo in low])
    # the module's rows from the first drafted token on
    d_worst, d_mean = _serve_loop.gap_numbers(
        [r[1][1:] for r in rows], [lo[1][1:].argmax(axis=-1) for lo in low])
    return {"logit_gap_worst": worst, "logit_gap_mean": mean,
            "draft_gap_worst": d_worst, "draft_gap_mean": d_mean}


def submit_backlog(run, params, model, vocab: int):
    """``_serve_loop.submit_backlog`` with what this kind's program
    adds: the temperature, the drafter and a key a request, derived
    from ``--seed`` and the request's index."""
    from mpistragglers_jl_tpu.models.serving import ServingScheduler

    program = run.config["program"]
    with run.spans.span("setup_traffic"):
        requests = traffic_gen.ordered_requests(run.traffic)
        prompts = traffic_gen.prompts_for(requests, vocab, run.seed)
    with run.spans.span("setup_scheduler"):
        T = float(program["temperature"])
        sched = ServingScheduler(
            params, model, slots=int(program["slots"]),
            n_inner=int(program["n_inner"]),
            quantize_kv=bool(program["quantize_kv"]),
            page_tokens=int(program["page_tokens"]),
            prompt_chunk=int(program["prompt_chunk"]),
            max_prompt=int(program["max_prompt"]),
            temperature=T, draft=program.get("draft"),
        )
        reqs = [
            sched.submit(p, r[2], **(
                {"key": request_key(run.seed, i)} if T else {}))
            for i, (p, r) in enumerate(zip(prompts, requests))]
    print(f"note int8_decode_kernel_routed {bool(sched.use_kernel)} "
          f"draft {program.get('draft')} temperature {T}", flush=True)
    return sched, reqs


def run(run) -> None:
    import jax

    cfg, program = run.config, run.config["program"]
    # first of all: a program that cannot describe this block fails here,
    # before a weight is made
    model = transformer_config(cfg)
    sz = sizes(cfg)
    drafting = program.get("draft") is not None
    if not drafting:
        sz["mtp_depth"] = 0
    with run.spans.span("setup_weights"):
        params = make_params(cfg, run.seed)
        jax.block_until_ready(params)
    sched, reqs = submit_backlog(run, params, model, sz["vocab"])
    pages = {k: p.n_pages - 1 for k, p in sched.pools.items()}
    print(f"note pool_pages {pages}", flush=True)
    index_of = {id(r.prompt): i for i, r in enumerate(reqs)}
    mtp_hits = []
    step = sched.step

    def counted_step():
        out = step()
        if sched.mtp_experts_hit is not None:
            mtp_hits.append(sched.mtp_experts_hit)
        return out

    sched.step = counted_step
    # a row is one position in ONE cache layer: the model's layers and,
    # drafting, the module's
    layers = sz["n_layers"] + sz["mtp_depth"]
    served = _serve_loop.serve(
        run, sched, reqs, kv_rows=lambda length: layers * length)
    drafted = sum(len(r.drafts) for r in reqs)
    accepted = sum(d[2] for r in reqs for d in r.drafts)
    print(f"note drafts drafted {drafted} accepted {accepted} "
          f"accept_rate {accepted / max(1, drafted):.4f} "
          f"mtp_experts_hit_mean "
          f"{float(np.mean(mtp_hits)) if mtp_hits else 0.0:.2f}", flush=True)
    streams = []
    for prompt, tokens in served.streams:
        i = index_of[id(prompt)]
        streams.append((prompt, tokens, request_key(run.seed, i),
                        list(reqs[i].drafts)))
    del sched, reqs, step, counted_step
    mtp_hit = float(np.mean(mtp_hits)) if mtp_hits else 0.0
    run.info.update(
        weight_bytes=counts_dsv3.draft_step_weight_bytes(
            experts_hit=served.experts_hit, mtp_experts_hit=mtp_hit, **sz),
        kv_row_bytes=latent_row_bytes(
            kv_rank=sz["kv_rank"], rope=sz["rope"],
            quantized=bool(program["quantize_kv"])),
        experts_hit=served.experts_hit, mtp_experts_hit=mtp_hit,
        mtp_step_bytes=counts_dsv3.mtp_step_bytes(
            mtp_experts_hit=mtp_hit, **sz),
    )
    ref = _model.reference_module(run)
    rows = []

    def reference_logits(_streams):
        rows.extend(reference_rows(ref, cfg, params, streams))
        return [r[0] for r in rows]

    _serve_loop.judge(run, params, served.streams, reference_logits)
    run.info["reference_dsv3"] = (params, streams, rows)
    if not drafting:
        print("note no drafter: comparisons 2 and 3 have nothing to read",
              flush=True)
        return
    limits = cfg["limits"]
    d = draft_numbers(streams, rows)
    print(f"note draft_check {d}", flush=True)
    run.check.require("drafts_were_verified", d["drafts"] > 0)
    if d["drafts"]:
        run.check.at_most("drafted_token_logit_gap_worst",
                          d["draft_gap_worst"], limits["draft_gap_worst"])
        run.check.at_most("drafted_token_logit_gap_mean",
                          d["draft_gap_mean"], limits["draft_gap_mean"])
        run.check.at_most(
            "accept_rate_gap_to_reference",
            abs(d["accept_rate"] - d["accept_rate_reference"]),
            limits["accept_rate_gap"])
