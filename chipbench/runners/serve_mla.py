"""Runner of the serving cells whose model keeps one latent row a
position (multi-head latent attention) under a residual path of several
streams, over a dense feed-forward in the leading layers and sigmoid
top-k experts behind them (the ``xing4_0`` block). The configuration
file's keys are the published ``config.json``'s; this module turns them
into the program's ``TransformerConfig`` (mixer, YaRN's table, the
softmax scale and the streams as data) and into the pytree of shapes
the weights are made over, counts the bytes a step reads
(chipbench/counts_mla.py: the experts that got a token, the latent rows
the decoding slots attend) and brings the reference
(chipbench/references/xing4_0.py) with its control. The run itself is
chipbench/runners/_serve_loop.py, as for runners/serve.py.
"""

from __future__ import annotations

import math

import numpy as np

from chipbench import counts_mla, weights
from chipbench.runners import _model, _serve_loop


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
        "n_experts": config["n_routed_experts"],
        "shared_experts": config["n_shared_experts"],
        "n_layers": config["num_hidden_layers"],
        "n_dense_layers": config["first_k_dense_replace"],
        "vocab": config["vocab_size"],
        "hc_mult": config["hc_mult"],
    }


def yarn(config: dict) -> tuple:
    """``(theta, factor, original_max, beta_fast, beta_slow, mscale,
    mscale_all_dim)``, as the reference takes them."""
    s = config["rope_scaling"]
    if s["type"] != "yarn":
        raise ValueError(f"rope_scaling of type {s['type']!r}")
    return (float(config["rope_theta"]), float(s["factor"]),
            int(s["original_max_position_embeddings"]),
            float(s["beta_fast"]), float(s["beta_slow"]),
            float(s["mscale"]), float(s["mscale_all_dim"]))


def _mscale(scale: float, factor: float) -> float:
    return 0.1 * scale * math.log(factor) + 1.0 if factor > 1 else 1.0


def transformer_config(config: dict):
    import jax.numpy as jnp

    from mpistragglers_jl_tpu.models.transformer import (
        HC_EPS,
        HC_RES_CLAMP,
        TransformerConfig,
        yarn_rope_table,
    )

    if (config["moe_layer_freq"] != 1 or config["n_group"] != 1
            or config["topk_group"] != 1 or config["attention_bias"]
            or config["scoring_func"] != "sigmoid"
            or not config["norm_topk_prob"]
            or config["num_key_value_heads"]
            != config["num_attention_heads"]
            or config["hc_eps"] != HC_EPS
            or (config["mhc_h_res_clamp_min"],
                config["mhc_h_res_clamp_max"]) != HC_RES_CLAMP):
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
        hc_mult=z["hc_mult"],
        hc_sinkhorn_iters=config["hc_sinkhorn_iters"],
        layer_experts=tuple(li >= z["n_dense_layers"] for li in range(n)),
        n_experts=z["n_experts"],
        experts_per_token=config["num_experts_per_tok"],
        d_expert=z["d_expert"], shared_experts=z["shared_experts"],
        route_scale=float(config["routed_scaling_factor"]),
        max_context=int(program["max_context"]),
    )


def param_shapes(config: dict):
    """The pytree of shapes that the program's ``init_params`` returns
    for this configuration, written out for the reason
    ``weights.transformer_shapes`` gives (tests/chipbench holds the two
    against each other at a tiny size). The router with its bias and the
    residual mixing are float32, as the program keeps them."""
    import jax
    import jax.numpy as jnp

    z = sizes(config)
    dtype = jnp.dtype(config["torch_dtype"])
    D, H, E, F = z["d_model"], z["n_heads"], z["n_experts"], z["d_expert"]
    R, n = z["kv_rank"], z["hc_mult"]
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
        for half in ("hc1", "hc2"):
            out.update({half + "_phi": f32(n * D, 2 * n + n * n),
                        half + "_alpha": f32(3),
                        half + "_b": f32(2 * n + n * n)})
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
    """Seeded weights on the device (chipbench/weights.py), then, as
    the configuration's ``assumed.initializer`` lists: every norm scale
    one; the residual mixing's ``phi`` brought to standard deviation
    ``1/sqrt(n d)`` (it is drawn at ``1/sqrt(d)`` like every matrix),
    its three ``alpha`` one (away from zero: the matrices follow the
    token, so a fault in ``phi`` shows) and its biases zero but for 2 on
    the diagonal of the stream-to-stream part and, where
    hyper-connections start their static matrices, +-2 so that the
    model's k-th half reads mostly stream k mod n (the program's
    ``hc_bias``).
    The expert bias keeps its draw, small and not zero."""
    import jax
    import jax.numpy as jnp

    from mpistragglers_jl_tpu.models.transformer import hc_bias

    z = sizes(config)
    n = z["hc_mult"]
    params = weights.make_params(
        param_shapes(config), seed, d_model=z["d_model"],
        n_layers=z["n_layers"],
    )

    def redraw(path, a):
        name = weights.leaf_name(path)
        if name.endswith("_s") or name.endswith("_alpha"):
            return jnp.ones_like(a)
        if name.endswith("_phi"):
            return a / math.sqrt(n)
        if name in ("hc1_b", "hc2_b"):
            half = 2 * int(weights.leaf_name(path[:-1])) + (name == "hc2_b")
            return jnp.asarray(hc_bias(n, half), a.dtype)
        return a

    return jax.jit(
        lambda p: jax.tree_util.tree_map_with_path(redraw, p),
        donate_argnums=(0,),
    )(params)


def reference_sizes(config: dict) -> dict:
    """The reference's keywords for this configuration's sizes."""
    return dict(
        hc_mult=config["hc_mult"], top_k=config["num_experts_per_tok"],
        route_scale=float(config["routed_scaling_factor"]),
        kv_rank=config["kv_lora_rank"], nope=config["qk_nope_head_dim"],
        iters=config["hc_sinkhorn_iters"], yarn=yarn(config),
    )


def reference_gaps(ref, config: dict, params, streams,
                   precision="float32"):
    """For each (prompt, served tokens): the reference's logits at the
    served positions, row by row: runners/serve_moe.py's function of
    this name (every stream padded to ``max_context``, every read 256
    rows, so each of the reference's programs compiles once) for this
    block. A causal layer never looks ahead, so the padding changes no
    row that is read."""
    import jax.numpy as jnp

    max_context = int(config["program"]["max_context"])
    rows = min(256, max_context)
    out = []
    for prompt, served in streams:
        tp, n = len(prompt), len(served)
        if n > rows or tp + n > max_context:
            raise ValueError("a stream outgrew the reference's one shape")
        seq = np.zeros((max_context,), np.int32)
        seq[:tp] = prompt
        seq[tp:tp + n] = served
        first = min(tp - 1, max_context - rows)
        lg = np.asarray(ref.stream_logits(
            params, jnp.asarray(seq), first, rows, precision=precision,
            **reference_sizes(config),
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
    low = reference_gaps(ref, run.config, params, streams, precision)
    worst, mean = _serve_loop.gap_numbers(
        ref_logits, [lo.argmax(axis=-1) for lo in low])
    return {"logit_gap_worst": worst, "logit_gap_mean": mean}


def run(run) -> None:
    import jax

    cfg, program = run.config, run.config["program"]
    # first of all: a program that cannot describe this block fails here,
    # before a weight is made
    model = transformer_config(cfg)
    sz = sizes(cfg)
    with run.spans.span("setup_weights"):
        params = make_params(cfg, run.seed)
        jax.block_until_ready(params)
    sched, reqs = _serve_loop.submit_backlog(run, params, model, sz["vocab"])
    pages = {k: p.n_pages - 1 for k, p in sched.pools.items()}
    print(f"note pool_pages {pages}", flush=True)
    # a row is one position in ONE layer; every layer keeps one
    served = _serve_loop.serve(
        run, sched, reqs, kv_rows=lambda length: sz["n_layers"] * length)
    del sched, reqs
    run.info.update(
        weight_bytes=counts_mla.step_weight_bytes(
            experts_hit=served.experts_hit, **sz),
        kv_row_bytes=counts_mla.latent_row_bytes(
            kv_rank=sz["kv_rank"], rope=sz["rope"],
            quantized=bool(program["quantize_kv"])),
        experts_hit=served.experts_hit,
    )
    ref = _model.reference_module(run)
    _serve_loop.judge(
        run, params, served.streams,
        lambda streams: reference_gaps(ref, cfg, params, streams),
    )
