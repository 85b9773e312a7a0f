"""Runner of the serving cells whose model mixes gated delta-rule
layers with gated attention layers over experts of which this chip
holds a share (the ``qwen3_next`` block). The configuration file's keys
are the published ``config.json``'s; this module turns them into the
program's ``TransformerConfig`` (mixer and share as data) and into the
pytree of shapes the weights are made over, counts the bytes a step
moves (chipbench/counts_gdn.py: the held experts that got a token, the
recurrent state, the one attention layer's K/V rows) and brings the
reference (chipbench/references/qwen3_next.py) with its control. The
run itself is chipbench/runners/_serve_loop.py, as for runners/serve.py.
"""

from __future__ import annotations

import math

import numpy as np

from chipbench import counts_gdn, counts_moe, weights
from chipbench.runners import _model, _serve_loop


def layer_mixers(config: dict) -> tuple:
    """Each layer's token mixer: full attention where ``(i + 1) %
    full_attention_interval == 0``, the gated delta rule elsewhere."""
    every = config["full_attention_interval"]
    return tuple("attn" if (i + 1) % every == 0 else "gdn"
                 for i in range(config["num_hidden_layers"]))


def sizes(config: dict) -> dict:
    return {
        "d_model": config["hidden_size"],
        "n_heads": config["num_attention_heads"],
        "kv_heads": config["num_key_value_heads"],
        "head_dim": config["head_dim"],
        "key_heads": config["linear_num_key_heads"],
        "value_heads": config["linear_num_value_heads"],
        "key_dim": config["linear_key_head_dim"],
        "value_dim": config["linear_value_head_dim"],
        "conv": config["linear_conv_kernel_dim"],
        "d_expert": config["moe_intermediate_size"],
        "d_shared": config["shared_expert_intermediate_size"],
        "router_experts": config["router_experts"],
        "n_layers": config["num_hidden_layers"],
        "gdn_layers": layer_mixers(config).count("gdn"),
        "vocab": config["vocab_size"],
    }


def transformer_config(config: dict):
    import jax.numpy as jnp

    from mpistragglers_jl_tpu.models.transformer import TransformerConfig

    if config["mlp_only_layers"] or config["decoder_sparse_step"] != 1:
        raise ValueError("every layer's feed-forward is the experts'")
    held = tuple(config["experts_held"])
    if held[1] - held[0] != config["num_experts"]:
        raise ValueError("experts_held does not hold num_experts experts")
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
        rope_theta=float(config["rope_theta"]),
        rope_dims=int(config["partial_rotary_factor"] * config["head_dim"]),
        layer_mixers=layer_mixers(config),
        gdn_key_heads=config["linear_num_key_heads"],
        gdn_value_heads=config["linear_num_value_heads"],
        gdn_key_dim=config["linear_key_head_dim"],
        gdn_value_dim=config["linear_value_head_dim"],
        gdn_conv=config["linear_conv_kernel_dim"],
        layer_experts=(True,) * n,
        n_experts=config["router_experts"],
        experts_held=held,
        experts_per_token=config["num_experts_per_tok"],
        d_expert=config["moe_intermediate_size"],
        shared_experts=(config["shared_expert_intermediate_size"]
                        // config["moe_intermediate_size"]),
        route_score="softmax",
        shared_gate=True,
        max_context=int(program["max_context"]),
    )


def param_shapes(config: dict):
    """The pytree of shapes that the program's ``init_params`` returns
    for this configuration, written out for the reason
    ``weights.transformer_shapes`` gives (tests/chipbench holds the two
    against each other at a tiny size). The router, ``A_log`` and
    ``dt_bias`` are float32, as the program keeps them."""
    import jax
    import jax.numpy as jnp

    z = sizes(config)
    dtype = jnp.dtype(config["torch_dtype"])
    D, H, Hkv, Dh = z["d_model"], z["n_heads"], z["kv_heads"], z["head_dim"]
    Hv, kw = z["value_heads"], z["key_heads"] * z["key_dim"]
    vw = Hv * z["value_dim"]
    E, Eh, F, Fs = (z["router_experts"], config["num_experts"],
                    z["d_expert"], z["d_shared"])
    s = lambda *shape: jax.ShapeDtypeStruct(shape, dtype)
    f32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32)

    def layer(mixer):
        if mixer == "gdn":
            out = {
                "gdn_wqkvz": s(D, 2 * kw + 2 * vw), "gdn_wba": s(D, 2 * Hv),
                "gdn_conv_w": s(z["conv"], 2 * kw + vw),
                "gdn_A_log": f32(Hv), "gdn_dt_bias": f32(Hv),
                "gdn_norm_s": s(z["value_dim"]), "gdn_wout": s(vw, D),
            }
        else:
            out = {
                "qn_s": s(Dh), "kn_s": s(Dh),
                "wq": s(D, H, Dh), "wk": s(D, Hkv, Dh), "wv": s(D, Hkv, Dh),
                "wo": s(H, Dh, D), "wog": s(D, H, Dh),
            }
        out.update({
            "ln1_s": s(D), "ln2_s": s(D), "router": f32(D, E),
            "we_gate": s(Eh, D, F), "we_up": s(Eh, D, F),
            "we_down": s(Eh, F, D),
            "ws_gate": s(D, Fs), "ws_up": s(D, Fs), "ws_down": s(Fs, D),
            "ws_sgate": s(D, 1),
        })
        return out

    return {
        "emb": s(z["vocab"], D),
        "layers": [layer(m) for m in layer_mixers(config)],
        "lnf_s": s(D),
        "head": s(z["vocab"], D),
    }


def make_params(config: dict, seed: int):
    """Seeded weights on the device (chipbench/weights.py), then, as
    the configuration's ``assumed`` lists: every norm scale one; in a
    delta-rule layer ``A_log`` and ``dt_bias`` drawn as the published
    initialisation draws them (A uniform in (0, 16], dt log-uniform in
    [0.001, 0.1], ``dt_bias`` its inverse softplus), so that the decay
    ``exp(g)`` lies mostly between 0.9 and 0.999 and the state
    remembers hundreds of tokens (a plain normal draw halves it every
    token, and a state lost at a chunk boundary would pass the
    comparison); the conv taps uniform in +-1/sqrt(taps); and the
    out-projection divided by sqrt(2 * layers) like ``wo``."""
    import jax
    import jax.numpy as jnp

    z = sizes(config)
    params = weights.make_params(
        param_shapes(config), seed, d_model=z["d_model"],
        n_layers=z["n_layers"],
    )
    taps = z["conv"]

    def redraw(path, a):
        name = weights.leaf_name(path)
        if name.endswith("_s"):
            return jnp.ones_like(a)
        if not name.startswith("gdn_"):
            return a
        li = int(weights.leaf_name(path[:-1]))
        u = lambda salt, lo, hi: jax.random.uniform(
            weights.seed_key(seed, 1000 + 8 * li + salt), a.shape,
            jnp.float32, lo, hi)
        if name == "gdn_A_log":
            return jnp.log(16.0 * (1.0 - u(0, 0.0, 1.0)))
        if name == "gdn_dt_bias":
            dt = jnp.exp(u(1, math.log(1e-3), math.log(1e-1)))
            return dt + jnp.log(-jnp.expm1(-dt))
        if name == "gdn_conv_w":
            return u(2, -taps ** -0.5, taps ** -0.5).astype(a.dtype)
        if name == "gdn_wout":
            return (a.astype(jnp.float32)
                    / math.sqrt(2.0 * z["n_layers"])).astype(a.dtype)
        return a

    return jax.jit(
        lambda p: jax.tree_util.tree_map_with_path(redraw, p),
        donate_argnums=(0,),
    )(params)


PAD_ROWS = 4096   # a stream is padded to a whole number of these
READ_ROWS = 256   # and as many rows are read: once, or four times


def reference_shape(config: dict, prompt_len: int, served: int):
    """(padded length, rows read) of the reference's program for one
    stream: the next whole number of ``PAD_ROWS`` at or past the
    stream's end, at most ``max_context`` (a cell whose contexts stop
    under ``PAD_ROWS`` has one length), and ``READ_ROWS`` rows or four
    times as many, so a cell's streams compile a handful of programs
    and a chat stream does not pay for the longest document."""
    max_context = int(config["program"]["max_context"])
    end = prompt_len + served
    rows = READ_ROWS if served <= READ_ROWS else 4 * READ_ROWS
    if served > rows or end > max_context:
        raise ValueError("a stream outgrew the reference's shapes")
    length = min(max_context, -(-end // PAD_ROWS) * PAD_ROWS)
    return length, min(rows, length)


def reference_gaps(ref, config: dict, params, streams,
                   precision="float32"):
    """For each (prompt, served tokens): the reference's logits at the
    served positions, row by row: runners/serve_moe.py's function of
    this name for this block, with the stream padded and read as
    ``reference_shape`` says. Neither a causal layer nor a recurrence
    looks ahead, so the padding changes no row that is read."""
    import jax.numpy as jnp

    out = []
    for prompt, served in streams:
        tp, n = len(prompt), len(served)
        length, rows = reference_shape(config, tp, n)
        seq = np.zeros((length,), np.int32)
        seq[:tp] = prompt
        seq[tp:tp + n] = served
        first = min(tp - 1, length - rows)
        lg = np.asarray(ref.stream_logits(
            params, jnp.asarray(seq), first, rows, precision=precision,
            top_k=config["num_experts_per_tok"],
            held_lo=config["experts_held"][0],
            key_heads=config["linear_num_key_heads"],
            key_dim=config["linear_key_head_dim"],
            rope_dims=int(config["partial_rotary_factor"]
                          * config["head_dim"]),
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
    print(f"note pool_pages {pages} state_slots {sched.S}", flush=True)
    # K/V rows: the attention layers alone have any
    attn_layers = sz["n_layers"] - sz["gdn_layers"]
    served = _serve_loop.serve(
        run, sched, reqs, kv_rows=lambda length: attn_layers * length)
    del sched, reqs
    state = {k: sz[k] for k in ("key_heads", "value_heads", "key_dim",
                                "value_dim", "conv")}
    state_bytes = counts_gdn.step_state_bytes(
        slots=int(program["slots"]), gdn_layers=sz["gdn_layers"], **state)
    # the readers of a step's bytes add K/V rows to ``weight_bytes``:
    # what a step moves besides them is the weights and the state
    run.info.update(
        weight_bytes=counts_gdn.step_weight_bytes(
            experts_hit=served.experts_hit, **sz) + state_bytes,
        state_bytes=state_bytes,
        # of which S alone, the part the single-token update works on
        state_S_bytes=2 * int(program["slots"]) * sz["gdn_layers"]
        * counts_gdn.gdn_state_bytes(**state)[0],
        kv_row_bytes=counts_moe.kv_layer_row_bytes(
            kv_heads=sz["kv_heads"], head_dim=sz["head_dim"],
            quantized=bool(program["quantize_kv"])),
        experts_hit=served.experts_hit,
    )
    ref = _model.reference_module(run)
    _serve_loop.judge(
        run, params, served.streams,
        lambda streams: reference_gaps(ref, cfg, params, streams),
    )
