"""Runner of the serving cells whose model has expert layers and layers
of more than one attention span (the ``afmoe`` block). The
configuration file's keys are the published ``config.json``'s; this
module turns them into the program's ``TransformerConfig`` (the layer
pattern as data) and into the pytree of shapes the weights are made
over, counts the bytes a step reads (chipbench/counts_moe.py: the
experts that got a token, K/V rows by layer kind) and brings the
reference (chipbench/references/afmoe.py, which takes each layer's
span) with its control. The run itself is
chipbench/runners/_serve_loop.py, as for runners/serve.py.
"""

from __future__ import annotations

import math

import numpy as np

from chipbench import counts_moe, weights
from chipbench.runners import _model, _serve_loop

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
    windows = layer_windows(cfg)
    with run.spans.span("setup_weights"):
        params = make_params(cfg, run.seed)
        jax.block_until_ready(params)
    sched, reqs = _serve_loop.submit_backlog(run, params, model, sz["vocab"])
    pages = {k: p.n_pages - 1 for k, p in sched.pools.items()}
    print(f"note pool_pages {pages}", flush=True)
    served = _serve_loop.serve(
        run, sched, reqs,
        kv_rows=lambda length: counts_moe.kv_layer_rows(length, windows))
    del sched, reqs
    # the existing readers multiply rows by bytes a row: here a row is
    # one position in ONE layer, and the rows are summed over the layers
    run.info.update(
        weight_bytes=counts_moe.step_weight_bytes(
            experts_hit=served.experts_hit, **sz),
        kv_row_bytes=counts_moe.kv_layer_row_bytes(
            kv_heads=sz["kv_heads"], head_dim=sz["head_dim"],
            quantized=bool(program["quantize_kv"])),
        experts_hit=served.experts_hit,
    )
    ref = _model.reference_module(run)
    _serve_loop.judge(
        run, params, served.streams,
        lambda streams: reference_gaps(
            ref, params, streams, windows, int(program["max_context"])),
    )
