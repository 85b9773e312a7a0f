"""Runner of the serving cells whose model mixes decayed
linear-attention layers with attention that reads a selection of its
key blocks, over a dense gated feed-forward (the ``minicpm_sala``
block). The configuration file's keys are the published
``config.json``'s, the selection's seven sizes its
``assumed.sparse_config``; this module turns them into the program's
``TransformerConfig`` (mixers, selection and scalings as data) and into
the pytree of shapes the weights are made over, counts the bytes a step
moves (chipbench/counts_sala.py: the weights, the recurrent state, and
of the one attention layer's cache what the selection MUST read) and
brings the reference (chipbench/references/minicpm_sala.py) with its
control. The run itself is chipbench/runners/_serve_loop.py, as for
runners/serve.py.
"""

from __future__ import annotations

import math

import numpy as np

from chipbench import counts_moe, counts_sala, weights
from chipbench.runners import _model, _serve_loop
from chipbench.runners.serve_gdn import reference_shape

MIXERS = {"minicpm4": "attn", "lightning-attn": "la"}


def layer_mixers(config: dict) -> tuple:
    """Each layer's token mixer in the program's names."""
    types = config["mixer_types"]
    if len(types) != config["num_hidden_layers"]:
        raise ValueError("mixer_types names another number of layers")
    return tuple(MIXERS[t] for t in types)


def published_layers(config: dict) -> int:
    return int(config.get("published", config)["num_hidden_layers"])


def selection(config: dict) -> dict:
    """The selection's sizes under the counts' names."""
    s = config["assumed"]["sparse_config"]
    return {"block": s["block_size"], "topk": s["topk"],
            "kernel": s["kernel_size"], "stride": s["kernel_stride"],
            "init_blocks": s["init_blocks"], "window": s["window_size"],
            "dense_len": s["dense_len"]}


def residual_scale(config: dict) -> float:
    return config["scale_depth"] / math.sqrt(published_layers(config))


def sizes(config: dict) -> dict:
    return {
        "d_model": config["hidden_size"],
        "n_heads": config["num_attention_heads"],
        "kv_heads": config["num_key_value_heads"],
        "head_dim": config["head_dim"],
        "la_heads": config["lightning_nh"],
        "la_head_dim": config["lightning_head_dim"],
        "d_ff": config["intermediate_size"],
        "n_layers": config["num_hidden_layers"],
        "la_layers": layer_mixers(config).count("la"),
        "vocab": config["vocab_size"],
    }


def transformer_config(config: dict):
    import jax.numpy as jnp

    from mpistragglers_jl_tpu.models.transformer import TransformerConfig

    if config["lightning_nkv"] != config["lightning_nh"]:
        raise ValueError("a lightning-attn layer's k and v have a head "
                         "each of the query's")
    if config["attn_use_rope"] or not config["lightning_use_rope"]:
        raise ValueError("rotary is the lightning-attn layers' alone")
    program, sel = config["program"], selection(config)
    return TransformerConfig(
        vocab=config["vocab_size"],
        d_model=config["hidden_size"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        d_head=config["head_dim"],
        n_layers=config["num_hidden_layers"],
        d_ff=config["intermediate_size"],
        attn=program.get("attn", "ulysses"),
        attn_impl=program.get("attn_impl", "flash"),
        dtype=jnp.dtype(config["torch_dtype"]),
        norm="rmsnorm",
        norm_eps=config["rms_norm_eps"],
        ffn="swiglu",
        tie_head=bool(config["tie_word_embeddings"]),
        qk_norm=bool(config["qk_norm"]),
        attn_gate=bool(config["attn_use_output_gate"]),
        rope_full=bool(config["attn_use_rope"]),
        rope_theta=float(config["rope_theta"]),
        emb_scale=float(config["scale_emb"]),
        residual_scale=residual_scale(config),
        head_scale=config["dim_model_base"] / config["hidden_size"],
        layer_mixers=layer_mixers(config),
        la_heads=config["lightning_nh"],
        la_head_dim=config["lightning_head_dim"],
        sparse_block=sel["block"], sparse_topk=sel["topk"],
        sparse_kernel=sel["kernel"], sparse_stride=sel["stride"],
        sparse_init_blocks=sel["init_blocks"],
        sparse_window=sel["window"], sparse_dense_len=sel["dense_len"],
        max_context=int(program["max_context"]),
    )


def reference_sizes(ref, config: dict):
    """The reference's ``Sizes`` from the same file."""
    return ref.Sizes(
        **selection(config), scale_emb=float(config["scale_emb"]),
        residual=residual_scale(config),
        head_divisor=config["hidden_size"] / config["dim_model_base"])


def param_shapes(config: dict):
    """The pytree of shapes that the program's ``init_params`` returns
    for this configuration, written out for the reason
    ``weights.transformer_shapes`` gives (tests/chipbench holds the two
    against each other at a tiny size). The decay exponents are
    float32, as the program keeps them."""
    import jax
    import jax.numpy as jnp

    z = sizes(config)
    dtype = jnp.dtype(config["torch_dtype"])
    D, H, Hkv, Dh = z["d_model"], z["n_heads"], z["kv_heads"], z["head_dim"]
    Hl, Dl, F = z["la_heads"], z["la_head_dim"], z["d_ff"]
    s = lambda *shape: jax.ShapeDtypeStruct(shape, dtype)

    def layer(mixer):
        if mixer == "la":
            out = {
                "la_wq": s(D, Hl, Dl), "la_wk": s(D, Hl, Dl),
                "la_wv": s(D, Hl, Dl), "la_qn_s": s(Dl), "la_kn_s": s(Dl),
                "la_wz": s(D, Hl * Dl),
                "la_slope": jax.ShapeDtypeStruct((Hl,), jnp.float32),
                "la_norm_s": s(Hl * Dl), "la_wo": s(Hl * Dl, D),
            }
        else:
            out = {
                "qn_s": s(Dh), "kn_s": s(Dh),
                "wq": s(D, H, Dh), "wk": s(D, Hkv, Dh), "wv": s(D, Hkv, Dh),
                "wo": s(H, Dh, D), "wog": s(D, H, Dh),
            }
        out.update({"ln1_s": s(D), "ln2_s": s(D), "w_gate": s(D, F),
                    "w_up": s(D, F), "w_down": s(F, D)})
        return out

    return {
        "emb": s(z["vocab"], D),
        "layers": [layer(m) for m in layer_mixers(config)],
        "lnf_s": s(D),
        "head": s(z["vocab"], D),
    }


def la_slopes(heads: int, layer: int, layers: int) -> np.ndarray:
    """The configuration's ``assumed.decay``: ``s_h = 2 ** (-8 (h + 1)
    / heads)`` times ``1 - layer / (layers - 1) + 1e-5``, ``layer`` the
    PUBLISHED index among the published ``layers``."""
    base = 2.0 ** (-8.0 * (np.arange(heads) + 1) / heads)
    return base * (1.0 - layer / (layers - 1) + 1e-5)


def make_params(config: dict, seed: int):
    """Seeded weights on the device (chipbench/weights.py), then, as
    the configuration's ``assumed`` lists: every norm scale one; a
    lightning-attn layer's decay exponents those of its published index
    (the cut keeps the first layers, so a layer's index here is its
    published one); its out-projection divided by sqrt(2 * layers)
    like ``wo``."""
    import jax
    import jax.numpy as jnp

    z = sizes(config)
    params = weights.make_params(
        param_shapes(config), seed, d_model=z["d_model"],
        n_layers=z["n_layers"],
    )
    deep = published_layers(config)

    def redraw(path, a):
        name = weights.leaf_name(path)
        if name.endswith("_s"):
            return jnp.ones_like(a)
        if name == "la_slope":
            li = int(weights.leaf_name(path[:-1]))
            return jnp.asarray(la_slopes(a.shape[0], li, deep), jnp.float32)
        if name == "la_wo":
            return (a.astype(jnp.float32)
                    / math.sqrt(2.0 * z["n_layers"])).astype(a.dtype)
        return a

    return jax.jit(
        lambda p: jax.tree_util.tree_map_with_path(redraw, p),
        donate_argnums=(0,),
    )(params)


def reference_gaps(ref, config: dict, params, streams,
                   precision="float32"):
    """For each (prompt, served tokens): the reference's logits at the
    served positions, row by row, the stream padded and read as
    runners/serve_gdn.py's ``reference_shape`` says (the next whole
    4,096 rows, 256 rows read or 1,024). Neither a causal layer nor a recurrence
    looks ahead, and a selection is made among the rows a query sees,
    so the padding changes no row that is read."""
    import jax.numpy as jnp

    z = reference_sizes(ref, config)
    out = []
    for prompt, served in streams:
        tp, n = len(prompt), len(served)
        length, rows = reference_shape(config, tp, n)
        seq = np.zeros((length,), np.int32)
        seq[:tp] = prompt
        seq[tp:tp + n] = served
        first = min(tp - 1, length - rows)
        lg = np.asarray(ref.stream_logits(
            params, jnp.asarray(seq), first, rows, z=z,
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
    sz, sel = sizes(cfg), selection(cfg)
    with run.spans.span("setup_weights"):
        params = make_params(cfg, run.seed)
        jax.block_until_ready(params)
    sched, reqs = _serve_loop.submit_backlog(run, params, model, sz["vocab"])
    pages = {k: p.n_pages - 1 for k, p in sched.pools.items()}
    print(f"note pool_pages {pages} state_slots {sched.S}", flush=True)
    row_bytes = counts_moe.kv_layer_row_bytes(
        kv_heads=sz["kv_heads"], head_dim=sz["head_dim"],
        quantized=bool(program["quantize_kv"]))
    attn_layers = sz["n_layers"] - sz["la_layers"]
    # K/V rows: the attention layers alone have any, and of a long
    # request's the mechanism must read a selection (and the pooled
    # keys it is made from, in rows' worth of bytes)
    served = _serve_loop.serve(
        run, sched, reqs,
        kv_rows=lambda length: attn_layers * counts_sala.must_read_rows(
            length, kv_heads=sz["kv_heads"], head_dim=sz["head_dim"],
            row_bytes=row_bytes, **sel))
    del sched, reqs
    state_bytes = counts_sala.step_state_bytes(
        slots=int(program["slots"]), la_layers=sz["la_layers"],
        heads=sz["la_heads"], head_dim=sz["la_head_dim"])
    # the readers of a step's bytes add K/V rows to ``weight_bytes``:
    # what a step moves besides them is the weights and the state
    run.info.update(
        weight_bytes=counts_sala.step_weight_bytes(**sz) + state_bytes,
        la_state_bytes=state_bytes,
        kv_row_bytes=row_bytes,
    )
    ref = _model.reference_module(run)
    _serve_loop.judge(
        run, params, served.streams,
        lambda streams: reference_gaps(ref, cfg, params, streams),
    )
