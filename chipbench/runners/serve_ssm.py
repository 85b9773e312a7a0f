"""Runner of the serving cells whose model holds attention AND a
Mamba-2 state-space mixer side by side in every layer, over a dense
gated feed-forward (the ``falcon_h1`` block). The configuration file's
keys are the published ``config.json``'s; this module turns them into
the program's ``TransformerConfig`` (the mixer's sizes and the family's
multipliers as data) and into the pytree of shapes the weights are made
over, counts the bytes a step moves (chipbench/counts_ssm.py: the
weights, every slot's state in every layer, the K/V rows of every
layer) and brings the reference (chipbench/references/falcon_h1.py)
with its controls. The run itself is chipbench/runners/_serve_loop.py,
as for runners/serve.py.
"""

from __future__ import annotations

import math

import numpy as np

from chipbench import counts_moe, counts_ssm, weights
from chipbench.runners import _model, _serve_loop

READ_ROWS = 256  # rows of a stream the reference's head reads

# what the block is written for; a file that says otherwise is refused
FIXED = {
    "attention_bias": False, "mamba_proj_bias": False, "mlp_bias": False,
    "projectors_bias": False, "mamba_conv_bias": True,
    "mamba_rms_norm": True, "mamba_norm_before_gate": False,
    "mamba_use_mlp": True, "attn_layer_indices": None,
    "rope_scaling": None, "hidden_act": "silu",
    "tie_word_embeddings": False,
}


def check_block(config: dict) -> None:
    for key, value in FIXED.items():
        if config[key] != value:
            raise ValueError(f"{key} {config[key]!r}: the falcon_h1 block "
                             f"is written for {value!r}")
    if config["mamba_d_ssm"] != (config["mamba_n_heads"]
                                 * config["mamba_d_head"]):
        raise ValueError("mamba_d_ssm is mamba_n_heads heads of "
                         "mamba_d_head")


def sizes(config: dict) -> dict:
    return {
        "d_model": config["hidden_size"],
        "n_heads": config["num_attention_heads"],
        "kv_heads": config["num_key_value_heads"],
        "head_dim": config["head_dim"],
        "d_ff": config["intermediate_size"],
        "n_layers": config["num_hidden_layers"],
        "vocab": config["vocab_size"],
        "ssm_heads": config["mamba_n_heads"],
        "ssm_head_dim": config["mamba_d_head"],
        "ssm_state": config["mamba_d_state"],
        "ssm_groups": config["mamba_n_groups"],
        "ssm_conv": config["mamba_d_conv"],
    }


def transformer_config(config: dict):
    import jax.numpy as jnp

    from mpistragglers_jl_tpu.models.transformer import TransformerConfig

    check_block(config)
    program, z = config["program"], sizes(config)
    m_gate, m_down = config["mlp_multipliers"]
    return TransformerConfig(
        vocab=z["vocab"], d_model=z["d_model"], n_heads=z["n_heads"],
        n_kv_heads=z["kv_heads"], d_head=z["head_dim"],
        n_layers=z["n_layers"], d_ff=z["d_ff"],
        attn=program.get("attn", "ulysses"),
        attn_impl=program.get("attn_impl", "flash"),
        dtype=jnp.dtype(config["torch_dtype"]),
        norm="rmsnorm", norm_eps=config["rms_norm_eps"], ffn="swiglu",
        tie_head=bool(config["tie_word_embeddings"]),
        rope_theta=float(config["rope_theta"]),
        emb_scale=float(config["embedding_multiplier"]),
        # the departure the file lists: on the final norm's output and
        # not on the logits (a power of two: the same bits)
        head_scale=float(config["lm_head_multiplier"]),
        layer_mixers=("attn_ssm",) * z["n_layers"],
        ssm_heads=z["ssm_heads"], ssm_head_dim=z["ssm_head_dim"],
        ssm_state=z["ssm_state"], ssm_groups=z["ssm_groups"],
        ssm_conv=z["ssm_conv"], ssm_chunk=config["mamba_chunk_size"],
        attn_in_scale=float(config["attention_in_multiplier"]),
        attn_out_scale=float(config["attention_out_multiplier"]),
        key_scale=float(config["key_multiplier"]),
        ssm_in_scale=float(config["ssm_in_multiplier"]),
        ssm_out_scale=float(config["ssm_out_multiplier"]),
        ssm_scales=tuple(float(m) for m in config["ssm_multipliers"]),
        ffn_gate_scale=float(m_gate), ffn_down_scale=float(m_down),
        max_context=int(program["max_context"]),
    )


def reference_sizes(ref, config: dict):
    """The reference's ``Sizes`` from the same file."""
    z = sizes(config)
    return ref.Sizes(
        heads=z["ssm_heads"], head_dim=z["ssm_head_dim"],
        state=z["ssm_state"], groups=z["ssm_groups"], conv=z["ssm_conv"],
        eps=float(config["rms_norm_eps"]),
        rope_theta=float(config["rope_theta"]),
        embedding_multiplier=float(config["embedding_multiplier"]),
        lm_head_multiplier=float(config["lm_head_multiplier"]),
        attention_in_multiplier=float(config["attention_in_multiplier"]),
        attention_out_multiplier=float(config["attention_out_multiplier"]),
        key_multiplier=float(config["key_multiplier"]),
        ssm_in_multiplier=float(config["ssm_in_multiplier"]),
        ssm_out_multiplier=float(config["ssm_out_multiplier"]),
        ssm_multipliers=tuple(float(m) for m in config["ssm_multipliers"]),
        mlp_multipliers=tuple(float(m) for m in config["mlp_multipliers"]))


def param_shapes(config: dict):
    """The pytree of shapes that the program's ``init_params`` returns
    for this configuration, written out for the reason
    ``weights.transformer_shapes`` gives (tests/chipbench holds the two
    against each other at a tiny size). ``A_log``, ``dt_bias`` and ``D``
    are float32, as the program keeps them."""
    import jax
    import jax.numpy as jnp

    z = sizes(config)
    dtype = jnp.dtype(config["torch_dtype"])
    D, H, Hkv, Dh, F = (z["d_model"], z["n_heads"], z["kv_heads"],
                        z["head_dim"], z["d_ff"])
    ssm = {k: z[k] for k in ("ssm_heads", "ssm_head_dim", "ssm_state",
                             "ssm_groups")}
    wide = z["ssm_heads"] * z["ssm_head_dim"]
    chans = counts_ssm.ssm_conv_channels(**ssm)
    s = lambda *shape: jax.ShapeDtypeStruct(shape, dtype)
    f32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32)
    layer = lambda: {
        "ln1_s": s(D),
        "ssm_win": s(D, counts_ssm.ssm_proj_width(**ssm)),
        "ssm_conv_w": s(z["ssm_conv"], chans), "ssm_conv_b": s(chans),
        "ssm_A_log": f32(z["ssm_heads"]), "ssm_dt_bias": f32(z["ssm_heads"]),
        "ssm_D": f32(z["ssm_heads"]),
        "ssm_norm_s": s(wide), "ssm_wout": s(wide, D),
        "wq": s(D, H, Dh), "wk": s(D, Hkv, Dh), "wv": s(D, Hkv, Dh),
        "wo": s(H, Dh, D), "ln2_s": s(D),
        "w_gate": s(D, F), "w_up": s(D, F), "w_down": s(F, D),
    }
    return {
        "emb": s(z["vocab"], D),
        "layers": [layer() for _ in range(z["n_layers"])],
        "lnf_s": s(D),
        "head": s(z["vocab"], D),
    }


def make_params(config: dict, seed: int):
    """Seeded weights on the device (chipbench/weights.py), then, as
    the configuration's ``assumed.initializer`` lists (Mamba-2's
    reference initialisation, each from the leaf's own normal draw ``x``
    through its distribution function ``u = Phi(x / sd)``, so that no
    second key is needed): every norm scale and ``D`` one; the conv's
    taps and bias uniform in (-0.5, 0.5); ``A`` uniform in [1, 16]; dt
    log-uniform in [0.001, 0.1] with ``dt_bias`` its inverse softplus;
    the mixer's out-projection divided by sqrt(2 * layers) like
    ``wo``. Made layer by layer, each from a seed of its own: the six
    layers are one program's six runs, where one program over all 10.5
    GB takes a minute to compile (runners/serve_dsv3.py's finding)."""
    import jax
    import jax.numpy as jnp

    z = sizes(config)
    shapes = param_shapes(config)

    def make(tree, k: int):
        return weights.make_params(tree, int(seed) * 64 + k,
                                   d_model=z["d_model"],
                                   n_layers=z["n_layers"])

    params = make({k: v for k, v in shapes.items() if k != "layers"}, 0)
    params["layers"] = [make(lp, 1 + li)
                        for li, lp in enumerate(shapes["layers"])]
    sd = 1.0 / math.sqrt(z["d_model"])
    uniform = lambda a: jax.scipy.special.ndtr(a.astype(jnp.float32) / sd)

    def redraw(path, a):
        name = weights.leaf_name(path)
        if name.endswith("_s") or name == "ssm_D":
            return jnp.ones_like(a)
        if name in ("ssm_conv_w", "ssm_conv_b"):
            return (uniform(a) - 0.5).astype(a.dtype)
        if name == "ssm_A_log":
            return jnp.log(1.0 + 15.0 * uniform(a))
        if name == "ssm_dt_bias":
            dt = jnp.exp(math.log(1e-3) + uniform(a) * math.log(1e2))
            dt = jnp.maximum(dt, 1e-4)
            return dt + jnp.log(-jnp.expm1(-dt))
        if name == "ssm_wout":
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
    served positions, row by row, every stream padded to the program's
    ``max_context`` and ``READ_ROWS`` rows read (the mix's longest
    answer; all of a shorter context), so that the reference
    compiles each of its programs once in every run. Neither a causal
    layer nor a recurrence looks ahead, so the padding changes no row
    that is read."""
    import jax.numpy as jnp

    z = reference_sizes(ref, config)
    length = int(config["program"]["max_context"])
    rows = min(READ_ROWS, length)
    out = []
    for prompt, served in streams:
        tp, n = len(prompt), len(served)
        if n > rows or tp + n > length:
            raise ValueError("a stream outgrew the reference's shapes")
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
    below the float32 reference's best. ``fp8`` / ``int8`` round both
    inputs of every matrix product; ``s_bf16`` keeps the state S in
    bfloat16 and every product in float32."""
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
    row_bytes = counts_moe.kv_layer_row_bytes(
        kv_heads=sz["kv_heads"], head_dim=sz["head_dim"],
        quantized=bool(program["quantize_kv"]))
    # K/V rows: every layer keeps them, and attends all a request has
    served = _serve_loop.serve(
        run, sched, reqs, kv_rows=lambda length: sz["n_layers"] * length)
    print(f"note state_resets {sched.state_resets}", flush=True)
    del sched, reqs
    state_bytes = counts_ssm.step_state_bytes(
        slots=int(program["slots"]), n_layers=sz["n_layers"],
        ssm_heads=sz["ssm_heads"], ssm_head_dim=sz["ssm_head_dim"],
        ssm_state=sz["ssm_state"])
    # the readers of a step's bytes add K/V rows to ``weight_bytes``:
    # what a step moves besides them is the weights and the state
    run.info.update(
        weight_bytes=counts_ssm.step_weight_bytes(**sz) + state_bytes,
        ssm_state_bytes=state_bytes,
        ssm_layers=sz["n_layers"],
        kv_row_bytes=row_bytes,
    )
    ref = _model.reference_module(run)
    _serve_loop.judge(
        run, params, served.streams,
        lambda streams: reference_gaps(ref, cfg, params, streams),
    )
