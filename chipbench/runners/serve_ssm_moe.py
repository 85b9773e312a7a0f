"""Runner of the serving cells whose model holds a Mamba-2 state-space
mixer ALONE in most layers (state and no K/V row), attention without
rotary in the others, and behind every mixer small softmax-routed
experts of which this chip holds a share beside one always-on gated
MLP (the ``granitemoehybrid`` block). The configuration file's keys are
the published ``config.json``'s; this module turns them into the
program's ``TransformerConfig`` (the layer pattern, the share and the
family's four constants as data) and into the pytree of shapes the
weights are made over, counts the bytes a step moves
(chipbench/counts_ssm_moe.py: the weights outside the experts, the held
experts that got a token, every slot's state in the state-space layers,
the attention layers' K/V rows, the head) and brings the reference
(chipbench/references/granitemoehybrid.py) with its controls. The run
itself is chipbench/runners/_serve_loop.py, as for runners/serve.py.
"""

from __future__ import annotations

import math

import numpy as np

from chipbench import counts_moe, counts_ssm, counts_ssm_moe, weights
from chipbench.runners import _model, _serve_loop

READ_ROWS = 256  # rows of a stream the reference's head reads
ORDER_SALT = 77  # the seed's order of the vocabulary under weights_draw

# what the block is written for; a file that says otherwise is refused
FIXED = {
    "attention_bias": False, "mamba_proj_bias": False,
    "mamba_conv_bias": True, "hidden_act": "silu",
    "normalization_function": "rmsnorm", "position_embedding_type": "nope",
    "rope_scaling": None, "tie_word_embeddings": True,
}
MIXERS = {"mamba": "ssm", "attention": "attn"}


def check_block(config: dict) -> None:
    for key, value in FIXED.items():
        if config[key] != value:
            raise ValueError(f"{key} {config[key]!r}: the granitemoehybrid "
                             f"block is written for {value!r}")
    if len(config["layer_types"]) != config["num_hidden_layers"]:
        raise ValueError("layer_types names every layer that runs")
    if (config["mamba_n_heads"] * config["mamba_d_head"]
            != config["mamba_expand"] * config["hidden_size"]):
        raise ValueError("mamba_n_heads heads of mamba_d_head are "
                         "mamba_expand times the hidden size")
    lo, hi = config["experts_held"]
    if hi - lo != config["num_local_experts"]:
        raise ValueError("experts_held does not hold num_local_experts")
    if config["shared_intermediate_size"] % config["intermediate_size"]:
        raise ValueError("the shared MLP is a whole number of expert widths")


def layer_mixers(config: dict) -> tuple:
    """Each layer's token mixer in the program's names."""
    return tuple(MIXERS[t] for t in config["layer_types"])


def sizes(config: dict) -> dict:
    return {
        "d_model": config["hidden_size"],
        "n_heads": config["num_attention_heads"],
        "kv_heads": config["num_key_value_heads"],
        # the published file has no head_dim: the hidden size over the heads
        "head_dim": config["hidden_size"] // config["num_attention_heads"],
        "d_expert": config["intermediate_size"],
        "d_shared": config["shared_intermediate_size"],
        "router_experts": config["router_experts"],
        "experts_held": config["num_local_experts"],
        "n_layers": config["num_hidden_layers"],
        "ssm_layers": layer_mixers(config).count("ssm"),
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
    n = z["n_layers"]
    return TransformerConfig(
        vocab=z["vocab"], d_model=z["d_model"], n_heads=z["n_heads"],
        n_kv_heads=z["kv_heads"], d_head=z["head_dim"], n_layers=n,
        d_ff=z["d_shared"],
        attn=program.get("attn", "ulysses"),
        attn_impl=program.get("attn_impl", "flash"),
        dtype=jnp.dtype(config["torch_dtype"]),
        norm="rmsnorm", norm_eps=config["rms_norm_eps"], ffn="swiglu",
        tie_head=True,
        # no rotary anywhere: no layer has a window
        rope_full=False, rope_theta=float(config["rope_theta"]),
        emb_scale=float(config["embedding_multiplier"]),
        attn_scale=float(config["attention_multiplier"]),
        residual_scale=float(config["residual_multiplier"]),
        # the departure the file lists: on the final norm's output and
        # not on the logits (a power of two: the same bits)
        head_scale=1.0 / float(config["logits_scaling"]),
        layer_mixers=layer_mixers(config),
        ssm_heads=z["ssm_heads"], ssm_head_dim=z["ssm_head_dim"],
        ssm_state=z["ssm_state"], ssm_groups=z["ssm_groups"],
        ssm_conv=z["ssm_conv"], ssm_chunk=config["mamba_chunk_size"],
        layer_experts=(True,) * n, n_experts=z["router_experts"],
        experts_held=tuple(config["experts_held"]),
        experts_per_token=config["num_experts_per_tok"],
        d_expert=z["d_expert"],
        shared_experts=z["d_shared"] // z["d_expert"],
        route_score="softmax",
        max_context=int(program["max_context"]),
    )


def reference_sizes(ref, config: dict):
    """The reference's ``Sizes`` from the same file."""
    z = sizes(config)
    return ref.Sizes(
        heads=z["ssm_heads"], head_dim=z["ssm_head_dim"],
        state=z["ssm_state"], groups=z["ssm_groups"], conv=z["ssm_conv"],
        eps=float(config["rms_norm_eps"]),
        top_k=config["num_experts_per_tok"],
        held_lo=config["experts_held"][0],
        embedding_multiplier=float(config["embedding_multiplier"]),
        residual_multiplier=float(config["residual_multiplier"]),
        attention_multiplier=float(config["attention_multiplier"]),
        logits_scaling=float(config["logits_scaling"]))


def param_shapes(config: dict):
    """The pytree of shapes that the program's ``init_params`` returns
    for this configuration, written out for the reason
    ``weights.transformer_shapes`` gives (tests/chipbench holds the two
    against each other at a tiny size). The router, ``A_log``,
    ``dt_bias`` and ``D`` are float32, as the program keeps them; the
    head is the embedding."""
    import jax
    import jax.numpy as jnp

    z = sizes(config)
    dtype = jnp.dtype(config["torch_dtype"])
    D, H, Hkv, Dh = z["d_model"], z["n_heads"], z["kv_heads"], z["head_dim"]
    E, Eh, F, Fs = (z["router_experts"], z["experts_held"], z["d_expert"],
                    z["d_shared"])
    ssm = {k: z[k] for k in ("ssm_heads", "ssm_head_dim", "ssm_state",
                             "ssm_groups")}
    wide = z["ssm_heads"] * z["ssm_head_dim"]
    chans = counts_ssm.ssm_conv_channels(**ssm)
    s = lambda *shape: jax.ShapeDtypeStruct(shape, dtype)
    f32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32)

    def layer(mixer):
        if mixer == "ssm":
            out = {
                "ssm_win": s(D, counts_ssm.ssm_proj_width(**ssm)),
                "ssm_conv_w": s(z["ssm_conv"], chans),
                "ssm_conv_b": s(chans),
                "ssm_A_log": f32(z["ssm_heads"]),
                "ssm_dt_bias": f32(z["ssm_heads"]),
                "ssm_D": f32(z["ssm_heads"]),
                "ssm_norm_s": s(wide), "ssm_wout": s(wide, D),
            }
        else:
            out = {"wq": s(D, H, Dh), "wk": s(D, Hkv, Dh),
                   "wv": s(D, Hkv, Dh), "wo": s(H, Dh, D)}
        out.update({
            "ln1_s": s(D), "ln2_s": s(D), "router": f32(D, E),
            "we_gate": s(Eh, D, F), "we_up": s(Eh, D, F),
            "we_down": s(Eh, F, D),
            "ws_gate": s(D, Fs), "ws_up": s(D, Fs), "ws_down": s(Fs, D),
        })
        return out

    return {
        "emb": s(z["vocab"], D),
        "layers": [layer(m) for m in layer_mixers(config)],
        "lnf_s": s(D),
    }


def make_params(config: dict, seed: int):
    """Seeded weights on the device (chipbench/weights.py), then, as
    the configuration's ``assumed.initializer`` lists (Mamba-2's
    reference initialisation, each from the leaf's own normal draw ``x``
    through its distribution function ``u = Phi(x / sd)``, as
    runners/serve_ssm.py does): every norm scale and ``D`` one; the
    conv's taps and bias uniform in (-0.5, 0.5); ``A`` uniform in [1,
    16]; dt log-uniform in [0.001, 0.1] with ``dt_bias`` its inverse
    softplus; the mixer's out-projection divided by sqrt(2 * layers)
    like ``wo``; and the embedding divided by ``embedding_multiplier``,
    so that ``x0 = emb[tok] * embedding_multiplier`` starts the residual
    stream at the 1/sqrt(hidden) a component every configuration here
    starts it at. The head is TIED: with rows of 1/sqrt(hidden) times
    12 in the stream, a position's own token scores 2.3 against 0.27
    for the best of the other 100,351, every served token is the token
    before it, and the comparison ranks nothing (PERF.md section 6, PR
    51: every control read 0 and 0). Made layer by layer, each from a seed of its own: ten
    runs of two programs (a mamba layer's, the attention layer's),
    where one program over all 9.9 GB takes a minute to compile.

    Where the configuration has ``weights_draw``, every seed gets that
    draw's values with the vocabulary's rows of the (tied) embedding in
    an order the seed draws, as runners/serve_dsv3.py ``make_params``
    does and for its reason."""
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
        if name == "emb":
            return (a.astype(jnp.float32)
                    / float(config["embedding_multiplier"])).astype(a.dtype)
        return a

    def finish(p, key):
        p = jax.tree_util.tree_map_with_path(redraw, p)
        if draw is not None:
            p["emb"] = p["emb"][jax.random.permutation(key, z["vocab"])]
        return p

    return jax.jit(finish, donate_argnums=(0,))(
        params, weights.seed_key(seed, ORDER_SALT))


def reference_gaps(ref, config: dict, params, streams,
                   precision="float32"):
    """For each (prompt, served tokens): the reference's logits at the
    served positions, row by row, every stream padded to the program's
    ``max_context`` and ``READ_ROWS`` rows read (the mix's longest
    answer; all of a shorter context), so that the reference compiles
    each of its programs once in every run. Neither a causal layer nor
    a recurrence looks ahead, and a row's experts are its own, so the
    padding changes no row that is read."""
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


def held_state(sched, reqs, model):
    """What the window left in the slots, read before the scheduler is
    dropped: of the requests still decoding, the one with the longest
    history (the same request in every run: the schedule follows from
    lengths), as ``(tokens fed so far, S of each state-space layer)``,
    S on the host as the reference lays it out, (heads, head_dim,
    d_state) float32. ``ServingScheduler.state_of`` says how many rows
    the state stands behind."""
    from mpistragglers_jl_tpu.models.transformer import ssm_state_heads

    req = max((r for r in reqs if len(r.tokens) > 1 and not r.finished),
              key=lambda r: len(r.prompt) + len(r.tokens))
    rows, layers = sched.state_of(req)
    fed = np.concatenate(
        [np.asarray(req.prompt), np.asarray(req.tokens, np.int32)])[:rows]
    # the kept layout to a block a head, then (N, P) to (P, N): on the
    # host, so that no program is made for it
    kept = [ssm_state_heads(np.asarray(st["S"])[None], model)[0]
            .transpose(0, 2, 1) for st in layers if st is not None]
    return fed, kept


def reference_state(ref, config: dict, params, fed, precision="float32"):
    """The reference's S of every mamba layer behind the rows ``fed``,
    the stream padded like :func:`reference_gaps`'s (the same
    programs)."""
    import jax.numpy as jnp

    seq = np.zeros((int(config["program"]["max_context"]),), np.int32)
    seq[:len(fed)] = fed
    return [np.asarray(S) for S in ref.stream_states(
        params, jnp.asarray(seq), len(fed),
        z=reference_sizes(ref, config), precision=precision)]


def bfloat16_share(states) -> float:
    """The share of the values of S that a bfloat16 holds exactly (the
    low 16 bits of the float32 are zero): next to none of a float32
    recurrence's, all of a state that is kept in bfloat16."""
    bits = np.concatenate([S.ravel() for S in states]).view(np.uint32)
    return float(((bits & 0xFFFF) == 0).mean())


def state_gap(states, ref_states) -> float:
    """How far S lies from the reference's, a layer at a time: the
    norm of the difference over the reference's norm, the largest of
    the layers'."""
    return max(float(np.linalg.norm(S - R) / np.linalg.norm(R))
               for S, R in zip(states, ref_states))


def judge_state(run, ref, fed, kept) -> None:
    """The one number of ``correct`` that sees S on the chip: the
    state is float32 where it lies, at this shape and in the layout
    the step kernel keeps (PERF.md section 2 says why the distance to
    the reference's S cannot decide that; it is printed)."""
    params = run.info["reference"][0]
    with run.spans.span("reference_state"):
        want = reference_state(ref, run.config, params, fed)
    run.info["held_state"] = (fed, want)
    print(f"note held_state rows {len(fed)} layers {len(kept)} "
          f"state_gap {state_gap(kept, want):.3e} reference_state_s "
          f"{run.spans.durations('reference_state')[0]:.2f}", flush=True)
    run.check.at_most("served_state_bfloat16_share", bfloat16_share(kept),
                      run.config["limits"]["state_bfloat16_share"])


def control(run, precision: str) -> dict:
    """The reference in a lower precision, put in the program's place
    without decoding: at each position of the same prompts and served
    tokens, how far the token that the lower precision puts first lies
    below the float32 reference's best; and its S behind the rows the
    checked slot was fed. ``fp8`` / ``int8`` round both inputs of every
    matrix product (the router's too); ``s_bf16`` keeps the state S in
    bfloat16 and every product in float32."""
    params, streams, ref_logits = run.info["reference"]
    fed, want = run.info["held_state"]
    ref = _model.reference_module(run)
    low = reference_gaps(ref, run.config, params, streams, precision)
    worst, mean = _serve_loop.gap_numbers(
        ref_logits, [lo.argmax(axis=-1) for lo in low])
    states = reference_state(ref, run.config, params, fed, precision)
    return {"logit_gap_worst": worst, "logit_gap_mean": mean,
            "state_bfloat16_share": bfloat16_share(states),
            "state_gap": state_gap(states, want)}


def run(run) -> None:
    import jax

    from mpistragglers_jl_tpu.models.transformer import ssm_rule_route

    cfg, program = run.config, run.config["program"]
    # first of all: a program that cannot describe this block fails here,
    # before a weight is made
    model = transformer_config(cfg)
    sz = sizes(cfg)
    with run.spans.span("setup_weights"):
        params = make_params(cfg, run.seed)
        jax.block_until_ready(params)
    sched, reqs = _serve_loop.submit_backlog(run, params, model, sz["vocab"])
    print("note ssm_step_kernel_routed "
          f"{ssm_rule_route(model, 1) == 'kernel'}", flush=True)
    pages = {k: p.n_pages - 1 for k, p in sched.pools.items()}
    print(f"note pool_pages {pages} state_slots {sched.S}", flush=True)
    # K/V rows: the attention layers alone have any
    attn_layers = sz["n_layers"] - sz["ssm_layers"]
    served = _serve_loop.serve(
        run, sched, reqs, kv_rows=lambda length: attn_layers * length)
    print(f"note state_resets {sched.state_resets}", flush=True)
    fed, kept = held_state(sched, reqs, model)
    del sched, reqs
    moved = counts_ssm_moe.step_bytes(
        experts_hit=served.experts_hit, slots=int(program["slots"]), **sz)
    print("note step_bytes " + " ".join(
        f"{k}={int(v)}" for k, v in moved.items()), flush=True)
    # the readers of a step's bytes add K/V rows to ``weight_bytes``:
    # what a step moves besides them is the weights and the state
    run.info.update(
        weight_bytes=(moved["outside_experts"] + moved["experts"]
                      + moved["head"] + moved["state"]),
        ssm_state_bytes=moved["state"],
        ssm_proj_bytes=moved["ssm_proj"],
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
    judge_state(run, ref, fed, kept)
