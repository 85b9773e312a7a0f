"""The Falcon-H1 block (``TransformerConfig(layer_mixers=("attn_ssm",
...))``): attention and a Mamba-2 state-space mixer side by side in
every layer, one residual add for both, the family's multipliers as
data. Held to the plain reference (chipbench/references/falcon_h1.py,
which imports nothing of the program) through the dense forward,
chunked prefill, decoding through a cache that is rows AND a state, and
the serving scheduler.

Sizes: 4 query heads on 2 K/V heads of 16; 4 state-space heads of 16
with a state of 8 in 2 groups, a conv of 4 taps, sub-chunks of 8 rows;
every multiplier off one, so that none can be dropped unseen.

Tolerances, float32 weights on the CPU: the forms of one recurrence
differ in the order of float32 sums (1e-5 on values of order 1); a
quantized cache adds the int8 rounding of the K/V rows."""

import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.references import falcon_h1 as ref
from mpistragglers_jl_tpu.models import decode, serving, transformer
from mpistragglers_jl_tpu.models.serving import ServingScheduler
from mpistragglers_jl_tpu.models.transformer import (
    TransformerConfig,
    forward_dense,
    init_params,
)
from mpistragglers_jl_tpu.ops import ssm_step as ssm_kernel

SCALES = dict(
    emb_scale=2.0, head_scale=0.5, attn_in_scale=0.9, attn_out_scale=0.7,
    key_scale=0.5, ssm_in_scale=0.8, ssm_out_scale=1.3,
    ssm_scales=(0.9, 1.1, 0.8, 1.2, 0.7), ffn_gate_scale=0.6,
    ffn_down_scale=1.4)


def make_cfg(d_head=16, ssm_head_dim=16, **kw):
    return TransformerConfig(**{**dict(
        vocab=96, d_model=64, n_heads=4, n_kv_heads=2, d_head=d_head,
        n_layers=2, d_ff=96, norm="rmsnorm", ffn="swiglu", tie_head=False,
        layer_mixers=("attn_ssm",) * 2, ssm_heads=4,
        ssm_head_dim=ssm_head_dim, ssm_state=8, ssm_groups=2, ssm_conv=4,
        ssm_chunk=8, rope_theta=1e6, max_context=128, **SCALES), **kw})


def sizes_of(cfg) -> ref.Sizes:
    """The reference's ``Sizes`` from the program's configuration."""
    return ref.Sizes(
        heads=cfg.ssm_heads, head_dim=cfg.ssm_head_dim, state=cfg.ssm_state,
        groups=cfg.ssm_groups, conv=cfg.ssm_conv, eps=cfg.norm_eps,
        rope_theta=cfg.rope_theta, embedding_multiplier=cfg.emb_scale,
        lm_head_multiplier=cfg.head_scale,
        attention_in_multiplier=cfg.attn_in_scale,
        attention_out_multiplier=cfg.attn_out_scale,
        key_multiplier=cfg.key_scale, ssm_in_multiplier=cfg.ssm_in_scale,
        ssm_out_multiplier=cfg.ssm_out_scale,
        ssm_multipliers=tuple(cfg.ssm_scales),
        mlp_multipliers=(cfg.ffn_gate_scale, cfg.ffn_down_scale))


@pytest.fixture(scope="module")
def model():
    cfg = make_cfg()
    return cfg, init_params(cfg, 0)


def tokens(n, seed=1):
    return np.random.default_rng(seed).integers(0, 96, (n,)).astype(np.int32)


def program_state(cache_l):
    """A cache layer's state as the reference lays it out: S (H, P, N)
    and the conv's rows, of request 0."""
    return (np.asarray(cache_l["S"][0]).transpose(0, 2, 1),
            np.asarray(cache_l["conv"][0]))


# -- the recurrence: a row scan, sub-chunks, one step, the kernel --------------


def _rule_inputs(T, H=4, P=16, N=8, G=2, seed=3):
    rng = np.random.default_rng(seed)
    f = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)
    x, Bm, Cm = f(1, T, H, P), f(1, T, G, N), f(1, T, G, N)
    dt = jax.nn.softplus(f(1, T, H))
    A = -jnp.exp(f(H))
    return x, Bm, Cm, dt, A


def test_chunked_form_is_the_row_scan():
    T = 37  # no multiple of the sub-chunk's 8 rows divides it
    x, Bm, Cm, dt, A = _rule_inputs(T)
    S0 = jnp.zeros((4, 16, 8), jnp.float32)
    want, S_want = ref.ssm_rows(x[0], Bm[0], Cm[0], dt[0], A, S0, False)
    y, S = transformer._ssm_chunks(x, Bm, Cm, dt * A, dt,
                                   S0.transpose(0, 2, 1)[None], 8)
    np.testing.assert_allclose(y[0], want, atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(S[0].transpose(0, 2, 1), S_want, atol=1e-5,
                               rtol=1e-5)
    # the state carried across calls: ragged chunks, then single steps
    S, outs, off = S0.transpose(0, 2, 1)[None], [], 0
    for n in (11, 13):
        y, S = transformer._ssm_chunks(
            x[:, off:off + n], Bm[:, off:off + n], Cm[:, off:off + n],
            (dt * A)[:, off:off + n], dt[:, off:off + n], S, 8)
        outs.append(y)
        off += n
    for t in range(off, T):
        y, S = transformer._ssm_step(x[:, t], Bm[:, t], Cm[:, t],
                                     (dt * A)[:, t], dt[:, t], S)
        outs.append(y[:, None])
    np.testing.assert_allclose(jnp.concatenate(outs, 1)[0], want,
                               atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(S[0].transpose(0, 2, 1), S_want, atol=1e-5,
                               rtol=1e-5)


def test_padding_rows_leave_the_state_alone(model):
    cfg, params = model
    lp = params["layers"][0]
    x = jnp.asarray(np.random.default_rng(2).standard_normal((1, 16, 64)),
                    jnp.float32)
    rng = np.random.default_rng(5)
    state = {"S": jnp.asarray(rng.standard_normal((1, 4, 8, 16)),
                              jnp.float32),
             "conv": jnp.asarray(rng.standard_normal((1, 3, 96)),
                                 jnp.float32)}
    a, st = transformer.ssm_half(x, lp, state, cfg, jnp.int32(11))
    a_want, st_want = transformer.ssm_half(x[:, :11], lp, state, cfg)
    np.testing.assert_allclose(a[:, :11], a_want, atol=1e-6)
    for kk in ("S", "conv"):
        np.testing.assert_allclose(st[kk], st_want[kk], atol=1e-6)


def test_the_state_in_bfloat16_misses_the_tolerance():
    x, Bm, Cm, dt, A = _rule_inputs(70)
    S0 = jnp.zeros((4, 16, 8), jnp.float32)
    want, _ = ref.ssm_rows(x[0], Bm[0], Cm[0], dt[0], A, S0, False)
    low, _ = ref.ssm_rows(x[0], Bm[0], Cm[0], dt[0], A, S0, True)
    assert float(jnp.abs(low - want).max()) > 100 * 1e-5


@pytest.mark.parametrize("H,G,N,P", [(8, 1, 16, 128), (16, 2, 8, 128)])
def test_step_kernel_is_the_plain_step(H, G, N, P):
    """ops/ssm_step.py interpreted: S updated in place over several
    steps, against ``transformer._ssm_step``."""
    assert ssm_kernel.ssm_step_viable(H, G, N, P)
    rng = np.random.default_rng(9)
    f = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)
    S = S_want = f(3, H, N, P)
    for _ in range(4):
        x, Bm, Cm = f(3, H, P), f(3, G, N), f(3, G, N)
        dt = jax.nn.softplus(f(3, H))
        dA = -jnp.exp(f(3, H)) * dt
        y, S = ssm_kernel.ssm_step(x, Bm, Cm, dA, dt, S, interpret=True)
        y_want, S_want = transformer._ssm_step(x, Bm, Cm, dA, dt, S_want)
        np.testing.assert_allclose(y, y_want, atol=1e-5, rtol=1e-5)
        np.testing.assert_allclose(S, S_want, atol=1e-6, rtol=1e-6)
    # a row with a = 1 and dt = 0 leaves S bit for bit
    zero = jnp.zeros((3, H), jnp.float32)
    _, S2 = ssm_kernel.ssm_step(x, Bm, Cm, zero, zero, S, interpret=True)
    np.testing.assert_array_equal(S2, S)


def test_the_kernel_refuses_what_it_cannot_take():
    assert not ssm_kernel.ssm_step_viable(4, 2, 8, 16)     # lanes
    assert not ssm_kernel.ssm_step_viable(6, 4, 8, 128)    # groups
    assert ssm_kernel.ssm_step_viable(32, 2, 256, 128)     # the 34B's
    assert ssm_kernel._heads_per_step(32, 2, 256, 128) == 16
    with pytest.raises(ValueError, match="single-token kernel"):
        z = jnp.zeros
        ssm_kernel.ssm_step(z((1, 4, 16)), z((1, 2, 8)), z((1, 2, 8)),
                            z((1, 4)), z((1, 4)), z((1, 4, 8, 16)))
    assert transformer.ssm_rule_route(make_cfg(), 1) == "xla"
    wide = make_cfg(ssm_head_dim=128, ssm_heads=16)
    assert transformer.ssm_rule_route(wide, 1) == "kernel"
    assert transformer.ssm_rule_route(wide, 16) == "xla"


# -- the block against the reference -------------------------------------------


def test_dense_forward_is_the_references(model):
    cfg, params = model
    toks = tokens(45)
    want = ref.forward(params, jnp.asarray(toks), z=sizes_of(cfg))
    got = forward_dense(params, jnp.asarray(toks[None]), cfg)[0]
    np.testing.assert_allclose(got, want, atol=2e-6)


MOVED = ([(k, None) for k in ("emb_scale", "head_scale", "attn_in_scale",
                              "attn_out_scale", "key_scale", "ssm_in_scale",
                              "ssm_out_scale", "ffn_gate_scale",
                              "ffn_down_scale")]
         + [("ssm_scales", i) for i in range(5)])


@pytest.mark.parametrize("key,index", MOVED,
                         ids=[k if i is None else f"{k}{i}"
                              for k, i in MOVED])
def test_each_multiplier_moves_the_logits(model, key, index):
    """Moved off its value, every multiplier of the configuration moves
    the program's logits, and moves them as it moves the reference's."""
    cfg, params = model
    value = getattr(cfg, key)
    if index is None:
        moved = value * 1.5
    else:
        moved = tuple(v * (1.5 if i == index else 1.0)
                      for i, v in enumerate(value))
    other = dataclasses.replace(cfg, **{key: moved})
    toks = jnp.asarray(tokens(24, seed=4))
    base = forward_dense(params, toks[None], cfg)[0]
    got = forward_dense(params, toks[None], other)[0]
    assert float(jnp.abs(got - base).max()) > 1e-4
    want = ref.forward(params, toks, z=sizes_of(other))
    np.testing.assert_allclose(got, want, atol=2e-6)


@pytest.mark.parametrize("quantize", [False, True])
def test_chunks_then_decode_steps_give_the_references_logits(model,
                                                             quantize):
    """Prefill in chunks of 11 rows (no multiple of the sub-chunk's 8
    divides it; the conv's 3 rows and S cross every boundary), then a
    token at a time through the cache: logits, S and the conv's rows."""
    cfg, params = model
    toks, prompt, total = tokens(60, seed=7), 41, 60
    want, states = ref.forward(params, jnp.asarray(toks), z=sizes_of(cfg),
                               state=True)
    cache = decode.init_cache(cfg, 1, 64, quantize_kv=quantize)
    assert set(cache[0]) >= {"k", "v", "S", "conv"}
    outs, off = [], 0
    while off < prompt:
        c = min(11, prompt - off)
        lg, cache = decode._incremental_forward(
            params, jnp.asarray(toks[None, off:off + c]), cache,
            jnp.int32(off), cfg, prefill=False)
        outs.append(lg)
        off += c
    while off < total:
        lg, cache = decode.decode_step_dense(
            params, jnp.asarray(toks[off:off + 1]), cache, jnp.int32(off),
            cfg)
        outs.append(lg[:, None])
        off += 1
    got = jnp.concatenate(outs, axis=1)[0]
    np.testing.assert_allclose(got, want, atol=2e-3 if quantize else 2e-6)
    if not quantize:  # layer 0's state does not pass through a cache
        for cl, (S, conv) in zip(cache, states):
            got_S, got_conv = program_state(cl)
            np.testing.assert_allclose(got_S, S, atol=1e-5, rtol=1e-5)
            np.testing.assert_allclose(got_conv, conv, atol=1e-5)


def test_a_padded_chunk_is_the_chunk_of_its_real_rows(model):
    cfg, params = model
    toks = tokens(16, seed=8)
    run = lambda c, valid: decode._incremental_hidden(
        params, jnp.asarray(toks[None, :c]), decode.init_cache(cfg, 1, 32),
        jnp.int32(0), cfg, prefill=False, valid=valid)
    x, cache = run(16, jnp.int32(11))
    x_want, cache_want = run(11, None)
    np.testing.assert_allclose(x[:, :11], x_want, atol=1e-6)
    for cl, cw in zip(cache, cache_want):
        for kk in ("S", "conv"):
            np.testing.assert_allclose(cl[kk], cw[kk], atol=1e-6)


# -- the serving scheduler -----------------------------------------------------


def _serve(cfg, params, prompts, quantize, **kw):
    sched = ServingScheduler(params, cfg, slots=kw.pop("slots", 4), n_inner=4,
                             quantize_kv=quantize, page_tokens=8,
                             prompt_chunk=16, max_prompt=96, **kw)
    reqs = [sched.submit(p, n) for p, n in prompts]
    sched.run()
    return sched, reqs


def _gaps(cfg, params, reqs):
    """How far each served token lies below the reference's best."""
    out = []
    for r in reqs:
        seq = np.concatenate([r.prompt, np.asarray(r.tokens, np.int32)])
        lg = np.asarray(ref.forward(params, jnp.asarray(seq),
                                    z=sizes_of(cfg)))
        rows = lg[len(r.prompt) - 1:len(seq) - 1]
        out.append(rows.max(-1) - rows[np.arange(len(r.tokens)), r.tokens])
    return np.concatenate(out)


PROMPTS = [(50, 30), (20, 25), (90, 8), (10, 6), (33, 5)]


@pytest.mark.parametrize("quantize", [False, True])
def test_scheduler_serves_the_references_tokens(model, quantize):
    """Five requests through four slots: every layer holds pages AND a
    state a slot (the gathered-view route here)."""
    cfg, params = model
    sched, reqs = _serve(cfg, params,
                         [(tokens(a, seed=a), b) for a, b in PROMPTS],
                         quantize)
    assert not sched.use_kernel and not sched.shares_prefixes
    assert sched.state_resets == len(PROMPTS)
    assert all(len(r.tokens) == b for r, (_, b) in zip(reqs, PROMPTS))
    assert _gaps(cfg, params, reqs).max() <= (2e-3 if quantize else 1e-6)
    layer = sched._caches[0]
    assert layer["S"].shape == (4, 4, 8, 16) and layer["k"].ndim == 3
    assert layer["conv"].shape == (4, 3, 4 * 16 + 2 * 2 * 8)


def test_the_kernels_route_serves_the_references_tokens():
    """Heads of 128 in both mixers (16 state-space heads, a group's 8
    a grid step) and an int8 cache: the tick takes
    the paged attention kernel AND the step kernel (both interpreted
    here), every slot's S updated where it lies."""
    cfg = make_cfg(d_head=128, ssm_head_dim=128, ssm_heads=16)
    params = init_params(cfg, 0)
    sched, reqs = _serve(cfg, params,
                         [(tokens(a, seed=a), b) for a, b in PROMPTS[:4]],
                         True)
    assert sched.use_kernel
    assert sched._step_route == {"ssm_rule": "kernel"}
    assert sched._rule_routes == {"ssm_rule": "xla"}
    assert _gaps(cfg, params, reqs).max() <= 2e-3


def test_a_slot_taken_again_serves_the_second_request_alone(model):
    """One slot, two requests: the second's stream is the stream it has
    alone (its S and conv rows written over the first's, pages of its
    own)."""
    cfg, params = model
    a, b = (tokens(70, seed=11), 20), (tokens(45, seed=12), 30)
    sched, both = _serve(cfg, params, [a, b], True, slots=1)
    _, alone = _serve(cfg, params, [b], True, slots=1)
    assert both[1].tokens == alone[0].tokens
    assert sched.state_resets == 2
    assert _gaps(cfg, params, both).max() <= 2e-3


def test_the_published_heads_take_the_paged_kernel_route():
    """20 query heads in groups of 5 to a K/V head of 128, pages of 64:
    a group no cell has run; the q tile is 8 rows."""
    from mpistragglers_jl_tpu.ops.decode_attention import _group_tile

    cfg = make_cfg(d_head=128, n_heads=20, n_kv_heads=4, d_model=80)
    assert decode._paged_kernel_possible(cfg, True, 64)
    assert not decode._paged_kernel_possible(cfg, False, 64)
    assert _group_tile(20 // 4) == 8


def test_other_paths_refuse_by_mechanism(model):
    cfg, params = model
    from jax.sharding import Mesh

    from mpistragglers_jl_tpu.models.transformer import make_train_step

    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1, 1),
                ("dp", "sp", "tp"))
    with pytest.raises(ValueError, match="state-space mixer"):
        make_train_step(cfg, mesh)
    with pytest.raises(ValueError, match="sharded tick.*state-space mixer"):
        serving.make_serving_scan(cfg, mesh, 4)
    with pytest.raises(ValueError, match="has no width"):
        decode.ring_widths(cfg)
    with pytest.raises(ValueError, match="state-space mixer needs"):
        make_cfg(ssm_groups=3)
    with pytest.raises(ValueError, match="five"):
        make_cfg(ssm_scales=(1.0, 1.0))
    from mpistragglers_jl_tpu.qos import TenantContract, TenantRegistry

    qos = TenantRegistry([TenantContract("a")])
    with pytest.raises(ValueError, match="page quotas.*state-space mixer"):
        ServingScheduler(params, cfg, slots=2, page_tokens=8,
                         prompt_chunk=16, max_prompt=96, qos=qos)


def test_scopes_and_span_arguments(model):
    cfg, params = model
    arena = serving._fresh_cache(cfg, 1, 96, False)
    text = serving._extend_chunk_dense(cfg, 16, 96).lower(
        params, np.zeros((1, 16), np.int32), arena, np.int32(0),
        np.int32(16)).as_text(debug_info=True)
    for scope in ("ssm_proj", "ssm_conv", "ssm_rule", "ssm_out", "attn_qkv",
                  "chunk_attn", "attn_out"):
        assert f"serving_prefill_chunk)/{scope}/" in text, scope
    sched = ServingScheduler(params, cfg, slots=2, n_inner=4,
                             page_tokens=8, prompt_chunk=16, max_prompt=96)
    # (inside the scan's body a path starts at the body)
    text = sched.lower_tick().as_text(debug_info=True)
    parts = {part for path in re.findall(r'loc\("([^"]+)"', text)
             for part in path.split("/")}
    assert parts >= {"ssm_proj", "ssm_conv", "ssm_rule", "ssm_out",
                     "attn_qkv", "decode_attn", "attn_out", "decode_mlp"}
    assert sched._rule_routes == {"ssm_rule": "xla"}
    assert sched._step_route == {"ssm_rule": "xla"}
