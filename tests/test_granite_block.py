"""The Granite-4.0-H block (``TransformerConfig(layer_mixers=(... "ssm"
...))``): a Mamba-2 state-space mixer ALONE in a layer (state and no
K/V row), attention without rotary in the others, softmax-routed
experts of which a share is held beside one always-on gated MLP behind
every mixer, the family's four constants as data. Held to the plain
reference (chipbench/references/granitemoehybrid.py, which imports
nothing of the program) through the dense forward, chunked prefill,
decoding through a cache whose layers are state-only or row-only, and
the serving scheduler.

Two sizes, both (ssm, ssm, attn, ssm) over 8 experts of 32, 3 a token,
4 query heads on 2 K/V heads of 16: ``plain`` has 8 state-space heads
of 16 at a state of 8 in 2 groups (the kernel does not take it: the
state is kept a block a head, the step is the plain one); ``packed``
has 4 heads of 32 in one group, four a lane tile (the step kernel's
layout, (1, 8, 128) a request, interpreted here). Every constant off
one, so that none can be dropped unseen.

Tolerances, float32 weights on the CPU: the forms of one recurrence
differ in the order of float32 sums (1e-5 on values of order 1, 2e-6 on
logits of order 0.1); a quantized cache adds the int8 rounding of the
one attention layer's K/V rows, a part in 127 of values that
reach logits of order one (5e-3). A state kept in bfloat16 moves S
(values of order 0.03 here) by more than ten times the first and the
logits by more than ten times the second
(``test_the_state_in_bfloat16_misses_the_tolerances``)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.references import granitemoehybrid as ref
from mpistragglers_jl_tpu.models import decode, serving, transformer
from mpistragglers_jl_tpu.models.serving import ServingScheduler
from mpistragglers_jl_tpu.models.transformer import (
    TransformerConfig,
    forward_dense,
    init_params,
)

MIXERS = ("ssm", "ssm", "attn", "ssm")
SIZES = {"plain": dict(ssm_heads=8, ssm_head_dim=16, ssm_groups=2),
         "packed": dict(ssm_heads=4, ssm_head_dim=32, ssm_groups=1)}


def make_cfg(size="plain", **kw):
    return TransformerConfig(**{**dict(
        vocab=96, d_model=64, n_heads=4, n_kv_heads=2, d_head=16,
        n_layers=len(MIXERS), d_ff=64, norm="rmsnorm", ffn="swiglu",
        tie_head=True, layer_mixers=MIXERS, ssm_state=8, ssm_conv=4,
        ssm_chunk=8, rope_full=False, max_context=128,
        layer_experts=(True,) * len(MIXERS), n_experts=8,
        experts_per_token=3, d_expert=32, shared_experts=2,
        route_score="softmax", emb_scale=12.0, attn_scale=0.125,
        residual_scale=0.6, head_scale=0.25, **SIZES[size]), **kw})


def sizes_of(cfg) -> ref.Sizes:
    """The reference's ``Sizes`` from the program's configuration."""
    return ref.Sizes(
        heads=cfg.ssm_heads, head_dim=cfg.ssm_head_dim, state=cfg.ssm_state,
        groups=cfg.ssm_groups, conv=cfg.ssm_conv, eps=cfg.norm_eps,
        top_k=cfg.experts_per_token,
        held_lo=0 if cfg.experts_held is None else cfg.experts_held[0],
        embedding_multiplier=cfg.emb_scale,
        residual_multiplier=cfg.residual_scale,
        attention_multiplier=cfg.softmax_scale,
        logits_scaling=1.0 / cfg.head_scale)


@pytest.fixture(scope="module", params=sorted(SIZES))
def model(request):
    cfg = make_cfg(request.param)
    params = init_params(cfg, 0)
    # an embedding of order one: with the head tied to it, rows of 0.02
    # would leave every logit near zero
    params["emb"] = params["emb"] * 8.0
    return cfg, params


def tokens(n, seed=1):
    return np.random.default_rng(seed).integers(0, 96, (n,)).astype(np.int32)


def program_state(cache_l, cfg):
    """A state layer's state as the reference lays it out: S (H, P, N)
    and the conv's rows, of request 0."""
    S = transformer.ssm_state_heads(cache_l["S"], cfg)
    return (np.asarray(S[0]).transpose(0, 2, 1),
            np.asarray(cache_l["conv"][0]))


# -- the configuration: who keeps what -----------------------------------------


def test_the_lone_mixer_keeps_state_and_no_rows():
    cfg = make_cfg()
    assert [cfg.state(li) for li in range(4)] == [True, True, False, True]
    assert [cfg.rows(li) for li in range(4)] == [False, False, True, False]
    assert [cfg.ssm_mixer(li) for li in range(4)] == [1, 1, 0, 1]
    assert not any(cfg.ssm(li) for li in range(4))   # none BESIDE attention
    assert cfg.ssm_layers == 3 and cfg.state_layers and cfg.counts_rows
    assert not cfg.plain_block
    both = dataclasses.replace(cfg, layer_mixers=("attn_ssm",) + MIXERS[1:])
    assert both.ssm(0) and both.rows(0) and both.state(0)
    assert both.ssm_layers == 3
    # a layer of a kind is traced once: layers 0, 1 and 3 are one kind
    assert [cfg.layer_like(li) for li in range(4)] == [0, 0, 2, 0]
    with pytest.raises(ValueError, match="'attn_ssm' or 'ssm'"):
        make_cfg(layer_mixers=("mamba",) * 4)
    with pytest.raises(ValueError, match="state-space mixer needs"):
        make_cfg(ssm_groups=3)


PERIOD = ("ssm",) * 5 + ("attn",) + ("ssm",) * 4


@pytest.mark.parametrize("size", sorted(SIZES))
@pytest.mark.parametrize("quantize", [False, True])
def test_the_period_keeps_state_only_and_row_only_caches_apart(size,
                                                               quantize):
    """Granite's period (5 mamba, attention, 4 mamba): nine layers'
    caches are S and the conv's rows and NOTHING a position, the
    tenth's is rows (pages) and no state, in the max_len cache, the
    prefill arena and the page pool alike."""
    cfg = make_cfg(size, n_layers=10, layer_mixers=PERIOD,
                   layer_experts=(True,) * 10)
    S = transformer.ssm_zero_state(cfg, 3)["S"].shape
    assert S == ((3, 1, 8, 128) if size == "packed" else (3, 8, 8, 16))
    rows = {"k", "v"} | ({"k_s", "v_s"} if quantize else set())
    caches = (decode.init_cache(cfg, 3, 32, quantize_kv=quantize),
              serving._fresh_cache(cfg, 3, 32, quantize),
              serving._fresh_pages(cfg, 9, 8, quantize, slots=3))
    for cache in caches:
        assert len(cache) == 10
        for li, cl in enumerate(cache):
            if li == 5:
                assert set(cl) == rows, li
            else:
                assert set(cl) == {"S", "conv"}, li
                assert cl["S"].shape == S and cl["S"].dtype == jnp.float32
                assert cl["conv"].shape == (
                    3, 3, cfg.ssm_heads * cfg.ssm_head_dim
                    + 2 * cfg.ssm_groups * 8)
    assert caches[2][5]["k"].shape[0] == 9       # pages, not slots
    assert decode._row_widths(cfg) == (None,) * 5 + (128,) + (None,) * 4


# -- the block against the reference -------------------------------------------


def test_dense_forward_is_the_references(model):
    cfg, params = model
    toks = tokens(45)
    want = ref.forward(params, jnp.asarray(toks), z=sizes_of(cfg))
    got = forward_dense(params, jnp.asarray(toks[None]), cfg)[0]
    assert float(jnp.abs(want).max()) > 0.5
    np.testing.assert_allclose(got, want, atol=2e-6)


@pytest.mark.parametrize("key", ["emb_scale", "attn_scale", "residual_scale",
                                 "head_scale"])
def test_each_constant_moves_the_logits(model, key):
    """Moved off its value, every constant of the configuration moves
    the program's logits, and moves them as it moves the reference's."""
    cfg, params = model
    other = dataclasses.replace(cfg, **{key: getattr(cfg, key) * 1.5})
    toks = jnp.asarray(tokens(24, seed=4))
    base = forward_dense(params, toks[None], cfg)[0]
    got = forward_dense(params, toks[None], other)[0]
    assert float(jnp.abs(got - base).max()) > 1e-4
    want = ref.forward(params, toks, z=sizes_of(other))
    np.testing.assert_allclose(got, want, atol=4e-6)


def test_the_two_mixers_run_one_body(model):
    """``"ssm"`` and ``"attn_ssm"`` call the same function for
    everything between the normed input and the out-projection: the
    lone layer's output is its input plus ``residual_scale`` times what
    ``ssm_half`` returns, to the bit."""
    cfg, params = model
    lp = params["layers"][0]
    x = jnp.asarray(np.random.default_rng(2).standard_normal((2, 9, 64)),
                    jnp.float32)
    state = transformer.zero_state(cfg, 0, 2)
    a, st = transformer.ssm_half(x, lp, state, cfg)
    y, st2 = transformer.state_half(x, lp, state, cfg, 0, rope=None)
    np.testing.assert_array_equal(y, x + a * jnp.float32(cfg.residual_scale))
    for kk in ("S", "conv"):
        np.testing.assert_array_equal(st[kk], st2[kk])


@pytest.mark.parametrize("quantize", [False, True])
def test_chunks_then_decode_steps_give_the_references_logits(model,
                                                             quantize):
    """Prefill in chunks of 11 rows (no multiple of the sub-chunk's 8
    divides it; the conv's 3 rows and S cross every boundary), then a
    token at a time through the cache: logits, and S and the conv's
    rows of every state layer to 1e-5."""
    cfg, params = model
    toks, prompt, total = tokens(60, seed=7), 41, 60
    want, states = ref.forward(params, jnp.asarray(toks), z=sizes_of(cfg),
                               state=True)
    cache = decode.init_cache(cfg, 1, 64, quantize_kv=quantize)
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
    np.testing.assert_allclose(got, want, atol=5e-3 if quantize else 2e-6)
    # layers 0 and 1 see no cached row at all: exact whatever the cache
    for li, (cl, kept) in enumerate(zip(cache, states)):
        if kept is None:
            assert li == 2 and "S" not in cl
            continue
        S, conv = kept
        got_S, got_conv = program_state(cl, cfg)
        tol = 1e-5 if (li < 2 or not quantize) else 5e-3
        np.testing.assert_allclose(got_S, S, atol=tol, rtol=tol)
        np.testing.assert_allclose(got_conv, conv, atol=tol)


def test_the_state_in_bfloat16_misses_the_tolerances(model):
    """What the tolerances above are tight enough for: the reference
    with S rounded to bfloat16 after every row lies outside both."""
    cfg, params = model
    toks = jnp.asarray(tokens(60, seed=7))
    want, states = ref.forward(params, toks, z=sizes_of(cfg), state=True)
    low, low_states = ref.forward(params, toks, z=sizes_of(cfg),
                                  precision="s_bf16", state=True)
    assert float(jnp.abs(low - want).max()) > 10 * 2e-6
    assert float(jnp.abs(low_states[0][0] - states[0][0]).max()) > 10 * 1e-5


def test_a_padded_chunk_is_the_chunk_of_its_real_rows(model):
    cfg, params = model
    toks = tokens(16, seed=8)
    run = lambda c, valid: decode._incremental_hidden(
        params, jnp.asarray(toks[None, :c]), decode.init_cache(cfg, 1, 32),
        jnp.int32(0), cfg, prefill=False, valid=valid)
    x, cache = run(16, jnp.int32(11))
    x_want, cache_want = run(11, None)
    np.testing.assert_allclose(x[:, :11], x_want, atol=1e-6)
    for li in (0, 1, 3):
        for kk in ("S", "conv"):
            np.testing.assert_allclose(cache[li][kk], cache_want[li][kk],
                                       atol=1e-6)


def test_the_lone_mixer_joins_residual_streams_as_every_half_does():
    """``hc_mult`` 2 over (ssm, attn, ssm): the mixer's result goes
    back through ``hc_post`` like any half's, so the dense forward and
    chunks then single steps through the cache agree."""
    cfg = TransformerConfig(
        vocab=96, d_model=64, n_heads=4, n_kv_heads=2, d_head=16,
        n_layers=3, d_ff=96, norm="rmsnorm", ffn="swiglu",
        layer_mixers=("ssm", "attn", "ssm"), ssm_state=8, ssm_chunk=8,
        hc_mult=2, max_context=64, residual_scale=0.5, **SIZES["plain"])
    params = init_params(cfg, 0)
    toks = tokens(30)
    want = forward_dense(params, jnp.asarray(toks[None]), cfg)[0]
    cache, outs, off = decode.init_cache(cfg, 1, 32), [], 0
    for c in (11, 9):
        lg, cache = decode._incremental_forward(
            params, jnp.asarray(toks[None, off:off + c]), cache,
            jnp.int32(off), cfg, prefill=False)
        outs.append(lg)
        off += c
    while off < 30:
        lg, cache = decode.decode_step_dense(
            params, jnp.asarray(toks[off:off + 1]), cache, jnp.int32(off),
            cfg)
        outs.append(lg[:, None])
        off += 1
    assert float(jnp.abs(want).max()) > 0.1
    np.testing.assert_allclose(jnp.concatenate(outs, 1)[0], want, atol=2e-6)


# -- this chip's share of the experts ------------------------------------------


def _halves(cfg, lp):
    """The layer's leaves as the two chips of a layer hold them."""
    cut = lambda lo, hi: {k: (v[lo:hi] if k in ref.STACKED else v)
                          for k, v in lp.items()}
    return ((dataclasses.replace(cfg, experts_held=(0, 4)), cut(0, 4)),
            (dataclasses.replace(cfg, experts_held=(4, 8)), cut(4, 8)))


@pytest.mark.parametrize("which", ["program", "reference"])
@pytest.mark.parametrize("top_k", [1, 3, 8])
def test_the_two_shares_add_up_to_the_whole_layer(model, which, top_k):
    """THE SHARE TEST. Experts [0, 4) on one chip and [4, 8) on the
    other, the router scoring all 8 on both: the two partial sums, the
    shared MLP counted once, are the uncut reference's whole
    feed-forward (every expert held)."""
    cfg, params = model
    cfg = dataclasses.replace(cfg, experts_per_token=top_k)
    lp = params["layers"][1]
    z = sizes_of(cfg)
    x = jnp.asarray(np.random.default_rng(3).standard_normal((21, 64)),
                    jnp.float32)
    f = {n: a for n, a in lp.items() if n not in ref.STACKED}
    g = ref.rms_norm(x, f["ln2_s"], z.eps)
    whole = ref.feed_forward(g, lp, f, z, "float32")
    shared = ref.gated_mlp(g, f["ws_gate"], f["ws_up"], f["ws_down"],
                           "float32")
    parts = []
    for held, lp_h in _halves(cfg, lp):
        if which == "reference":
            parts.append(ref.feed_forward(
                g, lp_h, f, z._replace(held_lo=held.experts_held[0]),
                "float32") - shared)
        else:
            y, _, hit = transformer.ffn_half(x[None], lp_h, held, 1)
            assert np.all((0 <= np.asarray(hit)) & (np.asarray(hit)[0] <= 4))
            parts.append((y[0] - x) / cfg.residual_scale - shared)
    assert float(jnp.abs(parts[0]).max()) > 1e-3 or top_k == 1
    np.testing.assert_allclose(parts[0] + parts[1] + shared, whole,
                               atol=5e-6)


def test_a_share_serves_the_references_share(model):
    """The dense forward of the model that HOLDS experts [4, 8) is the
    reference told the same share."""
    cfg, params = model
    held = dataclasses.replace(cfg, experts_held=(4, 8))
    cut = {**params, "layers": [
        {k: (v[4:] if k in ref.STACKED else v) for k, v in lp.items()}
        for lp in params["layers"]]}
    toks = jnp.asarray(tokens(30, seed=5))
    want = ref.forward(cut, toks, z=sizes_of(held))
    got = forward_dense(cut, toks[None], held)[0]
    np.testing.assert_allclose(got, want, atol=2e-6)
    whole = ref.forward(params, toks, z=sizes_of(cfg))
    assert float(jnp.abs(want - whole).max()) > 1e-3


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
    """Five requests through four slots, prompts of up to six chunks
    (the grouped chunk program among them): three layers a slot keep a
    state and no page, one keeps pages and no state."""
    cfg, params = model
    sched, reqs = _serve(cfg, params,
                         [(tokens(a, seed=a), b) for a, b in PROMPTS],
                         quantize)
    assert not sched.shares_prefixes
    assert sched.state_resets == len(PROMPTS)
    assert all(len(r.tokens) == b for r, (_, b) in zip(reqs, PROMPTS))
    assert len({t for r in reqs for t in r.tokens}) > 3   # no one token
    assert _gaps(cfg, params, reqs).max() <= (5e-3 if quantize else 1e-6)
    kernel = transformer.ssm_rule_route(cfg, 1)
    assert kernel == ("kernel" if cfg.ssm_head_dim == 32 else "xla")
    assert sched._step_route == {"ssm_rule": kernel}
    assert sched._rule_routes == {"ssm_rule": "xla"}
    assert sched._layer_kinds == {"state_layers": 3, "row_layers": 1}
    assert sched._ends_known()
    assert sched.ticks_ahead > 0     # planned ahead, as for Falcon-H1
    assert [set(cl) >= {"S", "conv"} for cl in sched._caches] == [
        True, True, False, True]
    assert "k" in sched._caches[2] and "k" not in sched._caches[0]
    assert sched._caches[0]["S"].shape[0] == 4


def test_a_slot_taken_again_holds_the_second_requests_state_alone(model):
    """One slot, two requests. The state is reset with its slot: the
    second request's stream is the stream it has alone, and the S and
    conv rows the slot holds at the end are the REFERENCE's after the
    second request's prompt and answer (its last token aside: sampled,
    never fed), whatever the first request left: to 1e-5, across three
    chunk boundaries and 28 single steps (an answer of 1 + 7 ticks of
    4: the tick's last step is the request's last)."""
    cfg, params = model
    a, b = (tokens(70, seed=11), 20), (tokens(45, seed=12), 29)
    sched, both = _serve(cfg, params, [a, b], False, slots=1)
    _, alone = _serve(cfg, params, [b], False, slots=1)
    assert both[1].tokens == alone[0].tokens
    assert sched.state_resets == 2
    assert _gaps(cfg, params, both).max() <= 1e-6
    seq = np.concatenate([b[0], np.asarray(both[1].tokens[:-1], np.int32)])
    _, states = ref.forward(params, jnp.asarray(seq), z=sizes_of(cfg),
                            state=True)
    for li in (0, 1, 3):
        S, conv = program_state(sched._caches[li], cfg)
        np.testing.assert_allclose(S, states[li][0], atol=1e-5, rtol=1e-5)
        np.testing.assert_allclose(conv, states[li][1], atol=1e-5)


def test_state_of_a_decoding_request_between_two_ticks(model):
    """``state_of`` mid-flight, admissions planned ahead: the rows it
    says the state stands behind are the prompt and every delivered
    token but the last, and S there is the reference's after exactly
    those rows, in float32 (a bfloat16 holds none of its values). A
    request without a slot, or still in prefill, is refused."""
    cfg, params = model
    sched = ServingScheduler(params, cfg, slots=2, n_inner=4,
                             quantize_kv=False, page_tokens=8,
                             prompt_chunk=16, max_prompt=96)
    first = sched.submit(tokens(40, seed=3), 30)
    short = sched.submit(tokens(12, seed=4), 6)
    queued = sched.submit(tokens(70, seed=5), 9)
    with pytest.raises(ValueError, match="not decoding"):
        sched.state_of(first)
    while not short.finished:
        sched.step()
    sched.step()
    assert not first.finished and sched.ticks_ahead > 0
    rows, layers = sched.state_of(first)
    assert rows == len(first.prompt) + len(first.tokens) - 1
    assert [st is None for st in layers] == [False, False, True, False]
    seq = np.concatenate([first.prompt, np.asarray(first.tokens, np.int32)])
    _, states = ref.forward(params, jnp.asarray(seq[:rows]),
                            z=sizes_of(cfg), state=True)
    for li in (0, 1, 3):
        S, conv = program_state(
            {k: a[None] for k, a in layers[li].items()}, cfg)
        np.testing.assert_allclose(S, states[li][0], atol=1e-5, rtol=1e-5)
        np.testing.assert_allclose(conv, states[li][1], atol=1e-5)
        assert S.dtype == np.float32
        assert (S.view(np.uint32) & 0xFFFF == 0).mean() < 0.01
    if queued.finished or queued.admitted_tick is None:
        with pytest.raises(ValueError, match="not decoding"):
            sched.state_of(queued)
    sched.run()
    with pytest.raises(ValueError, match="not decoding"):
        sched.state_of(first)


def test_other_paths_refuse_by_mechanism(model):
    cfg, params = model
    from jax.sharding import Mesh

    from mpistragglers_jl_tpu.models.transformer import make_train_step

    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1, 1),
                ("dp", "sp", "tp"))
    with pytest.raises(ValueError, match="state-space mixer, beside their "
                                         "attention or alone"):
        make_train_step(cfg, mesh)
    with pytest.raises(ValueError, match="sharded tick.*state-space mixer"):
        serving.make_serving_scan(cfg, mesh, 4)
    with pytest.raises(ValueError, match="has no width"):
        decode.ring_widths(cfg)
    with pytest.raises(ValueError, match="mtp|multi-token"):
        dataclasses.replace(cfg, mtp_depth=1)
    from mpistragglers_jl_tpu.qos import TenantContract, TenantRegistry

    qos = TenantRegistry([TenantContract("a")])
    with pytest.raises(ValueError, match="page quotas.*alone in its layer"):
        ServingScheduler(params, cfg, slots=2, page_tokens=8,
                         prompt_chunk=16, max_prompt=96, qos=qos)
    sched = ServingScheduler(params, cfg, slots=2, page_tokens=8,
                             prompt_chunk=16, max_prompt=96)
    req = sched.submit(tokens(20), 40)
    while not req.tokens:
        sched.step()
    with pytest.raises(ValueError, match="KV-page migration.*alone in its"):
        sched.export_page_state(req)
    with pytest.raises(ValueError, match="adopt_page_state.*alone in its"):
        sched._check_adopt_compat({})
    with pytest.raises(ValueError, match="draft"):
        ServingScheduler(params, cfg, slots=2, page_tokens=8,
                         prompt_chunk=16, max_prompt=96, draft="mtp")


def test_scopes_and_span_arguments(model):
    import re

    cfg, params = model
    arena = serving._fresh_cache(cfg, 1, 96, False)
    text = serving._extend_chunk_dense(cfg, 16, 96).lower(
        params, np.zeros((1, 16), np.int32), arena, np.int32(0),
        np.int32(16)).as_text(debug_info=True)
    for scope in ("ssm_proj", "ssm_conv", "ssm_rule", "ssm_out", "attn_qkv",
                  "attn_out", "moe_route", "moe_experts", "moe_shared"):
        assert f"/{scope}/" in text, scope
    sched = ServingScheduler(params, cfg, slots=2, n_inner=4,
                             page_tokens=8, prompt_chunk=16, max_prompt=96)
    text = sched.lower_tick().as_text(debug_info=True)
    parts = {part for path in re.findall(r'loc\("([^"]+)"', text)
             for part in path.split("/")}
    assert parts >= {"ssm_proj", "ssm_conv", "ssm_rule", "ssm_out",
                     "attn_qkv", "decode_attn", "attn_out", "decode_mlp",
                     "moe_route", "moe_experts", "moe_shared", "head"}
