"""A decode step that yields one or two tokens a slot, drafted by the
configuration's own multi-token-prediction module
(``TransformerConfig(mtp_depth=1)`` under ``ServingScheduler(draft=
"mtp")``), over latent attention and group-limited experts of which a
share is held. Tiny sizes, seeded random weights, float32 on the CPU.

The contract: the delivered streams are, token for token, the streams
the same scheduler delivers with the drafter off, at temperature 0 and
above it. The oracles of the block, of the routing and of the module
are the benchmark's plain reference
(chipbench/references/deepseek_v3.py), which imports nothing of the
program.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.references import deepseek_v3 as ref
from mpistragglers_jl_tpu.models import moe
from mpistragglers_jl_tpu.models import transformer as tr
from mpistragglers_jl_tpu.models.serving import ServingScheduler
from mpistragglers_jl_tpu.models.transformer import (
    TransformerConfig,
    forward_dense,
    forward_dense_mtp,
    init_params,
)

P, C, R, ROPE = 8, 8, 24, 4  # page, chunk, latent's width, rotated dims
YARN = (10000.0, 40.0, 16, 32.0, 1.0, 1.0, 1.0)
SCALE = float(12 ** -0.5 * (0.1 * np.log(40.0) + 1.0) ** 2)

CFG = TransformerConfig(
    vocab=64, d_model=32, n_heads=4, d_head=12, n_layers=2, d_ff=48,
    attn_impl="reference", norm="rmsnorm", norm_eps=1e-6, ffn="swiglu",
    tie_head=False, layer_mixers=("mla",) * 2, mla_q_rank=16,
    mla_kv_rank=R, mla_nope_dim=8, mla_rope_dim=ROPE, mla_v_dim=8,
    rope_table=tr.yarn_rope_table(ROPE, *YARN[:5]), attn_scale=SCALE,
    layer_experts=(False, True), n_experts=16, experts_per_token=4,
    d_expert=16, shared_experts=1, route_scale=2.5, route_groups=4,
    route_topk_groups=2, experts_held=(0, 8), max_context=96, mtp_depth=1,
)
PARAMS = init_params(CFG, seed=3)
REF_KW = dict(top_k=4, route_scale=2.5, n_group=4, topk_group=2, held_lo=0,
              kv_rank=R, nope=8, yarn=YARN)

PROMPTS = [np.random.default_rng(7).integers(0, CFG.vocab, n).astype(np.int32)
           for n in (5, 17, 9, 30, 12, 3, 23, 8)]
MAX_NEW = [7, 12, 1, 9, 20, 2, 16, 11]


def serve(draft, temperature, *, page_tokens=P, quantize_kv=True,
          eos_id=None, cfg=CFG, params=PARAMS, slots=3):
    sched = ServingScheduler(
        params, cfg, slots=slots, n_inner=4, prompt_chunk=C, max_prompt=32,
        quantize_kv=quantize_kv, temperature=temperature, eos_id=eos_id,
        page_tokens=page_tokens, draft=draft)
    reqs = [
        sched.submit(p, m, **({"key": jax.random.key(100 + i)}
                              if temperature else {}))
        for i, (p, m) in enumerate(zip(PROMPTS, MAX_NEW))]
    sched.run()
    return sched, reqs


# -- the contract: drafter on == drafter off, token for token ------------------


@pytest.mark.parametrize("page_tokens", [P, CFG.max_context],
                         ids=["pages", "one_page_a_slot"])
@pytest.mark.parametrize("temperature", [0.0, 0.05, 1.0])
def test_streams_equal_the_drafter_off_streams(temperature, page_tokens):
    _, off = serve(None, temperature, page_tokens=page_tokens)
    sched, on = serve("mtp", temperature, page_tokens=page_tokens)
    for a, b in zip(off, on):
        assert b.tokens == a.tokens and b.reason == a.reason
        assert not a.drafts
    drafts = [d for r in on for d in r.drafts]
    assert drafts
    # every draft that counts guessed a delivered token, and an accepted
    # one guessed it right
    for r in on:
        for at, tok, accepted in r.drafts:
            assert 1 <= at < len(r.tokens)
            assert accepted == (r.tokens[at] == tok)
    # every page came back
    assert all(p.used == 0 for p in sched.pools.values())


def _one(draft, prompt, n_new, *, n_inner=4, temperature=0.0, params=PARAMS):
    """One request alone: the scheduler when it has drained, the
    request, and the ticks' ``drafted`` and ``accepted`` summed."""
    sched = ServingScheduler(
        params, CFG, slots=2, n_inner=n_inner, prompt_chunk=C, max_prompt=32,
        quantize_kv=True, temperature=temperature, page_tokens=P, draft=draft)
    r = sched.submit(prompt, n_new, **({"key": jax.random.key(7)}
                                       if temperature else {}))
    drafted = accepted = 0
    while sched.pending or sched.active:
        sched.step()
        drafted, accepted = drafted + sched.drafted, accepted + sched.accepted
    return sched, r, drafted, accepted


@functools.lru_cache(maxsize=None)
def _off_stream(Tp, n_new, temperature):
    return tuple(_one(None, _grid_prompt(Tp), n_new,
                      temperature=temperature)[1].tokens)


def _grid_prompt(Tp):
    return np.random.default_rng(31 * Tp).integers(
        0, CFG.vocab, Tp).astype(np.int32)


@pytest.mark.parametrize("temperature", [0.0, 0.7], ids=["greedy", "T0.7"])
@pytest.mark.parametrize("n_inner", [1, 3, 4, 8])
@pytest.mark.parametrize("Tp,n_new", [(8, 17), (3, 5), (12, 30)])
def test_a_stream_alone_equals_the_drafter_off_stream(Tp, n_new, n_inner,
                                                     temperature):
    """Short and long budgets against steps a tick that divide them and
    do not (a tick of 8 drafting steps can overrun a budget of 5 by 11
    tokens): the stream is the drafter-off stream, greedy and sampled
    with the request's key."""
    sched, r, drafted, _ = _one("mtp", _grid_prompt(Tp), n_new,
                                n_inner=n_inner, temperature=temperature)
    assert tuple(r.tokens) == _off_stream(Tp, n_new, temperature)
    assert len(r.tokens) == n_new and r.reason == "length"
    assert drafted == len(r.drafts) > 0
    assert all(p.used == 0 for p in sched.pools.values())


def _walk(r):
    """The drafts of a finished request against its stream, token by
    token: every token behind the first is the one a step verified (its
    draft on record at that index) or the second of a step that
    accepted. Returns the steps and the accepted among them."""
    i = 1
    for at, tok, hit in r.drafts:
        assert at == i and hit == (r.tokens[i] == tok)
        i += 1 + hit
    # (the budget may end on the first of an accepted step's two)
    assert i in (len(r.tokens), len(r.tokens) + 1)
    return len(r.drafts), sum(d[2] for d in r.drafts)


@pytest.mark.parametrize("kind", ["random", "repetitive", "never_accepted"])
def test_drafted_and_accepted_are_the_streams_own_count(kind):
    """Three kinds of stream, each the drafter-off stream, and the
    ticks' ``drafted`` / ``accepted`` are what a walk along the stream
    counts: a random prompt; a prompt of period 6; a module whose every
    draft is rejected (its final norm zeroed: it drafts token 0, which
    this stream never holds), where each step delivers one token."""
    rng = np.random.default_rng(9)
    params, n_new = PARAMS, 24
    prompt = rng.integers(1, CFG.vocab, 12).astype(np.int32)
    if kind == "repetitive":
        prompt = np.tile(prompt[:6], 4)
    if kind == "never_accepted":
        params = {**PARAMS, "mtp": {
            **PARAMS["mtp"], "lnf_s": jnp.zeros_like(PARAMS["mtp"]["lnf_s"])}}
    off = _one(None, prompt, n_new)[1]
    _, on, drafted, accepted = _one("mtp", prompt, n_new, params=params)
    assert on.tokens == off.tokens and len(on.tokens) == n_new
    assert (drafted, accepted) == _walk(on)
    assert n_new - 1 <= drafted + accepted <= n_new
    if kind == "never_accepted":
        assert 0 not in off.tokens[1:]  # what the count rests on
        assert accepted == 0 and {d[1] for d in on.drafts} == {0}


# a latent of whole lane tiles: with four slots (``KERNEL_MIN_BATCH``)
# the paged tick takes the kernel's latent form, a slot's two rows of a
# drafting step as two rows of the kernel; with three it gathers
KCFG = dataclasses.replace(CFG, mla_kv_rank=128)
KPARAMS = init_params(KCFG, seed=3)


@pytest.mark.parametrize("temperature", [0.0, 1.0])
def test_the_kernel_route_delivers_the_same_streams(temperature):
    """On latent pages read in place, drafter on and off, the streams
    are the gather route's token for token (the drafter-off streams
    among them), every draft checked as above."""
    kw = dict(cfg=KCFG, params=KPARAMS)
    gather, want = serve(None, temperature, **kw)
    off_sched, off = serve(None, temperature, slots=4, **kw)
    sched, on = serve("mtp", temperature, slots=4, **kw)
    assert not gather.use_kernel and off_sched.use_kernel and sched.use_kernel
    for a, b, c in zip(want, off, on):
        assert a.tokens == b.tokens == c.tokens
        assert a.reason == b.reason == c.reason
    assert any(d[2] for r in on for d in r.drafts)
    assert any(not d[2] for r in on for d in r.drafts)
    for r in on:
        for at, tok, accepted in r.drafts:
            assert accepted == (r.tokens[at] == tok)
    assert all(p.used == 0 for p in sched.pools.values())
    text = sched.lower_tick().as_text(debug_info=True)
    assert "paged_latent_attention" in text and "mtp/mla_attn" in text
    assert "kv_page_gather" not in text and "kv_page_scatter" not in text


def test_the_cases_the_step_must_get_right_all_occur():
    """Over the three temperatures: a draft accepted and a draft
    rejected; a request whose budget ends on the FIRST of an accepted
    step's two tokens; requests that retire before their tick's last
    step; a rejected draft whose row is the first of a page."""
    seen = set()
    for temperature in (0.0, 0.05, 1.0):
        _, reqs = serve("mtp", temperature)
        for r, prompt in zip(reqs, PROMPTS):
            for at, _, accepted in r.drafts:
                seen.add("accepted" if accepted else "rejected")
                if accepted and at == r.max_new - 1:
                    seen.add("budget ends on the first of two")
                if not accepted and (len(prompt) + at) % P == 0:
                    seen.add("rejected draft opens a page")
            steps = len(r.drafts)
            if steps % 4:  # n_inner 4: the last tick was cut short
                seen.add("retired mid-tick")
    assert seen == {"accepted", "rejected", "budget ends on the first of two",
                    "rejected draft opens a page", "retired mid-tick"}


@pytest.mark.parametrize("temperature", [0.0, 1.0])
def test_an_end_of_sequence_token_mid_step_ends_the_stream(temperature):
    _, plain = serve(None, temperature)
    # a token that some stream emits early becomes the EOS
    eos = next(r.tokens[2] for r in plain if len(r.tokens) > 6)
    _, off = serve(None, temperature, eos_id=eos)
    _, on = serve("mtp", temperature, eos_id=eos)
    assert any(r.reason == "eos" for r in off)
    for a, b in zip(off, on):
        assert b.tokens == a.tokens and b.reason == a.reason


def test_the_drafts_are_the_modules_own_under_the_positions_noise():
    """Every draft the scheduler verified is what the dense oracle of
    the module gives at that position under the SAME noise the
    verification adds there: ``argmax(q / T + G(key, position))``, the
    noise drawn as the reference draws it."""
    T = 0.05
    _, reqs = serve("mtp", T, quantize_kv=False)
    checked = 0
    # one shape for all: a causal model's rows do not see the padding
    oracle = jax.jit(lambda seq: forward_dense_mtp(PARAMS, seq[None], CFG)[1])
    for i, (r, prompt) in enumerate(zip(reqs, PROMPTS)):
        if not r.drafts:
            continue
        seq = np.zeros(64, np.int32)
        seq[:len(prompt) + len(r.tokens)] = np.concatenate(
            [prompt, np.asarray(r.tokens, np.int32)])
        q = np.asarray(oracle(jnp.asarray(seq))[0], np.float32)
        tp = len(prompt)
        g = np.asarray(ref.gumbel_rows(jax.random.key(100 + i), 0, 64,
                                       CFG.vocab))
        for at, tok, _ in r.drafts:
            j = tp + at  # the position the draft is a draft of
            want = int(np.argmax(q[j - 2] / T + g[j - 1]))
            assert tok == want
            checked += 1
    assert checked > 20


def test_tick_counters_and_spans(monkeypatch):
    """``drafted`` / ``accepted`` / ``mtp_experts_hit`` ride on
    ``serving.tick`` and sum to what the requests' own ``drafts`` say;
    ``tokens`` on ``serving.harvest`` is one or two a live step."""
    from mpistragglers_jl_tpu.models import serving

    seen = []

    class Span:
        def __init__(self, name, **args):
            self.row = (name, dict(args))
            seen.append(self.row)

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            pass

        def set_metadata(self, **args):
            self.row[1].update(args)

    monkeypatch.setattr(serving, "_annotate", Span)
    sched, reqs = serve("mtp", 1.0)
    ticks = [a for n, a in seen if n == "serving.tick" and "drafted" in a]
    harvests = [a for n, a in seen if n == "serving.harvest"]
    drafted = sum(len(r.drafts) for r in reqs)
    accepted = sum(d[2] for r in reqs for d in r.drafts)
    assert sum(t["drafted"] for t in ticks) == drafted > 0
    assert sum(t["accepted"] for t in ticks) == accepted > 0
    assert all(0 < t["mtp_experts_hit"] <= 8 for t in ticks)
    # 3 slots x 4 experts a token and row, a half of the experts held
    assert all(0 < h["pairs_local"] <= 3 * 4 for h in harvests)
    delivered = sum(len(r.tokens) - 1 for r in reqs)  # less first tokens
    assert sum(h["tokens"] for h in harvests) == delivered
    # a live step delivers one token, or two where it accepted, but for
    # the second of two that a budget cut off
    assert drafted <= delivered <= drafted + accepted


def test_the_drafting_tick_carries_its_scopes():
    sched, _ = serve("mtp", 1.0)
    text = sched.lower_tick().as_text(debug_info=True)
    assert "module @jit_serving_tick_paged" in text
    for scope in ("mtp/mtp_proj", "mtp/mtp_head", "head/verify",
                  "mtp/mla_attn", "mtp/decode_mlp/ffn/moe_experts",
                  "kv_page_gather"):
        assert scope in text, scope
    # the model's head is under ``head`` and the module's is not
    assert "mtp/head" not in text and "mtp_head/head" not in text


# -- what a drafting scheduler refuses, by mechanism ---------------------------


def test_refusals():
    with pytest.raises(ValueError, match="multi-token-prediction module"):
        ServingScheduler(PARAMS, dataclasses.replace(CFG, mtp_depth=0),
                         draft="mtp", page_tokens=P)
    with pytest.raises(ValueError, match="None or 'mtp'"):
        ServingScheduler(PARAMS, CFG, draft="ngram", page_tokens=P)
    windowed = dataclasses.replace(
        CFG, layer_mixers=None, attn_window=16, mtp_depth=1, d_head=None,
        n_heads=4, layer_experts=None, n_experts=0, experts_held=None,
        route_groups=1, route_topk_groups=1)
    with pytest.raises(ValueError, match="sliding-window"):
        ServingScheduler(init_params(windowed, 0), windowed, draft="mtp",
                         page_tokens=P)
    for bad in (dict(hc_mult=4), dict(layer_mixers=("gdn", "mla"),
                                      gdn_key_heads=2, gdn_value_heads=2)):
        with pytest.raises(ValueError, match="multi-token-prediction"):
            dataclasses.replace(CFG, **bad)
    sched, _ = serve("mtp", 0.0)
    assert sched.shares_prefixes is False
    # a request that could write past the context budget counts the two
    # rows a step
    with pytest.raises(ValueError, match="8 rows"):
        sched.submit(np.zeros(30, np.int32), 96 - 30 - 7)


def test_the_drafter_off_scheduler_holds_nothing_of_the_module():
    sched, _ = serve(None, 0.0)
    assert sched.cfg.mtp_depth == 0 and len(sched._caches) == CFG.n_layers
    on, _ = serve("mtp", 0.0)
    assert len(on._caches) == CFG.n_layers + 1 and on._tok.shape == (3, 2)
    assert on.use_kernel is False


# -- program against the plain reference ---------------------------------------


def _tokens(n, seed=0):
    return np.random.default_rng(seed).integers(0, CFG.vocab, n).astype(
        np.int32)


def test_block_and_module_logits_against_the_reference():
    toks = _tokens(40, 1)
    logits, q = forward_dense_mtp(PARAMS, jnp.asarray(toks)[None], CFG)
    want, want_q = ref.stream_logits(PARAMS, jnp.asarray(toks), 0, 40,
                                     **REF_KW)
    np.testing.assert_allclose(np.asarray(logits[0]), np.asarray(want),
                               atol=2e-5)
    # the module's last row would need token 40: 39 rows
    np.testing.assert_allclose(np.asarray(q[0]), np.asarray(want_q)[:39],
                               atol=2e-5)
    # no forward of the model itself reads the module
    np.testing.assert_array_equal(
        np.asarray(logits), np.asarray(forward_dense(
            {k: v for k, v in PARAMS.items() if k != "mtp"},
            jnp.asarray(toks)[None], dataclasses.replace(CFG, mtp_depth=0))))
    # which half of eh_proj's rows is whose is a relabelling: swapped
    # halves read swapped inputs alike
    d = CFG.d_model
    assert PARAMS["mtp"]["eh_proj"].shape == (2 * d, d)


def test_group_limited_routing_differs_from_plain_top_k_where_it_must():
    """Scores laid out so that the plain top-4 takes the single best
    experts of four groups while the group limit keeps the two groups
    whose best TWO sum highest."""
    E, G = 16, 4
    s = np.full((1, E), 0.1, np.float32)
    s[0, [0, 4, 8, 12]] = [0.9, 0.8, 0.7, 0.95]  # one high expert a group
    s[0, [9, 10]] = [0.65, 0.6]                  # group 2 has depth
    s[0, [5]] = [0.62]                           # so has group 1
    logit = np.log(s / (1 - s))
    router = jnp.eye(E, dtype=jnp.float32)
    x = jnp.asarray(logit)
    plain, _ = moe.topk_route(x, router, None, 4, 1.0)
    limited, w = moe.topk_route(x, router, None, 4, 2.5, groups=G,
                                topk_groups=2)
    assert sorted(np.asarray(plain)[0]) == [0, 4, 8, 12]
    # groups score 1.0, 1.42, 1.35, 1.05: groups 1 and 2 stay
    assert sorted(np.asarray(limited)[0]) == [4, 5, 8, 9]
    np.testing.assert_allclose(np.asarray(w).sum(), 2.5, rtol=1e-6)
    want = np.asarray(ref.group_limited_weights(
        x[None], router, jnp.zeros((E,)), top_k=4, route_scale=2.5,
        n_group=G, topk_group=2, precision="float32"))[0, 0]
    got = np.zeros(E, np.float32)
    got[np.asarray(limited)[0]] = np.asarray(w)[0]
    np.testing.assert_allclose(got, want, rtol=1e-5)
    # the bias selects and does not weigh, under the limit too
    bias = jnp.zeros((E,)).at[6].set(1.0)
    idx, wb = moe.topk_route(x, router, bias, 4, 1.0, groups=G,
                             topk_groups=2)
    assert 6 in np.asarray(idx)[0]
    np.testing.assert_allclose(
        np.asarray(wb)[0][np.asarray(idx)[0] == 6],
        0.1 / float(np.asarray(s)[0][np.asarray(idx)[0]].sum()), rtol=1e-5)


@pytest.mark.parametrize("shares", [2, 4])
def test_the_shares_of_an_expert_layer_add_up_to_the_whole(shares):
    """The model-configs guide's test of a share: the parts of the
    result that all the shares of a layer give, the shared expert
    counted once, add up to what the uncut reference gives for the
    whole layer."""
    whole = dataclasses.replace(CFG, experts_held=None, mtp_depth=0)
    lp = init_params(whole, seed=11)["layers"][1]
    lp = {**lp, "ln2_s": jnp.ones_like(lp["ln2_s"])}
    x = jnp.asarray(np.random.default_rng(2).standard_normal((2, 9, 32)),
                    jnp.float32)
    h = ref.rms_norm(x, 1.0, 1e-6)  # the rows both halves' experts see
    E, stacked = whole.n_experts, ("we_gate", "we_up", "we_down")
    routed = {k: v for k, v in lp.items() if not k.startswith("ws_")}
    total, pairs = 0.0, 0
    for i in range(shares):
        lo, hi = i * E // shares, (i + 1) * E // shares
        y, hit = moe.moe_ffn_topk(
            h, {**routed, **{k: lp[k][lo:hi] for k in stacked}},
            dataclasses.replace(whole, experts_held=(lo, hi)))
        total, pairs = total + y, pairs + int(hit[1])
    assert pairs == 2 * 9 * 4  # every pair fell on exactly one share
    shared = tr._swiglu(h, lp["ws_gate"], lp["ws_up"], lp["ws_down"])
    want = ref.ffn_half(x, lp, top_k=4, route_scale=2.5, n_group=4,
                        topk_group=2, held_lo=0, precision="float32") - x
    np.testing.assert_allclose(np.asarray(total + shared), np.asarray(want),
                               atol=2e-5)
    # and one share alone is NOT the whole (the test can fail)
    assert float(jnp.abs(y + shared - want).max()) > 1e-3
