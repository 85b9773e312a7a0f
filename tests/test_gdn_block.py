"""A block whose layers mix gated delta-rule mixers (per-request state a
fixed block, no row a token) with gated full attention, over softmax
top-k experts of which a share is held here, beside a gated shared
expert; rotary on part of a head at a base of its own. Tiny sizes,
seeded random weights, float32 on the CPU.

The oracle is the benchmark's plain reference
(chipbench/references/qwen3_next.py), which imports nothing of the
program and runs the delta rule one token at a time.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.references import qwen3_next
from mpistragglers_jl_tpu.models import moe, serving
from mpistragglers_jl_tpu.models import transformer as tr
from mpistragglers_jl_tpu.models.decode import (
    generate_dense,
    init_cache,
    ring_widths,
)
from mpistragglers_jl_tpu.models.serving import (
    ServingScheduler,
    make_serving_scan,
)
from mpistragglers_jl_tpu.models.transformer import (
    TransformerConfig,
    forward_dense,
    init_params,
    param_specs,
)

C, P, TOP_K, E = 16, 8, 4, 16

# three delta-rule layers to one attention layer; 2 key heads serving 4
# value heads; rotary on 4 of a head's 16 dims; experts 0..7 of 16 held
CFG = TransformerConfig(
    vocab=97, d_model=32, n_heads=4, n_kv_heads=2, d_head=16, n_layers=4,
    d_ff=48, attn_impl="reference", norm="rmsnorm", norm_eps=1e-6,
    ffn="swiglu", tie_head=False, qk_norm=True, attn_gate=True,
    rope_theta=1e7, rope_dims=4,
    layer_mixers=("gdn", "gdn", "gdn", "attn"), gdn_key_heads=2,
    gdn_value_heads=4, gdn_key_dim=8, gdn_value_dim=8, gdn_conv=4,
    layer_experts=(True,) * 4, n_experts=E, experts_per_token=TOP_K,
    d_expert=16, shared_experts=1, route_score="softmax", shared_gate=True,
    experts_held=(0, E // 2), max_context=96,
)
PARAMS = init_params(CFG, seed=5)
REF_KW = dict(top_k=TOP_K, held_lo=0, key_heads=2, key_dim=8, rope_dims=4)


def _tokens(n, seed=0):
    return np.random.default_rng(seed).integers(
        0, CFG.vocab, size=n).astype(np.int32)


def _reference(seq, params=PARAMS, **kw):
    return np.asarray(qwen3_next.stream_logits(
        params, jnp.asarray(seq), 0, len(seq), **{**REF_KW, **kw}))


def _sched(**kw):
    kw = {"slots": 3, "n_inner": 4, "prompt_chunk": C, "max_prompt": 64,
          "page_tokens": P, **kw}
    return ServingScheduler(PARAMS, CFG, **kw)


def _alone(prompt, max_new, **kw):
    s = _sched(**kw)
    r = s.submit(prompt, max_new)
    s.run()
    return r.tokens


# -- the mathematics -------------------------------------------------------


def test_forward_dense_equals_the_plain_reference():
    seq = _tokens(70, seed=1)  # over a sub-chunk of 64 and not a multiple
    got = np.asarray(forward_dense(PARAMS, jnp.asarray(seq)[None], CFG))[0]
    np.testing.assert_allclose(got, _reference(seq), atol=2e-4)


@pytest.mark.parametrize("T", [1, 7, 64, 65, 150])
def test_chunked_delta_rule_equals_the_recurrence(T):
    """Across sub-chunk boundaries (64 rows), from a state that is not
    zero, with decays from nearly none to nearly all."""
    rng = np.random.default_rng(T)
    B, H, Dk, Dv = 2, 3, 8, 4
    f = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)
    q, k, v, S0 = f(B, T, H, Dk), f(B, T, H, Dk), f(B, T, H, Dv), f(B, H, Dk, Dv)
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    g = -jnp.asarray(rng.random((B, T, H)) ** 4 * 3.0, jnp.float32)
    beta = jnp.asarray(rng.random((B, T, H)), jnp.float32)
    S, want = S0, []
    for t in range(T):
        o, S = tr._delta_rule_step(q[:, t], k[:, t], v[:, t], g[:, t],
                                   beta[:, t], S)
        want.append(o)
    if T == 1:
        return  # the step is the definition
    o, S_chunks = tr._delta_rule_chunks(q, k, v, g, beta, S0)
    np.testing.assert_allclose(o, jnp.stack(want, 1), atol=2e-5)
    np.testing.assert_allclose(S_chunks, S, atol=2e-5)


def test_state_crosses_chunks_as_it_crosses_tokens():
    """One call on 40 rows, two calls on 16 and 24, and 40 single-token
    calls leave the same state and give the same rows."""
    lp = PARAMS["layers"][0]
    x = jnp.asarray(np.random.default_rng(2).standard_normal((1, 40, 32)),
                    jnp.float32)
    zero = tr.gdn_zero_state(CFG, 1)
    whole, s_whole = tr.gdn_half(x, lp, zero, CFG)
    a, s = tr.gdn_half(x[:, :16], lp, zero, CFG)
    b, s_two = tr.gdn_half(x[:, 16:], lp, s, CFG)
    s, rows = zero, []
    for t in range(40):
        r, s = tr.gdn_half(x[:, t:t + 1], lp, s, CFG)
        rows.append(r)
    for got in (jnp.concatenate([a, b], 1), jnp.concatenate(rows, 1)):
        np.testing.assert_allclose(got, whole, atol=2e-5)
    for got in (s_two, s):
        for leaf in ("S", "conv"):
            np.testing.assert_allclose(got[leaf], s_whole[leaf], atol=2e-5)


@pytest.mark.parametrize("true_len", [1, 2, 5, 16])
def test_padding_leaves_the_state_and_the_conv_rows_alone(true_len):
    """A chunk of 16 rows of which ``true_len`` are the prompt's gives
    the state of ``true_len`` rows, whatever the padding holds."""
    lp = PARAMS["layers"][1]
    rng = np.random.default_rng(true_len)
    x = jnp.asarray(rng.standard_normal((1, 16, 32)), jnp.float32)
    start = {"S": jnp.asarray(rng.standard_normal((1, 4, 8, 8)), jnp.float32),
             "conv": jnp.asarray(rng.standard_normal((1, 3, 64)), jnp.float32)}
    want_x, want = tr.gdn_half(x[:, :true_len], lp, start, CFG)
    got_x, got = jax.jit(
        lambda x, s, n: tr.gdn_half(x, lp, s, CFG, valid=n)
    )(x, start, jnp.int32(true_len))
    np.testing.assert_allclose(got_x[:, :true_len], want_x, atol=2e-5)
    np.testing.assert_allclose(got["S"], want["S"], atol=2e-5)
    np.testing.assert_allclose(got["conv"], want["conv"], atol=2e-5)


def test_rotary_on_part_of_a_head_at_its_own_base():
    import functools

    x = jnp.asarray(np.random.default_rng(3).standard_normal((1, 9, 2, 16)),
                    jnp.float32)
    pos = jnp.arange(9) + 1000
    rope = functools.partial(tr._rope, pos=pos, theta=1e7)
    got = tr._rope_leading(rope, x, 4)
    np.testing.assert_allclose(
        got, qwen3_next.rope_partial(x, pos, 4, 1e7), atol=1e-6)
    np.testing.assert_array_equal(got[..., 4:], x[..., 4:])
    rows = tr._rope_leading(
        functools.partial(serving._rope_rows, pos=pos, theta=1e7),
        x[0][:, None], 4)
    np.testing.assert_allclose(rows[:, 0], got[0], atol=1e-6)
    # the default is the old function: every dim, base 10000
    np.testing.assert_array_equal(
        tr._rope(x, pos), tr._rope_leading(
            functools.partial(tr._rope, pos=pos, theta=10000.0), x, None))


def test_param_leaves_follow_the_mixer():
    gdn, attn = PARAMS["layers"][0], PARAMS["layers"][3]
    assert "gdn_wqkvz" in gdn and "wq" not in gdn
    assert "wq" in attn and "gdn_wqkvz" not in attn
    assert gdn["gdn_wqkvz"].shape == (32, 2 * 16 + 2 * 32)
    assert gdn["gdn_conv_w"].shape == (4, 64)
    assert gdn["gdn_A_log"].dtype == jnp.float32
    # the published initialisation: decays near one at a = 0
    decay = np.exp(-np.exp(gdn["gdn_A_log"]) * np.log1p(
        np.exp(gdn["gdn_dt_bias"])))
    assert 0.15 < decay.min() and decay.max() < 1.0
    for lp in PARAMS["layers"]:
        assert lp["router"].shape == (32, E) and "router_bias" not in lp
        assert lp["we_gate"].shape == (E // 2, 32, 16)
        assert lp["ws_sgate"].shape == (32, 1)


# -- the share of the experts ----------------------------------------------


def _moe_layer(cfg, seed=0):
    return moe.init_topk_layer(np.random.default_rng(seed), cfg)


def test_the_shares_of_a_layer_add_up_to_the_whole():
    """Experts [0, E/2) and [E/2, E), with what every chip computes
    alike (the shared expert) counted once, give the uncut layer."""
    whole_cfg = dataclasses.replace(CFG, experts_held=None)
    lp = _moe_layer(whole_cfg, seed=4)
    h = jnp.asarray(np.random.default_rng(5).standard_normal((2, 11, 32)),
                    jnp.float32)
    want, hit = moe.moe_ffn_topk(h, lp, whole_cfg)
    shared_only = {k: v for k, v in lp.items() if k.startswith("ws_")}
    total = -shared_only_sum(h, shared_only)
    pairs = 0
    for lo in (0, E // 2):
        cfg = dataclasses.replace(CFG, experts_held=(lo, lo + E // 2))
        part = {k: (v[lo:lo + E // 2] if k.startswith("we_") else v)
                for k, v in lp.items()}
        y, counts = moe.moe_ffn_topk(h, part, cfg)
        total = total + y
        pairs += int(counts[1])
    np.testing.assert_allclose(total, want, atol=1e-5)
    assert pairs == 2 * 11 * TOP_K  # every pair fell on one of the shares


def shared_only_sum(h, lp):
    a = jax.nn.silu(h @ lp["ws_gate"]) * (h @ lp["ws_up"])
    return (a @ lp["ws_down"]) * jax.nn.sigmoid(h @ lp["ws_sgate"])


def test_holding_every_expert_is_the_old_layer_to_the_bit():
    """Trinity's block (sigmoid scores, selection bias, route_scale):
    ``experts_held`` = all of them changes no bit of the result."""
    base = TransformerConfig(
        d_model=32, n_layers=5, layer_experts=(True,) * 5, n_experts=8,
        experts_per_token=2, d_expert=16, shared_experts=1,
        route_scale=2.826,
    )
    lp = _moe_layer(base, seed=6)
    h = jnp.asarray(np.random.default_rng(7).standard_normal((3, 5, 32)),
                    jnp.float32)
    want, hit = moe.moe_ffn_topk(h, lp, base)
    got, counts = moe.moe_ffn_topk(
        h, lp, dataclasses.replace(base, experts_held=(0, 8)))
    np.testing.assert_array_equal(got, want)
    assert int(counts[0]) == int(hit) and int(counts[1]) == 3 * 5 * 2


def test_softmax_routing_normalises_over_the_chosen():
    x = jnp.asarray(np.random.default_rng(8).standard_normal((6, 32)),
                    jnp.float32)
    router = PARAMS["layers"][0]["router"]
    idx, w = moe.topk_route(x, router, None, TOP_K, 1.0, "softmax")
    p = jax.nn.softmax(x @ router, axis=-1)
    np.testing.assert_array_equal(idx, jax.lax.top_k(p, TOP_K)[1])
    np.testing.assert_allclose(w.sum(-1), 1.0, atol=1e-6)
    np.testing.assert_allclose(
        w, jnp.take_along_axis(p, idx, 1) / jnp.take_along_axis(
            p, idx, 1).sum(-1, keepdims=True), atol=1e-6)


# -- through the scheduler -------------------------------------------------


@pytest.mark.parametrize("paged,quantize", [
    (CFG.max_context, False),  # one page a slot
    (P, False), (P, True)])
def test_chunks_then_ticks_follow_the_reference(paged, quantize):
    """Prompts of one chunk, of several and of no whole number of
    chunks, more requests than slots: every served token is the
    reference's best at its position (int8 K/V: within a hair of it)."""
    sched = _sched(page_tokens=paged, quantize_kv=quantize)
    prompts = [_tokens(n, seed=n) for n in (5, 16, 23, 40, 7, 33, 64)]
    reqs = [sched.submit(p, 9) for p in prompts]
    sched.run()
    for p, r in zip(prompts, reqs):
        assert len(r.tokens) == 9
        seq = np.concatenate([p, r.tokens])
        rows = _reference(seq)[len(p) - 1:len(seq) - 1]
        gap = rows.max(-1) - rows[np.arange(9), r.tokens]
        assert gap.max() <= (0.05 if quantize else 1e-4), gap
    assert sched.pool.used == 0


def test_dense_generation_carries_the_state_too():
    prompt = _tokens(20, seed=9)
    toks = np.asarray(generate_dense(
        PARAMS, jnp.asarray(prompt)[None], 6, CFG))[0]
    assert list(toks) == _alone(prompt, 6)
    cache = init_cache(CFG, 1, 32)
    assert set(cache[0]) == {"S", "conv"} and set(cache[3]) == {"k", "v"}


def test_a_reused_slot_starts_from_the_new_prompts_state():
    """One slot, two requests one after the other: the second streams
    as it does alone, whatever the first left in the slot."""
    a, b = _tokens(30, seed=10), _tokens(21, seed=11)
    sched = _sched(slots=1)
    ra, rb = sched.submit(a, 7), sched.submit(b, 7)
    sched.run()
    assert ra.retired_tick < rb.admitted_tick + 1
    assert rb.tokens == _alone(b, 7, slots=1)
    assert ra.tokens == _alone(a, 7, slots=1)


def test_two_requests_with_one_prompt_share_no_page():
    """Prefix pages let admission skip their prefill; the state at the
    page boundary is kept nowhere, so under state layers nothing is
    shared or registered, and both stream as one does alone."""
    prompt = _tokens(3 * P + 2, seed=12)
    sched = _sched()
    assert sched.shares_prefixes is False
    r1 = sched.submit(prompt, 6)
    sched.step()
    r2 = sched.submit(prompt, 6)
    sched.run()
    assert sched.pool.share_hits == 0 and sched.pool.cow_copies == 0
    assert r1.tokens == r2.tokens == _alone(prompt, 6)
    # the same scheduler over attention layers alone does share
    plain = dataclasses.replace(CFG, layer_mixers=None)
    s2 = ServingScheduler(init_params(plain, 1), plain, slots=3, n_inner=4,
                          prompt_chunk=C, max_prompt=64, page_tokens=P)
    assert s2.shares_prefixes is True
    s2.submit(prompt, 6)
    s2.step()
    s2.submit(prompt, 6)
    s2.run()
    assert s2.pool.share_hits == 3


def test_spans_carry_state_slots_held_experts_hit_and_local_pairs():
    from mpistragglers_jl_tpu.obs import timeline

    seen = []

    class Spy:
        def __init__(self, name, **args):
            self.name, self.args = name, dict(args)

        def __enter__(self):
            seen.append(self)
            return self

        def __exit__(self, *exc):
            return None

        def set_metadata(self, **args):
            self.args.update(args)

    sched = _sched()
    for n in (20, 9):
        sched.submit(_tokens(n, seed=n), 6)
    real = serving._annotate
    serving._annotate = Spy
    try:
        sched.run()
    finally:
        serving._annotate = real
    assert timeline.annotate is real
    ticks = [s for s in seen if s.name == "serving.tick"]
    assert ticks[0].args["state_slots"] == 0  # counted as the tick begins
    assert max(t.args["state_slots"] for t in ticks) == 2
    harvest = [s for s in seen if s.name == "serving.harvest"]
    for h in harvest:
        assert 0 < h.args["experts_hit"] <= E // 2
        # 3 slots x 4 experts a token, about half of them held here
        assert 0 < h.args["pairs_local"] < 3 * TOP_K
    # the 20-token prompt's two chunks: the first in one program with
    # the 9-token prompt's only one (admitted in the same tick; rows
    # seen are summed over a program's chunks), the second alone
    chunks = [s for s in seen if s.name == "serving.prefill_chunk"]
    assert [(c.args["chunks"], c.args["of"], c.args["rows_seen"])
            for c in chunks] == [(2, "2,1", 16 + 16), (1, 2, 32)]


# -- refusals, each by mechanism ---------------------------------------------


def test_what_is_written_for_rows_refuses_state_layers():
    from mpistragglers_jl_tpu.qos import TenantContract, TenantRegistry

    with pytest.raises(ValueError, match="no width"):
        ring_widths(CFG)
    qos = TenantRegistry([TenantContract("a")])
    for kw in ({"qos": qos}, {"cache": object()}):
        with pytest.raises(ValueError, match="gated delta-rule layers"):
            _sched(**kw)
    mesh = jax.make_mesh((1, 1), ("dp", "tp"))
    with pytest.raises(ValueError, match="gated delta-rule layers"):
        make_serving_scan(CFG, mesh, 4)
    with pytest.raises(ValueError, match="gated delta-rule layers"):
        param_specs(CFG, mesh)
    sched = _sched()
    r = sched.submit(_tokens(12), 20)
    sched.step()
    with pytest.raises(ValueError, match="KV-page migration"):
        sched.export_page_state(r)
    with pytest.raises(ValueError, match="adopt_page_state"):
        sched.adopt_page_state({})
    assert sched.can_adopt_state({}) is False
    with pytest.raises(ValueError, match="none K/V rows"):
        all_gdn = dataclasses.replace(CFG, layer_mixers=("gdn",) * 4)
        ServingScheduler(init_params(all_gdn, 0), all_gdn, slots=2,
                         page_tokens=P)


def test_fields_are_checked_at_construction():
    with pytest.raises(ValueError, match="layer_mixers"):
        dataclasses.replace(CFG, layer_mixers=("gdn", "attn"))
    with pytest.raises(ValueError, match="'attn' or 'gdn'"):
        dataclasses.replace(CFG, layer_mixers=("gdn", "mamba", "gdn", "attn"))
    with pytest.raises(ValueError, match="gdn_key_heads"):
        dataclasses.replace(CFG, gdn_key_heads=3)
    with pytest.raises(ValueError, match="rope_dims"):
        dataclasses.replace(CFG, rope_dims=18)
    with pytest.raises(ValueError, match="experts_held"):
        dataclasses.replace(CFG, experts_held=(8, 20))
    with pytest.raises(ValueError, match="route_score"):
        dataclasses.replace(CFG, route_score="tanh")
    assert CFG.state_layers and not CFG.plain_block
    assert CFG.held_experts == E // 2
