"""A block with layers of two kinds: sliding-window and full attention,
dense and dropless top-k expert feed-forward, RMSNorm before and after
each half, q/k norms, an output gate, a head size of its own and an
untied head. Tiny sizes, seeded random weights, float32 on the CPU.

The oracle is the benchmark's plain reference
(chipbench/references/afmoe.py), which imports nothing of the program.
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.references import afmoe
from mpistragglers_jl_tpu.models import moe
from mpistragglers_jl_tpu.models.decode import ring_widths
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

W, P, TOP_K = 16, 4, 2

# a dense layer, three window expert layers, a full-attention expert
# layer; head_dim 16 where d_model // n_heads is 8
CFG = TransformerConfig(
    vocab=97, d_model=32, n_heads=4, n_kv_heads=2, d_head=16, n_layers=5,
    d_ff=48, attn_impl="reference", norm="rmsnorm", ffn="swiglu",
    tie_head=False, qk_norm=True, attn_gate=True, post_norm=True,
    emb_scale=math.sqrt(32), layer_windows=(W, W, W, W, None),
    rope_full=False, layer_experts=(False, True, True, True, True),
    n_experts=8, experts_per_token=TOP_K, d_expert=16, shared_experts=1,
    route_scale=2.826, max_context=64,
)
PARAMS = init_params(CFG, seed=3)


def _tokens(n, seed=0):
    return np.random.default_rng(seed).integers(
        0, CFG.vocab, size=n).astype(np.int32)


def _reference(seq, **kw):
    return np.asarray(afmoe.stream_logits(
        PARAMS, jnp.asarray(seq), 0, len(seq), window=CFG.windows,
        top_k=TOP_K, **kw))


def _gaps(req):
    """How far each served token's logit lies below the best logit of
    the reference's WHOLE forward over prompt + served tokens."""
    seq = np.concatenate([req.prompt, np.asarray(req.tokens, np.int32)])
    rows = _reference(seq)[len(req.prompt) - 1: len(seq) - 1]
    return rows.max(-1) - rows[np.arange(len(req.tokens)), req.tokens]


def test_the_expert_bias_is_not_zero_and_head_dim_is_its_own():
    assert CFG.head_dim == 16 != CFG.d_model // CFG.n_heads
    bias = np.asarray(PARAMS["layers"][1]["router_bias"])
    assert np.abs(bias).max() > 0
    assert PARAMS["layers"][1]["router"].dtype == jnp.float32
    assert ring_widths(CFG) == (W, W, W, W, 64)


def test_forward_dense_equals_the_plain_reference():
    # 40 tokens: longer than the 16-token window, so the band and the
    # full layer differ. Both sides are float32 at HIGHEST precision and
    # differ only in the order of sums: 1e-5 on logits of size ~1.
    seq = _tokens(40)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(forward_dense(PARAMS, jnp.asarray(seq)[None], CFG))[0]
    want = _reference(seq)
    assert np.abs(want).max() > 0.1
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=0)


def test_the_bias_selects_and_does_not_weigh():
    """With the bias taken out of the selection the reference changes;
    with it also added to the weights it would change again. Here: the
    program follows the reference, whose weights are the bare scores."""
    seq = _tokens(12, seed=5)
    no_bias = jax.tree.map(lambda a: a, PARAMS)
    no_bias["layers"] = [
        {**lp, "router_bias": jnp.zeros_like(lp["router_bias"])}
        if "router_bias" in lp else lp for lp in PARAMS["layers"]
    ]
    with jax.default_matmul_precision("highest"):
        a = np.asarray(forward_dense(PARAMS, jnp.asarray(seq)[None], CFG))
        b = np.asarray(forward_dense(no_bias, jnp.asarray(seq)[None], CFG))
    assert np.abs(a - b).max() > 1e-3


@pytest.mark.parametrize("quantize_kv", [False, True],
                         ids=["paged-f32", "paged-int8"])
def test_scheduler_prefill_in_chunks_then_decode_follows_the_reference(
        quantize_kv):
    """Chunked prefill, then decoding through the paged cache, against
    the reference's whole forward, on logits. One prompt under the
    window, one over it (its rings wrap while the full layer grows), and
    two that share a 8-token prefix (the second skips its prefill in
    both kinds of page). With float32 pages every served token is the
    reference's best (ties apart: 1e-4). int8 K/V with one scale a
    position and head moves a logit by up to about 0.02 here; 0.08 is
    four times that and a tenth of what a wrong layer gives (> 1)."""
    with jax.default_matmul_precision("highest"):
        sched = ServingScheduler(
            PARAMS, CFG, slots=2, n_inner=2, prompt_chunk=4, max_prompt=44,
            quantize_kv=quantize_kv, page_tokens=P,
        )
        shared = _tokens(8, seed=7)
        prompts = [
            _tokens(5, seed=1), _tokens(41, seed=2),
            np.concatenate([shared, _tokens(3, seed=3)]),
        ]
        reqs = [sched.submit(p, 9) for p in prompts]
        sched.run()
        # the sharer comes when the owner of the prefix is resident
        owner = sched.submit(np.concatenate([shared, _tokens(5, seed=4)]), 12)
        while owner.admitted_tick is None or not owner.tokens:
            sched.step()
        sharer = sched.submit(np.concatenate([shared, _tokens(2, seed=8)]), 6)
        sched.run()
    hits = {name: pool.share_hits for name, pool in sched.pools.items()}
    assert hits == {"window": 2, "full": 2}  # two pages of each kind
    limit = 0.08 if quantize_kv else 1e-4
    for r in reqs + [owner, sharer]:
        assert r.finished and len(r.tokens) == r.max_new
        assert _gaps(r).max() <= limit
    for pool in sched.pools.values():
        pool.check()
        assert pool.used == 0


def test_int8_kernel_route_through_both_page_tables():
    """head_dim 128 and a GQA group of 2 route the paged int8 Pallas
    kernel (interpreted here); the window layers read through the
    narrow table and the full layer through the wide one."""
    cfg = dataclasses.replace(
        CFG, d_model=32, n_heads=2, n_kv_heads=1, d_head=128, n_layers=3,
        layer_windows=(32, 32, None), layer_experts=(False, True, True),
        max_context=64,
    )
    params = init_params(cfg, seed=4)
    with jax.default_matmul_precision("highest"):
        sched = ServingScheduler(
            params, cfg, slots=4, n_inner=2, prompt_chunk=8, max_prompt=48,
            quantize_kv=True, page_tokens=16,
        )
        assert sched.use_kernel
        reqs = [sched.submit(_tokens(n, seed=n), 6) for n in (7, 40)]
        sched.run()
    for r in reqs:
        seq = np.concatenate([r.prompt, np.asarray(r.tokens, np.int32)])
        rows = np.asarray(afmoe.stream_logits(
            params, jnp.asarray(seq), 0, len(seq), window=cfg.windows,
            top_k=TOP_K))[len(r.prompt) - 1: len(seq) - 1]
        gap = rows.max(-1) - rows[np.arange(len(r.tokens)), r.tokens]
        assert gap.max() <= 0.08  # int8 K/V, as above


# -- the expert layer -----------------------------------------------------------


def _plain_expert_sum(x, lp, idx, w):
    """sum over ALL experts with weight zero for the unselected."""
    E = lp["we_gate"].shape[0]
    dense_w = jnp.zeros((x.shape[0], E)).at[
        jnp.arange(x.shape[0])[:, None], idx].add(w)
    a = jax.nn.silu(jnp.einsum("td,edf->etf", x, lp["we_gate"]))
    a = a * jnp.einsum("td,edf->etf", x, lp["we_up"])
    y = jnp.einsum("etf,efd->etd", a, lp["we_down"])
    return jnp.einsum("etd,te->td", y, dense_w)


def test_grouped_product_equals_the_plain_sum_over_experts():
    lp = PARAMS["layers"][2]
    x = jnp.asarray(np.random.default_rng(1).standard_normal((13, 32)),
                    jnp.float32)
    with jax.default_matmul_precision("highest"):
        idx, w = moe.topk_route(x, lp["router"], lp["router_bias"], TOP_K,
                                CFG.route_scale)
        want = _plain_expert_sum(x, lp, idx, w)
        shared = jax.nn.silu(x @ lp["ws_gate"]) * (x @ lp["ws_up"])
        want = want + shared @ lp["ws_down"]
        got, hit = moe.moe_ffn_topk(x[None], lp, CFG)
    # float32 both, different order of sums
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want),
                               atol=2e-6, rtol=0)
    assert int(hit) == len(np.unique(np.asarray(idx)))
    # weights: the bare scores of the chosen, normalised and scaled
    np.testing.assert_allclose(np.asarray(w.sum(-1)), CFG.route_scale,
                               rtol=1e-6)


def test_grouped_matmul_with_empty_and_uneven_groups():
    rng = np.random.default_rng(2)
    sizes = np.array([0, 5, 0, 1, 11, 0, 0, 2], np.int32)
    xs = jnp.asarray(rng.standard_normal((int(sizes.sum()), 32)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((8, 32, 16)), jnp.float32)
    with jax.default_matmul_precision("highest"):
        got = moe.grouped_matmul(xs, w, jnp.asarray(sizes), jnp.float32)
    ends = np.cumsum(sizes)
    want = np.concatenate([
        np.asarray(xs[e - n:e]) @ np.asarray(w[g])
        for g, (e, n) in enumerate(zip(ends, sizes))
    ])
    np.testing.assert_allclose(np.asarray(got), want, atol=1e-5, rtol=0)


def test_no_token_is_dropped_when_all_choose_the_same_experts():
    """A bias that sends every token of a 64-token batch to experts 0
    and 1: a capacity-bound layer would drop most of them; here every
    token gets its two experts, as the plain sum says."""
    lp = dict(PARAMS["layers"][1])
    lp["router_bias"] = jnp.asarray([9.0, 8.0] + [0.0] * 6, jnp.float32)
    x = jnp.asarray(np.random.default_rng(3).standard_normal((64, 32)),
                    jnp.float32)
    with jax.default_matmul_precision("highest"):
        idx, w = moe.topk_route(x, lp["router"], lp["router_bias"], TOP_K,
                                CFG.route_scale)
        assert set(np.unique(np.asarray(idx))) == {0, 1}
        got, hit = moe.moe_ffn_topk(x[None], lp, CFG)
        shared = jax.nn.silu(x @ lp["ws_gate"]) * (x @ lp["ws_up"])
        want = _plain_expert_sum(x, lp, idx, w) + shared @ lp["ws_down"]
    assert int(hit) == 2
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want),
                               atol=5e-6, rtol=0)
    # and the weights are the scores, not the biased scores
    s = jax.nn.sigmoid(x @ lp["router"])
    pick = jnp.take_along_axis(s, idx, axis=1)
    np.testing.assert_allclose(
        np.asarray(w), np.asarray(pick / pick.sum(-1, keepdims=True)
                                  * CFG.route_scale), rtol=1e-5)


# -- page accounting by kind ----------------------------------------------------


def _pages_held(sched, slot):
    return {kd.name: int((kd.pt_host[slot] != 0).sum())
            for kd in sched._kinds}


def test_a_request_holds_pages_of_both_kinds_and_gives_both_back():
    sched = ServingScheduler(
        PARAMS, CFG, slots=2, n_inner=1, prompt_chunk=16, max_prompt=48,
        quantize_kv=True, page_tokens=P,
    )
    assert {k: p.n_pages - 1 for k, p in sched.pools.items()} == {
        "window": 2 * W // P, "full": 2 * 64 // P}
    long = sched.submit(_tokens(41, seed=1), 10)   # horizon 41 + 10 + 1
    short = sched.submit(_tokens(5, seed=2), 6)    # horizon 5 + 6 + 1
    while not (long.tokens and short.tokens):
        sched.step()
    assert not short.finished
    held = [_pages_held(sched, s) for s in range(2)]
    # the window kind never holds more than W / P pages a request; the
    # full kind holds the request's whole horizon
    assert held[0] == {"window": W // P, "full": 13}
    assert held[1] == {"window": 3, "full": 3}
    assert {k: p.used for k, p in sched.pools.items()} == {
        "window": W // P + 3, "full": 13 + 3}
    assert sched.cancel(long)          # cancelled while decoding
    assert {k: p.used for k, p in sched.pools.items()} == {
        "window": 3, "full": 3}
    sched.run()                        # the short one finishes
    assert short.finished and short.reason == "length"
    for pool in sched.pools.values():
        pool.check()
        assert pool.used == 0


def test_a_request_cancelled_in_prefill_gives_back_both_kinds():
    sched = ServingScheduler(
        PARAMS, CFG, slots=1, n_inner=2, prompt_chunk=4, max_prompt=44,
        quantize_kv=False, page_tokens=P,
    )
    req = sched.submit(_tokens(40, seed=1), 4)
    sched.step()                        # one chunk of ten
    assert not req.tokens
    assert all(p.used > 0 for p in sched.pools.values())
    assert sched.cancel(req)
    assert all(p.used == 0 for p in sched.pools.values())


def test_a_request_past_the_context_budget_is_refused_at_submit():
    sched = ServingScheduler(
        PARAMS, CFG, slots=1, n_inner=2, prompt_chunk=4, max_prompt=60,
        page_tokens=P,
    )
    with pytest.raises(ValueError, match="max_context"):
        sched.submit(_tokens(56, seed=1), 8)   # 56 + 8 + 2 > 64
    sched.submit(_tokens(54, seed=1), 8)


def test_the_tick_span_counts_pages_by_kind_and_harvest_the_experts_hit():
    from mpistragglers_jl_tpu.models import serving

    seen = []

    class Spy:
        def __init__(self, name, **args):
            self.name, self.args = name, dict(args)
            seen.append(self)

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return None

        def set_metadata(self, **args):
            self.args.update(args)

    real = serving._annotate
    serving._annotate = Spy
    try:
        sched = ServingScheduler(
            PARAMS, CFG, slots=2, n_inner=2, prompt_chunk=8, max_prompt=44,
            page_tokens=P,
        )
        sched.submit(_tokens(20, seed=1), 6)
        sched.run()
    finally:
        serving._annotate = real
    ticks = [s for s in seen if s.name == "serving.tick"]
    assert ticks[0].args["pages_window"] == 0
    assert ticks[1].args["pages_window"] == W // P
    assert ticks[1].args["pages_full"] == -(-28 // P)
    harvests = [s for s in seen if s.name == "serving.harvest"]
    # two rows (one of them idle) x 2 experts a token over 8 experts
    assert all(1 <= h.args["experts_hit"] <= 4 for h in harvests)
    assert sched.experts_hit == harvests[-1].args["experts_hit"]


# -- what cannot run this block says so, by mechanism ---------------------------


def test_sharded_programs_and_one_pool_features_refuse_by_mechanism():
    from mpistragglers_jl_tpu.parallel.mesh import make_mesh
    from mpistragglers_jl_tpu.qos import TenantContract, TenantRegistry

    with pytest.raises(ValueError, match="more than one cache width"):
        param_specs(CFG)
    mesh = make_mesh((1, 1), ("dp", "tp"))
    with pytest.raises(ValueError, match="more than one cache width"):
        make_serving_scan(CFG, mesh, 2)
    qos = TenantRegistry([TenantContract("a")])
    with pytest.raises(ValueError, match="more than one cache width"):
        ServingScheduler(PARAMS, CFG, slots=1, page_tokens=P, qos=qos)
    sched = ServingScheduler(PARAMS, CFG, slots=1, n_inner=2,
                             prompt_chunk=4, max_prompt=16, page_tokens=P)
    req = sched.submit(_tokens(6), 8)
    while not req.tokens:
        sched.step()
    with pytest.raises(ValueError, match="more than one cache width"):
        sched.export_page_state(req)
    one_width = dataclasses.replace(
        CFG, layer_windows=(W,) * 5, max_context=None)
    with pytest.raises(ValueError, match="dropless top-k expert"):
        param_specs(one_width)
