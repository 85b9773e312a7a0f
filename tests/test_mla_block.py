"""A block whose token mixer is latent attention (one row a position:
the normalised latent beside one rotated key all heads share, from
which keys and values are both read) under a residual path of four
streams mixed by matrices made from the token, over a dense
feed-forward in the first layer and sigmoid top-k experts behind it.
Tiny sizes, seeded random weights, float32 on the CPU.

The oracle is the benchmark's plain reference
(chipbench/references/xing4_0.py), which imports nothing of the program,
expands every position's keys and values for all heads and keeps no
cache.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.references import xing4_0
from mpistragglers_jl_tpu.models import decode, moe, serving
from mpistragglers_jl_tpu.models import transformer as tr
from mpistragglers_jl_tpu.models.decode import init_cache, ring_widths
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

C, P, R, ROPE = 12, 8, 24, 4  # chunk, page, latent's width, rotated dims
YARN = (10000.0, 64.0, 16, 32.0, 1.0, 1.0, 1.0)
SCALE = 12 ** -0.5 * xing4_0.yarn_mscale(1.0, 64.0) ** 2

CFG = TransformerConfig(
    vocab=97, d_model=32, n_heads=4, d_head=12, n_layers=3, d_ff=48,
    attn_impl="reference", norm="rmsnorm", norm_eps=1e-6, ffn="swiglu",
    tie_head=False, layer_mixers=("mla",) * 3, mla_q_rank=16,
    mla_kv_rank=R, mla_nope_dim=8, mla_rope_dim=ROPE, mla_v_dim=8,
    rope_table=tr.yarn_rope_table(ROPE, *YARN[:5]), attn_scale=SCALE,
    hc_mult=4, layer_experts=(False, True, True), n_experts=8,
    experts_per_token=2, d_expert=16, shared_experts=1, route_scale=2.0,
    max_context=96,
)
PARAMS = init_params(CFG, seed=5)
REF_KW = dict(hc_mult=4, top_k=2, route_scale=2.0, kv_rank=R, nope=8,
              iters=20, yarn=YARN)


def _tokens(n, seed=0):
    return np.random.default_rng(seed).integers(0, CFG.vocab, n).astype(
        np.int32)


def _reference(tokens, params=PARAMS):
    return np.asarray(xing4_0.stream_logits(
        params, jnp.asarray(tokens), 0, len(tokens), **REF_KW))


# ``salt``: a test that patches the program traces its own copy
@functools.partial(jax.jit, static_argnames=("cfg", "salt"))
def _chunk(params, chunk, arena, off, cfg, salt=None):
    return decode._incremental_forward(params, chunk, arena, off, cfg,
                                       prefill=False)


@functools.partial(jax.jit, static_argnames=("cfg", "salt"))
def _step(params, tok, pos, pool, pt, cfg, salt=None):
    views = [serving._paged_gather(cl, pt, cfg.cache_heads(li), P,
                                   cfg.latent_width)
             for li, cl in enumerate(pool)]
    lg, views, _ = serving._serving_forward(params, tok, pos, views, cfg)
    return lg, [serving._paged_scatter(cl, vw, pt, P)
                for cl, vw in zip(pool, views)]


def _served_logits(tokens, prompt_len, quantize_kv, cfg=CFG, params=PARAMS,
                   salt=None):
    """Teacher-forced logits of every position through the serving
    programs' own pieces: the prompt in chunks of C (off the page size
    P) into a positional arena, the arena's rows gathered to a ring and
    laid into pages of a pool, then one batched serving step a token
    over the pages' gathered views."""
    W, n = cfg.max_context, len(tokens)
    arena = serving._fresh_cache(cfg, 1, 64, quantize_kv)
    out = []
    for off in range(0, prompt_len, C):
        chunk = jnp.asarray(tokens[None, off:min(off + C, prompt_len)])
        lg, arena = _chunk(params, chunk, arena, jnp.int32(off), cfg, salt)
        out.append(np.asarray(lg[0]))
    ring = [decode._ring_from_cache(cl, prompt_len, W) for cl in arena]
    pool = serving._fresh_pages(cfg, W // P + 1, P, quantize_kv)
    pt = jnp.arange(1, W // P + 1, dtype=jnp.int32)[None]  # page 0: null
    pool = [{kk: c[kk].at[pt[0]].set(serving._rows_to_pages(
        kk, r[kk][0], P, lanes=c[kk].shape[-1]).astype(c[kk].dtype))
        for kk in c}
        for c, r in zip(pool, ring)]
    for pos in range(prompt_len, n):
        lg, pool = _step(params, jnp.asarray(tokens[pos:pos + 1]),
                         jnp.asarray([pos], jnp.int32), pool, pt, cfg, salt)
        out.append(np.asarray(lg))
    return np.concatenate(out)


# -- the block against the reference ------------------------------------------


def test_dense_forward_is_the_reference():
    tokens = _tokens(40)
    got = np.asarray(jax.jit(forward_dense, static_argnums=2)(
        PARAMS, jnp.asarray(tokens)[None], CFG))[0]
    want = _reference(tokens)
    # float32 both, sums in another order: a few ulps of logits near 4
    np.testing.assert_allclose(got, want, atol=3e-5, rtol=0)


def test_absorbed_attention_is_the_expanded():
    """The incremental forward attends its cache in the absorbed form,
    the dense forward the whole sequence in the expanded one."""
    tokens = jnp.asarray(_tokens(33, seed=1))[None]
    dense = jax.jit(forward_dense, static_argnums=2)(PARAMS, tokens, CFG)
    cache = init_cache(CFG, 1, 40)
    a, cache = _chunk(PARAMS, tokens[:, :19], cache, jnp.int32(0), CFG)
    b, cache = _chunk(PARAMS, tokens[:, 19:], cache, jnp.int32(19), CFG)
    # float32: the key's up-projection moved to the query's side
    np.testing.assert_allclose(
        np.concatenate([a, b], axis=1), dense, atol=3e-6, rtol=0)
    assert set(cache[0]) == {"k"} and cache[0]["k"].shape == (1, 40, 1, R + ROPE)


def _differences(got, want):
    d = np.abs(got - want)
    return float(d.max()), float(d.mean())


def test_chunked_prefill_and_paged_decode_are_the_reference():
    """Logits, not tokens, at every position; the chunk's boundaries
    (12) fall off the page's (8)."""
    tokens = _tokens(57, seed=2)
    want = _reference(tokens)
    # float32 rows: what is left is the order of the sums
    worst, _ = _differences(_served_logits(tokens, 41, False), want)
    assert worst < 3e-5
    # int8 rows: half of 1/127 of a part's largest value a dim, through
    # three layers. Over four streams of tokens the logits (near 4)
    # move by 0.0011 to 0.0035 on average and 0.03 to 0.17 at most
    worst, mean = _differences(_served_logits(tokens, 41, True), want)
    assert 1e-4 < mean < 0.006 and worst < 0.3


def test_a_cache_below_the_stated_precision_fails_the_tolerance(monkeypatch):
    """Rows kept at 4 bits where 8 are stated: 0.018 to 0.020 on
    average over the same four streams, three times the tolerance that
    int8 rows pass (the largest single difference does not tell them
    apart: 0.14 to 0.25)."""
    real = decode._kv_quantize

    def four_bits(x):
        q, s = real(x)
        return (jnp.round(q.astype(jnp.float32) / 16) * 16).astype(q.dtype), s

    monkeypatch.setattr(decode, "_kv_quantize", four_bits)
    tokens = _tokens(57, seed=2)
    _, mean = _differences(
        _served_logits(tokens, 41, True, salt="4 bits"), _reference(tokens))
    assert mean > 0.006


@pytest.mark.parametrize("fault", ["phi_res", "rotated_key", "scale"])
def test_an_injected_fault_fails_the_comparison(fault, monkeypatch):
    tokens = _tokens(57, seed=2)
    params, cfg = PARAMS, CFG
    if fault == "phi_res":
        params = {**PARAMS, "layers": [
            {**lp, "hc1_phi": lp["hc1_phi"].at[:, 8:].set(0.0)}
            for lp in PARAMS["layers"]]}
    elif fault == "rotated_key":
        real = decode._latent_leaves
        for mod in (decode, serving):
            monkeypatch.setattr(
                mod, "_latent_leaves", lambda row, R_, q: real(
                    row.at[..., R_:].set(0.0), R_, q))
    else:
        cfg = dataclasses.replace(CFG, attn_scale=None)
    got = _served_logits(tokens, 41, False, cfg, params, salt=fault)
    # each moves logits by tenths where the sound run moves them by 3e-5
    assert np.abs(got - _reference(tokens)).max() > 0.05


# -- the residual path ---------------------------------------------------------


def test_stream_matrices_are_doubly_stochastic_and_follow_the_token():
    x = jax.random.normal(jax.random.key(0), (2, 5, 4, 32), jnp.float32)
    h, (xf, res, post) = tr.hc_pre(x, PARAMS["layers"][1], CFG, "hc1")
    res = np.asarray(res)  # (i, j, B, L)
    assert res.shape == (4, 4, 2, 5) and h.shape == (2, 5, 32)
    # rows were divided last: exact to float32; columns to what 20
    # rounds leave (read: 3e-4 for the slowest of these ten tokens)
    np.testing.assert_allclose(res.sum(axis=1), 1.0, atol=2e-6)
    np.testing.assert_allclose(res.sum(axis=0), 1.0, atol=1e-3)
    assert res.min() > 0
    # a token's matrices are its own
    assert np.abs(res[:, :, 0, 0] - res[:, :, 1, 3]).max() > 0.01
    # and the reference's, entry for entry
    lp = PARAMS["layers"][1]
    pre, post_ref, res_ref = xing4_0.hc_matrices(
        x, lp["hc1_phi"], lp["hc1_alpha"], lp["hc1_b"], iters=20,
        precision="float32")
    np.testing.assert_allclose(np.moveaxis(res, (0, 1), (2, 3)), res_ref,
                               atol=2e-6)
    np.testing.assert_allclose(np.moveaxis(np.asarray(post), 0, 2),
                               post_ref, atol=2e-6)
    # the half's result goes back by Hpost, the streams by Hres
    y = jnp.ones((2, 5, 32))
    back = np.asarray(tr.hc_post(h, y, (xf, jnp.asarray(res), post)))
    want = np.einsum("ijbl,bljd->blid", res, np.asarray(x)) + np.moveaxis(
        np.asarray(post), 0, 2)[..., None]
    np.testing.assert_allclose(back, want, atol=1e-5)


def test_one_stream_is_the_block_as_it_was():
    """``hc_mult`` 1 adds nothing to a program: the same objects go
    through, and a lowered forward names no mixing."""
    plain = TransformerConfig(vocab=97, d_model=32, n_heads=4, n_layers=2,
                              d_ff=48)
    x = jnp.ones((1, 3, 32))
    h, mix = tr.hc_pre(x, {}, plain, "hc1")
    assert h is x and mix is None
    assert tr.hc_fold(x, plain) is x
    np.testing.assert_array_equal(tr.hc_post(x, 2 * x, None), 3 * x)
    params = init_params(plain, 0)
    assert not any("hc" in name for name in params["layers"][0])
    text = jax.jit(lambda p, t: forward_dense(p, t, plain)).lower(
        params, jnp.zeros((1, 4), jnp.int32)).as_text()
    assert "hc_mix" not in text and "while" not in text
    assert plain.plain_block and plain.layer_like(1) == 0


def test_yarn_table_against_a_direct_evaluation():
    theta, factor, original, fast, slow = 10000.0, 64.0, 4096, 32.0, 1.0
    table = tr.yarn_rope_table(64, theta, factor, original, fast, slow)
    assert len(table) == 32
    dim_of = lambda turns: (64 * np.log(original / (turns * 2 * np.pi))
                            / (2 * np.log(theta)))
    lo, hi = np.floor(dim_of(fast)), np.ceil(dim_of(slow))
    assert (lo, hi) == (10, 23)
    for i, got in enumerate(table):
        f = theta ** (-2 * i / 64)
        ramp = min(max((i - lo) / (hi - lo), 0.0), 1.0)
        assert got == pytest.approx(f * (1 - ramp) + f / factor * ramp,
                                    rel=1e-12)
    assert table[10] == pytest.approx(theta ** (-20 / 64))
    assert table[23] == pytest.approx(theta ** (-46 / 64) / 64)
    # the rotary functions read it where they read the base
    x = jax.random.normal(jax.random.key(1), (1, 3, 2, 64))
    pos = jnp.asarray([0, 5, 4000])
    got = tr._rope(x, pos, table=table)
    ang = np.asarray(pos)[:, None] * np.asarray(table)[None]
    x1, x2 = np.asarray(x[..., :32]), np.asarray(x[..., 32:])
    cos, sin = np.cos(ang)[None, :, None], np.sin(ang)[None, :, None]
    np.testing.assert_allclose(
        got, np.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1),
        atol=2e-4)  # float32 angles near 4000
    rows = serving._rope_rows(jnp.moveaxis(x, 1, 0), pos, table=table)
    np.testing.assert_allclose(jnp.moveaxis(rows, 0, 1), got, atol=1e-6)
    with pytest.raises(ValueError, match="rope_table has 32"):
        tr._rope(x[..., :32], pos, table=table)


# -- the ragged expert tile ----------------------------------------------------


@pytest.mark.parametrize("K,N,tile", [
    (896, 256, (128, 256)),   # K is 3.5 tiles of 256: the tile follows it
    (256, 896, (256, 128)),
    (448, 384, (256, 384)),   # no multiple of 128 divides 448: ragged
])
def test_grouped_product_where_the_tile_does_not_divide(monkeypatch, K, N,
                                                        tile):
    """3584 = 3.5 x 1024 at a size the Pallas interpreter runs: the
    tile follows the width where a multiple of 128 divides it, and the
    kernel's own masking takes the ragged last tile where none does."""
    monkeypatch.setattr(moe, "_GROUP_TILE", (8, 256, 256))
    assert (moe._width_tile(K, 256), moe._width_tile(N, 256)) == tile
    rng = np.random.default_rng(0)
    sizes = np.array([5, 0, 11, 8], np.int32)
    xs = jnp.asarray(rng.standard_normal((24, K)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((4, K, N)) / np.sqrt(K), jnp.float32)
    got = moe.grouped_matmul(xs, w, jnp.asarray(sizes), jnp.float32)
    group = np.repeat(np.arange(4), sizes)
    with jax.default_matmul_precision("highest"):
        want = jnp.einsum("mk,mkn->mn", xs, w[group])
    np.testing.assert_allclose(got, want, atol=2e-5)


# (K, N) of the benchmark's expert matrices (Trinity-Mini's gate / up
# and down, Qwen3-Next's gate / up, Xing4.0's gate / up and down): the
# (k, n) tile of a decode step's rows, then of a chunk's and a group's
WIDTHS = {
    (2048, 1024): ((1024, 1024), (2048, 1024)),
    (1024, 2048): ((1024, 1024), (1024, 1024)),
    (2048, 512): ((1024, 512), (2048, 512)),
    (3584, 1024): ((1792, 1024), (3584, 512)),
    (1024, 3584): ((1024, 1792), (1024, 1792)),
}


@pytest.mark.parametrize("K,N", WIDTHS)
@pytest.mark.parametrize("rows", [
    64, 128, 160,        # a decode step's pairs (160: two row tiles)
    1024, 2048, 2560,    # a prefill chunk's
    4096, 8192, 10240,   # a group of four chunks'
])
def test_the_tile_of_the_widths_the_benchmark_has(monkeypatch, rows, K, N):
    """A decode step's rows are handed to the kernel at (128, 1024,
    1024), a narrower width whole, and 3584 at its widest divisor; a
    chunk's and a group's take K whole and the n tile whose block
    stays within 4 MiB of bfloat16."""
    from jax.experimental.pallas.ops.tpu import megablox

    seen = []

    def gmm(xs, w, sizes, tiling, **_):
        seen.append(tiling)
        return jnp.zeros((xs.shape[0], w.shape[2]), xs.dtype)

    monkeypatch.setattr(megablox, "gmm", gmm)
    jax.eval_shape(
        lambda xs, w: moe.grouped_matmul(
            xs, w, jnp.array([60, rows - 60]), jnp.float32),
        jax.ShapeDtypeStruct((rows, K), jnp.bfloat16),
        jax.ShapeDtypeStruct((2, K, N), jnp.bfloat16))
    tk, tn = WIDTHS[K, N][rows > 2 * 128]
    assert seen == [(min(rows, 128), tk, tn)]
    assert tk * tn * 2 <= moe._WHOLE_K_BLOCK


@pytest.mark.parametrize("M,K,N,block,tile", [
    (48, 512, 256, 4 << 20, (512, 256)),   # two k tiles became one
    (48, 896, 384, 4 << 20, (896, 384)),   # 3.5 k tiles; n follows N
    (48, 512, 256, 512 * 128 * 4, (512, 128)),  # the n tile shrinks to fit
    (48, 512, 256, 512 * 127 * 4, (256, 256)),  # K too wide: as before
    (16, 448, 384, 4 << 20, (256, 384)),   # two row tiles: ragged k tile
])
def test_grouped_product_with_k_whole_reads_what_the_plain_sum_does(
        monkeypatch, M, K, N, block, tile):
    """More than two row tiles: the k tile is all of K. Groups that
    straddle a row tile's edge, an empty group, and rows of no group
    behind the last (a layer that holds a share of its experts),
    against the plain product, in the Pallas interpreter."""
    from jax.experimental.pallas.ops.tpu import megablox

    monkeypatch.setattr(moe, "_GROUP_TILE", (8, 256, 256))
    monkeypatch.setattr(moe, "_WHOLE_K_BLOCK", block)
    assert moe.group_tiling(M, K, N, 4) == (8, *tile)
    real, seen = megablox.gmm, []

    def gmm(*a, tiling, **kw):
        seen.append(tiling)
        return real(*a, tiling=tiling, **kw)

    monkeypatch.setattr(megablox, "gmm", gmm)
    rng = np.random.default_rng(1)
    sizes = np.array({48: [5, 0, 13, 9, 3, 11], 16: [1, 0, 3, 2, 1, 4]}[M],
                     np.int32)
    held = int(sizes.sum())  # the rows behind them are no group's
    assert held < M and all(sizes.cumsum() % 8 != 0)
    xs = jnp.asarray(rng.standard_normal((M, K)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((6, K, N)) / np.sqrt(K), jnp.float32)
    got = moe.grouped_matmul(xs, w, jnp.asarray(sizes), jnp.float32)
    assert seen == [(8, *tile)] and got.shape == (M, N)
    group = np.repeat(np.arange(6), sizes)
    with jax.default_matmul_precision("highest"):
        want = jnp.einsum("mk,mkn->mn", xs[:held], w[group])
    np.testing.assert_allclose(got[:held], want, atol=2e-5)


# -- the scheduler ---------------------------------------------------------------


def _sched(**kw):
    kw = {"slots": 3, "n_inner": 4, "quantize_kv": True, "page_tokens": P,
          "prompt_chunk": 16, "max_prompt": 64, **kw}
    return ServingScheduler(PARAMS, CFG, **kw)


def test_scheduler_serves_what_the_reference_ranks_first():
    """Tokens through the whole scheduler (paged int8 rows, grouped
    chunks, the tick over gathered views): each served token is the
    reference's best or within the int8 rows' noise of it."""
    sched = _sched()
    assert sched.use_kernel is False and sched.shares_prefixes is True
    assert sched._group == 3
    prompts = [_tokens(n, seed=n) for n in (5, 37, 20, 64, 9)]
    reqs = [sched.submit(p, 10) for p in prompts]
    sched.run()
    pool = sched._caches[0]
    assert set(pool) == {"k", "k_s"}
    # a row's 28 values in whole lane tiles, zeros behind them
    assert pool["k"].shape[1:] == (P, 128) and pool["k"].dtype == jnp.int8
    assert not np.asarray(pool["k"][..., R + ROPE:]).any()
    assert np.asarray(pool["k"][..., :R + ROPE]).any()
    assert pool["k_s"].shape[1] == 2
    for p, r in zip(prompts, reqs):
        assert len(r.tokens) == 10
        lg = _reference(np.concatenate([p, r.tokens]))[len(p) - 1:-1]
        gap = lg.max(-1) - lg[np.arange(10), r.tokens]
        assert gap.max() < 0.08


# a latent of whole lane tiles: with four slots the paged tick takes the
# kernel's latent form (interpreted here), with three it gathers
KCFG = dataclasses.replace(CFG, mla_kv_rank=128)
KPARAMS = init_params(KCFG, seed=5)


def test_the_kernel_route_serves_the_gather_routes_tokens():
    """The same requests over latent pages read in place and over
    gathered views: token for token the same streams, a shared prefix
    page among them, each token within the int8 rows' noise of the
    reference's best; the tick span says which route it took."""
    prompts = [_tokens(n, seed=n) for n in (5, 37, 20, 64, 9)]
    prompts.append(np.concatenate([prompts[1][:2 * P], _tokens(3, seed=1)]))

    def run(slots):
        sched = ServingScheduler(
            KPARAMS, KCFG, slots=slots, n_inner=4, quantize_kv=True,
            page_tokens=P, prompt_chunk=16, max_prompt=64)
        reqs = [sched.submit(p, 10) for p in prompts]
        return sched, reqs, _spans_of_a_run(sched)

    kern, got, spans = run(4)
    gather, want, gather_spans = run(3)
    assert kern.use_kernel and not gather.use_kernel
    assert kern.pool.share_hits > 0
    for route, seen in ((1, spans), (0, gather_spans)):
        ticks = [s for s in seen if s.name == "serving.tick"]
        assert ticks and {t.args["kernel"] for t in ticks} == {route}
    text = kern.lower_tick().as_text(debug_info=True)
    assert "paged_latent_attention" in text and "mla_attn" in text
    assert "kv_page_gather" not in text
    assert "kv_page_gather" in gather.lower_tick().as_text(debug_info=True)
    ref = functools.partial(xing4_0.stream_logits, KPARAMS,
                            **{**REF_KW, "kv_rank": 128})
    for p, r, w in zip(prompts, got, want):
        assert r.tokens == w.tokens and len(r.tokens) == 10
        toks = np.concatenate([p, r.tokens])
        lg = np.asarray(ref(jnp.asarray(toks), 0, len(toks)))[len(p) - 1:-1]
        assert (lg.max(-1) - lg[np.arange(10), r.tokens]).max() < 0.08


def test_the_latent_kernel_route_is_read_from_the_configuration():
    """What the paged tick's latent form needs, each from the
    configuration or the cache: int8 rows, a latent of whole lane
    tiles, every layer latent, a page that is a block of the kernel's
    and fits its budget under the absorbed query's rows."""
    possible = decode._paged_kernel_possible
    assert possible(KCFG, True, P)
    assert not possible(KCFG, False, P)  # rows in the model's dtype
    assert not possible(CFG, True, P)  # a latent of 24: no lane tile
    assert not possible(KCFG, True, 12)  # no whole 8-row tiles
    assert not possible(KCFG, True, 8192)  # a page past the VMEM budget
    # the positional and ring programs have no latent kernel
    assert not decode._kernel_possible(KCFG, True)


def test_a_shared_prefix_page_is_shared():
    sched = _sched()
    prompt = _tokens(3 * P + 3, seed=3)
    a = sched.submit(prompt, 6)
    sched.step()
    b = sched.submit(prompt, 6)
    sched.run()
    assert sched.pool.share_hits == 3
    assert a.tokens == b.tokens


def _spans_of_a_run(sched):
    """Run ``sched`` until it is empty; the ``serving.*`` spans it
    entered, in order."""
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

    real = serving._annotate
    serving._annotate = Spy
    try:
        sched.run()
    finally:
        serving._annotate = real
    assert timeline.annotate is real
    return seen


def test_spans_carry_latent_rows():
    sched = _sched()
    for n in (20, 9):
        sched.submit(_tokens(n, seed=n), 6)
    seen = _spans_of_a_run(sched)
    ticks = [s for s in seen if s.name == "serving.tick"]
    # counted as the tick begins: nothing decodes in the first; the
    # 9-token prompt is placed in it and decodes 4 steps; in the second
    # it has its 6 tokens and retires, the 20-token prompt's second
    # chunk runs and it decodes 4 steps
    assert [t.args["latent_rows"] for t in ticks[:3]] == [0, 9 + 4, 20 + 4]
    chunks = [s for s in seen if s.name == "serving.prefill_chunk"]
    assert [(c.args["chunks"], c.args["rows_seen"]) for c in chunks] == [
        (2, 16 + 16), (1, 32)]


@pytest.mark.parametrize("experts", [True, False])
def test_the_chunk_span_names_the_tile_of_its_expert_products(experts):
    """``expert_tile`` on ``serving.prefill_chunk``: the k x n tile the
    program's grouped gate and up products take for the rows it holds
    (a lone chunk's 16 x 2 pairs, the grouped program's three times
    that); a block without expert layers has no such product."""
    if experts:
        sched = _sched()
    else:
        cfg = TransformerConfig(vocab=97, d_model=32, n_heads=4,
                                n_kv_heads=2, n_layers=2, d_ff=64,
                                attn_window=64)
        sched = ServingScheduler(
            init_params(cfg, seed=5), cfg, slots=3, n_inner=4,
            quantize_kv=True, page_tokens=P, prompt_chunk=16, max_prompt=64)
    for n in (20, 9):
        sched.submit(_tokens(n, seed=n), 6)
    chunks = [s for s in _spans_of_a_run(sched)
              if s.name == "serving.prefill_chunk"]
    assert [c.args["chunks"] for c in chunks] == [2, 1]
    if experts:  # (32, 16) matrices, whole at 96 and at 32 rows
        assert [c.args["expert_tile"] for c in chunks] == ["32x16"] * 2
    else:
        assert not any("expert_tile" in c.args for c in chunks)


# -- refusals, each by mechanism ---------------------------------------------


def test_what_is_written_for_kv_heads_refuses_latent_layers():
    with pytest.raises(ValueError, match="no V"):
        ring_widths(CFG)
    mesh = jax.make_mesh((1, 1), ("dp", "tp"))
    with pytest.raises(ValueError, match="latent-attention layers"):
        make_serving_scan(CFG, mesh, 4)
    with pytest.raises(ValueError, match="latent-attention layers"):
        param_specs(CFG, mesh)
    sched = _sched()
    r = sched.submit(_tokens(12), 20)
    sched.step()
    with pytest.raises(ValueError, match="KV-page migration"):
        sched.export_page_state(r)
    with pytest.raises(ValueError, match="adopt_page_state"):
        sched.adopt_page_state({})
    assert sched.can_adopt_state({}) is False


def test_what_is_written_for_one_stream_refuses_streams():
    streams = TransformerConfig(vocab=97, d_model=32, n_heads=4, n_layers=2,
                                d_ff=48, hc_mult=2, attn_window=16)
    params = init_params(streams, 0)
    assert not streams.plain_block
    mesh = jax.make_mesh((1, 1), ("dp", "tp"))
    with pytest.raises(ValueError, match="2 streams"):
        param_specs(streams, mesh)
    with pytest.raises(ValueError, match="2 streams"):
        make_serving_scan(streams, mesh, 4)
    # and every other mixer takes the streams: attention under two
    tokens = jnp.asarray(_tokens(12))[None]
    dense = forward_dense(params, tokens, streams)
    cache = init_cache(streams, 1, 16)
    lg, _ = decode._incremental_forward(
        params, tokens, cache, jnp.int32(0), streams, prefill=False)
    np.testing.assert_allclose(lg, dense, atol=3e-6)


def test_fields_are_checked_at_construction():
    with pytest.raises(ValueError, match="five sizes"):
        dataclasses.replace(CFG, mla_kv_rank=0)
    with pytest.raises(ValueError, match="five sizes"):
        dataclasses.replace(CFG, d_head=16)
    with pytest.raises(ValueError, match="takes no window"):
        dataclasses.replace(CFG, attn_window=8)
    with pytest.raises(ValueError, match="hc_mult"):
        dataclasses.replace(CFG, hc_mult=0)
    assert CFG.latent_layers and not CFG.state_layers
    assert not CFG.plain_block and CFG.latent_width == R + ROPE
    assert CFG.softmax_scale == SCALE
    assert [CFG.cache_heads(li) for li in range(3)] == [1, 1, 1]
    # layers 1 and 2 are alike (mixer, span, kind of feed-forward)
    assert [CFG.layer_like(li) for li in range(3)] == [0, 1, 1]
