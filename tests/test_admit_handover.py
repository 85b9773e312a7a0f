"""The admission's hand-over costs what the prompt holds: the arena's
rows become ring rows without a gather where no prompt can wrap the
ring (``decode._ring_from_cache``), and placement writes the pages the
prompt covers and leaves the others as the pool holds them
(``serving._place_paged``).

What the second half rests on is that no reader of a page needs zeros
behind the prompt, and it is shown here and not argued: a slot whose
pages a window-filling request left dirty serves a short request the
tokens a fresh scheduler serves it, on both routes of the tick, for
every kind of cache layer the program has. Sizes are the tests': a ring
of 64 to 128 rows in pages of 8 or 16, a "4,000-token" request one that
fills (or wraps) its ring, a "40-token" one a page or two.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.references import xing4_0
from mpistragglers_jl_tpu.models import decode, serving
from mpistragglers_jl_tpu.models import transformer as tr
from mpistragglers_jl_tpu.models.paging import NULL_PAGE
from mpistragglers_jl_tpu.models.serving import ServingScheduler
from mpistragglers_jl_tpu.models.transformer import (
    TransformerConfig,
    init_params,
)

# -- (a) the ring's rows: as they lie, or gathered ---------------------------

P = 8       # a page of the cases below
STRIDE = 2  # rows a pooled cell


def _gathered(cache_l, Tp, W, stride):
    """The parent's ``_ring_from_cache``: every leaf by ``jnp.take``."""
    s = jnp.arange(W)
    p = (Tp - 1) - jnp.mod((Tp - 1) - s, W)
    valid = p >= 0

    def gather(a):
        g = jnp.take(a, jnp.maximum(p, 0), axis=1)
        return jnp.where(valid.reshape((1, W) + (1,) * (a.ndim - 2)), g, 0)

    def cells(a):
        c = jnp.arange(W // stride)
        g = jnp.take(a, jnp.minimum(c, a.shape[1] - 1), axis=1)
        live = c < -(-Tp // stride)
        return jnp.where(live.reshape((1, -1) + (1,) * (a.ndim - 2)), g, 0)

    return {kk: a if kk in decode.STATE_LEAVES else cells(a) if kk == "kp"
            else gather(a) for kk, a in cache_l.items()}


def _arena_layer(L, seed):
    """One layer of a positional cache of ``L`` rows with nothing zero
    in it: int8 rows, their scales, pooled cells and a block of state."""
    rng = np.random.default_rng(seed)
    rows = lambda: jnp.asarray(  # noqa: E731
        rng.integers(1, 127, (1, L, 2, 16)), jnp.int8)
    scales = lambda: jnp.asarray(  # noqa: E731
        rng.uniform(0.5, 2.0, (1, L, 2)), jnp.float32)
    return {
        "k": rows(), "v": rows(), "k_s": scales(), "v_s": scales(),
        "kp": jnp.asarray(rng.standard_normal((1, L // STRIDE, 2, 16)) + 3,
                          jnp.float32),
        "S": jnp.asarray(rng.standard_normal((1, 2, 4, 4)), jnp.float32),
    }


def _takes_gather(L, W):
    layer = _arena_layer(L, 0)
    text = str(jax.make_jaxpr(
        lambda c, t: decode._ring_from_cache(c, t, W, STRIDE))(
            layer, jnp.int32(3)))
    return "gather" in text


LMAX = 48
SLICED = [(Tp, W) for W in (64, LMAX)  # Lmax < W, Lmax == W
          for Tp in (1, P - 1, P, P + 1, LMAX - 1, LMAX)]


@pytest.mark.parametrize("Tp,W", SLICED)
def test_the_ring_by_slice_is_the_gathers_ring(Tp, W):
    """An arena no longer than the ring: every leaf byte for byte what
    the gather gave, the rows a padded chunk left behind the prompt
    zero, and no gather in the program; the length traced, as the
    scheduler's program has it."""
    layer = _arena_layer(LMAX, seed=Tp)
    got = jax.jit(lambda c, t: decode._ring_from_cache(c, t, W, STRIDE))(
        layer, jnp.int32(Tp))
    want = _gathered(layer, Tp, W, STRIDE)
    assert set(got) == set(want)
    for kk in want:
        assert got[kk].dtype == want[kk].dtype, kk
        np.testing.assert_array_equal(
            np.asarray(got[kk]), np.asarray(want[kk]), err_msg=kk)
    assert got["k"].shape[1] == W and got["kp"].shape[1] == W // STRIDE
    assert not np.asarray(got["k"][:, Tp:]).any()
    assert np.asarray(got["k"][:, :Tp]).all()
    assert np.array_equal(got["S"], layer["S"])
    assert not _takes_gather(LMAX, W)


@pytest.mark.parametrize("Tp", [1, P + 1, 24, 25, 40, LMAX])
def test_an_arena_longer_than_the_ring_is_still_gathered(Tp):
    """``Lmax > W``: a prompt can wrap the ring, slot s holds the latest
    position congruent to it, and that is a gather, as it was."""
    W = 24
    layer = _arena_layer(LMAX, seed=Tp)
    got = decode._ring_from_cache(layer, Tp, W, STRIDE)
    want = _gathered(layer, Tp, W, STRIDE)
    for kk in want:
        np.testing.assert_array_equal(
            np.asarray(got[kk]), np.asarray(want[kk]), err_msg=kk)
    if Tp > W:  # wrapped: slot 0 holds a position of the second lap
        lap = (Tp - 1) - ((Tp - 1) % W)
        np.testing.assert_array_equal(
            np.asarray(got["k"][:, 0]), np.asarray(layer["k"][:, lap]))
    assert _takes_gather(LMAX, W)


# -- (b) placement writes the prompt's pages and no other --------------------


def _cfg(**kw):
    return TransformerConfig(**{**dict(
        vocab=97, d_model=32, n_heads=4, n_kv_heads=2, d_head=16,
        n_layers=2, d_ff=48), **kw})


PLACED = {
    # one width, a budget of 64 rows
    "dense": _cfg(max_context=64),
    # two widths: rings of 32 rows beside a budget of 64 (Trinity's kind)
    "windows": _cfg(n_layers=3, layer_windows=(32, 32, None),
                    max_context=64),
    # pooled cells beside the rows, and layers that are state alone
    "selecting": _cfg(
        n_layers=2, norm="rmsnorm", ffn="swiglu", tie_head=False,
        layer_mixers=("attn", "la"), la_heads=4, la_head_dim=16,
        max_context=64, sparse_block=P, sparse_topk=2, sparse_kernel=4,
        sparse_stride=STRIDE, sparse_init_blocks=1, sparse_window=16,
        sparse_dense_len=32),
    # rows AND a block of state in every layer
    "attn_ssm": _cfg(
        norm="rmsnorm", ffn="swiglu", tie_head=False,
        layer_mixers=("attn_ssm",) * 2, ssm_heads=4, ssm_head_dim=16,
        ssm_state=8, ssm_groups=2, ssm_conv=4, ssm_chunk=8,
        max_context=64),
}
SLOTS = 3


def _dirty(tree, seed):
    """The same shapes and types with nothing zero anywhere."""
    rng = np.random.default_rng(seed)

    def fill(a):
        if a.dtype == jnp.int8:
            return jnp.asarray(rng.integers(1, 127, a.shape), jnp.int8)
        return jnp.asarray(rng.uniform(1.0, 2.0, a.shape), a.dtype)

    return jax.tree.map(fill, tree)


def _parent_place(cfg, caches, ring, rows, s):
    """The parent's placement: every table entry of every leaf."""
    return [
        {kk: c[kk].at[s].set(r[kk][0].astype(c[kk].dtype))
         if kk in serving.STATE_LEAVES else c[kk].at[row].set(
             serving._rows_to_pages(kk, r[kk][0], P, cfg.sparse_stride,
                                    c[kk].shape[-1]).astype(c[kk].dtype))
         for kk in c}
        for c, r, row in zip(caches, ring, rows)
    ]


@pytest.mark.parametrize("pos0", [1, P, P + 1, 3 * P - 1, 31, 32, 33, 63,
                                  64, 70])
@pytest.mark.parametrize("name", sorted(PLACED))
def test_placement_writes_the_pages_the_rows_have_reached(name, pos0):
    """Pages ``0 .. ceil(pos0 / P) - 1`` of each width's table row (all
    of them once ``pos0`` has passed the width) hold the parent's
    bytes; every other page of the pool, the null page among them, holds
    what it held; pooled cells are the parent's in every page (zeros
    behind the prompt: the tick adds to them); the slot's state is set
    and no other slot's."""
    cfg = PLACED[name]
    kinds, kind_of = serving._layer_kinds(cfg)
    counts = tuple(0 if k is None else SLOTS * (kinds[k] // P) + 1
                   for k in kind_of)
    pools = _dirty(serving._fresh_pages(cfg, counts, P, True, slots=SLOTS),
                   seed=1)
    widths = decode._row_widths(cfg)
    arena = _dirty(serving._fresh_cache(cfg, 1, 64, True), seed=2)
    ring = [cl if W is None else decode._ring_from_cache(
        cl, min(pos0, 64), W, cfg.sparse_stride or None)
        for cl, W in zip(arena, widths)]
    # the slot's pages in no order, the entries behind its budget null
    rng = np.random.default_rng(pos0)
    budget = pos0 + 9
    pt_row = []
    for W in kinds:
        n = W // P
        row = rng.permutation(np.arange(1, SLOTS * n + 1))[:n]
        row[-(-min(W, budget) // P):] = NULL_PAGE
        pt_row.append(row.astype(np.int32))
    s = 1
    before = jax.tree.map(np.asarray, pools)
    want = jax.tree.map(np.asarray, _parent_place(
        cfg, pools, ring, serving._layer_tables(cfg, tuple(pt_row)), s))
    S = SLOTS
    got, tok, pos, done, keys = serving._place_paged(cfg, P)(
        pools, ring, jnp.zeros((S,), jnp.int32), jnp.zeros((S,), jnp.int32),
        jnp.ones((S,), bool), jax.random.split(jax.random.key(0), S),
        tuple(pt_row), np.int32(s), np.int32(7), np.int32(pos0),
        jax.random.key(5))
    assert int(tok[s]) == 7 and int(pos[s]) == pos0 and not bool(done[s])
    assert bool(done[0]) and bool(done[2])
    for li, (g, w, b) in enumerate(zip(got, want, before)):
        if kind_of[li] is None:
            row, held = None, 0
        else:
            row = pt_row[kind_of[li]]
            held = min(-(-pos0 // P), len(row))
        for kk in w:
            gk = np.asarray(g[kk])
            if kk in serving.STATE_LEAVES:
                np.testing.assert_array_equal(gk, w[kk])
                np.testing.assert_array_equal(gk[[0, 2]], b[kk][[0, 2]])
                assert not np.array_equal(gk[s], b[kk][s])
                continue
            if kk == "kp":  # every entry, as the parent wrote it
                np.testing.assert_array_equal(gk, w[kk])
                continue
            written = row[:held]
            assert NULL_PAGE not in written
            np.testing.assert_array_equal(
                gk[written], w[kk][written], err_msg=f"{li} {kk}")
            rest = np.setdiff1d(np.arange(gk.shape[0]), written)
            np.testing.assert_array_equal(
                gk[rest], b[kk][rest], err_msg=f"{li} {kk} untouched")
            # and the parent would have written the rest of the budget
            if held < len(row) and row[held] != NULL_PAGE:
                assert not np.array_equal(w[kk][row[held]],
                                          b[kk][row[held]])


# -- (c) a slot taken again behind a request that filled its pages -----------

RES = 1.4 / np.sqrt(32)
ROPE = 4
YARN = (10000.0, 64.0, 16, 32.0, 1.0, 1.0, 1.0)

# heads of 128 and an int8 cache: with four slots the tick takes the
# paged kernels (interpreted here), with three the gathered views
REUSED = {
    # full attention under a budget of 128 rows, the arena as long
    "dense": (TransformerConfig(
        vocab=97, d_model=256, n_heads=2, n_kv_heads=1, n_layers=2,
        d_ff=256, max_context=128), dict(page_tokens=16, max_prompt=128),
        (112, 12), (19, 16)),
    # rings of 64 rows under an arena of 128: the long request's prompt
    # wraps them (the gather), the short one's pages lie in their first lap
    "window": (TransformerConfig(
        vocab=97, d_model=256, n_heads=2, n_kv_heads=1, n_layers=2,
        d_ff=256, attn_window=64), dict(page_tokens=16, max_prompt=128),
        (120, 6), (19, 16)),
    # one latent row a position, whole lane tiles
    "latent": (TransformerConfig(
        vocab=97, d_model=32, n_heads=4, d_head=12, n_layers=3, d_ff=48,
        attn_impl="reference", norm="rmsnorm", norm_eps=1e-6, ffn="swiglu",
        tie_head=False, layer_mixers=("mla",) * 3, mla_q_rank=16,
        mla_kv_rank=128, mla_nope_dim=8, mla_rope_dim=ROPE, mla_v_dim=8,
        rope_table=tr.yarn_rope_table(ROPE, *YARN[:5]),
        attn_scale=12 ** -0.5 * xing4_0.yarn_mscale(1.0, 64.0) ** 2,
        hc_mult=4, layer_experts=(False, True, True), n_experts=8,
        experts_per_token=2, d_expert=16, shared_experts=1, route_scale=2.0,
        max_context=96), dict(page_tokens=8, max_prompt=64),
        (64, 28), (11, 16)),
    # rows and a state-space mixer's state side by side in every layer
    "recurrent": (TransformerConfig(
        vocab=96, d_model=64, n_heads=4, n_kv_heads=2, d_head=128,
        n_layers=2, d_ff=96, norm="rmsnorm", ffn="swiglu", tie_head=False,
        layer_mixers=("attn_ssm",) * 2, ssm_heads=16, ssm_head_dim=128,
        ssm_state=8, ssm_groups=2, ssm_conv=4, ssm_chunk=8, rope_theta=1e6,
        max_context=128), dict(page_tokens=8, max_prompt=96),
        (96, 28), (11, 16)),
    # a selection of key blocks by their pooled cells, dense up to 32
    # rows: the short request decodes far past that, so that its picks
    # are six of twelve blocks, made from cells the tick ADDS its keys to
    # (stale cells there change the picks, and this test's tokens)
    "selecting": (TransformerConfig(
        vocab=97, d_model=64, n_heads=4, n_kv_heads=2, d_head=128,
        n_layers=4, d_ff=128, norm="rmsnorm", norm_eps=1e-6, ffn="swiglu",
        tie_head=False, qk_norm=True, attn_gate=True, rope_full=False,
        emb_scale=12.0, layer_mixers=("attn", "la", "la", "la"),
        la_heads=4, la_head_dim=16, residual_scale=RES, head_scale=0.25,
        max_context=128, sparse_block=8, sparse_topk=2, sparse_kernel=4,
        sparse_stride=2, sparse_init_blocks=1, sparse_window=16,
        sparse_dense_len=32), dict(page_tokens=8, max_prompt=96),
        (96, 28), (11, 84)),
}


@functools.lru_cache(maxsize=None)
def _params(name):
    return init_params(REUSED[name][0], seed=7)


def _tokens(n, seed, vocab=96):
    return np.random.default_rng(seed).integers(
        1, vocab, (n,)).astype(np.int32)


@pytest.mark.parametrize("temperature", [0.0, 0.9])
@pytest.mark.parametrize("slots", [4, 3])
@pytest.mark.parametrize("name", sorted(REUSED))
def test_a_slot_behind_a_long_request_serves_the_fresh_schedulers_tokens(
        name, slots, temperature):
    """Every slot serves a request that fills its pages (and wraps the
    ring where rings wrap) to its end; then each serves a short one, of
    which placement writes a page or two among pages that still hold the
    long request's rows, scales and cells. Its tokens are those a
    scheduler with an untouched pool gives it, greedy and sampled, over
    pages read in place (four slots: the kernels) and over gathered
    views (three)."""
    cfg, kw, long, short = REUSED[name]

    def sched():
        return ServingScheduler(
            _params(name), cfg, slots=slots, n_inner=4, quantize_kv=True,
            prompt_chunk=16, temperature=temperature,
            top_k=8 if temperature else None, **kw)

    def submit(s, i):
        key = {"key": jax.random.key(100 + i)} if temperature else {}
        return s.submit(_tokens(short[0] + i, seed=50 + i), short[1], **key)

    used = sched()
    assert used.use_kernel == (slots == 4)
    olds = [used.submit(_tokens(long[0], seed=i), long[1])
            for i in range(slots)]
    used.run()
    assert all(r.finished for r in olds)
    # no page of the pool (but the null page) is as it was made
    for kd in used._kinds:
        leaf = np.asarray(used._caches[kd.layers[0]]["k"])
        assert all(leaf[p].any() for p in range(1, kd.pool.n_pages))
    got = [submit(used, i) for i in range(slots)]
    used.run()
    fresh = sched()
    want = [submit(fresh, i) for i in range(slots)]
    fresh.run()
    for g, w in zip(got, want):
        assert g.finished and len(g.tokens) == short[1]
        assert g.tokens == w.tokens
    for s in (used, fresh):
        for kd in s._kinds:
            kd.pool.check()
            assert kd.pool.used == 0


# -- (d) a migrated request, short of the ring's width and past it -----------

MCFG = TransformerConfig(
    vocab=61, d_model=64, n_heads=8, n_kv_heads=2, n_layers=2, d_ff=128,
    attn_window=16,
)
MPARAMS = init_params(MCFG, seed=11)


@pytest.mark.parametrize("prompt,ticks", [(5, 2), (9, 2), (11, 4)])
def test_a_migrated_request_decodes_as_it_did(prompt, ticks):
    """Captured (``models/disagg.py``'s image) at a position short of
    ``W`` (its pages in their first lap: the destination writes those
    the position has reached) and past it (a wrapped ring: every page):
    the stream continues token for token as the oracle's, out of a
    destination whose pool window-filling requests left dirty."""
    from mpistragglers_jl_tpu.models.decode import generate_ring_dense

    def make():
        return ServingScheduler(MPARAMS, MCFG, slots=2, n_inner=4,
                                prompt_chunk=8, max_prompt=32,
                                page_tokens=4)

    src, dst = make(), make()
    for i in range(2):
        dst.submit(_tokens(30, seed=i, vocab=61), 6)
    dst.run()
    p = _tokens(prompt, seed=9, vocab=61)
    r = src.submit(p, 24)
    for _ in range(ticks):
        src.step()
    assert not r.finished
    state = src.export_page_state(r)
    assert (state["pos"] > MCFG.attn_window) == (ticks == 4)
    dst.adopt_page_state(state)
    dst.run()
    want = generate_ring_dense(MPARAMS, jnp.asarray(p)[None], 24, MCFG)
    assert r.finished and r.tokens == [int(t) for t in np.asarray(want)[0]]
    for pool in (src.pool, dst.pool):
        pool.check()
        assert pool.used == 0
