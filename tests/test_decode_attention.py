"""Pallas int8 decode-attention kernel (ops/decode_attention.py):
online-softmax single-query attention with in-VMEM dequantization,
pinned against the einsum-form oracle (models/decode.py
``_cache_scores``/``_cache_pv`` composition) on identical quantized
caches. Shapes use head_dim 128 — the kernel's lane-width gate — so
the same configs the flagship serves are what the CI mesh tests
(interpret mode off-TPU, like the flash kernels).
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mpistragglers_jl_tpu.models.decode import (
    _cache_pv,
    _cache_scores,
    _band_mask,
    _NEG,
    _kv_quantize,
    generate_dense,
    init_cache,
    prefill_dense,
)
from mpistragglers_jl_tpu.models.transformer import (
    TransformerConfig,
    init_params,
)
from mpistragglers_jl_tpu.ops.decode_attention import (
    quantized_decode_attention,
)


def _quant_cache(B, L, Hkv, D, seed=0):
    rng = np.random.default_rng(seed)
    k = jnp.asarray(rng.standard_normal((B, L, Hkv, D)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, L, Hkv, D)), jnp.float32)
    kq, ks = _kv_quantize(k)
    vq, vs = _kv_quantize(v)
    return {"k": kq, "k_s": ks, "v": vq, "v_s": vs}


def _oracle(q, cache_l, pos, scale, window=None):
    """The einsum-form masked attention (the path the kernel replaces)."""
    L = cache_l["k"].shape[1]
    s = _cache_scores(q, cache_l, scale)
    mask = _band_mask(pos[None], jnp.arange(L), True, window)
    s = jnp.where(mask[None, None], s, _NEG)
    p = jax.nn.softmax(s, axis=-1)
    return _cache_pv(p, cache_l).astype(q.dtype)


# GQA groups of 4, 1 and 8 (one 8-row q tile a K/V head), then 12, 16
# and 3: the tile follows the group (16, 16 and 8 rows)
GROUPS = [(8, 2), (4, 4), (8, 1), (24, 2), (16, 1), (12, 4)]


@pytest.mark.parametrize("Hq,Hkv", GROUPS)
@pytest.mark.parametrize("pos", [0, 7, 200, 255])
def test_kernel_matches_einsum_oracle(Hq, Hkv, pos):
    B, L, D = 2, 256, 128
    cache = _quant_cache(B, L, Hkv, D, seed=pos)
    rng = np.random.default_rng(99)
    q = jnp.asarray(rng.standard_normal((B, 1, Hq, D)), jnp.float32)
    scale = D ** -0.5
    want = _oracle(q, cache, jnp.int32(pos), scale)
    got = quantized_decode_attention(
        q, cache, jnp.int32(pos), scale, block_k=128, interpret=True
    )
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), atol=2e-5, rtol=2e-5
    )


@pytest.mark.parametrize("W", [5, 64, 1000])
def test_kernel_window_band(W):
    B, L, Hq, Hkv, D = 1, 256, 4, 2, 128
    cache = _quant_cache(B, L, Hkv, D, seed=W)
    rng = np.random.default_rng(3)
    q = jnp.asarray(rng.standard_normal((B, 1, Hq, D)), jnp.float32)
    scale = D ** -0.5
    pos = jnp.int32(200)
    want = _oracle(q, cache, pos, scale, window=W)
    got = quantized_decode_attention(
        q, cache, pos, scale, window=W, block_k=128, interpret=True
    )
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), atol=2e-5, rtol=2e-5
    )


def _ring_oracle(q, cache_l, pos, scale):
    """The einsum-form ring attention (``_ring_cached_attention`` /
    ``_ring_attention_rows`` math): slot s holds position
    ``pos - ((pos - s) mod W)``, valid iff that position is >= 0.
    ``pos`` may be scalar or (B,) per-row."""
    W = cache_l["k"].shape[1]
    B = q.shape[0]
    posv = jnp.broadcast_to(
        jnp.asarray(pos, jnp.int32).reshape(-1), (B,)
    )
    s = _cache_scores(q, cache_l, scale)  # (B, H, 1, W)
    kpos = posv[:, None] - jnp.mod(
        posv[:, None] - jnp.arange(W)[None, :], W
    )
    s = jnp.where((kpos >= 0)[:, None, None, :], s, _NEG)
    p = jax.nn.softmax(s, axis=-1)
    return _cache_pv(p, cache_l).astype(q.dtype)


@pytest.mark.parametrize("B", [1, 4, 8])
@pytest.mark.parametrize("Hq,Hkv", GROUPS)
def test_batched_kernel_per_row_positions_match_oracle(B, Hq, Hkv):
    """The batched grid with a (B,) position vector — every row at its
    own decode step, the serving scheduler's shape — matches the
    einsum oracle row-for-row."""
    L, D = 256, 128
    cache = _quant_cache(B, L, Hkv, D, seed=10 * B + Hkv)
    rng = np.random.default_rng(100 + B)
    q = jnp.asarray(rng.standard_normal((B, 1, Hq, D)), jnp.float32)
    scale = D ** -0.5
    pos = jnp.asarray(rng.integers(0, L, B), jnp.int32)
    want = jnp.concatenate([
        _oracle(
            q[i:i + 1],
            {kk: vv[i:i + 1] for kk, vv in cache.items()},
            pos[i], scale,
        )
        for i in range(B)
    ])
    got = quantized_decode_attention(
        q, cache, pos, scale, block_k=128, interpret=True
    )
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), atol=2e-5, rtol=2e-5
    )


@pytest.mark.parametrize("B", [1, 4, 8])
@pytest.mark.parametrize("W", [128, 256])
@pytest.mark.parametrize("pos", [37, 129, 1000])
def test_ring_kernel_matches_ring_einsum(B, W, pos):
    """ring=True reads the O(W) ring layout: warmup (pos < W, stale
    slots masked), first wrap, and deep-stream positions all match the
    einsum ring reference."""
    Hq, Hkv, D = 8, 2, 128
    cache = _quant_cache(B, W, Hkv, D, seed=W + pos)
    rng = np.random.default_rng(7)
    q = jnp.asarray(rng.standard_normal((B, 1, Hq, D)), jnp.float32)
    scale = D ** -0.5
    want = _ring_oracle(q, cache, jnp.int32(pos), scale)
    got = quantized_decode_attention(
        q, cache, jnp.int32(pos), scale, ring=True, block_k=128,
        interpret=True,
    )
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), atol=2e-5, rtol=2e-5
    )


@pytest.mark.parametrize("Hq,Hkv", [(8, 2), (8, 1), (4, 4), (24, 2),
                                    (16, 1), (12, 4)])
def test_ring_kernel_per_row_positions(Hq, Hkv):
    """Per-row positions in ring mode — the serving tick's exact call:
    rows simultaneously in warmup, at the wrap boundary, and deep."""
    B, W, D = 4, 256, 128
    cache = _quant_cache(B, W, Hkv, D, seed=Hq)
    rng = np.random.default_rng(8)
    q = jnp.asarray(rng.standard_normal((B, 1, Hq, D)), jnp.float32)
    scale = D ** -0.5
    pos = jnp.asarray([3, 255, 256, 1000], jnp.int32)
    want = _ring_oracle(q, cache, pos, scale)
    got = quantized_decode_attention(
        q, cache, pos, scale, ring=True, block_k=128, interpret=True
    )
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), atol=2e-5, rtol=2e-5
    )


def _paged_case(B, Hq, Hkv, P, max_pages, pos, seed):
    """A page pool in the serving layout with every row's pages
    scattered over it: row 1 shares row 0's first page, the last row's
    last table entry is the null page (page 0, poisoned: a huge scale
    that would swamp anything it leaked into), the rest is shuffled.
    Returns (q, pool, page table, positions)."""
    from mpistragglers_jl_tpu.models.serving import _rows_to_pages

    D = 128
    rng = np.random.default_rng(seed)
    n_pages = B * max_pages + 1
    rows = _quant_cache(1, n_pages * P, Hkv, D, seed=seed)
    rows["k_s"] = rows["k_s"].at[:, :P].set(1e9)
    rows["v_s"] = rows["v_s"].at[:, :P].set(1e9)
    pool = {kk: _rows_to_pages(kk, a[0], P) for kk, a in rows.items()}
    pt = rng.permutation(np.arange(1, n_pages)).reshape(B, max_pages)
    if B > 1:
        pt[1, 0] = pt[0, 0]
    q = jnp.asarray(rng.standard_normal((B, 1, Hq, D)), jnp.float32)
    pos = np.asarray(pos, np.int32)
    if pos[-1] < (max_pages - 1) * P:  # the entry is never reached
        pt[-1, -1] = 0
    return q, pool, jnp.asarray(pt, jnp.int32), jnp.asarray(pos)


def _paged_oracle(q, pool, pt, pos, P, Hkv):
    """The gather route of the serving tick: every row's ring view out
    of the pool, then the einsum rows."""
    from mpistragglers_jl_tpu.models.serving import (
        _paged_gather,
        _ring_attention_rows,
    )

    view = _paged_gather(pool, pt, Hkv, P)
    return _ring_attention_rows(q, view, pos, q.shape[-1] ** -0.5)


# (max_pages, pages a grid step, positions of three rows): tables of
# 64 and 32 entries take 8 pages a step, one of 68 takes 4; rows in
# their first page only (every later step predicated off), rows mid
# table, and rows whose ring has wrapped (every page live)
PAGED = [
    (64, 8, (0, 9, 15)),
    (64, 8, (17, 500, 1023)),
    (64, 8, (1024, 1500, 5000)),
    (32, 8, (3, 200, 511)),
    (32, 8, (512, 513, 2000)),
    (68, 4, (5, 700, 1087)),
    (68, 4, (1088, 1100, 4000)),
]


@pytest.mark.parametrize("Hq,Hkv", [(24, 2), (8, 1)])
@pytest.mark.parametrize("max_pages,n,pos", PAGED)
def test_paged_kernel_matches_gather_route(Hq, Hkv, max_pages, n, pos):
    """The page-table form, several pages a grid step, against the
    serving tick's gather route (``_paged_gather`` +
    ``_ring_attention_rows``) on the same pool and table."""
    from mpistragglers_jl_tpu.ops.decode_attention import (
        _group_tile,
        _pages_per_step,
    )

    P = 16
    assert _pages_per_step(
        max_pages, P, Hkv, 128, _group_tile(Hq // Hkv)) == n
    q, pool, pt, posv = _paged_case(
        3, Hq, Hkv, P, max_pages, pos, seed=max_pages + pos[1])
    want = _paged_oracle(q, pool, pt, posv, P, Hkv)
    got = quantized_decode_attention(
        q, pool, posv, 128 ** -0.5, ring=True, page_table=pt,
        page_tokens=P, interpret=True,
    )
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), atol=2e-5, rtol=2e-5
    )


def test_paged_kernel_refuses_a_pool_in_another_layout():
    q, pool, pt, posv = _paged_case(2, 8, 2, 16, 8, (3, 40), seed=1)
    rows = {kk: a.reshape((-1,) + a.shape[2:]) for kk, a in pool.items()}
    with pytest.raises(ValueError, match="page pool leaves"):
        quantized_decode_attention(
            q, rows, posv, 1.0, ring=True, page_table=pt,
            page_tokens=16, interpret=True,
        )


# -- the latent form: one int8 row a position, key and value both -------------

LR, LROPE, LP = 128, 8, 16  # the latent's width, rotated dims, page rows


def _latent_case(B, H, max_pages, pos, seed, poison=True):
    """A latent layer's page pool in the serving layout
    (``_fresh_pages``: the row's values in whole lane tiles, its two
    scales as two "heads" of ``k_s``), tables as :func:`_paged_case`
    lays them: row 1 shares row 0's first page, the last row's last
    entry is the null page where no position reaches it, page 0
    poisoned. Returns (q, pool, page table, positions)."""
    from mpistragglers_jl_tpu.models.decode import _latent_leaves
    from mpistragglers_jl_tpu.models.serving import (
        _rows_to_pages,
        paged_row_lanes,
    )

    width = LR + LROPE
    rng = np.random.default_rng(seed)
    n_pages = B * max_pages + 1
    rows = _latent_leaves(jnp.asarray(rng.standard_normal(
        (1, n_pages * LP, 1, width)), jnp.float32), LR, True)
    if poison:
        rows["k_s"] = rows["k_s"].at[:, :LP].set(1e9)
    pool = {kk: _rows_to_pages(kk, a[0], LP, lanes=paged_row_lanes(width))
            for kk, a in rows.items()}
    assert pool["k"].shape == (n_pages, LP, 256)
    pt = rng.permutation(np.arange(1, n_pages)).reshape(B, max_pages)
    if B > 1:
        pt[1, 0] = pt[0, 0]
    pos = np.asarray(pos, np.int32)
    if pos.max(initial=0) < (max_pages - 1) * LP:
        pt[-1, -1] = 0
    T = pos.shape[1] if pos.ndim > 1 else 1  # queries a row
    q = jnp.asarray(rng.standard_normal((B, T, H, width)), jnp.float32)
    return q, pool, jnp.asarray(pt, jnp.int32), jnp.asarray(pos)


def _latent_oracle(q, pool, pt, pos):
    """The gather route of a latent layer's tick: every row's ring
    view out of the pool, then ``_ring_attention_rows(latent=R)``."""
    from mpistragglers_jl_tpu.models.serving import (
        _paged_gather,
        _ring_attention_rows,
    )

    view = _paged_gather(pool, pt, 1, LP, LR + LROPE)
    return _ring_attention_rows(q, view, pos, 0.11, latent=LR)


# (query rows, table entries, pages a grid step, positions of three
# rows): a row at 0, at a page's last row and at the next page's first;
# a slot inside its first page beside one at the table's last row
# (every page live); tables that 8 pages a step do not divide, 68
# entries (the null page behind a short request's budget) and 13: the
# last block repeats the last live page behind it; 3 entries, 4 pages a
# step; one entry. 32 and 128 query rows are the two cells' head
# counts; 4 are padded to a sublane tile
LATENT = [
    (32, 8, 8, (0, LP - 1, LP)),
    (32, 8, 8, (5, 8 * LP - 1, 3 * LP)),
    (32, 68, 8, (3, 700, 68 * LP - 1)),
    (32, 68, 8, (17, 2 * LP - 1, 40)),
    (32, 13, 8, (0, 100, 13 * LP - 1)),
    (32, 3, 4, (0, 2 * LP, 3 * LP - 1)),
    (32, 1, 1, (0, 7, LP - 1)),
    (128, 68, 8, (3, 700, 68 * LP - 1)),
    (128, 13, 8, (LP, 100, 13 * LP - 1)),
    (4, 8, 8, (5, 8 * LP - 1, 3 * LP)),
]


@pytest.mark.parametrize("H,max_pages,n,pos", LATENT)
def test_latent_kernel_matches_gather_route(H, max_pages, n, pos):
    """The latent form of the paged kernel against the einsum over the
    gathered rings of the same int8 pages."""
    from mpistragglers_jl_tpu.ops.decode_attention import (
        latent_pages_per_step,
        latent_decode_attention,
    )

    assert latent_pages_per_step(max_pages, LP, LR + LROPE, H) == n
    q, pool, pt, posv = _latent_case(3, H, max_pages, pos,
                                     seed=max_pages + pos[1])
    got = latent_decode_attention(q, pool, posv, pt, scale=0.11, P=LP, R=LR,
                                 interpret=True)
    assert got.shape == (3, 1, H, LR)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(_latent_oracle(q, pool, pt, posv)),
        atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("pos", [(0, LP - 2, 77), (LP - 1, 3 * LP, 8 * LP - 2)])
def test_latent_kernel_takes_a_drafting_steps_two_rows(pos):
    """A drafting step's ``(S, 2)`` queries at ``(p, p + 1)``: the
    kernel's ``2 S`` rows, a slot's table row once for each, against
    the einsum's two queries a slot over the same pages (both rows in
    the pages: the first must not see the second)."""
    from mpistragglers_jl_tpu.models.serving import _paged_latent_rows

    pos2 = np.asarray(pos, np.int32)[:, None] + np.arange(2, dtype=np.int32)
    q, pool, pt, posv = _latent_case(3, 32, 8, pos2, seed=int(pos2.sum()))
    assert q.shape[:2] == (3, 2)
    got = _paged_latent_rows(q, pool, pt, posv, 0.11, LP, LR)
    want = _latent_oracle(q, pool, pt, posv)
    assert got.shape == want.shape == (3, 2, 32, LR)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


def test_latent_kernel_on_a_retired_slots_null_table():
    """A retired slot still ticks over a table of null pages at a
    position past its end: its row reads page 0 alone, as the einsum's
    does, and leaves the other rows' results where they were."""
    from mpistragglers_jl_tpu.ops.decode_attention import (
        latent_decode_attention,
    )

    q, pool, pt, posv = _latent_case(3, 32, 8, (9, 100, 50), seed=4,
                                     poison=False)
    call = lambda t: latent_decode_attention(
        q, pool, posv, t, scale=0.11, P=LP, R=LR, interpret=True)
    retired = pt.at[1].set(0)
    got = call(retired)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(_latent_oracle(q, pool, retired, posv)),
        atol=2e-5, rtol=2e-5)
    np.testing.assert_array_equal(np.asarray(got[::2]),
                                  np.asarray(call(pt)[::2]))


def test_latent_kernel_reads_no_row_past_the_position():
    """Rows behind a slot's position (a rejected draft's stale row, the
    rest of its page, the pages behind) are never read: poison them."""
    from mpistragglers_jl_tpu.ops.decode_attention import (
        latent_decode_attention,
    )

    q, pool, pt, posv = _latent_case(1, 32, 8, (LP + 3,), seed=2)
    page, behind = pt[0, 1], pt[0, 2:]
    dirty = {"k": pool["k"].at[page, 4:].set(127).at[behind].set(127),
             "k_s": pool["k_s"].at[page, :, 4:].set(1e9)
                               .at[behind].set(1e9)}
    call = lambda pl_: latent_decode_attention(
        q, pl_, posv, pt, scale=0.11, P=LP, R=LR, interpret=True)
    np.testing.assert_array_equal(np.asarray(call(pool)),
                                  np.asarray(call(dirty)))


def test_latent_kernel_refuses_what_it_cannot_read():
    from mpistragglers_jl_tpu.ops.decode_attention import (
        latent_pages_per_step,
        latent_decode_attention,
    )

    q, pool, pt, posv = _latent_case(2, 32, 8, (3, 40), seed=1)
    call = functools.partial(latent_decode_attention, scale=1.0, P=LP,
                             interpret=True)
    with pytest.raises(ValueError, match="latent page pool leaves"):
        call(q, {**pool, "k": pool["k"][..., :LR + LROPE]}, posv, pt, R=LR)
    with pytest.raises(ValueError, match="whole lane tiles"):
        call(q, pool, posv, pt, R=LR - 8)
    with pytest.raises(ValueError, match="single-query"):
        call(jnp.concatenate([q, q], axis=1), pool, posv, pt, R=LR)
    # a page too large for the budget under 128 query rows
    assert latent_pages_per_step(8, 2048, 576, 128) is None


def test_ring_rejects_window():
    cache = _quant_cache(1, 128, 2, 128)
    q = jnp.zeros((1, 1, 4, 128), jnp.float32)
    with pytest.raises(ValueError, match="ring"):
        quantized_decode_attention(
            q, cache, jnp.int32(0), 1.0, window=64, ring=True,
            interpret=True,
        )


def test_kernel_block_predication_excludes_future():
    """Blocks wholly past pos (and entries past pos inside a block)
    must not leak: poison the future with huge values."""
    B, L, Hq, Hkv, D = 1, 128, 4, 2, 128
    cache = _quant_cache(B, L, Hkv, D, seed=1)
    poisoned = dict(cache)
    poisoned["k_s"] = cache["k_s"].at[:, 40:].set(1e9)
    poisoned["v_s"] = cache["v_s"].at[:, 40:].set(1e9)
    rng = np.random.default_rng(5)
    q = jnp.asarray(rng.standard_normal((B, 1, Hq, D)), jnp.float32)
    scale = D ** -0.5
    clean = quantized_decode_attention(
        q, cache, jnp.int32(39), scale, block_k=128, interpret=True
    )
    dirty = quantized_decode_attention(
        q, poisoned, jnp.int32(39), scale, block_k=128, interpret=True
    )
    np.testing.assert_array_equal(np.asarray(clean), np.asarray(dirty))


@pytest.mark.slow
def test_kernel_rides_generation_at_head_dim_128():
    """End-to-end: at a batch the rule routes (KERNEL_MIN_BATCH rows),
    a D=128 config's quantized greedy generation runs its decode steps
    through the kernel and matches the exact-cache stream, dense
    path."""
    from mpistragglers_jl_tpu.models.decode import KERNEL_MIN_BATCH

    cfg = TransformerConfig(
        vocab=97, d_model=256, n_heads=2, n_kv_heads=1, n_layers=2,
        d_ff=256,
    )
    assert cfg.head_dim == 128
    params = init_params(cfg, seed=7)
    rng = np.random.default_rng(8)
    prompt = jnp.asarray(
        rng.integers(0, cfg.vocab, (KERNEL_MIN_BATCH, 6)), jnp.int32
    )

    def quantized(p):
        return generate_dense(params, p, 7, cfg, quantize_kv=True)

    assert "pallas_call" in str(jax.make_jaxpr(quantized)(prompt))
    want = generate_dense(params, prompt, 7, cfg)
    np.testing.assert_array_equal(
        np.asarray(quantized(prompt)), np.asarray(want)
    )


def test_kernel_validation():
    cache = _quant_cache(1, 64, 2, 128)
    q = jnp.zeros((1, 2, 4, 128), jnp.float32)
    with pytest.raises(ValueError, match="single-query"):
        quantized_decode_attention(
            q, cache, jnp.int32(0), 1.0, interpret=True
        )
