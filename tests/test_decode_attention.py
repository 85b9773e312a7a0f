"""Pallas int8 decode-attention kernel (ops/decode_attention.py):
online-softmax single-query attention with in-VMEM dequantization,
pinned against the einsum-form oracle (models/decode.py
``_cache_scores``/``_cache_pv`` composition) on identical quantized
caches. Shapes use head_dim 128 — the kernel's lane-width gate — so
the same configs the flagship serves are what the CI mesh tests
(interpret mode off-TPU, like the flash kernels).
"""

import dataclasses

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
