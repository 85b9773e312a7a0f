"""A prefill chunk computes what is read: its attention walks the key
blocks its queries can see (models/decode.py ``_chunk_attention``), and
the server's head runs on the one row a request's first token reads
(models/serving.py ``serving_first_token``).

The plain reference kept here is the form the walk replaced: scores of
the chunk against every row of the cache, the band mask, one softmax
over the cache's length, p.v over all of it.
"""

import itertools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mpistragglers_jl_tpu.models import decode
from mpistragglers_jl_tpu.models.decode import (
    _NEG,
    CHUNK_BLOCK_K,
    _cache_pv,
    _cache_scores,
    _chunk_attention,
    _chunk_rows_seen,
    _incremental_forward,
    _kv_quantize,
)
from mpistragglers_jl_tpu.models.serving import (
    _extend_chunk_dense,
    _finish_admit_dense,
    _fresh_cache,
    _pick_rows,
)
from mpistragglers_jl_tpu.models.transformer import (
    TransformerConfig,
    init_params,
)
from mpistragglers_jl_tpu.parallel.ring_attention import _band_mask

T, D = 32, 16  # a chunk's rows, a head's width


def _dense_masked_attention(q, cache_l, qpos, scale, window, latent=None):
    """The whole cache scored at once: what ``_cached_attention`` did
    for a chunk before the walk, and still does for one query
    (``_chunk_attention``'s parameters; K/V rows only)."""
    assert latent is None
    Lmax = cache_l["k"].shape[1]
    s = _cache_scores(q, cache_l, scale)  # (B, H, T, Lmax) f32
    mask = _band_mask(qpos, jnp.arange(Lmax), True, window)
    s = jnp.where(mask[None, None], s, _NEG)
    p = jax.nn.softmax(s, axis=-1)
    return _cache_pv(p, cache_l).astype(q.dtype)


def _arena(rng, Lmax, Hkv, kind):
    """A cache layer whose EVERY row is written, loudly (rows past a
    chunk are another request's leftovers, fifty times a real row's
    size): what a recycled arena holds."""
    k, v = (
        jnp.asarray(rng.normal(size=(1, Lmax, Hkv, D)) * 50.0,
                    jnp.float32)
        for _ in range(2)
    )
    if kind == "int8":
        (kq, ks), (vq, vs) = _kv_quantize(k), _kv_quantize(v)
        return {"k": kq, "v": vq, "k_s": ks, "v_s": vs}
    return {"k": k.astype(jnp.bfloat16), "v": v.astype(jnp.bfloat16)}


# Lmax -> offsets: 0, one page (64), mid-arena, Lmax - T
SEVERAL = 4 * CHUNK_BLOCK_K
OFFSETS = (0, 64, SEVERAL // 2 + 40, SEVERAL - T)
# None; wider than every offset but the last (lo == 0 until the arena's
# end); narrower than the mid-arena offset (lo > 0)
WINDOWS = (None, SEVERAL - 300, 300)
CASES = [
    (SEVERAL, off, window, kind, group)
    for off, window, kind, group in itertools.product(
        OFFSETS, WINDOWS, ("int8", "bf16"), (12, 8))
] + [
    # one block: the arena of the small tests, and of a short prompt
    (CHUNK_BLOCK_K // 2, off, window, kind, 12)
    for off, window, kind in itertools.product(
        (0, 64, CHUNK_BLOCK_K // 2 - T), (None, 40), ("int8", "bf16"))
] + [
    # no multiple of the block: the last block slides back inside
    (2 * CHUNK_BLOCK_K + 76, off, window, "int8", 8)
    for off, window in itertools.product(
        (0, 2 * CHUNK_BLOCK_K - 10, 2 * CHUNK_BLOCK_K + 76 - T),
        (None, 300))
]


@pytest.mark.parametrize(
    "Lmax,off,window,kind,group", CASES,
    ids=[f"L{c[0]}-off{c[1]}-w{c[2]}-{c[3]}-g{c[4]}" for c in CASES],
)
def test_chunk_walk_equals_the_dense_masked_form(Lmax, off, window, kind,
                                                 group):
    rng = np.random.default_rng([Lmax, off, group])
    Hkv = 2
    cache_l = _arena(rng, Lmax, Hkv, kind)
    q = jnp.asarray(rng.normal(size=(1, T, Hkv * group, D)), jnp.float32)
    qpos = off + jnp.arange(T)
    scale = D ** -0.5
    got = jax.jit(_chunk_attention, static_argnums=(3, 4))(
        q, cache_l, qpos, scale, window)
    want = _dense_masked_attention(q, cache_l, qpos, scale, window)
    assert got.shape == want.shape == q.shape
    assert bool(jnp.all(jnp.isfinite(got)))
    # float32 scores on both sides; the walk only reorders the sums.
    # Values are up to 50 * sqrt(D) large (the leftovers' scale)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-3)


def test_chunk_size_does_not_reach_a_row():
    """Blocks lie on absolute positions: a query row meets the same
    blocks in the same order in a chunk of 32 at its offset and in a
    chunk of 8 inside it, so the two differ by what a product of
    another shape rounds differently and no more."""
    rng = np.random.default_rng(5)
    cache_l = _arena(rng, SEVERAL, 2, "int8")
    q = jnp.asarray(rng.normal(size=(1, T, 24, D)), jnp.float32)
    off, scale = SEVERAL // 2 + 40, D ** -0.5
    f = jax.jit(_chunk_attention, static_argnums=(3, 4))
    for window in (None, 300):
        whole = f(q, cache_l, off + jnp.arange(T), scale, window)
        part = f(q[:, 16:24], cache_l, off + 16 + jnp.arange(8), scale,
                 window)
        np.testing.assert_allclose(whole[:, 16:24], part, rtol=2e-5,
                                   atol=2e-3)


@pytest.mark.parametrize("windows", [(None, None), (300, None),
                                     (300, 700)])
def test_rows_seen_counts_the_walks_rows(windows):
    """The host's count for the span is the walk's own ``lo`` and
    ``hi``: rows from the first block the widest layer reads to the
    chunk's last row."""
    for off in (0, 64, 256, 1000, SEVERAL - T):
        bk = CHUNK_BLOCK_K
        lows = [0 if w is None else max(off - w + 1, 0) // bk
                for w in windows]
        assert _chunk_rows_seen(off, T, SEVERAL, windows) == (
            off + T - min(lows) * bk)
    assert _chunk_rows_seen(0, 256, 4096, (None,)) == 256
    assert _chunk_rows_seen(3840, 256, 4096, (None,)) == 4096
    assert _chunk_rows_seen(3840, 256, 4096, (2048, 2048)) == 4096 - 1536


CFG = TransformerConfig(
    vocab=53, d_model=64, n_heads=4, n_kv_heads=2, n_layers=2, d_ff=96,
    layer_windows=(40, None), max_context=2 * CHUNK_BLOCK_K + 64,
)
C, LMAX = 16, CFG.max_context


@pytest.fixture(scope="module")
def params():
    return init_params(CFG, seed=9)


def _prefill(params, prompt, quantize_kv=True):
    """Chunks of ``C`` through the server's own chunk program; returns
    (last hidden, cache, last chunk's offset)."""
    extend = _extend_chunk_dense(CFG, C, LMAX)
    cache = _fresh_cache(CFG, 1, LMAX, quantize_kv)
    n = -(-prompt.size // C)
    padded = np.zeros((1, n * C), np.int32)
    padded[0, :prompt.size] = prompt
    for i in range(n):
        hidden, cache = extend(
            params, jnp.asarray(padded[:, i * C:(i + 1) * C]), cache,
            jnp.int32(i * C))
    return hidden, cache, (n - 1) * C


@pytest.mark.parametrize("quantize_kv", [True, False], ids=["int8", "bf16"])
def test_cache_after_chunks_is_the_dense_forms(params, monkeypatch,
                                               quantize_kv):
    """The chunk's K/V writes are not the walk's. With the dense masked
    form in the walk's place (the program before it): the first layer's
    leaves, which no attention feeds, come out the same to the bit;
    rows outside the chunks are as the arena had them, to the bit, in
    every layer; a deeper layer's rows follow the attention below them,
    whose float32 sums the walk reorders (an int8 row by at most one
    step)."""
    prompt = np.random.default_rng(2).integers(1, CFG.vocab, size=3 * C)
    _, walked, _ = _prefill(params, prompt, quantize_kv)
    monkeypatch.setattr(decode, "_chunk_attention", _dense_masked_attention)
    _extend_chunk_dense.cache_clear()
    try:
        _, dense, _ = _prefill(params, prompt, quantize_kv)
    finally:
        _extend_chunk_dense.cache_clear()
    fresh = _fresh_cache(CFG, 1, LMAX, quantize_kv)
    for li, (wl, dl, fl) in enumerate(zip(walked, dense, fresh)):
        assert wl.keys() == dl.keys()
        for name in wl:
            np.testing.assert_array_equal(wl[name][:, 3 * C:],
                                          fl[name][:, 3 * C:])
            if li == 0:
                np.testing.assert_array_equal(wl[name], dl[name])
            elif wl[name].dtype == jnp.int8:
                step = np.abs(np.asarray(wl[name], np.int32)
                              - np.asarray(dl[name], np.int32))
                assert step.max() <= 1 and (step > 0).mean() < 1e-2
            else:
                np.testing.assert_allclose(
                    np.asarray(wl[name], np.float32),
                    np.asarray(dl[name], np.float32),
                    rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("temperature,top_k", [(0.0, None), (0.8, 5)],
                         ids=["greedy", "sampled"])
@pytest.mark.parametrize("true_len", [2 * C + 5, 3 * C],
                         ids=["ends_mid_chunk", "ends_on_last_row"])
def test_first_token_is_the_head_on_the_one_row(params, true_len,
                                                temperature, top_k):
    """``serving_first_token`` applies the head to one row of the last
    chunk's hidden state; the row taken from the chunk's (1, C, V)
    logits picks the same token, greedy and sampled."""
    prompt = np.random.default_rng(true_len).integers(
        1, CFG.vocab, size=true_len)
    hidden, cache, last_off = _prefill(params, prompt)
    assert hidden.shape == (1, C, CFG.d_model)
    key = jax.random.key(17)
    finish = _finish_admit_dense(CFG, LMAX, temperature, top_k)
    tok0, ring = finish(params, cache, hidden, jnp.int32(true_len),
                        jnp.int32(last_off), key)
    # the same last chunk with the head on every row, over the cache
    # as it stood before it (the chunk rewrites its own rows alike)
    padded = np.zeros((1, C), np.int32)
    padded[0, :true_len - last_off] = prompt[last_off:]
    logits, _ = _incremental_forward(
        params, jnp.asarray(padded), cache, jnp.int32(last_off), CFG,
        prefill=False)
    assert logits.shape == (1, C, CFG.vocab)
    row = logits[0, true_len - 1 - last_off]
    want = _pick_rows(row[None], jnp.asarray([true_len - 1]), key[None],
                      temperature, top_k, jnp.int32)[0]
    assert int(tok0) == int(want)
    assert len(ring) == CFG.n_layers


def test_one_chunk_program_and_no_score_tensor_of_the_arenas_length(
        params):
    """One program per (cfg, C, Lmax) whatever the offsets, and in its
    lowered text no tensor with both the chunk's rows and the arena's
    length: scores exist a block at a time."""
    extend = _extend_chunk_dense(CFG, C, LMAX)
    assert extend is _extend_chunk_dense(CFG, C, LMAX)
    cache = _fresh_cache(CFG, 1, LMAX, True)
    chunk = jnp.ones((1, C), jnp.int32)
    text = extend.lower(params, chunk, cache, jnp.int32(0)).as_text()
    assert not re.search(rf"x{C}x{LMAX}x", text), "a (.., C, Lmax) tensor"
    assert re.search(rf"x{C}x{CHUNK_BLOCK_K}x", text), "a block's scores"
    before = extend._cache_size()
    for off in (0, C, 600, LMAX - C):
        _, cache = extend(params, chunk, cache, jnp.int32(off))
    assert extend._cache_size() - before <= 1
