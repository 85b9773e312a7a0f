"""Sliding-window attention (round 4): the Mistral-style band through
every kernel and the serving path.

Contract: ``TransformerConfig(attn_window=W)`` makes position q attend
positions (q-W, q] only. The reference oracle implements the band as a
plain mask; the flash kernels must match it (they additionally SKIP
blocks entirely left of the band); ring and Ulysses must match the
dense oracle under sequence sharding; the KV-cache decode path masks
the same band, so teacher-forced decode equals the windowed training
forward.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from mpistragglers_jl_tpu.models.decode import (
    _ring_from_cache,
    decode_step_dense,
    decode_step_ring_dense,
    generate_dense,
    generate_ring_dense,
    init_cache,
    init_ring_cache,
    make_ring_generate,
    prefill_dense,
)
from mpistragglers_jl_tpu.models.transformer import (
    TransformerConfig,
    forward_dense,
    init_params,
    make_forward,
    shard_params,
)
from mpistragglers_jl_tpu.ops.flash_attention import flash_attention
from mpistragglers_jl_tpu.parallel import make_mesh
from mpistragglers_jl_tpu.parallel.ring_attention import (
    reference_attention,
)

CFG = TransformerConfig(
    vocab=61, d_model=64, n_heads=8, n_kv_heads=2, n_layers=2, d_ff=128,
    attn_window=5,
)


def _qkv(Hq, Hkv, B=2, L=32, D=8, seed=0):
    rng = np.random.default_rng(seed)
    mk = lambda h: jnp.asarray(rng.standard_normal((B, L, h, D)),
                               jnp.float32)
    return mk(Hq), mk(Hkv), mk(Hkv)


def test_reference_window_band_semantics():
    """The oracle's band: position q sees exactly (q-W, q]."""
    q, k, v = _qkv(1, 1, B=1, L=8)
    W = 3
    out = reference_attention(q, k, v, causal=True, window=W)
    # hand-build the same thing row by row
    for t in range(8):
        lo = max(0, t - W + 1)
        qs = q[:, t:t + 1]
        want = reference_attention(
            qs, k[:, lo:t + 1], v[:, lo:t + 1], causal=False
        )
        np.testing.assert_allclose(
            np.asarray(out[:, t:t + 1]), np.asarray(want),
            atol=1e-5, rtol=1e-5,
        )


@pytest.mark.parametrize("blocks", [(8, 8), (16, 8)])
@pytest.mark.parametrize("hkv", [1, 4])
@pytest.mark.parametrize("W", [1, 5, 16, 100])
def test_flash_window_matches_reference(W, hkv, blocks):
    """Flash (block-skipping + in-block band mask) vs the oracle —
    values and all three grads, GQA included, in square blocks and where
    the band crosses unequal ones; W=100 > L pins
    window-larger-than-sequence == full causal."""
    q, k, v = _qkv(4, hkv, L=32)
    bq, bk = blocks

    def f_flash(q, k, v):
        o = flash_attention(
            q, k, v, causal=True, window=W, block_q=bq, block_k=bk,
        )
        return (o.astype(jnp.float32) ** 2).sum()

    def f_ref(q, k, v):
        o = reference_attention(q, k, v, causal=True, window=W)
        return (o.astype(jnp.float32) ** 2).sum()

    o_got = flash_attention(
        q, k, v, causal=True, window=W, block_q=bq, block_k=bk
    )
    o_want = reference_attention(q, k, v, causal=True, window=W)
    np.testing.assert_allclose(
        np.asarray(o_got), np.asarray(o_want), atol=1e-5, rtol=1e-5
    )
    g_got = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
    g_want = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b, n in zip(g_got, g_want, "qkv"):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=1e-4, rtol=1e-4,
            err_msg=f"d{n} W={W}",
        )


@pytest.mark.parametrize("tile", [8, 4])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("hkv", [1, 4])
def test_flash_grid_with_every_kind_of_block(hkv, dtype, tile,
                                             monkeypatch):
    """L 64 in blocks of 8 under a window of 20: every q block's sweep
    holds one block visible in full (the body without a mask), the
    causal diagonal and two blocks on the window's left edge (the body
    with one) and skipped blocks on both sides; computed whole, and in
    four sub-tiles of 4 x 4 a block, where a fetched block holds tiles
    of more than one kind. Forward and the three gradients against the
    oracle, float32 at this file's tolerances, bfloat16 at
    tests/test_flash_attention.py's."""
    from mpistragglers_jl_tpu.ops import flash_attention as fa

    monkeypatch.setattr(fa, "_TILE", tile)
    L, W, blk = 64, 20, 8
    plan = fa.block_plan(L, L, causal=True, window=W, block_q=blk,
                         block_k=blk)
    n = L // blk
    assert plan["block"] == "8x8" and plan["grid_steps"] == n * n
    assert plan["tile"] == f"{tile}x{tile}"
    if tile == blk:  # interior: j == i - 1; run: j in i-3 .. i
        assert plan["interior_steps"] == n - 1
        assert plan["run_steps"] == n + (n - 1) + (n - 2) + (n - 3)
    else:
        assert plan["interior_steps"] > 3 * (n - 1)
        assert plan["pairs_run"] < (4 * n - 6) * blk * blk
    q, k, v = (x.astype(dtype) for x in _qkv(4, hkv, L=L, seed=11))
    w = _qkv(4, hkv, L=L, seed=12)[0]

    def loss(attn):
        return lambda q, k, v: jnp.sum(
            attn(q, k, v).astype(jnp.float32) * w)

    flash = lambda q, k, v: flash_attention(
        q, k, v, causal=True, window=W, block_q=blk, block_k=blk)
    oracle = lambda q, k, v: reference_attention(
        q, k, v, causal=True, window=W)
    exact = dtype == jnp.float32
    tol = dict(atol=1e-5, rtol=1e-5) if exact else dict(atol=3e-2,
                                                        rtol=3e-2)
    got = flash(q, k, v)
    assert got.dtype == dtype
    np.testing.assert_allclose(
        np.asarray(got, np.float32),
        np.asarray(oracle(q, k, v), np.float32), **tol)
    g_got = jax.grad(loss(flash), argnums=(0, 1, 2))(q, k, v)
    g_want = jax.grad(loss(oracle), argnums=(0, 1, 2))(q, k, v)
    gtol = dict(atol=1e-4, rtol=1e-4) if exact else tol
    for a, b, name in zip(g_got, g_want, "qkv"):
        assert a.dtype == dtype
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32),
            err_msg=f"d{name}", **gtol)


@pytest.mark.parametrize(
    "shape,attn",
    [
        ((2, 2, 2), "ring"),
        ((1, 4, 2), "ring"),
        ((2, 2, 2), "ulysses"),
    ],
)
def test_sharded_window_forward_matches_dense(shape, attn):
    """The band crosses sequence shards: ring/Ulysses with attn_window
    must match the dense windowed oracle."""
    cfg = dataclasses.replace(CFG, attn=attn)
    mesh = make_mesh(shape, ("dp", "sp", "tp"))
    params = init_params(cfg, seed=1)
    rng = np.random.default_rng(2)
    toks = jnp.asarray(rng.integers(0, cfg.vocab, (4, 16)), jnp.int32)
    want = forward_dense(params, toks, cfg)
    # sanity: the window really changes the function
    full = forward_dense(
        params, toks, dataclasses.replace(cfg, attn_window=None)
    )
    assert not np.allclose(np.asarray(want), np.asarray(full), atol=1e-3)
    fwd = make_forward(cfg, mesh)
    got = fwd(
        shard_params(params, cfg, mesh),
        jax.device_put(toks, NamedSharding(mesh, P("dp", "sp"))),
    )
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), atol=2e-4, rtol=2e-4
    )


def test_windowed_decode_teacher_forced():
    """The serving path masks the same band: prefill + decode steps
    reproduce the windowed training forward position-for-position."""
    cfg = CFG
    params = init_params(cfg, seed=3)
    rng = np.random.default_rng(4)
    toks = jnp.asarray(rng.integers(0, cfg.vocab, (2, 12)), jnp.int32)
    want = forward_dense(params, toks, cfg)
    cache = init_cache(cfg, 2, 12)
    lg, cache = prefill_dense(params, toks[:, :6], cache, cfg)
    np.testing.assert_allclose(
        np.asarray(lg), np.asarray(want[:, :6]), atol=1e-4, rtol=1e-4
    )
    for t in range(6, 12):
        lg, cache = decode_step_dense(
            params, toks[:, t], cache, jnp.int32(t), cfg
        )
        np.testing.assert_allclose(
            np.asarray(lg), np.asarray(want[:, t]), atol=1e-4,
            rtol=1e-4, err_msg=f"position {t}",
        )


def test_window_validation():
    with pytest.raises(ValueError, match="attn_window must be"):
        TransformerConfig(attn_window=0)
    q, k, v = _qkv(2, 2, L=8)
    with pytest.raises(ValueError, match="window must be"):
        flash_attention(q, k, v, causal=True, window=0)


@pytest.mark.slow
@pytest.mark.parametrize("Tp", [3, 12])
def test_ring_decode_teacher_forced(Tp):
    """The O(W) ring cache reproduces the windowed training forward
    position-for-position, through multiple slot wraparounds (decode
    runs to position 19 with W=5, so every slot is overwritten at least
    once) and through the Tp < W warmup (Tp=3 leaves unwritten slots
    that must self-mask)."""
    cfg = CFG
    L = 20
    params = init_params(cfg, seed=3)
    rng = np.random.default_rng(4)
    toks = jnp.asarray(rng.integers(0, cfg.vocab, (2, L)), jnp.int32)
    want = forward_dense(params, toks, cfg)
    cache = init_cache(cfg, 2, Tp)
    lg, cache = prefill_dense(params, toks[:, :Tp], cache, cfg)
    ring = [_ring_from_cache(cl, Tp, cfg.attn_window) for cl in cache]
    for t in range(Tp, L):
        lg, ring = decode_step_ring_dense(
            params, toks[:, t], ring, jnp.int32(t), cfg
        )
        np.testing.assert_allclose(
            np.asarray(lg), np.asarray(want[:, t]), atol=1e-4,
            rtol=1e-4, err_msg=f"position {t}",
        )


@pytest.mark.parametrize("Tp", [3, 12])
def test_ring_generate_matches_masked_generate(Tp):
    """generate_ring_dense == generate_dense token-for-token on a
    window config: same band, different storage. n_new=13 with W=5
    wraps every slot."""
    cfg = CFG
    params = init_params(cfg, seed=5)
    rng = np.random.default_rng(6)
    prompt = jnp.asarray(rng.integers(0, cfg.vocab, (2, Tp)), jnp.int32)
    want = generate_dense(params, prompt, 13, cfg)
    got = generate_ring_dense(params, prompt, 13, cfg)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_ring_generate_sampled_matches_masked():
    """Sampling draws from identical logits streams (same fold-in key
    schedule), so the sampled token streams agree too."""
    cfg = CFG
    params = init_params(cfg, seed=7)
    rng = np.random.default_rng(8)
    prompt = jnp.asarray(rng.integers(0, cfg.vocab, (2, 6)), jnp.int32)
    key = jax.random.key(9)
    want = generate_dense(
        params, prompt, 8, cfg, temperature=0.8, top_k=7, key=key
    )
    got = generate_ring_dense(
        params, prompt, 8, cfg, temperature=0.8, top_k=7, key=key
    )
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("shape", [(2, 2), (1, 4)])
def test_sharded_ring_generate_matches_dense(shape):
    """make_ring_generate over dp x tp == the dense ring generator —
    including tp=4 > kv_heads=2, the replicated-groups cache layout."""
    cfg = CFG
    mesh = make_mesh(shape, ("dp", "tp"))
    params = init_params(cfg, seed=10)
    rng = np.random.default_rng(11)
    prompt = jnp.asarray(rng.integers(0, cfg.vocab, (2, 7)), jnp.int32)
    want = generate_ring_dense(params, prompt, 9, cfg)
    gen = make_ring_generate(cfg, mesh, 9)
    got = gen(
        shard_params(params, cfg, mesh),
        jax.device_put(prompt, NamedSharding(mesh, P("dp", None))),
    )
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_ring_cache_is_O_window():
    """The structural claim: ring leaves are (B, W, Hkv, Dh) however
    long the stream — no max_len anywhere in the layout."""
    cfg = CFG
    ring = init_ring_cache(cfg, batch=3)
    for layer in ring:
        assert layer["k"].shape == (
            3, cfg.attn_window, cfg.kv_heads, cfg.head_dim
        )
        assert layer["v"].shape == layer["k"].shape


def test_ring_requires_window():
    cfg = dataclasses.replace(CFG, attn_window=None)
    with pytest.raises(ValueError, match="sliding-window"):
        init_ring_cache(cfg, batch=1)
    params = init_params(cfg, seed=0)
    prompt = jnp.zeros((1, 4), jnp.int32)
    with pytest.raises(ValueError, match="sliding-window"):
        generate_ring_dense(params, prompt, 2, cfg)


@pytest.mark.parametrize("maker_kind", ["ring", "ulysses"])
def test_standalone_wrappers_take_window(maker_kind):
    from mpistragglers_jl_tpu.parallel.ring_attention import (
        make_ring_attention,
        make_ulysses_attention,
    )

    mesh = make_mesh((4,), ("sp",))
    q, k, v = _qkv(4, 4, L=32)
    maker = (
        make_ring_attention if maker_kind == "ring"
        else make_ulysses_attention
    )
    f = maker(mesh, causal=True, window=5)
    spec = NamedSharding(mesh, P(None, "sp", None, None))
    got = f(*(jax.device_put(x, spec) for x in (q, k, v)))
    want = reference_attention(q, k, v, causal=True, window=5)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), atol=1e-5, rtol=1e-5
    )


def test_public_ring_from_cache_matches_private_and_guards():
    """ADVICE r4: the prefill->ring handoff is public API now; the
    guard rejects a source cache too short to hold the prompt (a
    clamped dynamic_update_slice would otherwise corrupt the ring
    silently)."""
    from mpistragglers_jl_tpu.models.decode import ring_from_cache

    cfg = CFG
    Tp = 7
    params = init_params(cfg, seed=5)
    rng = np.random.default_rng(6)
    toks = jnp.asarray(rng.integers(0, cfg.vocab, (2, Tp)), jnp.int32)
    cache = init_cache(cfg, 2, Tp)
    _, cache = prefill_dense(params, toks, cache, cfg)
    pub = ring_from_cache(cache, Tp, cfg)
    priv = [_ring_from_cache(cl, Tp, cfg.attn_window) for cl in cache]
    for a, b in zip(pub, priv):
        for kk in a:
            np.testing.assert_array_equal(np.asarray(a[kk]),
                                          np.asarray(b[kk]))
    short = init_cache(cfg, 2, Tp - 2)
    with pytest.raises(ValueError, match="positions < prompt"):
        ring_from_cache(short, Tp, cfg)
    # prefilling a too-short arena refuses at trace time, too
    with pytest.raises(ValueError, match="does not fit the cache"):
        prefill_dense(params, toks, short, cfg)
