"""int8 KV cache (round 4): half the serving cache bytes, bounded error.

Layout: int8 K/V plus per-(batch, position, head) f32 absmax scales
(models/decode.py ``_kv_quantize``). Dequantization is a rank-1
correction folded into the attention einsums — scores scale by ``k_s``,
probabilities by ``v_s`` — so no full-size dequantized copy exists.
Quantization is a serving-time flag orthogonal to cache layout: masked
max_len, O(W) ring, and chunked-extend paths all share the one write
path (``_cache_write``), which these tests pin pairwise.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from mpistragglers_jl_tpu.models.decode import (
    _aligned_quantized_prefill,
    _incremental_forward,
    _kv_quantize,
    decode_step_dense,
    generate_dense,
    generate_ring_dense,
    init_cache,
    make_extend,
    make_generate,
    make_prefill,
    make_ring_generate,
    prefill_dense,
    ring_from_cache,
    shard_cache,
)
from mpistragglers_jl_tpu.models.transformer import (
    TransformerConfig,
    forward_dense,
    init_params,
    shard_params,
)
from mpistragglers_jl_tpu.parallel import make_mesh

CFG = TransformerConfig(
    vocab=61, d_model=64, n_heads=8, n_kv_heads=2, n_layers=2, d_ff=128
)


def _toks(B, L, seed=2):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.integers(0, CFG.vocab, (B, L)), jnp.int32)


def test_quantize_roundtrip_bound():
    """Absmax int8: per-element error <= scale/2 (round-to-nearest)."""
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((2, 5, 3, 16)), jnp.float32)
    xq, s = _kv_quantize(x)
    assert xq.dtype == jnp.int8
    err = jnp.abs(x - xq.astype(jnp.float32) * s[..., None])
    assert float(jnp.max(err - s[..., None] / 2)) <= 1e-6


@pytest.mark.slow
def test_teacher_forced_quantized_error_bounded():
    """int8 teacher-forced decode tracks the exact forward: logit error
    small against the logit scale (int8 absmax keeps ~2 decimal digits
    per row)."""
    params = init_params(CFG, seed=1)
    toks = _toks(2, 12)
    want = forward_dense(params, toks, CFG)
    cache = init_cache(CFG, 2, 12, quantize_kv=True)
    lg, cache = prefill_dense(params, toks[:, :6], cache, CFG)
    worst = 0.0
    for t in range(6, 12):
        lg, cache = decode_step_dense(
            params, toks[:, t], cache, jnp.int32(t), CFG
        )
        worst = max(worst, float(jnp.max(jnp.abs(lg - want[:, t]))))
    scale = float(jnp.std(want))
    assert worst < 0.15 * scale, (worst, scale)


def test_quantized_cache_halves_bytes():
    bf = init_cache(CFG, 2, 64)
    q8 = init_cache(CFG, 2, 64, quantize_kv=True)
    nbytes = lambda c: sum(x.nbytes for x in jax.tree.leaves(c))
    # int8 data is half of bf16... CFG default dtype is f32 in tests, so
    # compare against the quarter-size int8 payload + small scales
    kv_bytes = sum(
        layer[k].nbytes for layer in q8 for k in ("k", "v")
    )
    scale_bytes = sum(
        layer[k].nbytes for layer in q8 for k in ("k_s", "v_s")
    )
    itemsize = np.dtype(CFG.dtype).itemsize
    assert kv_bytes * itemsize == sum(
        layer[k].nbytes for layer in bf for k in ("k", "v")
    )
    # scales are the per-position vectors — D-fold smaller than data
    assert scale_bytes * CFG.head_dim == kv_bytes * 4  # f32 scales
    assert nbytes(q8) < nbytes(bf)


def test_generate_quantized_matches_exact_greedy():
    """On this model the int8 error does not flip the argmax: greedy
    streams agree with the exact cache (seeded, deterministic)."""
    params = init_params(CFG, seed=1)
    prompt = _toks(2, 6)
    want = generate_dense(params, prompt, 6, CFG)
    got = generate_dense(params, prompt, 6, CFG, quantize_kv=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("shape", [(2, 2), (1, 4)])
def test_sharded_quantized_generate_matches_dense(shape):
    """make_generate(quantize_kv=True) over dp x tp == the dense
    quantized generator, incl. tp=4 > kv_heads=2 replicated groups."""
    mesh = make_mesh(shape, ("dp", "tp"))
    params = init_params(CFG, seed=3)
    prompt = _toks(2, 7, seed=4)
    want = generate_dense(params, prompt, 8, CFG, quantize_kv=True)
    gen = make_generate(CFG, mesh, 8, quantize_kv=True)
    got = gen(
        shard_params(params, CFG, mesh),
        jax.device_put(prompt, NamedSharding(mesh, P("dp", None))),
    )
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_ring_quantized_matches_masked_quantized():
    """Quantization composes with the O(W) ring: same band, same int8
    values, same tokens."""
    cfg = dataclasses.replace(CFG, attn_window=5)
    params = init_params(cfg, seed=5)
    prompt = _toks(2, 6, seed=6)
    want = generate_dense(params, prompt, 9, cfg, quantize_kv=True)
    got = generate_ring_dense(params, prompt, 9, cfg, quantize_kv=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    mesh = make_mesh((2, 2), ("dp", "tp"))
    gen = make_ring_generate(cfg, mesh, 9, quantize_kv=True)
    got_sh = gen(
        shard_params(params, cfg, mesh),
        jax.device_put(prompt, NamedSharding(mesh, P("dp", None))),
    )
    np.testing.assert_array_equal(np.asarray(got_sh), np.asarray(want))


def test_aligned_prefill_scan_matches_one_shot():
    """The quantized ring oracle prefill's ``lax.scan``-ed full chunks
    are the same math as one directly traced chunk: every position
    attends the already-quantized cache either way, so the chunk size
    is invisible (the identity generate_ring_dense's docstring claims).
    chunk=4 over a 13-token prompt forces the scan body (3 full chunks)
    plus the ragged tail; chunk=64 traces the whole prompt at once."""
    cfg = dataclasses.replace(CFG, attn_window=5)
    params = init_params(cfg, seed=9)
    prompt = _toks(2, 13, seed=10)

    def run(chunk):
        c = init_cache(cfg, 2, 13, quantize_kv=True)
        return _aligned_quantized_prefill(
            params, prompt, c, cfg, decode_kernel=False, chunk=chunk
        )

    lg_scan, c_scan = run(4)
    lg_one, c_one = run(64)
    # each call returns its LAST chunk's logits; only the final
    # position overlaps (and it is the one generation consumes)
    np.testing.assert_allclose(
        np.asarray(lg_scan[:, -1]), np.asarray(lg_one[:, -1]),
        atol=1e-4, rtol=0,
    )
    for a, b in zip(c_scan, c_one):
        np.testing.assert_array_equal(
            np.asarray(a["k"]), np.asarray(b["k"])
        )
        np.testing.assert_array_equal(
            np.asarray(a["v"]), np.asarray(b["v"])
        )


def test_chunked_extend_quantized_matches_prefill():
    """Streaming prefill vs one-shot with int8 cache. Layer 0's cache
    is BITWISE equal (same embeddings -> same K/V -> same quantizer).
    Deeper layers and logits agree to quantization tolerance only: the
    extend path attends through the quantized cache while one-shot
    prefill's chunk kernel attends the exact chunk K/V, so layer-1+
    activations (hence their K/V, hence the rounding) drift by the
    quantization error — the documented asymmetry of exact-prefill."""
    mesh = make_mesh((1, 2), ("dp", "tp"))
    params = shard_params(init_params(CFG, seed=7), CFG, mesh)
    prompt = jax.device_put(
        _toks(1, 8, seed=8), NamedSharding(mesh, P("dp", None))
    )
    Lmax = 10
    prefill = make_prefill(CFG, mesh, quantize_kv=True)
    c0 = shard_cache(init_cache(CFG, 1, Lmax, mesh, quantize_kv=True),
                     CFG, mesh)
    lg_one, c_one = prefill(params, prompt, c0)
    extend = make_extend(CFG, mesh, quantize_kv=True)
    c = shard_cache(init_cache(CFG, 1, Lmax, mesh, quantize_kv=True),
                    CFG, mesh)
    for i in range(0, 8, 4):
        lg, c = extend(params, prompt[:, i:i + 4], c, jnp.int32(i))
    np.testing.assert_allclose(
        np.asarray(lg[:, -1]), np.asarray(lg_one), atol=1e-2
    )
    for kk in ("k", "v"):  # layer 0: bitwise
        np.testing.assert_array_equal(
            np.asarray(c[0][kk]), np.asarray(c_one[0][kk])
        )
    for la, lb in zip(c[1:], c_one[1:]):  # deeper: dequant tolerance
        for kk in ("k", "v"):
            da = np.asarray(la[kk], np.float32) * np.asarray(
                la[f"{kk}_s"]
            )[..., None]
            db = np.asarray(lb[kk], np.float32) * np.asarray(
                lb[f"{kk}_s"]
            )[..., None]
            np.testing.assert_allclose(da, db, atol=2e-2)


D128 = TransformerConfig(
    vocab=97, d_model=256, n_heads=2, n_kv_heads=1, n_layers=2,
    d_ff=256,
)  # head_dim 128: the decode kernel's lane gate


def _traces_kernel(f, *args) -> bool:
    """Did tracing ``f(*args)`` reach the Pallas decode kernel?"""
    return "pallas_call" in str(jax.make_jaxpr(f)(*args))


def _greedy(params, prompt, n_new, cfg, *, decode_kernel, ring=False):
    """The dense runners' quantized greedy program with the decode
    route given by hand, as the resolved bool the inner functions
    take: the only way to the route the rule would not pick."""
    B, Tp = prompt.shape
    c = init_cache(cfg, B, Tp if ring else Tp + n_new, quantize_kv=True)
    if ring:
        logits, c = _aligned_quantized_prefill(
            params, prompt, c, cfg, decode_kernel=decode_kernel
        )
        c = ring_from_cache(c, Tp, cfg)
    else:
        logits, c = prefill_dense(params, prompt, c, cfg)
    tok = jnp.argmax(logits[:, -1], axis=-1).astype(prompt.dtype)

    def step(carry, pos):
        tok, c = carry
        lg, c = _incremental_forward(
            params, tok[:, None], c, pos, cfg, prefill=False, ring=ring,
            decode_kernel=decode_kernel,
        )
        return (jnp.argmax(lg[:, 0], axis=-1).astype(tok.dtype), c), tok

    (tok, _), toks = jax.lax.scan(
        step, (tok, c), Tp + jnp.arange(n_new - 1)
    )
    return jnp.concatenate([toks, tok[None]], axis=0).swapaxes(0, 1)


@pytest.mark.parametrize("window", [None, 128])
def test_batched_kernel_in_scan_matches_einsum(window):
    """B=4 >= KERNEL_MIN_BATCH: the in-scan decode steps route through
    the Pallas int8 kernel (interpreted on the CI mesh) — token streams
    equal the einsum dequant path exactly, full and sliding-window
    masks both."""
    from mpistragglers_jl_tpu.models.decode import KERNEL_MIN_BATCH

    cfg = dataclasses.replace(D128, attn_window=window)
    params = init_params(cfg, seed=9)
    B = KERNEL_MIN_BATCH
    rng = np.random.default_rng(10)
    prompt = jnp.asarray(rng.integers(0, cfg.vocab, (B, 6)), jnp.int32)

    def routed(p):
        return generate_dense(params, p, 7, cfg, quantize_kv=True)

    def einsum(p):
        return _greedy(params, p, 7, cfg, decode_kernel=False)

    assert _traces_kernel(routed, prompt)
    assert not _traces_kernel(einsum, prompt)
    np.testing.assert_array_equal(
        np.asarray(routed(prompt)), np.asarray(jax.jit(einsum)(prompt))
    )


def test_ring_kernel_in_scan_matches_einsum():
    """The O(W) ring generator at batch: the kernel's ring mode routes
    inside the decode scan; streams equal the einsum path."""
    cfg = dataclasses.replace(D128, attn_window=128)
    params = init_params(cfg, seed=11)
    rng = np.random.default_rng(12)
    prompt = jnp.asarray(rng.integers(0, cfg.vocab, (4, 6)), jnp.int32)

    def routed(p):
        return generate_ring_dense(params, p, 8, cfg, quantize_kv=True)

    def einsum(p):
        return _greedy(params, p, 8, cfg, decode_kernel=False, ring=True)

    assert _traces_kernel(routed, prompt)
    assert not _traces_kernel(einsum, prompt)
    np.testing.assert_array_equal(
        np.asarray(routed(prompt)), np.asarray(jax.jit(einsum)(prompt))
    )


def test_kernel_not_routed_below_min_batch():
    """B=1 stays on the einsum path (the per-call scan boundary cost
    isn't amortized): the program must still match the einsum stream
    AND the kernel stream — routing is a perf decision, never a
    numerics one."""
    params = init_params(D128, seed=13)
    rng = np.random.default_rng(14)
    prompt = jnp.asarray(rng.integers(0, D128.vocab, (1, 5)), jnp.int32)

    def routed(p):
        return generate_dense(params, p, 6, D128, quantize_kv=True)

    def by_hand(decode_kernel):
        return lambda p: _greedy(params, p, 6, D128,
                                 decode_kernel=decode_kernel)

    assert not _traces_kernel(routed, prompt)
    assert not _traces_kernel(by_hand(False), prompt)
    assert _traces_kernel(by_hand(True), prompt)
    auto = np.asarray(routed(prompt))
    np.testing.assert_array_equal(
        auto, np.asarray(jax.jit(by_hand(False))(prompt)))
    np.testing.assert_array_equal(
        auto, np.asarray(jax.jit(by_hand(True))(prompt)))


def test_shard_cache_places_scale_leaves():
    mesh = make_mesh((2, 2, 2), ("dp", "ep", "tp"))
    cfg = CFG  # dense: ep unused by specs but mesh may carry it
    c = shard_cache(init_cache(cfg, 2, 16, mesh, quantize_kv=True),
                    cfg, mesh)
    sh = c[0]["k_s"].sharding
    assert sh.spec == P(("dp",), None, "tp")
