"""Continuous-batching serving scheduler (models/serving.py).

North-star serving scope — the reference is transport-only (SURVEY §2).
The oracle for every stream is the single-request ring generator
(models/decode.py ``generate_ring_dense``): the scheduler's batched
per-row step must reproduce it token-for-token for every request, no
matter how admissions, retirements, and slot reuse interleave.
"""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mpistragglers_jl_tpu.models.decode import generate_ring_dense
from mpistragglers_jl_tpu.models.serving import (
    Request,
    ServingScheduler,
    make_serving_scan,
    serving_decode_step_dense,
)
from mpistragglers_jl_tpu.models.transformer import (
    TransformerConfig,
    init_params,
)
from mpistragglers_jl_tpu.parallel import make_mesh

CFG = TransformerConfig(
    vocab=61, d_model=64, n_heads=8, n_kv_heads=2, n_layers=2, d_ff=128,
    attn_window=6,
)
PARAMS = init_params(CFG, seed=11)
RNG = np.random.default_rng(12)


def _prompt(n):
    return RNG.integers(1, CFG.vocab, size=n).astype(np.int32)


def _oracle(prompt, n_new, eos_id=None):
    toks = generate_ring_dense(
        PARAMS, jnp.asarray(prompt)[None], n_new, CFG, eos_id=eos_id
    )
    out = [int(t) for t in np.asarray(toks)[0]]
    if eos_id is not None and eos_id in out:
        out = out[: out.index(eos_id) + 1]
    return out


def test_single_request_matches_oracle():
    sched = ServingScheduler(PARAMS, CFG, slots=2, n_inner=4,
                             prompt_chunk=8, max_prompt=64, page_tokens=3)
    p = _prompt(5)
    r = sched.submit(p, max_new=13)
    sched.run()
    assert r.finished and r.reason == "length"
    assert r.tokens == _oracle(p, 13)


def test_batch_matches_oracle_every_request():
    """8 concurrent requests, varied prompt lengths and budgets — each
    stream equals its independent oracle (batching changes wall-clock,
    never content)."""
    sched = ServingScheduler(PARAMS, CFG, slots=4, n_inner=4,
                             prompt_chunk=8, max_prompt=64, page_tokens=3)
    reqs = [
        (sched.submit(p, max_new=n), p, n)
        for p, n in [(_prompt(3), 9), (_prompt(11), 6), (_prompt(8), 17),
                     (_prompt(1), 5), (_prompt(20), 8), (_prompt(6), 12),
                     (_prompt(15), 4), (_prompt(9), 10)]
    ]
    sched.run()
    for r, p, n in reqs:
        assert r.finished
        assert r.tokens == _oracle(p, n), f"request {r.id}"


def test_admission_queues_beyond_slots_and_reuses():
    """More requests than slots: the extras wait, retirements free
    slots, every slot is reused, and reuse never corrupts a stream
    (the kpos mask + row overwrite discipline)."""
    S = 2
    sched = ServingScheduler(PARAMS, CFG, slots=S, n_inner=2,
                             prompt_chunk=8, max_prompt=32, page_tokens=3)
    reqs = [(sched.submit(_prompt(4 + i), max_new=5 + i), 4 + i, 5 + i)
            for i in range(6)]
    assert sched.pending == 6 - 0  # nothing admitted before a tick
    sched.run()
    for r, plen, n in reqs:
        assert r.finished
        assert len(r.tokens) == n
    # 6 requests through 2 slots: at least one slot served >= 3
    admit_ticks = sorted(r.admitted_tick for r, _, _ in reqs)
    assert admit_ticks[0] == 1 and admit_ticks[-1] > 1


def test_straggling_requests_slot_reuse_mid_flight():
    """Requests arriving WHILE others decode (straggling admissions):
    short requests retire and their slots serve late arrivals; the
    long-running request's stream is unperturbed."""
    sched = ServingScheduler(PARAMS, CFG, slots=2, n_inner=2,
                             prompt_chunk=8, max_prompt=32, page_tokens=3)
    p_long = _prompt(6)
    r_long = sched.submit(p_long, max_new=24)
    p_short = _prompt(3)
    r_short = sched.submit(p_short, max_new=4)
    late = []
    for _ in range(30):
        sched.step()
        if r_short.finished and not late:
            # the short request's slot is free mid-flight; add two
            # stragglers that must reuse it
            late = [(sched.submit(_prompt(5), 6), 5, 6),
                    (sched.submit(_prompt(2), 3), 2, 3)]
        if (r_long.finished and late
                and all(r.finished for r, _, _ in late)):
            break
    assert r_long.finished and r_short.finished
    assert r_long.tokens == _oracle(p_long, 24)
    assert r_short.tokens == _oracle(p_short, 4)
    for r, _, _ in late:
        assert r.finished and len(r.tokens) == r.max_new
        assert r.admitted_tick > r_short.retired_tick - 1


def test_eos_retirement():
    """Rows retire at EOS with the tail stripped; an EOS-free oracle
    prefix check pins content."""
    # find an eos_id that actually occurs early in some greedy stream
    p = _prompt(7)
    free_run = _oracle(p, 16)
    eos = free_run[3]
    sched = ServingScheduler(PARAMS, CFG, slots=2, n_inner=4,
                             prompt_chunk=8, max_prompt=32, eos_id=eos,
                             page_tokens=3)
    r = sched.submit(p, max_new=16)
    sched.run()
    assert r.finished and r.reason == "eos"
    assert r.tokens == _oracle(p, 16, eos_id=eos)
    assert r.tokens[-1] == eos and eos not in r.tokens[:-1]


def test_chunked_prefill_interleaves_with_decode():
    """A long prompt admits chunk-by-chunk: in-flight decode keeps
    producing tokens during the admission ticks (the bounded-stall
    property), and the long prompt's stream still matches its oracle."""
    sched = ServingScheduler(PARAMS, CFG, slots=2, n_inner=2,
                             prompt_chunk=4, max_prompt=64, page_tokens=3)
    r_first = sched.submit(_prompt(4), max_new=40)  # admits in 1 chunk
    sched.step()
    tokens_before = len(r_first.tokens)
    p_long = _prompt(23)  # 6 chunks of 4
    r_long = sched.submit(p_long, max_new=6)
    # during the long admission, the first request must keep decoding
    sched.step()
    assert len(r_first.tokens) > tokens_before
    assert r_long.admitted_tick is not None and not r_long.tokens
    sched.run()
    assert r_long.tokens == _oracle(p_long, 6)
    assert r_first.tokens == _oracle(np.asarray(r_first.prompt), 40)


def test_request_validation():
    sched = ServingScheduler(PARAMS, CFG, slots=1, n_inner=1,
                             prompt_chunk=4, max_prompt=8, page_tokens=3)
    with pytest.raises(ValueError, match="exceeds max_prompt"):
        sched.submit(_prompt(9), max_new=2)
    with pytest.raises(ValueError, match="max_new"):
        Request(_prompt(3), 0)
    with pytest.raises(ValueError, match="empty"):
        Request(np.zeros(0, np.int32), 3)
    no_window = dataclasses.replace(CFG, attn_window=None)
    with pytest.raises(ValueError, match="ring cache"):
        ServingScheduler(PARAMS, no_window, slots=1, page_tokens=3)
    moe = dataclasses.replace(
        CFG, n_experts=2, d_model=64, attn="ulysses"
    )
    with pytest.raises(ValueError, match="dense-FFN"):
        ServingScheduler(init_params(moe, seed=1), moe, slots=1, page_tokens=3)


@pytest.mark.parametrize("block,names", [
    (dict(norm="rmsnorm", ffn="swiglu"), "a block other than"),
    (dict(tie_head=False), "a block other than"),
    (dict(mtp_depth=1), "multi-token-prediction module"),
    (dict(layer_windows=(6, None), max_context=32),
     "more than one cache width"),
], ids=["rmsnorm_swiglu", "untied_head", "mtp_module", "two_widths"])
def test_the_sharded_tick_refuses_what_the_whitelist_excludes(block, names):
    """``make_serving_scan`` asks ``require_plain_block``: whatever the
    sharded layout is not written for is refused under the tick's own
    name, with no function of the tick's to keep up to date."""
    cfg = dataclasses.replace(CFG, **block)
    assert not cfg.plain_block
    mesh = make_mesh((1, 1), ("dp", "tp"))
    with pytest.raises(ValueError, match=f"sharded tick.*{names}"):
        make_serving_scan(cfg, mesh, 2)


@pytest.mark.slow
def test_sharded_serving_scan_matches_dense():
    """The dp x tp serving tick (the driver-dryrun leg) reproduces the
    dense per-row step exactly on the virtual mesh."""
    S, n_inner = 4, 3
    mesh = make_mesh((2, 2), ("dp", "tp"))
    scan = make_serving_scan(CFG, mesh, n_inner)
    tok = jnp.asarray(RNG.integers(1, CFG.vocab, S), jnp.int32)
    pos = jnp.asarray([6, 3, 9, 7], jnp.int32)
    done = jnp.zeros((S,), bool)
    W = CFG.attn_window
    key = jax.random.key(0)
    mk = lambda k: jax.random.normal(  # noqa: E731
        k, (S, W, CFG.kv_heads, CFG.head_dim), CFG.dtype
    ) * 0.1
    caches = []
    ks = jax.random.split(key, 2 * CFG.n_layers)
    for i in range(CFG.n_layers):
        caches.append({"k": mk(ks[2 * i]), "v": mk(ks[2 * i + 1])})
    # dense reference: n_inner greedy steps by hand
    dtok, dpos, dcaches = tok, pos, caches
    want = []
    for _ in range(n_inner):
        lg, dcaches = serving_decode_step_dense(
            PARAMS, dtok, dpos, dcaches, CFG
        )
        dtok = jnp.argmax(lg, axis=-1).astype(tok.dtype)
        dpos = dpos + 1
        want.append(dtok)
    want = jnp.stack(want, axis=1)
    keys = jax.random.split(jax.random.key(0), S)
    got = scan(PARAMS, tok, pos, done,
               [dict(c) for c in caches], keys)  # donated: pass copies
    np.testing.assert_array_equal(np.asarray(got[4]), np.asarray(want))
    np.testing.assert_array_equal(np.asarray(got[0]),
                                  np.asarray(want[:, -1]))


def test_admission_time_retirement_in_step_return():
    """max_new=1 retires at admission; step() must report it (review
    r5 finding: it was freed but missing from the returned list)."""
    sched = ServingScheduler(PARAMS, CFG, slots=1, n_inner=2,
                             prompt_chunk=8, max_prompt=16, page_tokens=3)
    p = _prompt(4)
    r = sched.submit(p, max_new=1)
    retired = sched.step()
    assert r.finished and retired == [r]
    assert r.tokens == _oracle(p, 1)


def test_quantized_scheduler_matches_quantized_oracle():
    """quantize_kv=True serves the int8 ring cache end-to-end; streams
    equal the quantized single-request oracle AS AN IDENTITY: the
    oracle's quantized-ring prefill attends the already-quantized cache
    (decode.py ``_dense_runner``), the only math the scheduler's
    chunked admission can evaluate (raw K/V of earlier chunks are gone
    once written), and per-position absmax quantization makes chunking
    itself invisible — so exact token equality is the contract, not an
    empirical coincidence of this checkpoint."""
    sched = ServingScheduler(PARAMS, CFG, slots=2, n_inner=3,
                             prompt_chunk=8, max_prompt=32,
                             quantize_kv=True, page_tokens=3)
    pairs = [(sched.submit(p, max_new=n), p, n)
             for p, n in [(_prompt(5), 8), (_prompt(9), 6),
                          (_prompt(3), 11)]]
    sched.run()
    for r, p, n in pairs:
        toks = generate_ring_dense(
            PARAMS, jnp.asarray(p)[None], n, CFG, quantize_kv=True
        )
        assert r.tokens == [int(t) for t in np.asarray(toks)[0]], (
            f"request {r.id}"
        )


def test_quantized_parity_is_chunk_size_invariant():
    """The load-bearing premise of the identity above (ADVICE r5 ->
    repaired in PR 1): admission attends the ALREADY-QUANTIZED cache,
    and because quantization is per-position absmax (a position's
    scale never depends on its neighbours), the chunking itself must
    be invisible — the same request must emit the same stream at ANY
    prompt_chunk, including one larger than the whole prompt (the
    oracle's shape). If this ever breaks, the scheduler==oracle parity
    silently degrades from identity to coincidence; this test makes
    that failure loud and names the property, not just the symptom."""
    prompts = [(_prompt(11), 7), (_prompt(4), 9)]
    streams = []
    for chunk in (2, 4, 8, 16):
        sched = ServingScheduler(PARAMS, CFG, slots=2, n_inner=3,
                                 prompt_chunk=chunk, max_prompt=32,
                                 quantize_kv=True, page_tokens=3)
        reqs = [sched.submit(p, max_new=n) for p, n in prompts]
        sched.run()
        streams.append([r.tokens for r in reqs])
    for other in streams[1:]:
        assert other == streams[0]
    # and the chunk-invariant stream IS the oracle stream
    for (p, n), toks in zip(prompts, streams[0]):
        want = generate_ring_dense(
            PARAMS, jnp.asarray(p)[None], n, CFG, quantize_kv=True
        )
        assert toks == [int(t) for t in np.asarray(want)[0]]


def test_quantized_scheduler_kernel_tick_matches_oracle():
    """head_dim-128 config at S=4 slots: the scheduler's tick routes
    the batched int8 Pallas ring kernel (AUTO gate — S >= 4 amortizes
    the per-call scan boundary; interpreted on the CI mesh) while the
    B=1 oracle stays einsum — streams must still be identical, which
    pins kernel-vs-einsum parity through the full serving path."""
    cfg = TransformerConfig(
        vocab=97, d_model=256, n_heads=2, n_kv_heads=1, n_layers=2,
        d_ff=256, attn_window=128,
    )
    params = init_params(cfg, seed=31)
    sched = ServingScheduler(params, cfg, slots=4, n_inner=3,
                             prompt_chunk=8, max_prompt=32,
                             quantize_kv=True, page_tokens=128)
    assert sched.use_kernel  # the whole point: the tick is kernelized
    pairs = [(sched.submit(p, max_new=n), p, n)
             for p, n in [(_prompt(5), 8), (_prompt(9), 6),
                          (_prompt(3), 10), (_prompt(7), 7),
                          (_prompt(12), 5)]]
    sched.run()
    for r, p, n in pairs:
        toks = generate_ring_dense(
            params, jnp.asarray(p)[None], n, cfg, quantize_kv=True
        )
        assert r.tokens == [int(t) for t in np.asarray(toks)[0]], (
            f"request {r.id}"
        )


def test_sharded_serving_scan_quantized():
    """The sharded tick accepts the int8 cache layout (scale leaves
    sharded like K/V) and matches the dense per-row step."""
    from mpistragglers_jl_tpu.models.decode import _kv_quantize

    S, n_inner = 4, 2
    mesh = make_mesh((2, 2), ("dp", "tp"))
    scan = make_serving_scan(CFG, mesh, n_inner, quantize_kv=True)
    tok = jnp.asarray(RNG.integers(1, CFG.vocab, S), jnp.int32)
    pos = jnp.asarray([7, 4, 8, 6], jnp.int32)
    done = jnp.zeros((S,), bool)
    W = CFG.attn_window
    key = jax.random.key(3)
    caches = []
    ks = jax.random.split(key, 2 * CFG.n_layers)
    for i in range(CFG.n_layers):
        kf = jax.random.normal(
            ks[2 * i], (S, W, CFG.kv_heads, CFG.head_dim), CFG.dtype
        ) * 0.1
        vf = jax.random.normal(
            ks[2 * i + 1], (S, W, CFG.kv_heads, CFG.head_dim), CFG.dtype
        ) * 0.1
        kq, ksc = _kv_quantize(kf)
        vq, vsc = _kv_quantize(vf)
        caches.append({"k": kq, "v": vq, "k_s": ksc, "v_s": vsc})
    dtok, dpos, dc = tok, pos, caches
    for _ in range(n_inner):
        lg, dc = serving_decode_step_dense(PARAMS, dtok, dpos, dc, CFG)
        dtok = jnp.argmax(lg, axis=-1).astype(tok.dtype)
        dpos = dpos + 1
    keys = jax.random.split(jax.random.key(0), S)
    got = scan(PARAMS, tok, pos, done, [dict(c) for c in caches], keys)
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(dtok))


def test_sharded_serving_scan_gqa_wider_tp():
    """kv_heads < tp: the replicated-groups cache layout (global head
    axis = tp slots, slot t holding kv head t*kv/tp) reproduces the
    dense per-row step — the same layout make_ring_generate uses."""
    from mpistragglers_jl_tpu.models.decode import _cache_heads_global

    S, n_inner = 4, 2
    mesh = make_mesh((1, 4), ("dp", "tp"))
    assert CFG.kv_heads == 2 and mesh.shape["tp"] == 4
    scan = make_serving_scan(CFG, mesh, n_inner)
    Hc = _cache_heads_global(CFG, mesh)
    assert Hc == 4  # tp slots
    tok = jnp.asarray(RNG.integers(1, CFG.vocab, S), jnp.int32)
    pos = jnp.asarray([5, 8, 3, 7], jnp.int32)
    done = jnp.zeros((S,), bool)
    W = CFG.attn_window
    key = jax.random.key(9)
    caches_dense, caches_rep = [], []
    ks = jax.random.split(key, 2 * CFG.n_layers)
    head_map = jnp.arange(Hc) * CFG.kv_heads // Hc  # slot -> kv head
    for i in range(CFG.n_layers):
        kf = jax.random.normal(
            ks[2 * i], (S, W, CFG.kv_heads, CFG.head_dim), CFG.dtype
        ) * 0.1
        vf = jax.random.normal(
            ks[2 * i + 1], (S, W, CFG.kv_heads, CFG.head_dim), CFG.dtype
        ) * 0.1
        caches_dense.append({"k": kf, "v": vf})
        caches_rep.append({
            "k": kf[:, :, head_map], "v": vf[:, :, head_map],
        })
    dtok, dpos, dc = tok, pos, caches_dense
    for _ in range(n_inner):
        lg, dc = serving_decode_step_dense(PARAMS, dtok, dpos, dc, CFG)
        dtok = jnp.argmax(lg, axis=-1).astype(tok.dtype)
        dpos = dpos + 1
    keys = jax.random.split(jax.random.key(0), S)
    got = scan(PARAMS, tok, pos, done, caches_rep, keys)
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(dtok))


def test_sampled_serving_matches_sampled_oracle():
    """temperature/top-k serving: each request's sampled stream equals
    ``generate_ring_dense`` with the SAME key (the per-row pick uses
    decode.py's exact (key, pos, row 0) fold discipline), through
    admission order, retirement, and slot reuse."""
    temp, tk = 0.8, 7
    sched = ServingScheduler(PARAMS, CFG, slots=2, n_inner=3,
                             prompt_chunk=8, max_prompt=32,
                             temperature=temp, top_k=tk, page_tokens=3)
    pairs = []
    for i, (plen, n) in enumerate([(5, 9), (11, 6), (3, 12), (8, 7)]):
        p = _prompt(plen)
        key = jax.random.key(100 + i)
        pairs.append((sched.submit(p, n, key=key), p, n, key))
    sched.run()
    for r, p, n, key in pairs:
        want = generate_ring_dense(
            PARAMS, jnp.asarray(p)[None], n, CFG,
            temperature=temp, top_k=tk, key=key,
        )
        assert r.tokens == [int(t) for t in np.asarray(want)[0]], (
            f"request {r.id}"
        )


def test_sampled_serving_default_keys_differ_per_request():
    """Without explicit keys, two identical prompts sample DIFFERENT
    streams (id-derived keys) — no accidental stream coupling."""
    sched = ServingScheduler(PARAMS, CFG, slots=2, n_inner=3,
                             prompt_chunk=8, max_prompt=32,
                             temperature=1.0, page_tokens=3)
    p = _prompt(6)
    r1 = sched.submit(p, 12)
    r2 = sched.submit(p, 12)
    sched.run()
    assert r1.tokens != r2.tokens


def test_sampling_validation():
    with pytest.raises(ValueError, match="temperature"):
        ServingScheduler(PARAMS, CFG, slots=1, temperature=-0.5, page_tokens=3)
    with pytest.raises(ValueError, match="top_k"):
        ServingScheduler(PARAMS, CFG, slots=1, temperature=1.0, top_k=0,
                         page_tokens=3)
    sched = ServingScheduler(PARAMS, CFG, slots=1, prompt_chunk=8,
                             max_prompt=16, page_tokens=3)
    with pytest.raises(ValueError, match="greedy scheduler"):
        sched.submit(_prompt(3), 4, key=jax.random.key(1))


def test_clear_cached_programs_drops_all_model_caches():
    """models.clear_cached_programs is the one chokepoint for dropping
    lru-cached jitted program factories (bench uses it between rung
    blocks to release HBM) — it must clear every registered cache."""
    from mpistragglers_jl_tpu.models import clear_cached_programs
    from mpistragglers_jl_tpu.models import decode, serving

    sched = ServingScheduler(PARAMS, CFG, slots=1, n_inner=1,
                             prompt_chunk=4, max_prompt=8, page_tokens=3)
    r = sched.submit(_prompt(3), 2)
    sched.run()
    assert r.finished
    generate_ring_dense(PARAMS, jnp.asarray(_prompt(3))[None], 2, CFG)
    caches = (
        decode._dense_runner, serving._fresh_arena,
        serving._serving_scan_paged, serving._extend_chunk_dense,
        serving._finish_admit_dense, serving._place_paged,
    )
    assert any(c.cache_info().currsize > 0 for c in caches)
    clear_cached_programs()
    for c in caches:
        assert c.cache_info().currsize == 0, c
