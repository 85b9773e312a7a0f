"""Paged serving cache: parity, sharing, COW, and capacity contracts.

The PR 1 oracle identity is unchanged by the page refactor: a paged
scheduler's every stream equals ``generate_ring_dense`` token-for-token
— greedy and sampled, fp and int8, einsum gather and Pallas page-table
kernel, across page sizes and any admission/retirement/COW
interleaving. The einsum fallback gathers each slot's ring view with
``jnp.take`` and runs the SAME per-row attention as the slot ring, so
parity here is parity by construction being *verified*, not an
empirical coincidence (models/serving.py ``_paged_gather``).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mpistragglers_jl_tpu.models.decode import (
    KERNEL_MIN_BATCH,
    _kernel_viable,
    _paged_kernel_possible,
    generate_ring_dense,
)
from mpistragglers_jl_tpu.models.serving import ServingScheduler
from mpistragglers_jl_tpu.models.transformer import (
    TransformerConfig,
    init_params,
)
from mpistragglers_jl_tpu.obs import MetricsRegistry

# same shapes as tests/test_serving.py so the jitted oracles are shared
CFG = TransformerConfig(
    vocab=61, d_model=64, n_heads=8, n_kv_heads=2, n_layers=2, d_ff=128,
    attn_window=6,
)
PARAMS = init_params(CFG, seed=11)
RNG = np.random.default_rng(21)

# head_dim-128 config: the int8 Pallas kernel's page-table mode routes
# (interpreted on the CI mesh); W=128 admits PAGE_TOKENS in {16, 64}
KCFG = TransformerConfig(
    vocab=97, d_model=256, n_heads=2, n_kv_heads=1, n_layers=2,
    d_ff=256, attn_window=128,
)
KPARAMS = init_params(KCFG, seed=31)


# a GQA group of 12 on one K/V head (StarCoder2's group): the kernel's
# q tile is 16 rows; W=32 in pages of 8 wraps within a short stream
GCFG = TransformerConfig(
    vocab=97, d_model=1536, n_heads=12, n_kv_heads=1, n_layers=2,
    d_ff=256, attn_window=32,
)
GPARAMS = init_params(GCFG, seed=41)


def _prompt(n, vocab=CFG.vocab):
    return RNG.integers(1, vocab, size=n).astype(np.int32)


def _oracle(p, n, *, params=PARAMS, cfg=CFG, quantize_kv=False,
            eos_id=None, **kw):
    toks = generate_ring_dense(
        params, jnp.asarray(p)[None], n, cfg, quantize_kv=quantize_kv,
        eos_id=eos_id, **kw,
    )
    out = [int(t) for t in np.asarray(toks)[0]]
    if eos_id is not None and eos_id in out:
        out = out[: out.index(eos_id) + 1]
    return out


def _drained(sched):
    """Post-run pool invariants: zero leaks, refcounts at baseline."""
    sched.pool.check()
    assert sched.pool.used == 0 and sched.pool.reserved == 0


@pytest.mark.parametrize("page_tokens", [1, 2, 3, 6])
def test_paged_batch_matches_oracle_under_churn(page_tokens):
    """The slot-churn schedule of test_serving.py on the paged cache,
    at every page size dividing the window (6), from a page a row to
    one page a window (a ring a slot): queueing beyond slots,
    reuse, wrap, varied budgets — every stream equals its oracle and
    the pool drains leak-free."""
    sched = ServingScheduler(PARAMS, CFG, slots=3, n_inner=4,
                             prompt_chunk=8, max_prompt=64,
                             page_tokens=page_tokens)
    reqs = [
        (sched.submit(p, max_new=n), p, n)
        for p, n in [(_prompt(3), 9), (_prompt(11), 6), (_prompt(8), 17),
                     (_prompt(1), 5), (_prompt(20), 8), (_prompt(6), 12),
                     (_prompt(15), 4), (_prompt(9), 10)]
    ]
    sched.run()
    for r, p, n in reqs:
        assert r.finished
        assert r.tokens == _oracle(p, n), f"request {r.id} (P={page_tokens})"
    _drained(sched)


def test_shared_prefix_divergence_cow_matches_oracle():
    """Two prompts sharing a page-aligned system prefix but diverging
    after it: the second admission shares the prefix pages, both
    requests wrap the window (forcing COW of the shared pages), and
    BOTH streams still equal their independent oracles — the COW copy
    never mutated the page the other slot was reading."""
    sys_prompt = _prompt(4)
    pa = np.concatenate([sys_prompt, _prompt(2)])
    pb = np.concatenate([sys_prompt, _prompt(2)])
    sched = ServingScheduler(PARAMS, CFG, slots=2, n_inner=3,
                             prompt_chunk=8, max_prompt=64,
                             page_tokens=2)
    ra = sched.submit(pa, max_new=14)
    rb = sched.submit(pb, max_new=14)
    sched.run()
    assert ra.tokens == _oracle(pa, 14)
    assert rb.tokens == _oracle(pb, 14)
    assert sched.pool.share_hits > 0, "prefix sharing never fired"
    assert sched.pool.cow_copies > 0, "COW never fired (wrap schedule?)"
    _drained(sched)


def test_prefix_share_skips_prefill_counter_verified():
    """A prefix-sharing admission must SKIP the shared pages' prefill
    chunks — pinned through serving_prefill_chunks_total, not timing.
    (Sharing needs a resident registrant whose pages are still prefix
    content: W=128 so neither request wraps; r1 stays mid-decode while
    r2 admits.)"""
    reg = MetricsRegistry()
    p = _prompt(40, KCFG.vocab)
    sched = ServingScheduler(KPARAMS, KCFG, slots=2, n_inner=2,
                             prompt_chunk=8, max_prompt=64,
                             page_tokens=16, registry=reg)
    chunks = reg.counter("serving_prefill_chunks_total")
    r1 = sched.submit(p, max_new=8)
    while not r1.tokens:
        sched.step()  # r1 fully admitted (5 chunks), still decoding
    c1 = chunks.value
    assert c1 == 5
    r2 = sched.submit(p, max_new=8)
    sched.run()
    # identical 40-token prompt at P=16: (40-1)//16 = 2 pages shared
    # -> 32 tokens skip prefill; the remaining 8 are one 8-token chunk
    assert chunks.value - c1 == 1
    assert sched.pool.share_hits == 2
    assert r1.tokens == _oracle(p, 8, params=KPARAMS, cfg=KCFG)
    assert r2.tokens == _oracle(p, 8, params=KPARAMS, cfg=KCFG)
    _drained(sched)


def test_page_capacity_defers_admission_fifo():
    """A pool too small for every request at once: admission defers
    (FIFO) until retirements return pages, every request still serves
    exactly, and the pool never leaks. This is the capacity contract —
    cache_pages bounds concurrency, not correctness."""
    # each request needs ceil(min(6, Tp+max_new+n_inner)/2) = 3 pages;
    # 4 usable pages => strictly one resident request at a time
    sched = ServingScheduler(PARAMS, CFG, slots=3, n_inner=2,
                             prompt_chunk=8, max_prompt=32,
                             page_tokens=2, cache_pages=5)
    reqs = [(sched.submit(_prompt(3 + i), max_new=4 + i), 3 + i, 4 + i)
            for i in range(4)]
    sched.step()
    assert sched.active == 1 and sched.pending == 3  # pages, not slots
    sched.run()
    for r, plen, n in reqs:
        assert r.finished and len(r.tokens) == n
    admit_ticks = [r.admitted_tick for r, _, _ in reqs]
    assert admit_ticks == sorted(admit_ticks)  # FIFO, no reordering
    _drained(sched)


def test_paged_quantized_matches_quantized_oracle():
    sched = ServingScheduler(PARAMS, CFG, slots=2, n_inner=3,
                             prompt_chunk=8, max_prompt=32,
                             quantize_kv=True, page_tokens=3)
    pairs = [(sched.submit(p, max_new=n), p, n)
             for p, n in [(_prompt(5), 8), (_prompt(9), 6),
                          (_prompt(3), 11)]]
    sched.run()
    for r, p, n in pairs:
        assert r.tokens == _oracle(p, n, quantize_kv=True), (
            f"request {r.id}"
        )
    _drained(sched)


def test_paged_sampled_matches_sampled_oracle():
    """Sampling through the paged tick: per-request keys, same fold
    discipline — streams equal ``generate_ring_dense`` with the same
    key through admission order, retirement, and page churn."""
    temp, tk = 0.8, 7
    sched = ServingScheduler(PARAMS, CFG, slots=2, n_inner=3,
                             prompt_chunk=8, max_prompt=32,
                             temperature=temp, top_k=tk, page_tokens=2)
    pairs = []
    for i, (plen, n) in enumerate([(5, 9), (11, 6), (3, 12), (8, 7)]):
        p = _prompt(plen)
        key = jax.random.key(300 + i)
        pairs.append((sched.submit(p, n, key=key), p, n, key))
    sched.run()
    for r, p, n, key in pairs:
        want = _oracle(p, n, temperature=temp, top_k=tk, key=key)
        assert r.tokens == want, f"request {r.id}"
    _drained(sched)


def test_paged_eos_retirement_returns_pages():
    p = _prompt(7)
    free_run = _oracle(p, 16)
    eos = free_run[3]
    sched = ServingScheduler(PARAMS, CFG, slots=2, n_inner=4,
                             prompt_chunk=8, max_prompt=32,
                             eos_id=eos, page_tokens=2)
    r = sched.submit(p, max_new=16)
    sched.run()
    assert r.finished and r.reason == "eos"
    assert r.tokens == _oracle(p, 16, eos_id=eos)
    _drained(sched)


@pytest.mark.parametrize("page_tokens", [16, 64])
@pytest.mark.parametrize("quantize_kv", [False, True])
def test_paged_page_sizes_and_kernel_tick_match_oracle(
    page_tokens, quantize_kv
):
    """PAGE_TOKENS in {16, 64} at head_dim 128 under a slot-reuse
    schedule, fp AND int8. The int8 variant is the kernel-tick leg:
    S=4 routes the Pallas page-table mode (per-slot page rows in
    scalar-prefetch SMEM) while the B=1 oracle stays einsum — the
    identity pins kernel-vs-gather parity through the full path."""
    sched = ServingScheduler(KPARAMS, KCFG, slots=4, n_inner=3,
                             prompt_chunk=8, max_prompt=32,
                             quantize_kv=quantize_kv,
                             page_tokens=page_tokens)
    if quantize_kv:
        assert sched.use_kernel  # the whole point of this leg
    pairs = [(sched.submit(p, max_new=n), p, n)
             for p, n in [(_prompt(5, KCFG.vocab), 8),
                          (_prompt(9, KCFG.vocab), 6),
                          (_prompt(3, KCFG.vocab), 10),
                          (_prompt(7, KCFG.vocab), 7),
                          (_prompt(12, KCFG.vocab), 5)]]
    sched.run()
    for r, p, n in pairs:
        want = _oracle(p, n, params=KPARAMS, cfg=KCFG,
                       quantize_kv=quantize_kv)
        assert r.tokens == want, f"request {r.id}"
    _drained(sched)


def _group12_schedule(sched):
    """Six requests through four slots (or three): two share a
    page-aligned 8-token prefix and both wrap the 32-row window (COW of
    the shared page), slots retire and are reused."""
    sys_prompt = _prompt(8, GCFG.vocab)
    prompts = [
        np.concatenate([sys_prompt, _prompt(3, GCFG.vocab)]),
        np.concatenate([sys_prompt, _prompt(5, GCFG.vocab)]),
        _prompt(6, GCFG.vocab), _prompt(20, GCFG.vocab),
        _prompt(2, GCFG.vocab), _prompt(13, GCFG.vocab),
    ]
    pairs = [(sched.submit(p, max_new=n), p, n)
             for p, n in zip(prompts, (30, 27, 9, 16, 5, 7))]
    sched.run()
    return pairs


def test_group_of_12_routes_the_kernel_and_matches_gather_and_oracle():
    """12 query heads on one K/V head at head size 128: the paged tick
    routes the int8 kernel (no group is refused any more), and over a
    schedule with admission, retirement and COW its streams equal the
    gather route's (a scheduler of three slots, under
    ``KERNEL_MIN_BATCH``) and the dense oracle's."""
    assert GCFG.head_dim == 128

    def make(slots=KERNEL_MIN_BATCH):
        return ServingScheduler(GPARAMS, GCFG, slots=slots, n_inner=4,
                                prompt_chunk=16, max_prompt=32,
                                quantize_kv=True, page_tokens=8)

    kern = make()
    assert kern.use_kernel
    state = RNG.bit_generator.state
    by_kernel = _group12_schedule(kern)
    assert kern.pool.share_hits > 0 and kern.pool.cow_copies > 0
    _drained(kern)
    # one slot fewer than the kernel routes for: the int8 gather route
    gather = make(slots=KERNEL_MIN_BATCH - 1)
    assert not gather.use_kernel
    RNG.bit_generator.state = state  # the same prompts again
    by_gather = _group12_schedule(gather)
    for (r, p, n), (rg, pg, _) in zip(by_kernel, by_gather):
        assert np.array_equal(p, pg)
        assert r.finished and r.tokens == rg.tokens, f"request {r.id}"
        assert r.tokens == _oracle(p, n, params=GPARAMS, cfg=GCFG,
                                   quantize_kv=True), f"request {r.id}"


def test_what_the_kernel_route_still_refuses(monkeypatch):
    """A group of any size routes; what the kernel really cannot take
    is still refused: a cache that is not int8, a head size off the
    lane width, query heads that do not fall into whole groups, and,
    compiled, a page that is not whole int8 sublane tiles."""
    from mpistragglers_jl_tpu.models.decode import _zero_cache_layer
    from mpistragglers_jl_tpu.ops import decode_attention as da

    assert _paged_kernel_possible(GCFG, True, 8)
    assert not _paged_kernel_possible(GCFG, False, 8)  # bfloat16 cache
    assert not _paged_kernel_possible(CFG, True, 8)  # head size 8
    d64 = TransformerConfig(vocab=97, d_model=768, n_heads=12,
                            n_kv_heads=1, n_layers=1, d_ff=64,
                            attn_window=32)
    assert d64.head_dim == 64 and not _paged_kernel_possible(d64, True, 8)
    q = jnp.zeros((4, 1, 12, 128), jnp.bfloat16)
    assert _kernel_viable(q, _zero_cache_layer(4, 256, 1, 128, q.dtype, True))
    assert not _kernel_viable(
        q, _zero_cache_layer(4, 256, 1, 128, q.dtype, False))
    assert not _kernel_viable(  # 12 heads on 5: no whole groups
        q, _zero_cache_layer(4, 256, 5, 128, q.dtype, True))
    assert not _kernel_viable(
        q[..., :64], _zero_cache_layer(4, 256, 1, 64, q.dtype, True))
    assert not _kernel_viable(  # two queries: prefill, not decode
        jnp.zeros((4, 2, 12, 128), jnp.bfloat16),
        _zero_cache_layer(4, 256, 1, 128, q.dtype, True))
    # interpreted, any page of whole 8-row tiles streams; compiled, the
    # int8 tile is 32 rows
    assert _paged_kernel_possible(GCFG, True, 48)
    monkeypatch.setattr(da, "_use_interpret", lambda: False)
    assert not _paged_kernel_possible(GCFG, True, 48)
    assert _paged_kernel_possible(GCFG, True, 64)


def test_page_pool_metrics_exported():
    """The opt-in page-pool series: occupancy gauges track the pool
    and the share/COW counters match its lifetime tallies."""
    reg = MetricsRegistry()
    p = _prompt(40, KCFG.vocab)
    sched = ServingScheduler(KPARAMS, KCFG, slots=2, n_inner=2,
                             prompt_chunk=8, max_prompt=64,
                             page_tokens=16, registry=reg)
    r1 = sched.submit(p, max_new=8)
    while not r1.tokens:
        sched.step()  # registration happens at admission finish
    assert reg.gauge("serving_cache_pages_used").value == sched.pool.used
    assert reg.gauge("serving_cache_pages_free").value == sched.pool.free
    r2 = sched.submit(p, max_new=8)
    sched.run()
    assert r1.finished and r2.finished
    assert (reg.counter("serving_prefix_share_hits_total",
                        tier="hbm").value
            == sched.pool.share_hits > 0)
    assert (reg.counter("serving_cow_copies_total").value
            == sched.pool.cow_copies)
    assert reg.gauge("serving_cache_pages_used").value == 0
    # the names survive the Prometheus exposition round trip
    text = reg.to_prometheus()
    for name in ("serving_cache_pages_free", "serving_cache_pages_used",
                 "serving_prefix_share_hits_total",
                 "serving_cow_copies_total"):
        assert f"\n{name}" in text or text.startswith(name)


def test_paged_validation():
    with pytest.raises(ValueError, match="divide the attention window"):
        ServingScheduler(PARAMS, CFG, slots=1, page_tokens=4)  # W=6
    with pytest.raises(ValueError, match="cannot hold even one"):
        ServingScheduler(PARAMS, CFG, slots=1, page_tokens=2,
                         cache_pages=3)  # needs W/P + 1 = 4


def test_a_scheduler_without_a_page_size_is_a_type_error():
    """The page pool is the one cache manager: ``page_tokens`` has no
    default, with ``cache_pages`` or without."""
    for kw in ({}, {"cache_pages": 8}):
        with pytest.raises(TypeError, match="page_tokens"):
            ServingScheduler(PARAMS, CFG, slots=1, **kw)
