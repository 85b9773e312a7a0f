"""The chunks that are due in one tick run as one program
(models/decode.py ``_grouped_hidden``, models/serving.py
``_extend_chunk_group`` and ``ServingScheduler._run_pending``): every
weight is read once for all of them, and what is per request (its K/V
rows in its own arena at its own offset, its recurrent state) stays per
request.

The oracle of the program is the program of one chunk
(``_extend_chunk_dense``), run once a member on the same arenas; the
oracle of the scheduler's streams is ``generate_ring_dense``, as for
every other serving test.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mpistragglers_jl_tpu.models import serving
from mpistragglers_jl_tpu.models.decode import (
    CHUNK_BLOCK_K,
    generate_ring_dense,
)
from mpistragglers_jl_tpu.models.serving import (
    ServingScheduler,
    _chunk_group_cap,
    _extend_chunk_dense,
    _extend_chunk_group,
    _fresh_cache,
)
from mpistragglers_jl_tpu.models.transformer import (
    TransformerConfig,
    init_params,
)

C = 16
PLAIN = TransformerConfig(
    vocab=53, d_model=32, n_heads=4, n_kv_heads=2, n_layers=2, d_ff=48,
    max_context=2 * CHUNK_BLOCK_K + 64,
)
EXPERTS = dataclasses.replace(
    PLAIN, norm="rmsnorm", ffn="swiglu", layer_experts=(False, True),
    n_experts=8, experts_per_token=2, d_expert=16, shared_experts=1,
    max_context=128,
)
DELTA = dataclasses.replace(
    PLAIN, d_head=8, attn_impl="reference", norm="rmsnorm", ffn="swiglu",
    layer_mixers=("gdn", "attn"), gdn_key_heads=2, gdn_value_heads=4,
    gdn_key_dim=8, gdn_value_dim=8, gdn_conv=4, max_context=128,
)
# (configuration, int8 arenas?, each member's offset; a member's valid
# rows, where the configuration has state layers)
CASES = {
    # full attention, arenas of several key blocks: every member's walk
    # is as long as its own offset asks
    "full": (PLAIN, True, (0, 2 * CHUNK_BLOCK_K, 64, CHUNK_BLOCK_K + 16)),
    "window": (dataclasses.replace(PLAIN, layer_windows=(40, 300)), True,
               (700, 0, 48, 2 * CHUNK_BLOCK_K + 48)),
    "int8": (dataclasses.replace(PLAIN, max_context=128), True,
             (0, 16, 64, 112)),
    "bf16": (dataclasses.replace(PLAIN, max_context=128,
                                 dtype=jnp.bfloat16), False,
             (32, 0, 112, 16)),
    "experts": (EXPERTS, True, (0, 48, 16, 96)),
    "delta_rule": (DELTA, True, (16, 0, 96, 32)),
    # a shared prefix of whole pages (of 8) is no multiple of the chunk
    "shared_prefix": (dataclasses.replace(PLAIN, max_context=128), True,
                      (8, 24, 0, 40)),
}
VALID = (16, 5, 1, 11)


def _loud(rng, like):
    """A leaf with every entry written, loudly: what a recycled arena
    (or a state that has seen tokens) holds."""
    x = rng.normal(size=like.shape) * 3.0
    if like.dtype == jnp.int8:
        return jnp.asarray(np.clip(np.round(x * 20), -127, 127), jnp.int8)
    return jnp.asarray(np.abs(x) * 0.1 if like.ndim == 3 else x,
                       like.dtype)


def _arenas(cfg, quantize_kv, n, seed):
    rng = np.random.default_rng(seed)
    return [
        jax.tree.map(lambda a: _loud(rng, a),
                     _fresh_cache(cfg, 1, cfg.max_context, quantize_kv))
        for _ in range(n)
    ]


@pytest.mark.parametrize("n", [2, 3, 4])
@pytest.mark.parametrize("case", sorted(CASES))
def test_a_group_of_chunks_equals_the_chunks_one_by_one(case, n):
    cfg, quantize_kv, offsets = CASES[case]
    offsets = offsets[:n]
    Lmax = cfg.max_context
    params = init_params(cfg, seed=7)
    rng = np.random.default_rng([n, len(case)])
    chunks = rng.integers(1, cfg.vocab, size=(n, C)).astype(np.int32)
    valid = ((np.asarray(VALID[:n], np.int32),)
             if cfg.state_layers else ())
    one = _extend_chunk_dense(cfg, C, Lmax)
    want = [
        one(params, chunks[i:i + 1], arena, np.int32(offsets[i]),
            *(v[i] for v in valid))
        for i, arena in enumerate(_arenas(cfg, quantize_kv, n, seed=n))
    ]
    group = _extend_chunk_group(cfg, C, Lmax, n)
    assert group.__name__ == f"serving_prefill_chunk_x{n}"
    hidden, arenas = group(
        params, chunks, tuple(_arenas(cfg, quantize_kv, n, seed=n)),
        np.asarray(offsets, np.int32), *valid)
    assert len(hidden) == len(arenas) == n
    for i, (h_want, arena_want) in enumerate(want):
        assert hidden[i].shape == h_want.shape == (1, C, cfg.d_model)
        # the rows of different requests meet only in products that are
        # row-wise, and a product of another height may sum a row in
        # another order (on this CPU from 64 rows on): the tolerance of
        # tests/test_chunk_attention.py (hidden values reach 80 over
        # these loud arenas), an int8 row by at most a step
        tol = (dict(rtol=1e-4, atol=2e-3) if cfg.dtype == jnp.float32
               else dict(rtol=2e-2, atol=2e-2))
        for got, ref in zip(jax.tree.leaves((hidden[i], arenas[i])),
                            jax.tree.leaves((h_want, arena_want))):
            assert got.shape == ref.shape and got.dtype == ref.dtype
            if got.dtype == jnp.int8:
                step = np.abs(np.asarray(got, np.int32)
                              - np.asarray(ref, np.int32))
                assert step.max() <= 1 and (step > 0).mean() < 1e-2
            else:
                np.testing.assert_allclose(
                    np.asarray(got, np.float32),
                    np.asarray(ref, np.float32), **tol)


def test_a_group_that_does_not_fill_the_program_is_padded():
    """Two requests' chunks in the program of four: the other two
    members are nobody's (no valid row, scratch arenas) and leave the
    two alone."""
    cfg, quantize_kv, offsets = CASES["delta_rule"]
    params = init_params(cfg, seed=7)
    chunks = np.zeros((4, C), np.int32)
    chunks[:2] = np.random.default_rng(3).integers(1, cfg.vocab, (2, C))
    valid = np.asarray([16, 5, 0, 0], np.int32)
    offs = np.asarray([*offsets[:2], 0, 0], np.int32)
    one = _extend_chunk_dense(cfg, C, cfg.max_context)
    want = [one(params, chunks[i:i + 1], arena, offs[i], valid[i])
            for i, arena in enumerate(_arenas(cfg, quantize_kv, 2, seed=1))]
    hidden, arenas = _extend_chunk_group(cfg, C, cfg.max_context, 4)(
        params, chunks, tuple(_arenas(cfg, quantize_kv, 4, seed=1)), offs,
        valid)
    for i, (h_want, arena_want) in enumerate(want):
        for got, ref in zip(jax.tree.leaves((hidden[i], arenas[i])),
                            jax.tree.leaves((h_want, arena_want))):
            if got.dtype == jnp.int8:
                assert np.abs(np.asarray(got, np.int32)
                              - np.asarray(ref, np.int32)).max() <= 1
            else:
                np.testing.assert_allclose(got, ref, rtol=1e-4, atol=2e-3)
    # a member without a valid row leaves its recurrent state alone
    for got, ref in zip(jax.tree.leaves(arenas[2][0]), jax.tree.leaves(
            _arenas(cfg, quantize_kv, 4, seed=1)[2][0])):
        np.testing.assert_array_equal(got, ref)


def test_the_group_follows_from_what_a_chunk_gives_a_weight():
    # a dense chunk of 256 rows is at the ridge: a program a chunk, as
    # it always was; narrower chunks wait for the weights' bytes
    assert _chunk_group_cap(PLAIN, 256, 16) == 1
    assert _chunk_group_cap(PLAIN, 128, 16) == 4
    # 2 of 8 experts a token: an expert sees a quarter of the rows
    assert _chunk_group_cap(EXPERTS, 256, 16) == 4
    assert _chunk_group_cap(EXPERTS, 1024, 16) == 1
    assert [_chunk_group_cap(PLAIN, C, s) for s in (1, 2, 3, 9)] == [
        1, 2, 3, 4]
    two = ServingScheduler(init_params(PLAIN, 1), PLAIN, slots=2,
                           prompt_chunk=C, max_prompt=64, page_tokens=64)
    assert two._extend_group.__name__ == "serving_prefill_chunk_x2"
    assert two._extend.__name__ == "serving_prefill_chunk"
    wide = ServingScheduler(init_params(PLAIN, 1), PLAIN, slots=4,
                            prompt_chunk=256, max_prompt=512,
                            page_tokens=64)
    assert wide._group == 1 and wide._extend_group is None
    r = wide.submit(np.arange(1, 300) % PLAIN.vocab, max_new=2)
    wide.run()
    assert r.finished and wide._scratch_arenas is None


# -- the scheduler ------------------------------------------------------------

SERVE = TransformerConfig(
    vocab=61, d_model=32, n_heads=4, n_kv_heads=2, n_layers=2, d_ff=48,
    attn_window=16,
)
SERVE_PARAMS = init_params(SERVE, seed=11)


def _prompts(lengths, seed=3):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, SERVE.vocab, size=n).astype(np.int32)
            for n in lengths]


def _oracle(prompt, n_new, **kw):
    toks = generate_ring_dense(
        SERVE_PARAMS, jnp.asarray(prompt)[None], n_new, SERVE, **kw)
    return [int(t) for t in np.asarray(toks)[0]]


def _sched(**kw):
    kw = {"slots": 8, "n_inner": 2, "prompt_chunk": 8, "max_prompt": 64,
          **kw}
    return ServingScheduler(SERVE_PARAMS, SERVE, **kw)


class _Spy:
    """``serving._annotate`` replaced: every span with its arguments."""

    seen: list = []

    def __init__(self, name, **args):
        self.name, self.args = name, dict(args)

    def __enter__(self):
        _Spy.seen.append(self)
        return self

    def __exit__(self, *exc):
        return None

    def set_metadata(self, **args):
        self.args.update(args)


@pytest.fixture
def spans(monkeypatch):
    _Spy.seen = []
    monkeypatch.setattr(serving, "_annotate", _Spy)
    return _Spy.seen


def _greedy(n, size=4):
    return [size] * (n // size) + ([n % size] if n % size else [])


# prompts of 1 to 8 chunks of 8: with all of them admitted in the first
# tick, the ticks that follow begin with 7, 6, 6, 5, 4, 3, 2, 1
# admitting slots
LENGTHS = (5, 12, 20, 27, 36, 44, 52, 61)


@pytest.mark.parametrize("quantize_kv", [True, False], ids=["int8", "bf16"])
@pytest.mark.parametrize("page_tokens", [4, 16],
                         ids=["paged", "one_page_a_window"])
def test_streams_of_concurrent_admissions_match_the_oracle(
        spans, page_tokens, quantize_kv):
    sched = _sched(page_tokens=page_tokens, quantize_kv=quantize_kv)
    prompts = _prompts(LENGTHS)
    reqs = [sched.submit(p, max_new=6 + i) for i, p in enumerate(prompts)]
    late = sched.submit(_prompts((30,), seed=5)[0], max_new=5)
    sched.run()
    for r, p in zip(reqs + [late], prompts + [late.prompt]):
        assert r.finished and r.reason == "length"
        assert r.tokens == _oracle(p, r.max_new, quantize_kv=quantize_kv), (
            f"request {r.id}")
    ticks = [s for s in spans if s.name == "serving.tick"]
    met = {t.args["admitting"] for t in ticks}
    assert {3, 4, 5, 6, 7} <= met
    programs = [s for s in spans if s.name == "serving.prefill_chunk"]
    assert {p.args["chunks"] for p in programs} == {1, 2, 3, 4}
    # every chunk of every request ran exactly once, in order
    by_req: dict[int, list[int]] = {}
    for p in programs:
        ids, cursor, of = (str(p.args[k]).split(",")
                           for k in ("req", "chunk", "of"))
        assert len(ids) == len(cursor) == len(of) == p.args["chunks"]
        for rid, c in zip(ids, cursor):
            by_req.setdefault(int(rid), []).append(int(c))
    for r in reqs + [late]:
        assert by_req[r.id] == list(range(-(-r.prompt.size // 8)))


def _programs_by_tick(spans):
    """{tick: the chunk counts of its prefill programs, in order}. A
    tick's programs are dispatched in its admit phase, and that runs
    wherever the scheduler can count a tick's ends BEHIND the dispatch
    of the tick before (``ServingScheduler._admit_ahead``): what a step
    runs after its own tick's dispatch is the next tick's."""
    out: dict[int, list[int]] = {}
    owner = 0
    for s in spans:
        if s.name == "serving.tick":
            owner = s.args["tick"]
        elif s.name == "serving.decode_dispatch":
            owner += 1
        elif s.name == "serving.prefill_chunk":
            out.setdefault(owner, []).append(s.args["chunks"])
    return out


def _own(spans):
    """The spans of one step up to its tick's dispatch: its own
    tick's."""
    names = [s.name for s in spans]
    return spans[:names.index("serving.decode_dispatch")
                 if "serving.decode_dispatch" in names else None]


def test_a_tick_dispatches_the_greedy_splits_number_of_programs(spans):
    sched = _sched(page_tokens=4)
    for p in _prompts(LENGTHS):
        sched.submit(p, max_new=4)
    sched.run()
    ticks = [s for s in spans if s.name == "serving.tick"]
    by_tick = _programs_by_tick(spans)
    # the counts are the schedule's as a tick begins, wherever its
    # admit phase ran: a prompt of n chunks is through after n ticks
    assert [t.args["admitting"] for t in ticks][:9] == [
        0, 7, 6, 5, 4, 3, 2, 1, 0]
    for tick in ticks:
        programs = by_tick.get(tick.args["tick"], [])
        assert tick.args["chunks"] == sum(programs)
        assert tick.args["chunk_programs"] == len(programs)
        if not tick.args["queue"]:
            # nothing is admitted: the admitting slots' chunks are all
            # the tick runs, four a program
            assert programs == _greedy(tick.args["admitting"])
    # every tick but the first was planned behind the one before it
    assert [t.args["ahead"] for t in ticks] == [0] + [1] * (len(ticks) - 1)
    assert sched.ticks_ahead == sched.tick_count - 1
    # the first tick admitted eight prompts: the seven of several
    # chunks wait for each other (the one-chunk prompt, first in the
    # queue, has its first token before the second is looked at)
    assert by_tick[1] == [1, 4, 3]


@pytest.mark.parametrize("kw,first", [
    # pages of 4: the 5-token prompt registers a page the next request's
    # plan may share, so its admission ends before that plan is made
    ({"page_tokens": 4}, [1, 4, 3]),
    # one page a window: the 5-token prompt fills none and registers
    # nothing, so its one chunk waits for the other seven
    ({"page_tokens": 16}, [4, 4]),
    # ... unless its first token may be the EOS that frees its slot
    ({"page_tokens": 16, "eos_id": 0}, [1, 4, 3]),
    # a prompt shorter than a page registers nothing either
    ({"page_tokens": 8}, [4, 4]),
], ids=["pages_to_register", "one_page_a_window", "may_retire_at_once",
        "short_of_a_page"])
def test_an_admission_ends_before_the_next_plan_only_where_it_binds_it(
        spans, kw, first):
    sched = _sched(**kw)
    reqs = [sched.submit(p, max_new=4) for p in _prompts(LENGTHS)]
    sched.step()
    assert _programs_by_tick(spans)[1] == first
    assert reqs[0].tokens and not reqs[1].tokens
    sched.run()
    for r in reqs:
        assert r.finished
        if sched.eos_id is None:
            assert r.tokens == _oracle(r.prompt, 4)


def test_a_chunk_joins_the_chunks_that_are_due_when_it_is_admitted(spans):
    """A request admitted while others are mid-prompt runs its first
    chunk in their program, where none of them ends its admission in
    that tick."""
    sched = _sched(page_tokens=4)
    long_a, long_b, short = _prompts((40, 33, 6))
    a = sched.submit(long_a, max_new=3)
    b = sched.submit(long_b, max_new=3)
    sched.step()
    c = sched.submit(short, max_new=3)
    before = len(spans)
    sched.step()
    programs = [s.args for s in _own(spans[before:])
                if s.name == "serving.prefill_chunk"]
    assert [p["chunks"] for p in programs] == [3]
    assert programs[0]["req"] == f"{a.id},{b.id},{c.id}"
    assert programs[0]["chunk"] == "1,1,0"
    assert c.tokens and not a.tokens and not b.tokens
    sched.run()
    for r in (a, b, c):
        assert r.tokens == _oracle(r.prompt, 3)


def test_a_size_met_late_compiles_and_loads_nothing():
    """The grouped program has run before the scheduler's first tick
    returns: whether and when a tick has several chunks due is the
    traffic's, and the tick that is first to pays no compile."""
    from jax import monitoring

    cfg = dataclasses.replace(SERVE, d_ff=40)  # programs of its own
    sched = ServingScheduler(init_params(cfg, 2), cfg, slots=8, n_inner=2,
                             prompt_chunk=8, max_prompt=64, page_tokens=4)
    one, three = _prompts((20,)), _prompts((30, 28, 26), seed=9)
    first = sched.submit(one[0], max_new=2)
    sched.step()  # construction and one tick, one slot admitting
    assert sched._extend._cache_size() == 1
    assert sched._extend_group._cache_size() == 1
    assert len(sched._scratch_arenas) == 2
    sched.run()  # first token, placement and the decode tick have run
    sched.submit(one[0], max_new=2)
    sched.run()  # and an arena has come off the free list
    compiled = []
    listen = lambda event, secs, **kw: compiled.append(event) if (
        event == "/jax/core/compile/backend_compile_duration") else None
    monitoring.register_event_duration_secs_listener(listen)
    try:
        for p in three:
            sched.submit(p, max_new=2)
        sched.step()  # a group of three is met first here, and padded
        assert len(sched._admitting) == 3
        sched.run()
    finally:
        monitoring.unregister_event_duration_listener(listen)
    assert first.finished
    assert compiled == []
    assert sched._extend_group._cache_size() == 1


def test_cancel_between_two_chunks_leaves_the_others_streams():
    sched = _sched(slots=4, page_tokens=4)
    prompts = _prompts((50, 41, 33, 26))
    reqs = [sched.submit(p, max_new=5) for p in prompts]
    sched.step()
    sched.step()  # all four mid-prompt, in one program a tick
    assert len(sched._admitting) == 4
    used = sched.pool.used
    assert sched.cancel(reqs[1])
    assert reqs[1].reason == "cancelled" and not reqs[1].tokens
    assert len(sched._admitting) == 3 and len(sched._free_arenas) == 1
    assert sched.pool.used < used
    late = sched.submit(_prompts((19,), seed=4)[0], max_new=5)
    sched.run()
    for r in (reqs[0], reqs[2], reqs[3], late):
        assert r.finished and r.tokens == _oracle(r.prompt, 5)
