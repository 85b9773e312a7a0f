"""The prefill arena: the zeroed ``(1, max_prompt)`` positional cache an
admission prefills into is made by one program
(``serving_fresh_arena``), goes to the scheduler's free list at every
exit of admission, and comes back zeroed in place
(``serving_reset_arena``). A recycled arena is byte for byte a new one,
so every stream, page and dense row is what a scheduler that never
recycled would hold."""

from __future__ import annotations

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mpistragglers_jl_tpu.models import serving
from mpistragglers_jl_tpu.models.serving import ServingScheduler
from mpistragglers_jl_tpu.models.transformer import (
    TransformerConfig,
    init_params,
)

CFG = TransformerConfig(
    vocab=37, d_model=32, n_heads=4, n_kv_heads=2, n_layers=2, d_ff=64,
    attn_window=32,
)
PARAMS = init_params(CFG, seed=5)
C = 8  # prompt_chunk

KINDS = pytest.mark.parametrize("page_tokens", [4, CFG.attn_window],
                                ids=["pages", "one_page_a_window"])
QUANT = pytest.mark.parametrize("quantize_kv", [True, False],
                                ids=["int8", "bf16"])


def _sched(page_tokens, quantize_kv, slots=3, max_prompt=32):
    return ServingScheduler(
        PARAMS, CFG, slots=slots, n_inner=2, prompt_chunk=C,
        max_prompt=max_prompt, quantize_kv=quantize_kv,
        page_tokens=page_tokens,
    )


def _prompt(seed, n):
    return np.random.default_rng(seed).integers(
        1, CFG.vocab, size=n).astype(np.int32)


def _recorded(sched) -> list[str]:
    """Where each arena this scheduler hands out from now on came from
    ("new" / "reused"), in order."""
    kinds: list[str] = []
    take = sched._take_arena

    def recording():
        arena, kind = take()
        kinds.append(kind)
        return arena, kind

    sched._take_arena = recording
    return kinds


def _pointers(arena) -> list[int]:
    return [a.unsafe_buffer_pointer() for a in jax.tree.leaves(arena)]


def _bytes(arena) -> list[np.ndarray]:
    return [np.asarray(a) for a in jax.tree.leaves(arena)]


def _same_bytes(a, b) -> None:
    """Two arenas, or two lists of arrays, leaf for leaf."""
    for x, y in zip(_bytes(a), _bytes(b), strict=True):
        assert x.dtype == y.dtype and x.shape == y.shape
        np.testing.assert_array_equal(x, y)


def _resident_bytes(sched, slot) -> list[np.ndarray]:
    """What the cache holds for the request in ``slot``: the rows its
    position has reached, read out of its pages leaf by leaf. (A page
    behind them keeps what the pool held, since placement writes the
    pages a prompt covers and no other; no reader passes the position:
    tests/test_admit_handover.py.)"""
    ring = sched._gather(sched._caches, jnp.asarray(sched._pt_host[slot]))
    return [np.asarray(a[:, :sched._host_pos[slot]])
            for a in jax.tree.leaves(ring)]


def _slot_of(sched, req) -> int:
    return sched._slot_req.index(req)


def _step_until_first_token(sched, req) -> None:
    for _ in range(64):
        if req.tokens:
            return
        sched.step()
    raise AssertionError("request never got its first token")


def _arena_invariants(sched) -> None:
    """Free list and live admissions hold disjoint, live arenas, and
    together no more of them than the scheduler has slots."""
    live = [st.cache for st in sched._admitting.values()]
    assert all(c is not None for c in live)
    arenas = live + sched._free_arenas
    assert len(arenas) <= sched.S
    leaves = [leaf for a in arenas for leaf in jax.tree.leaves(a)]
    assert len({id(leaf) for leaf in leaves}) == len(leaves)
    assert not any(leaf.is_deleted() for leaf in leaves)


# -- (a) the two programs -------------------------------------------------


@QUANT
@KINDS
def test_arena_leaves_are_buffers_of_their_own_and_a_reset_is_zeros(
        page_tokens, quantize_kv):
    sched = _sched(page_tokens, quantize_kv)
    arena, kind = sched._take_arena()
    assert kind == "new"
    n_leaves = CFG.n_layers * (4 if quantize_kv else 2)
    assert len(set(_pointers(arena))) == n_leaves
    assert all(not x.any() for x in _bytes(arena))
    # dirty every row the way admission does: prefill chunks donate the
    # arena and return it filled
    for i in range(32 // C):
        _, arena = sched._extend(
            PARAMS, jnp.asarray(_prompt(i, C))[None], arena,
            jnp.int32(i * C),
        )
    assert all(x.any() for x in _bytes(arena))
    before = _pointers(arena)
    sched._free_arenas.append(arena)
    again, kind = sched._take_arena()
    assert kind == "reused" and not sched._free_arenas
    assert all(leaf.is_deleted() for leaf in jax.tree.leaves(arena))
    assert all(not x.any() for x in _bytes(again))
    # in place: each leaf took the buffer of the leaf it replaces
    assert _pointers(again) == before
    assert len(set(before)) == n_leaves
    _same_bytes(again, serving._fresh_cache(CFG, 1, 32, quantize_kv))
    # the dense constructor's (S, W) cache comes from the same program
    big = serving._fresh_cache(CFG, 3, CFG.attn_window, quantize_kv)
    assert len(set(_pointers(big))) == n_leaves
    assert jax.tree.leaves(big)[0].shape[:2] == (3, CFG.attn_window)


def test_the_arena_programs_have_names_of_their_own():
    assert serving._fresh_arena(CFG, 1, 32, True).__name__ == (
        "serving_fresh_arena")
    assert serving.serving_reset_arena.__name__ == "serving_reset_arena"


# -- (b) a long prompt, then a short one through its arena ---------------


@QUANT
@KINDS
def test_short_prompt_through_a_recycled_arena_is_served_as_by_a_fresh_one(
        page_tokens, quantize_kv):
    used = _sched(page_tokens, quantize_kv)
    kinds = _recorded(used)
    long = used.submit(_prompt(1, 29), max_new=3)
    used.run()
    assert long.finished and len(used._free_arenas) == 1
    # every row of the dead arena is the long prompt's
    assert all(x.any() for x in _bytes(used._free_arenas[0]))
    fresh = _sched(page_tokens, quantize_kv)
    short = _prompt(2, 5)
    a, b = used.submit(short, max_new=7), fresh.submit(short, max_new=7)
    _step_until_first_token(used, a)
    _step_until_first_token(fresh, b)
    assert kinds == ["new", "reused"]
    assert a.tokens == b.tokens
    # the arena as the short prompt's prefill left it, rows past the
    # prompt included: no stale row of the long prompt anywhere
    _same_bytes(used._free_arenas[-1], fresh._free_arenas[-1])
    _same_bytes(_resident_bytes(used, _slot_of(used, a)),
                _resident_bytes(fresh, _slot_of(fresh, b)))
    used.run()
    fresh.run()
    assert a.tokens == b.tokens and len(a.tokens) == 7
    _arena_invariants(used)


# -- (c) the seed path donates a recycled arena too ----------------------


@QUANT
def test_shared_prefix_seeds_a_recycled_arena_as_it_seeds_a_new_one(
        quantize_kv):
    prefix = _prompt(3, 8)  # two whole pages
    p1 = np.concatenate([prefix, _prompt(4, 3)])
    p2 = np.concatenate([prefix, _prompt(5, 3)])

    def serve(recycle: bool):
        # an arena longer than the window: the seed program rewrites
        # rows [0, W) only, the rows behind them are the arena's own
        sched = _sched(4, quantize_kv, max_prompt=64)
        kinds = _recorded(sched)
        if recycle:
            # retires at admission (max_new == 1); leaves every row of
            # its arena written
            sched.submit(_prompt(6, 63), max_new=1)
            sched.run()
            assert all(x[:, CFG.attn_window:].any()
                       for x in _bytes(sched._free_arenas[0]))
        r1 = sched.submit(p1, max_new=6)
        _step_until_first_token(sched, r1)
        if not recycle:
            sched._free_arenas.clear()
        r2 = sched.submit(p2, max_new=6)
        _step_until_first_token(sched, r2)
        assert sched.pool.share_hits == 2
        held = _resident_bytes(sched, _slot_of(sched, r2))
        arena = _bytes(sched._free_arenas[-1])
        sched.run()
        _arena_invariants(sched)
        return kinds, r1.tokens, r2.tokens, held, arena

    kinds, t1, t2, held, arena = serve(recycle=True)
    assert kinds == ["new", "reused", "reused"]
    kinds0, u1, u2, held0, arena0 = serve(recycle=False)
    assert kinds0 == ["new", "new"]
    assert (t1, t2) == (u1, u2)
    _same_bytes(held, held0)
    _same_bytes(arena, arena0)


# -- (d) cancel in the middle of a chunked prefill ------------------------


@QUANT
@KINDS
def test_cancel_mid_prefill_returns_the_arena_and_the_next_reuses_it(
        page_tokens, quantize_kv):
    # (four slots: with three, a prompt of four chunks would take three
    # of them in one wide program, tests/test_prefill_wide.py)
    sched = _sched(page_tokens, quantize_kv, slots=4)
    kinds = _recorded(sched)
    doomed = sched.submit(_prompt(7, 30), max_new=4)  # four chunks
    sched.step()
    sched.step()
    assert sched._admitting and not doomed.tokens
    arena = next(iter(sched._admitting.values())).cache
    assert sched.cancel(doomed)
    assert not sched._admitting
    assert len(sched._free_arenas) == 1
    assert sched._free_arenas[0] is arena
    _arena_invariants(sched)
    p = _prompt(8, 11)
    nxt = sched.submit(p, max_new=5)
    sched.run()
    assert kinds == ["new", "reused"]
    other = _sched(page_tokens, quantize_kv)
    same = other.submit(p, max_new=5)
    other.run()
    assert nxt.tokens == same.tokens and len(nxt.tokens) == 5
    _arena_invariants(sched)


@QUANT
@KINDS
def test_free_list_and_live_admissions_stay_disjoint_under_churn(
        page_tokens, quantize_kv):
    """Any sequence of submit / step / cancel: no arena is on the list
    and in an admission at once, none was donated away, and there are
    never more of them than slots."""
    rng = np.random.default_rng(11 + 2 * (page_tokens == 4) + quantize_kv)
    sched = _sched(page_tokens, quantize_kv)
    kinds = _recorded(sched)
    reqs = []
    for i in range(60):
        op = rng.choice(["submit", "step", "step", "cancel"])
        if op == "submit":
            reqs.append(sched.submit(
                _prompt(100 + i, int(rng.integers(1, 31))),
                max_new=int(rng.integers(1, 6)),
            ))
        elif op == "step":
            sched.step()
        elif reqs:
            sched.cancel(reqs[int(rng.integers(len(reqs)))])
        _arena_invariants(sched)
    sched.run()
    _arena_invariants(sched)
    assert all(r.finished for r in reqs)
    assert not sched._admitting
    # as many arenas as the most prompts ever in prefill at once
    assert len(sched._free_arenas) == kinds.count("new") <= sched.S
    assert "reused" in kinds


# -- (e) an admission makes nothing eagerly -------------------------------


@QUANT
@KINDS
def test_a_warm_admission_calls_no_eager_zeros(page_tokens, quantize_kv,
                                               monkeypatch):
    sched = _sched(page_tokens, quantize_kv, slots=2)
    first = sched.submit(_prompt(9, 13), max_new=3)
    sched.run()  # every program has been traced
    assert first.finished

    def refuse(*a, **kw):
        raise AssertionError("jnp.zeros called during an admission")

    monkeypatch.setattr(serving.jnp, "zeros", refuse)
    kinds = _recorded(sched)
    reqs = [sched.submit(_prompt(20 + i, 13), max_new=3) for i in range(3)]
    sched.run()
    assert all(r.finished for r in reqs)
    # two slots: the second prompt is in prefill while the first still
    # is, so one more arena is made, by the program and not leaf by leaf
    assert kinds.count("reused") >= 2 and kinds.count("new") <= 1


@QUANT
@KINDS
def test_a_warm_admission_dispatches_no_eager_slice_or_scalar(
        page_tokens, quantize_kv, monkeypatch):
    """A chunk and the scalars of the admission programs go to the
    device with those programs' own dispatch (host slices, numpy
    scalars): no eager ``dynamic_slice`` or ``jnp.int32`` before them,
    each of which is a dispatch and a transfer of its own; and the
    scalars' types trace no program a second time."""
    sched = _sched(page_tokens, quantize_kv, slots=2)
    alone = sched.submit(_prompt(9, 21), max_new=3)
    sched.run()  # every program has been traced
    sizes = {name: getattr(sched, name)._cache_size()
             for name in ("_extend", "_finish")}

    def refuse(*a, **kw):
        raise AssertionError("an eager device operation in an admission")

    monkeypatch.setattr(serving.jax.lax, "dynamic_slice_in_dim", refuse)
    monkeypatch.setattr(serving.jnp, "int32", refuse)
    again = sched.submit(_prompt(9, 21), max_new=3)
    seen = []
    while not again.finished:
        sched.step()
        seen += [type(st.padded) for st in sched._admitting.values()]
    assert seen and all(t is np.ndarray for t in seen)
    assert again.tokens == alone.tokens
    assert sizes == {name: getattr(sched, name)._cache_size()
                     for name in sizes}
