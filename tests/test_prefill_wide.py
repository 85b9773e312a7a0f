"""A document's prefill takes a whole grouped program's rows a tick
(models/serving.py ``_extend_chunk_wide``, ``ServingScheduler._wide_slot``
and ``._run_wide_chunk``): where a chunk waits for the weights' bytes
(``_group > 1``) and a prompt can need more ticks of prefill than there
are slots, the request admitted first advances ``_group`` chunks in one
program of its own, the other due chunks are grouped as ever, and a
tick that goes wide runs one more prefill program at most.

The oracle of the streams is the same scheduler with the wide program
withheld: the schedule changes, no token does.
"""

from __future__ import annotations

import dataclasses

import numpy as np
import pytest

from mpistragglers_jl_tpu.models import serving
from mpistragglers_jl_tpu.models.serving import (
    ServingScheduler,
    _extend_chunk_dense,
    _extend_chunk_group,
    _extend_chunk_wide,
    _fresh_cache,
)
from mpistragglers_jl_tpu.models.transformer import (
    TransformerConfig,
    init_params,
)
from mpistragglers_jl_tpu.obs import MetricsRegistry

C, G = 8, 4
PLAIN = TransformerConfig(
    vocab=53, d_model=32, n_heads=4, n_kv_heads=2, n_layers=2, d_ff=48,
    max_context=256,
)
# 2 of 8 experts a token: an expert sees a quarter of a chunk's rows,
# so chunks wait for bytes and share programs, four at most
EXPERTS = dataclasses.replace(
    PLAIN, norm="rmsnorm", ffn="swiglu", layer_experts=(False, True),
    n_experts=8, experts_per_token=2, d_expert=16, shared_experts=1,
)
DELTA = dataclasses.replace(
    EXPERTS, d_head=8, attn_impl="reference", layer_mixers=("gdn", "attn"),
    gdn_key_heads=2, gdn_value_heads=4, gdn_key_dim=8, gdn_value_dim=8,
    gdn_conv=4,
)
CONFIGS = {"attention_experts": EXPERTS, "delta_rule_experts": DELTA}
PARAMS = {name: init_params(cfg, seed=5) for name, cfg in CONFIGS.items()}


def _sched(name="delta_rule_experts", *, withheld=False, **kw):
    """Four slots and prompts of up to 16 chunks of 8: the deployment
    serves documents. ``withheld``: the same scheduler without its wide
    program, which is the schedule every tick had before."""
    kw = {"slots": 4, "n_inner": 2, "prompt_chunk": C, "max_prompt": 128,
          "page_tokens": 8, **kw}
    sched = ServingScheduler(PARAMS[name], CONFIGS[name], **kw)
    assert sched._group == G
    if kw["max_prompt"] // C > kw["slots"]:
        assert sched._extend_wide.__name__ == f"serving_prefill_chunk_w{G}"
    if withheld:
        sched._extend_wide = None
    return sched


def _prompts(lengths, seed=3):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, PLAIN.vocab, size=n).astype(np.int32)
            for n in lengths]


# one document of 16 chunks and one of 12 among prompts of 1 to 4
MIX = (5, 125, 12, 20, 90, 7, 30)


def _serve(sched, lengths=MIX, max_new=6):
    reqs = [sched.submit(p, max_new=max_new) for p in _prompts(lengths)]
    sched.run()
    assert all(r.finished for r in reqs)
    return [r.tokens for r in reqs]


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


def _programs_by_tick(spans):
    """{tick: its ``serving.prefill_chunk`` spans' arguments, in order}
    (a tick's programs are dispatched in its admit phase, which runs
    behind the dispatch of the tick before where ends can be counted:
    tests/test_prefill_group.py)."""
    out: dict[int, list[dict]] = {}
    owner = 0
    for s in spans:
        if s.name == "serving.tick":
            owner = s.args["tick"]
        elif s.name == "serving.decode_dispatch":
            owner += 1
        elif s.name == "serving.prefill_chunk":
            out.setdefault(owner, []).append(s.args)
    return out


# -- (a) the streams ----------------------------------------------------------


@pytest.mark.parametrize("quantize_kv", [True, False], ids=["int8", "bf16"])
@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_a_wide_chunk_serves_the_tokens_of_its_chunks_one_by_one(
        name, quantize_kv):
    wide = _sched(name, quantize_kv=quantize_kv)
    got = _serve(wide)
    plain = _sched(name, quantize_kv=quantize_kv, withheld=True)
    assert got == _serve(plain)
    assert plain.wide_chunks == 0 and wide.wide_chunks >= 3 * G
    # the same chunks in all, over fewer ticks
    assert wide.prefill_chunks == plain.prefill_chunks == sum(
        -(-n // C) for n in MIX)
    assert wide.tick_count < plain.tick_count


@pytest.mark.parametrize("case", ["delta_rule", "drafter"])
def test_the_wide_program_is_the_lone_chunks_body_at_its_width(case):
    """``g`` chunks one after another through the lone chunk's program
    leave the arena that one wide chunk leaves (an arena that has seen
    another request, an offset that is no multiple of the wide chunk's
    rows): the recurrent state of a delta-rule layer, told how many
    rows are real, and under a drafter the rows of the module's own
    cache layer, made from the tokens that follow each row."""
    if case == "delta_rule":
        cfg, params = DELTA, PARAMS["delta_rule_experts"]
        extra = lambda rows, at, n: dict(valid=np.int32(n))
    else:
        cfg = dataclasses.replace(EXPERTS, mtp_depth=1)
        params = init_params(cfg, seed=5)
        extra = lambda rows, at, n: dict(nxt=rows[:, at + 1:at + n + 1])
    Lmax, off = 128, 3 * C
    rng = np.random.default_rng(2)
    rows = rng.integers(1, cfg.vocab, size=(1, G * C + 1)).astype(np.int32)
    loud = lambda: [
        {k: (a + 1).astype(a.dtype) for k, a in cl.items()}
        for cl in _fresh_cache(cfg, 1, Lmax, True)]
    one = _extend_chunk_dense(cfg, C, Lmax)
    want = loud()
    for i in range(G):
        _, want = one(params, rows[:, i * C:(i + 1) * C], want,
                      np.int32(off + i * C), **extra(rows, i * C, C))
    hidden, got = _extend_chunk_wide(cfg, C, Lmax, G)(
        params, rows[:, :G * C], loud(), np.int32(off),
        **extra(rows, 0, G * C))
    assert hidden.shape == (1, G * C, cfg.d_model)
    assert len(got) == len(want) == cfg.n_layers + (case == "drafter")
    for cl_got, cl_want in zip(got, want):
        assert cl_got.keys() == cl_want.keys()
        for k in cl_got:
            a, b = np.asarray(cl_got[k]), np.asarray(cl_want[k])
            assert a.shape == b.shape and a.dtype == b.dtype
            if a.dtype == np.int8:  # a quantized row, by a step at most
                assert np.abs(a.astype(np.int32) - b).max() <= 1
            else:
                np.testing.assert_allclose(
                    a.astype(np.float32), b.astype(np.float32),
                    rtol=1e-4, atol=2e-3)


# -- (b) the schedule ---------------------------------------------------------


def test_the_oldest_document_advances_g_chunks_a_tick(spans):
    sched = _sched()
    doc_a, doc_b, short = _prompts((125, 90, 12))
    a = sched.submit(doc_a, max_new=4)   # 16 chunks
    b = sched.submit(doc_b, max_new=4)   # 12 chunks
    c = sched.submit(short, max_new=4)   # 2 chunks
    sched.run()
    by_tick = _programs_by_tick(spans)
    cursor: dict[int, list] = {a.id: [], b.id: [], c.id: []}
    for tick, programs in sorted(by_tick.items()):
        wide = [p for p in programs if p.get("wide")]
        # one wide program a tick at most, and then one more at most
        assert len(wide) <= 1 and (not wide or len(programs) <= 2)
        for p in programs:
            ids = [int(i) for i in str(p["req"]).split(",")]
            at = [int(i) for i in str(p["chunk"]).split(",")]
            of = [int(i) for i in str(p["of"]).split(",")]
            if p.get("wide"):
                assert p["chunks"] == G and len(ids) == 1
                # G whole chunks BEFORE the last: that one stays C wide
                assert at[0] + G <= of[0] - 1
            else:
                assert "wide" not in p and p["chunks"] == len(ids)
            for rid, i in zip(ids, at):
                cursor[rid].append((tick, i, bool(p.get("wide"))))
    # the document admitted first goes wide while G chunks stand before
    # its last, then chunk by chunk; the second one chunk a tick the
    # while, and wide once it is the oldest that can
    assert [(i, w) for _, i, w in cursor[a.id]] == [
        (0, True), (4, True), (8, True),
        (12, False), (13, False), (14, False), (15, False)]
    assert [(i, w) for _, i, w in cursor[b.id]] == [
        (0, False), (1, False), (2, False), (3, True), (7, True),
        (11, False)]
    assert [(i, w) for _, i, w in cursor[c.id]] == [(0, False), (1, False)]
    # a tick a step: no tick is skipped, none runs a request twice
    for seen in cursor.values():
        ticks = [t for t, _, _ in seen]
        assert ticks == list(range(ticks[0], ticks[0] + len(ticks)))
    # every last chunk ran in the program its first token is read from
    for r in (a, b, c):
        assert r.finished and len(r.tokens) == 4


def test_a_tick_with_more_than_g_other_chunks_due_runs_none_wide(spans):
    sched = _sched(slots=8)
    assert sched._extend_wide is not None  # 16 chunks a prompt, 8 slots
    reqs = [sched.submit(p, max_new=3)
            for p in _prompts((125, 60, 50, 44, 36, 28))]
    sched.step()
    by_tick = _programs_by_tick(spans)
    # six chunks are due in the first tick, five beside the document's
    assert [p["chunks"] for p in by_tick[1]] == [4, 2]
    assert not any(p.get("wide") for p in by_tick[1])
    sched.run()
    by_tick = _programs_by_tick(spans)
    for tick, programs in by_tick.items():
        others = sum(p["chunks"] for p in programs if not p.get("wide"))
        if any(p.get("wide") for p in programs):
            assert others <= G and len(programs) <= 2
    # once the short prompts are through, the document does go wide
    assert sched.wide_chunks >= G
    assert all(r.finished for r in reqs)


# -- (c) where no wide program exists -----------------------------------------


def _lowered(sched):
    """The lowered text of the lone chunk's and the grouped program as
    this scheduler would run them."""
    cfg, n = sched.cfg, sched._group
    arena = lambda: _fresh_cache(cfg, 1, sched.Lmax, sched.quantize_kv)
    valid = (np.int32(0),) if cfg.counts_rows else ()
    one = sched._extend.lower(
        sched.params, np.zeros((1, C), np.int32), arena(), np.int32(0),
        *valid).as_text()
    group = sched._extend_group.lower(
        sched.params, np.zeros((n, C), np.int32),
        tuple(arena() for _ in range(n)), np.zeros((n,), np.int32),
        *(np.zeros((n,), np.int32) for _ in valid)).as_text()
    return one, group


def test_no_wide_program_where_the_rule_says_no():
    # chunks at the ridge share no program, and none is wide
    dense = ServingScheduler(
        init_params(PLAIN, 1), PLAIN, slots=4, prompt_chunk=256,
        max_prompt=4096, page_tokens=64)
    assert dense._group == 1 and dense._extend_wide is None
    # sixteen chunks a prompt at most and sixteen slots: no prompt needs
    # more ticks of prefill than there are slots
    chat = _sched(slots=16, max_prompt=128)
    assert chat._group == G and chat._extend_wide is None
    texts = _lowered(chat)
    for build in (_extend_chunk_dense, _extend_chunk_group,
                  _extend_chunk_wide):
        build.cache_clear()
    # ... and one slot fewer: the programs both schedulers share are the
    # same text, built anew beside a wide program
    docs = _sched(slots=15, max_prompt=128)
    assert docs._extend is not chat._extend
    assert docs._extend_wide is not None
    assert _lowered(docs) == texts


def test_a_scheduler_without_the_wide_program_runs_what_it_ran(spans):
    sched = _sched(slots=16, max_prompt=128)
    tokens = _serve(sched)
    assert sched.wide_chunks == 0
    assert len(sched._scratch_arenas) == G - 2
    ticks = [s for s in spans if s.name == "serving.tick"]
    assert ticks and all("wide_chunks" not in t.args for t in ticks)
    programs = [s for s in spans if s.name == "serving.prefill_chunk"]
    assert programs and not any("wide" in p.args for p in programs)
    assert tokens == _serve(_sched())


# -- (d) the counters ---------------------------------------------------------


def test_the_counters_say_what_ran_wide(spans):
    registry = MetricsRegistry()
    sched = _sched(registry=registry)
    _serve(sched)
    ticks = [s for s in spans if s.name == "serving.tick"]
    by_tick = _programs_by_tick(spans)
    wide_ticks = 0
    for t in ticks:
        programs = by_tick.get(t.args["tick"], [])
        assert t.args["chunks"] == sum(p["chunks"] for p in programs)
        assert t.args["chunk_programs"] == len(programs)
        assert t.args["wide_chunks"] == sum(
            p["chunks"] for p in programs if p.get("wide"))
        assert t.args["wide_chunks"] in (0, G)
        wide_ticks += t.args["wide_chunks"] > 0
    assert wide_ticks >= 3
    wide = [p for ps in by_tick.values() for p in ps if p.get("wide")]
    for p in wide:
        assert p["wide"] == 1 and p["chunks"] == G
        assert p["gdn_rule"] == "xla" and "expert_tile" in p
        assert p["rows_seen"] == (p["chunk"] + G) * C
    total = sum(t.args["chunks"] for t in ticks)
    assert sched.prefill_chunks == total == sum(-(-n // C) for n in MIX)
    assert sched.wide_chunks == G * len(wide) == sum(
        t.args["wide_chunks"] for t in ticks)
    assert registry.counter(
        "serving_prefill_chunks_total").value == total
    assert registry.counter(
        "serving_prefill_wide_chunks_total").value == sched.wide_chunks


# -- (e) planned behind the running tick, and in the old order ----------------


def test_the_same_tokens_planned_ahead_and_in_the_old_order():
    ahead = _sched()
    tokens = _serve(ahead)
    assert ahead.ticks_ahead > 0 and ahead.wide_chunks > 0
    # a token no stream holds ends none of them: the schedule keeps the
    # order it always had, every first token read where it is made
    eos = next(t for t in range(PLAIN.vocab)
               if all(t not in stream for stream in tokens))
    in_order = _sched(eos_id=eos)
    assert _serve(in_order) == tokens
    assert in_order.ticks_ahead == 0
    # (a last chunk is due at once there, and the tick it is due in
    # runs no wide chunk: fewer of them, not none)
    assert 0 < in_order.wide_chunks <= ahead.wide_chunks
    assert _serve(_sched(eos_id=eos, withheld=True)) == tokens


def test_cancel_behind_a_wide_chunk_leaves_the_others_streams():
    sched = _sched()
    doc, other, late = _prompts((125, 90, 40))
    a = sched.submit(doc, max_new=4)
    b = sched.submit(other, max_new=4)
    sched.step()
    assert [st.next_chunk for st in sched._admitting.values()] == [G, 1]
    assert sched.cancel(a) and a.reason == "cancelled" and not a.tokens
    assert len(sched._admitting) == 1 and len(sched._free_arenas) == 1
    c = sched.submit(late, max_new=4)  # into the slot and the arena
    sched.run()
    plain = _sched(withheld=True)
    want = [plain.submit(p, max_new=4) for p in (other, late)]
    plain.run()
    assert [b.tokens, c.tokens] == [r.tokens for r in want]
    assert sched.wide_chunks > G  # the next document in line went wide
