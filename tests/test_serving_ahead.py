"""A tick's admissions planned behind the tick that is still running
(models/serving.py ``ServingScheduler.step``, ``_ends_known``,
``_admit_ahead``): where a request can only end by its length the host
counts a tick's ends before its tokens are back, frees those slots,
and plans and dispatches the NEXT tick's admit phase while the chip
runs this one; a first token stays on the device until its request's
first tick is fetched.

The contract is that nothing but the order of the host's work changes.
The oracle is the same scheduler answering False where it is asked
whether ends can be counted (``InOrder``: every tick's admit phase at
the top of its own ``step``, the first token read where it is made, as
the scheduler always ran and still runs with ``eos_id`` or a drafter):
the same requests in the same slots and pages, the same chunks in the
same programs, the same ``admitted_tick`` / ``retired_tick``, the same
tokens; and ``generate_*`` where the other serving tests compare with
it. Tiny sizes, float32 on the CPU.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mpistragglers_jl_tpu.models import serving
from mpistragglers_jl_tpu.models import transformer as tr
from mpistragglers_jl_tpu.models.decode import (
    generate_dense,
    generate_ring_dense,
)
from mpistragglers_jl_tpu.models.serving import ServingScheduler
from mpistragglers_jl_tpu.models.transformer import (
    TransformerConfig,
    init_params,
)

C = 8  # the prefill chunk

DENSE = TransformerConfig(
    vocab=61, d_model=32, n_heads=4, n_kv_heads=2, n_layers=2, d_ff=48,
    attn_window=16,
)
PLAIN = dataclasses.replace(DENSE, attn_window=None, max_context=128)
EXPERTS = dataclasses.replace(
    PLAIN, norm="rmsnorm", ffn="swiglu", layer_experts=(False, True),
    n_experts=8, experts_per_token=2, d_expert=16, shared_experts=1,
)
STATE = dataclasses.replace(
    PLAIN, d_head=8, attn_impl="reference", norm="rmsnorm", ffn="swiglu",
    layer_mixers=("gdn", "attn"), gdn_key_heads=2, gdn_value_heads=4,
    gdn_key_dim=8, gdn_value_dim=8, gdn_conv=4,
)
YARN = (10000.0, 40.0, 16, 32.0, 1.0)
# latent attention over group-limited experts, with the module a drafter
# drafts with (served without it unless ``draft="mtp"`` asks)
LATENT = TransformerConfig(
    vocab=64, d_model=32, n_heads=4, d_head=12, n_layers=2, d_ff=48,
    attn_impl="reference", norm="rmsnorm", norm_eps=1e-6, ffn="swiglu",
    tie_head=False, layer_mixers=("mla",) * 2, mla_q_rank=16,
    mla_kv_rank=24, mla_nope_dim=8, mla_rope_dim=4, mla_v_dim=8,
    rope_table=tr.yarn_rope_table(4, *YARN),
    attn_scale=float(12 ** -0.5 * (0.1 * np.log(40.0) + 1.0) ** 2),
    layer_experts=(False, True), n_experts=16, experts_per_token=4,
    d_expert=16, shared_experts=1, route_scale=2.5, route_groups=4,
    route_topk_groups=2, experts_held=(0, 8), max_context=128, mtp_depth=1,
)
# (configuration, the scheduler's keywords, the generator its streams
# equal token for token where the other serving tests hold it to one)
CASES = {
    # sliding windows, pages of 4: prompts register prefix pages, so a
    # last chunk ends its admission before the next plan (at once), and
    # a request's lifetime wraps its ring
    "dense": (DENSE, dict(page_tokens=4), generate_ring_dense),
    "experts": (EXPERTS, dict(page_tokens=8, quantize_kv=True), None),
    "state": (STATE, dict(page_tokens=16), generate_dense),
    "latent": (LATENT, dict(page_tokens=8, quantize_kv=True), None),
}
SLOTS, N_INNER = 4, 3


@pytest.fixture(scope="module", params=sorted(CASES))
def case(request):
    cfg, kw, generate = CASES[request.param]
    return request.param, cfg, init_params(cfg, seed=11), kw, generate


class InOrder(ServingScheduler):
    """The scheduler as it runs wherever an end is a token's value."""

    def _ends_known(self):
        return False


class _Watched:
    """Mixed into both sides: what the pools hold and which pages every
    slot reads as each tick's program is dispatched (after the tick's
    admissions and its copy-on-write pass), and what each step
    returned."""

    def _decode_dispatch(self):
        self.dispatched.append((
            self.tick_count,
            {name: pool.used for name, pool in self.pools.items()},
            [kd.pt_host.copy() for kd in self._kinds],
        ))
        super()._decode_dispatch()


def _scheduler(cls, cfg, params, **kw):
    kw = {"slots": SLOTS, "n_inner": N_INNER, "prompt_chunk": C,
          "max_prompt": 64, **kw}
    sched = type(cls.__name__, (_Watched, cls), {})(params, cfg, **kw)
    sched.dispatched = []
    return sched


class _Spy:
    """``serving._annotate`` replaced: every span with its arguments,
    and one log of every span's entry and exit in order."""

    seen: list = []
    log: list = []

    def __init__(self, name, **args):
        self.name, self.args = name, dict(args)

    def __enter__(self):
        _Spy.seen.append(self)
        _Spy.log.append(("enter", self.name))
        return self

    def __exit__(self, *exc):
        _Spy.log.append(("exit", self.name))

    def set_metadata(self, **args):
        self.args.update(args)


@pytest.fixture
def spans(monkeypatch):
    _Spy.seen, _Spy.log = [], []
    monkeypatch.setattr(serving, "_annotate", _Spy)
    return _Spy


LENGTHS = [5, C, 20, 37, 12, 3, 26, 9, 33, 17, 40, 6, 14, 4]
ANSWERS = [6, 13, 4, 9, 2, 11, 7, 3, 12, 5, 8, 10, 13, 12]


def _backlog(cfg, n=14, seed=3):
    """A mixed backlog of more than three rounds of the slots: prompts
    of one to five chunks (one of exactly a chunk), answers of 2 to 13
    tokens (some a multiple of a tick's, some not); the last to come
    are short prompts with long answers, so that some slot decodes in
    every tick to the end."""
    rng = np.random.default_rng(seed)
    return [(rng.integers(1, cfg.vocab, size=t).astype(np.int32), m)
            for t, m in zip(LENGTHS[:n], ANSWERS)]


def _drain(sched, work, late=()):
    """Submit ``work``, step until drained (``late``: {after which
    step: more work}); the requests and what each step returned."""
    reqs = [sched.submit(p, m) for p, m in work]
    returned, seen = [], []
    while sched.pending or sched.active:
        returned.append(sched.step())
        # what a caller sees of every request after the step
        seen.append([(r.admitted_tick, len(r.tokens), r.finished)
                     for r in reqs])
        for p, m in late.get(len(returned), ()) if late else ():
            reqs.append(sched.submit(p, m))
        assert len(returned) < 500
    for step in returned:
        assert all(r.finished and len(r.tokens) == r.max_new for r in step)
    return reqs, ([[reqs.index(r) for r in step] for step in returned],
                  seen)


def _ticks(spy):
    """``serving.tick``'s arguments, tick by tick, ``ahead`` apart."""
    ticks = [dict(s.args) for s in spy.seen if s.name == "serving.tick"]
    return [t.pop("ahead") for t in ticks], ticks


def _both_sides(case, spans, work, late=()):
    """The same work through the scheduler that plans ahead and through
    the one that runs in order: every observable of the schedule equal,
    side by side. Returns the ahead side with its requests and its
    ticks' ``ahead``. (With ``late`` arrivals one count may differ and
    is left out: a request that arrives between two steps finds the
    tick's other chunks dispatched already, so its first chunk is a
    program of its own where in order it would have joined theirs.)"""
    _, cfg, params, kw, _ = case
    sides = []
    for cls in (ServingScheduler, InOrder):
        spans.seen, spans.log = [], []
        sched = _scheduler(cls, cfg, params, **kw)
        reqs, returned = _drain(sched, work, late)
        sides.append((sched, reqs, returned, _ticks(spans)))
    (a, a_reqs, a_ret, (a_ahead, a_ticks)), \
        (b, b_reqs, b_ret, (b_ahead, b_ticks)) = sides
    for i, (x, y) in enumerate(zip(a_reqs, b_reqs)):
        assert x.tokens == y.tokens, f"request {i}"
        assert (x.admitted_tick, x.retired_tick, x.reason) == (
            y.admitted_tick, y.retired_tick, y.reason), f"request {i}"
    # each step returns the requests that ended in its tick, and after
    # every step every request shows the caller the same stamp, the
    # same number of tokens and the same ``finished``
    assert a_ret == b_ret
    # tick by tick the same counts as the tick begins, the same chunks
    # in the same number of programs
    assert a.tick_count == b.tick_count == len(a_ticks) == len(b_ticks)
    for x, y in zip(a_ticks, b_ticks):
        if late:
            assert x.pop("chunk_programs") >= y.pop("chunk_programs")
        assert x == y, f"tick {x['tick']}"
    # and as every tick's program is dispatched, the same pages in use
    # and the same pages behind every slot. (After a STEP the side that
    # plans ahead has the next tick's requests in their slots and pages
    # already: the two are equal at the same point of the schedule.)
    assert len(a.dispatched) == len(b.dispatched)
    for (tx, ux, px), (ty, uy, py) in zip(a.dispatched, b.dispatched):
        assert (tx, ux) == (ty, uy)
        for mx, my in zip(px, py):
            np.testing.assert_array_equal(mx, my, err_msg=f"tick {tx}")
    assert ({n: p.used for n, p in a.pools.items()}
            == {n: p.used for n, p in b.pools.items()})
    assert b.ticks_ahead == 0 and not any(b_ahead)
    return a, a_reqs, a_ahead


def test_a_backlog_is_served_as_in_order_and_every_tick_but_the_first_ahead(
        case, spans):
    name, cfg, params, _, generate = case
    work = _backlog(cfg)
    sched, reqs, ahead = _both_sides(case, spans, work)
    assert sched._group == 4 and len(work) > 3 * SLOTS
    # the first tick has nothing behind it; every other was planned
    # behind the tick before (which had a program to plan behind: the
    # slots of a backlog never all stand in prefill)
    assert [t for t, _, _ in sched.dispatched] == list(
        range(1, sched.tick_count + 1))
    assert ahead == [0] + [1] * (sched.tick_count - 1)
    assert sched.ticks_ahead == sched.tick_count - 1
    assert all(r.reason == "length" and r._first is None for r in reqs)
    if generate is not None:
        for r, (p, m) in zip(reqs, work):
            want = generate(params, jnp.asarray(p)[None], m,
                            dataclasses.replace(cfg, mtp_depth=0))
            assert r.tokens == [int(t) for t in np.asarray(want)[0]]


def test_no_device_value_is_read_between_a_dispatch_and_its_fetch(
        case, spans, monkeypatch):
    """The property the gain rests on: from the dispatch of a tick's
    program to the fetch of its tokens the host dispatches (the slots'
    retirements, the next tick's prefill programs, first tokens,
    placements) and reads nothing back, so the chip never waits for it
    there. Every read of a device value, through ``np.asarray`` /
    ``np.array``, ``jax.device_get`` or a conversion, is logged with
    the scheduler's spans: on the path that plans ahead each lies
    inside ``serving.decode_wait`` (the tick's tokens) or
    ``serving.first_token_wait`` in the harvest behind it."""
    _, cfg, params, kw, _ = case
    log = spans.log

    def logged(f):
        def reads(a, *args, **kwargs):
            if isinstance(a, jax.Array):
                spans.log.append(("read", f.__name__))
            return f(a, *args, **kwargs)
        return reads

    array_type = type(jnp.zeros(()))
    value = array_type._value
    monkeypatch.setattr(array_type, "_value", property(
        lambda self: (spans.log.append(("read", "_value")),
                      value.fget(self))[1]))
    monkeypatch.setattr(np, "asarray", logged(np.asarray))
    monkeypatch.setattr(np, "array", logged(np.array))
    sched = _scheduler(ServingScheduler, cfg, params, **kw)
    spans.seen, spans.log = [], []
    reqs, _ = _drain(sched, _backlog(cfg))
    log = spans.log
    assert sched.ticks_ahead == sched.tick_count - 1
    assert sum(r.max_new for r in reqs) == sum(len(r.tokens) for r in reqs)
    open_spans: list[str] = []
    reads = {"serving.decode_wait": 0, "serving.first_token_wait": 0}
    since_dispatch = None  # reads since the last dispatch, until its fetch
    for kind, name in log:
        if kind == "enter":
            open_spans.append(name)
            if name == "serving.decode_wait":
                assert since_dispatch == 0
                since_dispatch = None
        elif kind == "exit":
            assert open_spans.pop() == name
            if name == "serving.decode_dispatch":
                since_dispatch = 0
        else:
            assert open_spans[-1] in reads, (name, open_spans)
            reads[open_spans[-1]] += 1
            if since_dispatch is not None:
                since_dispatch += 1
    # the reads there are: every tick's tokens, every request's first
    assert reads["serving.decode_wait"] >= len(sched.dispatched)
    assert reads["serving.first_token_wait"] >= len(reqs)
    # and a first token is read in the harvest, behind the fetch
    inside = [(log[i - 1], log[i]) for i in range(1, len(log))
              if log[i] == ("enter", "serving.first_token_wait")]
    assert inside and all(
        before[1] in ("serving.harvest", "serving.first_token_wait")
        for before, _ in inside)


def test_the_phases_stay_siblings_and_the_ahead_admit_lies_between_two_decodes(
        case, spans):
    _, cfg, params, kw, _ = case
    sched = _scheduler(ServingScheduler, cfg, params, **kw)
    spans.seen, spans.log = [], []
    _drain(sched, _backlog(cfg, n=9))
    phases = ("serving.admit", "serving.decode", "serving.harvest")
    depth, per_tick = 0, []
    for kind, name in spans.log:
        if name == "serving.tick":
            if kind == "enter":
                per_tick.append([])
            continue
        if name in phases:
            if kind == "enter":
                assert depth == 0, "a phase inside a phase"
                per_tick[-1].append(name.removeprefix("serving."))
            depth += 1 if kind == "enter" else -1
    assert per_tick[0] == ["admit", "decode", "admit", "decode", "harvest"]
    assert all(p in (["admit"], per_tick[0]) for p in per_tick)
    # the prefill programs of a tick planned ahead are dispatched
    # between the tick before's dispatch and its fetch
    names = [n for k, n in spans.log if k == "enter"]
    first_fetch = names.index("serving.decode_wait")
    first_dispatch = names.index("serving.decode_dispatch")
    assert "serving.prefill_chunk" in names[first_dispatch:first_fetch]


@pytest.mark.parametrize("guard", ["eos_id", "draft", "in_order"])
def test_where_an_end_is_a_tokens_value_nothing_runs_ahead(spans, guard):
    """With ``eos_id`` set or a drafter attached the old order runs:
    ``ticks_ahead`` stays 0, every tick says ``ahead=0``, the phases
    are admit, decode, harvest, and a first token is read where it is
    made (inside ``serving.admit``)."""
    params = init_params(LATENT, seed=11)
    kw = dict(page_tokens=8, quantize_kv=True)
    cls = InOrder if guard == "in_order" else ServingScheduler
    if guard == "eos_id":
        kw["eos_id"] = 5
    elif guard == "draft":
        kw["draft"] = "mtp"
    sched = _scheduler(cls, LATENT, params, **kw)
    assert not sched._ends_known()
    reqs = [sched.submit(p, m) for p, m in _backlog(LATENT, n=9)]
    sched.run()
    assert all(r.finished for r in reqs)
    assert sched.ticks_ahead == 0 and sched.tick_count > 3
    ahead, _ = _ticks(spans)
    assert ahead == [0] * sched.tick_count
    names = [n for k, n in spans.log if k == "enter"]
    per_tick = "|".join(
        n.removeprefix("serving.") for n in names
        if n in ("serving.tick", "serving.admit", "serving.decode",
                 "serving.harvest")).split("tick|")[1:]
    assert set(per_tick) <= {"admit|", "admit", "admit|decode|harvest|",
                             "admit|decode|harvest"}
    open_spans = []
    for kind, name in spans.log:
        if kind == "enter":
            if name == "serving.first_token_wait":
                assert "serving.admit" in open_spans
            open_spans.append(name)
        else:
            open_spans.pop()


def test_a_submit_between_two_steps_is_admitted_in_the_step_that_follows(
        case, spans):
    """The admit phase at the top of ``step`` stays: what arrives
    between two steps (here with slots free and with none) is admitted
    by the tick that follows, as in order, whether or not that tick's
    other admissions were planned ahead."""
    _, cfg, _, _, _ = case
    work = _backlog(cfg, n=3)
    more = _backlog(cfg, n=8, seed=5)
    late = {1: more[:1], 2: more[1:4], 6: more[4:]}
    sched, reqs, ahead = _both_sides(case, spans, work, late)
    assert len(reqs) == 11 and 1 in ahead
    # a slot was free when the first came: admitted by the very next tick
    assert reqs[3].admitted_tick == 2
    assert all(r.reason == "length" for r in reqs)


def test_an_answer_of_one_token_and_a_prompt_of_one_chunk(case, spans):
    """``max_new == 1`` stays an at-once case: the request ends where
    its first token is made, which reads the token, so the tick before
    does not plan ahead while one is in prefill or next in the queue;
    the ticks around it do."""
    _, cfg, _, _, _ = case
    rng = np.random.default_rng(9)
    prompt = lambda n: rng.integers(1, cfg.vocab, size=n).astype(np.int32)
    work = [(prompt(C), 5), (prompt(7), 9), (prompt(C), 1), (prompt(19), 1),
            (prompt(11), 6), (prompt(2 * C), 4), (prompt(5), 1),
            (prompt(C), 2), (prompt(30), 7), (prompt(4), 3)]
    sched, reqs, ahead = _both_sides(case, spans, work)
    ones = [r for r in reqs if r.max_new == 1]
    assert len(ones) == 3 and all(len(r.tokens) == 1 for r in ones)
    # the one that waited in the queue was admitted by a tick that was
    # not planned ahead; ticks were planned ahead before and after
    assert ahead[reqs[6].admitted_tick - 1] == 0
    assert 0 < sched.ticks_ahead < sched.tick_count - 1
    assert 1 in ahead[reqs[6].admitted_tick:]


def test_cancel_and_run_see_a_consistent_scheduler_between_two_steps(case):
    """Between two steps the next tick's admissions are already in
    their slots: a request cancelled there (queued, mid-prompt, or
    admitted ahead with its first token still on the device) gives back
    its slot and pages, and the others' streams are what they are
    without it."""
    _, cfg, params, kw, _ = case
    work = _backlog(cfg, n=10)
    alone = _scheduler(InOrder, cfg, params, **kw)
    want, _ = _drain(alone, work)
    sched = _scheduler(ServingScheduler, cfg, params, **kw)
    reqs = [sched.submit(p, m) for p, m in work]
    sched.step()
    sched.step()
    # through prefill behind tick 2 as tick 3's: in their slots, the
    # first token still a device value
    pending = [r for r in reqs if r._first is not None]
    assert pending and not any(r.tokens for r in pending)
    # and nothing carries the stamp of a tick that has not run
    assert {r.admitted_tick for r in reqs} <= {None, 1, 2}
    ahead = [r for r in sched._slot_req
             if r is not None and r.admitted_tick is None]
    assert ahead and set(ahead) == set(sched._admitted_ahead)
    gone = [pending[0], reqs[-1]]
    mid = [st.req for st in sched._admitting.values()]
    gone += mid[:1]
    used = {n: p.used for n, p in sched.pools.items()}
    for r in gone:
        assert sched.cancel(r) and r.reason == "cancelled" and not r.tokens
    assert any(p.used < used[n] for n, p in sched.pools.items())
    sched.run()
    assert all(p.used == 0 for p in sched.pools.values())
    for r, w in zip(reqs, want):
        if r not in gone:
            assert r.finished and r.tokens == w.tokens
