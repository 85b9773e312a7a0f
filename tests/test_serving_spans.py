"""The scheduler's phase boundaries as a profiler session sees them
(models/serving.py: ``serving.*`` annotations, entered with nothing
attached), what they cost with no session open, and the stable names
of the serving programs and of the parts of the tick."""

from __future__ import annotations

import contextlib
import glob
import os
import time

import numpy as np
import pytest

SPANS = {
    "serving.tick", "serving.admit", "serving.admit_new",
    "serving.prefill_chunk", "serving.first_token",
    "serving.first_token_wait", "serving.decode",
    "serving.decode_dispatch", "serving.decode_wait", "serving.harvest",
}
INSIDE = {
    "serving.admit": "serving.tick",
    "serving.decode": "serving.tick",
    "serving.harvest": "serving.tick",
    "serving.admit_new": "serving.admit",
    "serving.prefill_chunk": "serving.admit",
    "serving.first_token": "serving.admit",
    # the first token is read with the fetch of its request's first
    # tick (no eos_id here: ``ServingScheduler._ends_known``)
    "serving.first_token_wait": "serving.harvest",
    "serving.decode_dispatch": "serving.decode",
    "serving.decode_wait": "serving.decode",
}
# the phases of a tick, siblings in this order: no slot decodes; in
# order (the first tick, and every tick where an end is a token's
# value); the next tick's admit phase planned behind the dispatch
PHASE_ORDERS = (
    ["admit"],
    ["admit", "decode", "harvest"],
    ["admit", "decode", "admit", "decode", "harvest"],
)


@pytest.fixture(scope="module")
def tiny():
    from mpistragglers_jl_tpu.models.transformer import (
        TransformerConfig,
        init_params,
    )

    cfg = TransformerConfig(
        vocab=37, d_model=32, n_heads=4, n_kv_heads=2, n_layers=2,
        d_ff=64, attn_window=8,
    )
    return cfg, init_params(cfg, seed=3)


def _sched(cfg, params, **kw):
    from mpistragglers_jl_tpu.models.serving import ServingScheduler

    kw.setdefault("page_tokens", 4)
    return ServingScheduler(
        params, cfg, slots=2, n_inner=4, prompt_chunk=8, max_prompt=32,
        quantize_kv=True, **kw,
    )


def _host_events(log_dir):
    """[(name, start_ns, end_ns, args)] of the ``serving.*`` host events
    of the newest trace under ``log_dir``, in order of start."""
    from jax.profiler import ProfileData

    path = sorted(
        glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                  recursive=True),
        key=os.path.getmtime,
    )[-1]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("serving."):
                    out.append((ev.name, ev.start_ns,
                                ev.start_ns + ev.duration_ns,
                                dict(ev.stats)))
    return sorted(out, key=lambda e: (e[1], -e[2]))


@contextlib.contextmanager
def _profiled(log_dir):
    """A profiler session that keeps host events (the annotations) and
    leaves Python's own frames out."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


WORK = [(5, 6), (11, 9), (3, 5)]  # (prompt tokens, max_new)


def _submit_work(sched, vocab):
    rng = np.random.default_rng(0)
    return [sched.submit(rng.integers(1, vocab, size=p), max_new=m)
            for p, m in WORK]


@pytest.fixture(scope="module")
def in_order(tiny):
    """The same work through a scheduler that answers False where it is
    asked whether a tick's ends can be counted: every tick's admit phase
    runs at the top of its own ``step``, so the scheduler's state before
    a step IS the schedule as that tick begins. [(that state, what the
    tick delivered to whom, who had no token yet, who retired)]."""
    from mpistragglers_jl_tpu.models.serving import ServingScheduler

    class InOrder(ServingScheduler):
        def _ends_known(self):
            return False

    cfg, params = tiny
    sched = InOrder(
        params, cfg, slots=2, n_inner=4, prompt_chunk=8, max_prompt=32,
        quantize_kv=True, page_tokens=4,
    )
    reqs = _submit_work(sched, cfg.vocab)
    state = []
    while sched.pending or sched.active:
        n_free = sched._slot_req.count(None)
        begin = {
            "tick": sched.tick_count + 1, "queue": sched.pending,
            "admitting": len(sched._admitting), "free": n_free,
            "decoding": sched.S - n_free - len(sched._admitting),
            "kernel": int(sched.use_kernel),
        }
        before = [len(r.tokens) for r in reqs]
        first = {i for i, r in enumerate(reqs) if not r.tokens}
        retired = sched.step()
        delivered = [len(r.tokens) - n for r, n in zip(reqs, before)]
        state.append((begin, delivered, first,
                      [reqs.index(r) for r in retired]))
    assert sched.ticks_ahead == 0
    return state, [list(r.tokens) for r in reqs]


@pytest.fixture(scope="module")
def traced(tiny, in_order, tmp_path_factory):
    """A dark paged scheduler run to the end under a profiler session:
    three requests over two slots (one prompt of two chunks, one slot
    reused). Returns (events, the schedule tick by tick as the
    scheduler that runs in order has it, the requests): what each step
    delivered and returned is checked against it here."""
    cfg, params = tiny
    sched = _sched(cfg, params)
    reqs = _submit_work(sched, cfg.vocab)
    log_dir = str(tmp_path_factory.mktemp("serving_trace"))
    state, tokens = in_order
    with _profiled(log_dir):
        for begin, delivered, first, retired in state:
            assert sched.pending or sched.active
            before = [len(r.tokens) for r in reqs]
            assert {i for i, r in enumerate(reqs) if not r.tokens} == first
            assert [reqs.index(r) for r in sched.step()] == retired
            assert [len(r.tokens) - n
                    for r, n in zip(reqs, before)] == delivered
    assert not sched.pending and not sched.active
    assert all(r.finished for r in reqs)
    assert [list(r.tokens) for r in reqs] == tokens
    assert sched.ticks_ahead == sched.tick_count - 1
    state = [
        (begin, dict(zip((r.id for r in reqs), delivered)),
         {reqs[i].id for i in first}, [reqs[i].id for i in retired])
        for begin, delivered, first, retired in state
    ]
    return _host_events(log_dir), state, reqs


def _programs_by_tick(events):
    """{tick: the ``chunks`` of its prefill programs, in order}: a
    tick's programs are dispatched in its admit phase, and what a step
    runs after its own tick's dispatch is the NEXT tick's admit phase,
    planned ahead."""
    out: dict[int, list[int]] = {}
    owner = 0
    for name, _, _, args in events:
        if name == "serving.tick":
            owner = args["tick"]
        elif name == "serving.decode_dispatch":
            owner += 1
        elif name == "serving.prefill_chunk":
            out.setdefault(owner, []).append(args["chunks"])
    return out


def _children(events, parent):
    _, a, b, _ = parent
    return [e for e in events if e is not parent and a <= e[1]
            and e[2] <= b]


def test_every_span_of_the_table_is_written_and_nested(traced):
    events, state, _ = traced
    assert {e[0] for e in events} == SPANS
    by = {n: [e for e in events if e[0] == n] for n in SPANS}
    assert len(by["serving.tick"]) == len(state)
    for name, outer in INSIDE.items():
        for e in by[name]:
            holders = [o for o in by[outer]
                       if o[1] <= e[1] and e[2] <= o[2]]
            assert len(holders) == 1, (name, outer)


def test_admit_decode_harvest_partition_the_tick(traced):
    events, _, _ = traced
    tick_ns = left_ns = 0
    for tick in (e for e in events if e[0] == "serving.tick"):
        parts = [
            e for e in _children(events, tick)
            if e[0] in ("serving.admit", "serving.decode",
                        "serving.harvest")
        ]
        names = [e[0].removeprefix("serving.") for e in parts]
        assert names in PHASE_ORDERS
        for a, b in zip(parts, parts[1:]):
            assert a[2] <= b[1]  # in order, no overlap
        tick_ns += tick[2] - tick[1]
        left_ns += (tick[2] - tick[1]) - sum(e[2] - e[1] for e in parts)
    # what the three leave is the tick's own self time
    assert 0 <= left_ns <= 0.1 * tick_ns


def test_arguments_equal_the_schedulers_own_state(traced, tiny):
    events, state, reqs = traced
    ticks = [e for e in events if e[0] == "serving.tick"]
    assert len(ticks) == len(state)
    by_tick = _programs_by_tick(events)
    for tick, (begin, delivered, first, retired) in zip(ticks, state):
        inside = _children(events, tick)
        # counted when admission is done: the chunks the tick ran and
        # the programs they ran in; the counts as the tick begins are
        # the in-order scheduler's own state before its step
        programs = by_tick.get(begin["tick"], [])
        # (two slots and prompts of up to four chunks: the scheduler
        # holds the wide prefill program, tests/test_prefill_wide.py,
        # and no prompt of this run has a chunk to spare for it)
        assert tick[3] == {**begin, "ahead": int(begin["tick"] > 1),
                           "chunks": sum(programs),
                           "chunk_programs": len(programs),
                           "wide_chunks": 0}
        harvest = [e for e in inside if e[0] == "serving.harvest"]
        decode = [e for e in inside if e[0] == "serving.decode"]
        # tokens delivered by the decode scan: all but first tokens
        firsts = {e[3]["req"] for e in inside
                  if e[0] == "serving.first_token_wait"}
        assert firsts <= first
        from_decode = sum(delivered.values()) - len(firsts)
        if harvest:
            # no request here retires at admission, so whoever got a
            # first token this tick decodes in it
            for d in decode:
                assert d[3] == {"slots": begin["decoding"] + len(firsts)}
            assert harvest[0][3] == {"tokens": from_decode,
                                     "retired": len(retired)}
        else:
            assert from_decode == 0
    by_id = {r.id: r for r in reqs}
    new = [e for e in events if e[0] == "serving.admit_new"]
    assert sorted(e[3]["req"] for e in new) == sorted(by_id)
    for e in new:
        r = by_id[e[3]["req"]]
        assert e[3]["prompt_tokens"] == r.prompt.size
        assert e[3]["chunks"] == -(-r.prompt.size // 8)
        assert e[3]["shared_pages"] == 0
        assert e[3]["slot"] in (0, 1)


def _member(args, rid):
    """A span's arguments as they concern request ``rid``, or None: a
    ``serving.prefill_chunk`` of several chunks names its members'
    ``req``, ``slot``, ``chunk`` and ``of`` in order, comma-separated."""
    ids = str(args.get("req", "")).split(",")
    if str(rid) not in ids:
        return None
    i = ids.index(str(rid))
    return {k: int(str(v).split(",")[i]) if k in ("req", "slot", "chunk",
                                                  "of") else v
            for k, v in args.items()}


def test_the_spans_of_one_request_share_its_id(traced):
    events, _, reqs = traced
    for r in reqs:
        mine = [e for e in events if _member(e[3], r.id) is not None]
        names = [e[0] for e in mine]
        chunks = -(-r.prompt.size // 8)
        assert names.count("serving.admit_new") == 1
        assert names.count("serving.prefill_chunk") == chunks
        assert names.count("serving.first_token") == 1
        assert names.count("serving.first_token_wait") == 1
        # in the order of a request's life, on one slot
        assert names[0] == "serving.admit_new"
        assert names[-1] == "serving.first_token_wait"
        mine = [_member(e[3], r.id) for e in mine]
        assert len({a["slot"] for a in mine if "slot" in a}) == 1
        cursor = [a["chunk"] for a in mine if "chunk" in a]
        assert cursor == list(range(chunks))
        assert {a["of"] for a in mine if "of" in a} == {chunks}


def test_first_token_says_what_the_hand_over_costs(traced):
    """``pages_placed``: the pages placement writes, which are the
    prompt's own (rings of 8 rows in pages of 4: two for 5 and for 11
    tokens, the second a wrapped ring, one for 3), never more than the
    slot's table row names; ``ring_gathers``: both layers, since an
    arena of 32 rows can wrap a ring of 8."""
    events, _, reqs = traced
    by_id = {r.id: r for r in reqs}
    firsts = [e for e in events if e[0] == "serving.first_token"]
    assert sorted(e[3]["req"] for e in firsts) == sorted(by_id)
    for e in firsts:
        r = by_id[e[3]["req"]]
        assert e[3]["pages_placed"] == min(-(-r.prompt.size // 4), 8 // 4)
        assert e[3]["ring_gathers"] == 2
    assert [e[3]["pages_placed"] for e in sorted(
        firsts, key=lambda e: e[3]["req"])] == [2, 2, 1]


HANDOVERS = {
    # rings no arena of 32 rows can wrap: nothing gathered
    "no_wrap": (dict(attn_window=32), 0),
    # two widths: rings of 8 rows (gathered) beside a budget of 64
    "two_widths": (dict(n_layers=3, layer_windows=(8, 8, None),
                        max_context=64), 2),
}


@pytest.mark.parametrize("name", sorted(HANDOVERS))
def test_the_hand_overs_counts_follow_the_page_tables(name, monkeypatch):
    """Each width's share of ``pages_placed`` is the entries of its
    table row that the prompt's rows reach, which the slot's row holds
    as pages of its own when the span closes."""
    from mpistragglers_jl_tpu.models import serving
    from mpistragglers_jl_tpu.models.paging import NULL_PAGE
    from mpistragglers_jl_tpu.models.transformer import (
        TransformerConfig,
        init_params,
    )

    kw, gathers = HANDOVERS[name]
    cfg = TransformerConfig(**{**dict(
        vocab=37, d_model=32, n_heads=4, n_kv_heads=2, n_layers=2,
        d_ff=64), **kw})
    sched = _sched(cfg, init_params(cfg, seed=3))
    seen = []

    class Spy:
        def __init__(self, name, **args):
            self.name, self.args = name, dict(args)

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            if self.name == "serving.first_token":
                rows = [kd.pt_host[self.args["slot"]] for kd in sched._kinds]
                seen.append((self.args, [
                    int((row != NULL_PAGE).sum()) for row in rows]))

        def set_metadata(self, **args):
            self.args.update(args)

    monkeypatch.setattr(serving, "_annotate", Spy)
    reqs = {r.id: r for r in _submit_work(sched, cfg.vocab)}
    sched.run()
    assert len(seen) == len(reqs)
    for args, held in seen:
        r = reqs[args["req"]]
        reached = [min(-(-r.prompt.size // 4), kd.max_pages)
                   for kd in sched._kinds]
        assert args["pages_placed"] == sum(reached)
        assert all(a <= b for a, b in zip(reached, held))
        assert args["ring_gathers"] == gathers


def test_the_phase_table_names_the_hand_overs_counts():
    doc = os.path.join(os.path.dirname(__file__), "..", "docs", "API.md")
    with open(doc) as f:
        row = [ln for ln in f if ln.startswith("| `serving.first_token` |")]
    assert len(row) == 1
    arguments = row[0].split("|")[3]
    assert "`pages_placed`" in arguments and "`ring_gathers`" in arguments


def test_admit_new_says_where_its_arena_came_from(traced):
    """The scheduler's first admission finds the free list empty; the
    second runs in the same tick, after the first prompt's one chunk
    gave its arena back, and the third long after."""
    events, _, _ = traced
    new = [e for e in events if e[0] == "serving.admit_new"]
    assert [e[3]["arena"] for e in new] == ["new", "reused", "reused"]


@pytest.mark.parametrize("quantize_kv", [True, False],
                         ids=["int8", "bf16"])
@pytest.mark.parametrize("page_tokens", [4, 8],
                         ids=["paged", "one_page_a_window"])
def test_a_backlog_of_one_chunk_prompts_reuses_one_arena(
        tiny, tmp_path, page_tokens, quantize_kv):
    """``arena="new"`` exactly when the free list was empty: every
    prompt is through prefill in the tick that admits it, so that is
    the scheduler's first admission and, where two prompts admitted in
    one tick share their prefill program and so are in prefill at once
    (a prompt shorter than a page registers nothing the second's plan
    could read), one more."""
    from mpistragglers_jl_tpu.models.serving import ServingScheduler

    cfg, params = tiny
    sched = ServingScheduler(
        params, cfg, slots=2, n_inner=4, prompt_chunk=8, max_prompt=32,
        quantize_kv=quantize_kv, page_tokens=page_tokens,
    )
    rng = np.random.default_rng(1)
    reqs = [
        sched.submit(rng.integers(1, cfg.vocab, size=int(n)), max_new=3)
        for n in rng.integers(1, 9, size=7)
    ]
    with _profiled(str(tmp_path)):
        sched.run()
    assert all(r.finished for r in reqs)
    new = [e for e in _host_events(str(tmp_path))
           if e[0] == "serving.admit_new"]
    assert [e[3]["req"] for e in new] == [r.id for r in reqs]
    kinds = [e[3]["arena"] for e in new]
    assert kinds[0] == "new"
    # never more arenas than prompts were in prefill at once (2 slots)
    assert len(sched._free_arenas) == kinds.count("new") <= 2


def test_prefill_chunk_says_how_many_key_rows_it_attends(tmp_path):
    """``rows_seen`` on ``serving.prefill_chunk``: the key rows the
    chunk's attention walks, from the host's own arithmetic. A prompt
    of three chunks of 256 prints 256, 512, 768; the same prompt
    again, 11 of its pages of 64 shared, prefills one chunk at ``base``
    704 and prints ``base + C``."""
    from mpistragglers_jl_tpu.models.serving import ServingScheduler
    from mpistragglers_jl_tpu.models.transformer import (
        TransformerConfig,
        init_params,
    )

    cfg = TransformerConfig(
        vocab=37, d_model=32, n_heads=4, n_kv_heads=2, n_layers=2,
        d_ff=64, attn_window=1024,
    )
    sched = ServingScheduler(
        init_params(cfg, seed=3), cfg, slots=2, n_inner=2,
        prompt_chunk=256, max_prompt=1024, quantize_kv=True,
        page_tokens=64,
    )
    prompt = np.random.default_rng(4).integers(1, cfg.vocab, size=768)
    with _profiled(str(tmp_path)):
        first = sched.submit(prompt, max_new=40)
        while not first.tokens:
            sched.step()
        second = sched.submit(prompt, max_new=2)
        sched.run()
    assert first.finished and second.finished
    chunks = [e[3] for e in _host_events(str(tmp_path))
              if e[0] == "serving.prefill_chunk"]
    assert [(c["req"], c["chunk"], c["of"], c["rows_seen"])
            for c in chunks] == [
        (first.id, 0, 3, 256), (first.id, 1, 3, 512),
        (first.id, 2, 3, 768), (second.id, 0, 1, 704 + 256),
    ]
    # against chunks x Lmax: what share of the arena is still scored
    assert sum(c["rows_seen"] for c in chunks) / (4 * 1024) < 0.61


def test_recorder_spans_are_cut_at_the_same_boundaries(tiny):
    """``spans=`` draws admit/decode/retire from the phases the
    profiler sees: each recorder span lies inside its tick's, in
    order."""
    from mpistragglers_jl_tpu.obs import SpanRecorder

    cfg, params = tiny
    rec = SpanRecorder("serving")
    sched = _sched(cfg, params, spans=rec)
    sched.submit(np.arange(1, 6, dtype=np.int32), max_new=6)
    sched.run()
    ticks = [s for s in rec.spans if s[1].startswith("tick ")]
    parts = [s for s in rec.spans if s[1] in ("admit", "decode", "retire")]
    assert len(ticks) == sched.tick_count
    for _, _, t0, dur, _ in ticks:
        mine = [s for s in parts if t0 <= s[2] and s[2] + s[3] <= t0 + dur]
        assert [s[1].replace("retire", "harvest")
                for s in mine] in PHASE_ORDERS
        for a, b in zip(mine, mine[1:]):
            assert a[2] + a[3] <= b[2]


@pytest.mark.parametrize("slots,routed", [(4, 1), (2, 0)])
def test_tick_span_says_which_route_the_tick_took(monkeypatch, slots,
                                                  routed):
    """``serving.tick`` carries ``kernel``: 1 where the scheduler
    resolved the int8 Pallas kernel (head size 128, int8 pages, enough
    slots a call), 0 where the tick gathers and takes the einsum."""
    from mpistragglers_jl_tpu.models import serving
    from mpistragglers_jl_tpu.models.transformer import (
        TransformerConfig,
        init_params,
    )

    cfg = TransformerConfig(vocab=97, d_model=256, n_heads=2,
                            n_kv_heads=1, n_layers=1, d_ff=64,
                            attn_window=32)
    seen = []

    class Span:
        def __enter__(self):
            return self

        def __exit__(self, *exc):
            pass

        def set_metadata(self, **args):
            pass

    def record(name, **args):
        seen.append((name, args))
        return Span()

    monkeypatch.setattr(serving, "_annotate", record)
    sched = serving.ServingScheduler(
        init_params(cfg, seed=3), cfg, slots=slots, n_inner=2,
        prompt_chunk=8, max_prompt=16, quantize_kv=True, page_tokens=8,
    )
    assert sched.use_kernel == bool(routed)
    sched.submit(np.arange(1, 6), max_new=3)
    sched.step()
    ticks = [args for name, args in seen if name == "serving.tick"]
    assert [t["kernel"] for t in ticks] == [routed]


def test_annotations_cost_under_a_thousandth_of_a_tick():
    """With no profiler session open: the annotations of a heavy tick
    (sixteen slots each advancing a prefill chunk, four of them
    finishing their admission), measured directly as
    test_obs.py::test_noop_overhead_under_budget measures the guards,
    against 0.1% of the serving cells' 160 ms tick."""
    from mpistragglers_jl_tpu.obs.timeline import annotate

    def heavy_tick():
        with annotate("serving.tick", tick=7, queue=3, decoding=12,
                      admitting=4, free=0, kernel=1):
            with annotate("serving.admit"):
                for s in range(16):
                    with annotate("serving.prefill_chunk", req=s,
                                  slot=s, chunk=1, of=4):
                        pass
                for s in range(4):
                    with annotate("serving.admit_new", req=s, slot=s,
                                  prompt_tokens=300) as span:
                        span.set_metadata(chunks=2, shared_pages=0,
                                          arena="reused")
                    with annotate("serving.first_token", req=s, slot=s):
                        pass
                    with annotate("serving.first_token_wait", req=s):
                        pass
            with annotate("serving.decode", slots=12):
                with annotate("serving.decode_dispatch"):
                    pass
                with annotate("serving.decode_wait"):
                    pass
            with annotate("serving.harvest") as span:
                span.set_metadata(tokens=96, retired=1)

    heavy_tick()
    best = float("inf")
    for _ in range(5):
        t0 = time.perf_counter()
        for _ in range(200):
            heavy_tick()
        best = min(best, (time.perf_counter() - t0) / 200)
    assert best <= 0.001 * 0.160, (
        f"{best * 1e6:.1f} us for the annotations of one tick"
    )


def test_annotate_takes_arguments_and_resolves_its_class_once():
    from mpistragglers_jl_tpu.obs import timeline

    with timeline.annotate("serving.tick", tick=1) as span:
        span.set_metadata(more=2)
    assert timeline._trace_annotation() is timeline._trace_annotation()
    assert timeline._trace_annotation.cache_info().hits >= 1
    quiet = timeline._NoAnnotation()
    with quiet as span:
        span.set_metadata(anything=1)


PROGRAMS = {
    "_scan": "serving_tick_paged", "_seed": "serving_seed_prefix",
    "_place": "serving_place_pages", "_copy": "serving_copy_pages",
    "_gather": "serving_gather_ring", "_extend": "serving_prefill_chunk",
    "_finish": "serving_first_token",
    "_extend_group": "serving_prefill_chunk_x2",
}


@pytest.mark.parametrize("attr,name", sorted(PROGRAMS.items()))
def test_serving_programs_have_names_of_their_own(tiny, attr, name):
    cfg, params = tiny
    assert getattr(_sched(cfg, params), attr).__name__ == name


def test_the_sharded_tick_is_named_too(tiny):
    import jax
    from jax.sharding import Mesh

    from mpistragglers_jl_tpu.models.serving import make_serving_scan

    cfg, params = tiny
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("dp", "tp"))
    assert make_serving_scan(cfg, mesh, 2).__name__ == (
        "serving_tick_sharded")


def test_the_tick_program_carries_its_scopes(tiny):
    cfg, params = tiny
    text = _sched(cfg, params).lower_tick().as_text(debug_info=True)
    assert "module @jit_serving_tick_paged" in text
    for scope in ("kv_page_gather", "kv_page_scatter", "decode_attn",
                  "decode_mlp"):
        assert scope in text, scope
