"""The serving loop that both serving kinds share
(chipbench/runners/_serve_loop.py), driven by a scripted scheduler and
a patched ``common.now``: no chip, no model. The scripted scheduler
follows the admission rules of the program's own (one FIFO queue, free
slots filled at the start of a tick, one prefill chunk per admitting
slot and tick, a slot decodes from the tick that ends its prefill,
``n_inner`` tokens a tick, retirement at ``max_new``) and asks no
clock; each ``step()`` moves the scripted clock by what the tick
costs."""

from __future__ import annotations

import ast
import json
import time
from collections import deque
from pathlib import Path

import numpy as np
import pytest

from chipbench import common, counts_moe, traffic_gen
from chipbench import run as bench
from chipbench.runners import _serve_loop

REPO = Path(__file__).resolve().parents[2]
RUN_SECONDS = 30
SLOTS, N_INNER, CHUNK = 4, 4, 16

TRAFFIC = {
    "round": 8, "rounds": 12, "warm_rounds": 2, "window_rounds": 6,
    "check_requests": 5, "trace_seconds": 1,
    "classes": [
        {"name": "short", "share": 0.75, "prompt": [4, 20, "log_uniform"],
         "output": [6, 16, "log_uniform"]},
        {"name": "long", "share": 0.25, "prompt": [30, 60, "log_uniform"],
         "output": [4, 8, "log_uniform"]},
    ],
}


def committed_mix(name):
    return json.loads(
        (REPO / "chipbench" / "traffic" / f"{name}.json").read_text())


# -- a scheduler and a clock that follow a script -------------------------


class Clock:
    """``common.now`` under the test's hand: ``cost(tick)`` seconds pass
    in each tick, times what the variant adds."""

    def __init__(self, jitter: float = 0.0, stall_at: int | None = None):
        self.t = 100.0
        self.rng = np.random.default_rng(7)
        self.jitter, self.stall_at = jitter, stall_at

    def tick(self, number: int, chunks: int, decoding: int) -> None:
        cost = 0.002 * chunks + (0.007 if decoding else 0.0) + 0.0005
        cost *= 1.0 + self.jitter * float(self.rng.uniform(-1, 1))
        if number == self.stall_at:
            cost += 2.0
        self.t += cost

    def now(self) -> float:
        return self.t


class ScriptedRequest:
    def __init__(self, prompt_len: int, max_new: int):
        self.prompt = np.zeros((prompt_len,), np.int32)
        self.max_new = max_new
        self.tokens: list[int] = []
        self.finished = False
        self.reason = None
        self.admitted_tick = None


class ScriptedScheduler:
    def __init__(self, clock: Clock, experts_hit=None):
        self.clock = clock
        self.queue: deque = deque()
        self.slot_req = [None] * SLOTS
        self.chunks_left: dict[int, int] = {}
        self.tick_count = 0
        self.experts_hit = experts_hit

    def submit(self, prompt_len: int, max_new: int) -> ScriptedRequest:
        req = ScriptedRequest(prompt_len, max_new)
        self.queue.append(req)
        return req

    @property
    def pending(self) -> int:
        return len(self.queue)

    def _chunk(self, s: int) -> None:
        self.chunks_left[s] -= 1
        if self.chunks_left[s] == 0:
            del self.chunks_left[s]
            self.slot_req[s].tokens.append(1)   # the first token

    def step(self) -> None:
        self.tick_count += 1
        chunks = len(self.chunks_left)
        for s in list(self.chunks_left):
            self._chunk(s)
        for s in range(SLOTS):
            if self.slot_req[s] is None and self.queue:
                req = self.queue.popleft()
                req.admitted_tick = self.tick_count
                self.slot_req[s] = req
                self.chunks_left[s] = -(-len(req.prompt) // CHUNK)
                self._chunk(s)
                chunks += 1
        decoding = [s for s, r in enumerate(self.slot_req)
                    if r is not None and s not in self.chunks_left]
        for s in decoding:
            req = self.slot_req[s]
            req.tokens.extend([2] * N_INNER)
            if len(req.tokens) >= req.max_new:
                del req.tokens[req.max_new:]
                req.finished, req.reason = True, "length"
                self.slot_req[s] = None
        self.clock.tick(self.tick_count, chunks, len(decoding))


KV_ROWS = {
    "serve": lambda length: min(32, length),
    "serve_moe": lambda length: counts_moe.kv_layer_rows(
        length, (16, 16, 16, None)),
}


def scripted(monkeypatch, clock, *, seconds=RUN_SECONDS, traffic=TRAFFIC,
             kind="serve"):
    """A run, a scripted scheduler and its backlog, under ``clock``."""
    monkeypatch.setattr(common, "now", clock.now)
    run = bench.Run(
        root=REPO, cell={}, traffic=traffic, seed=5,
        config={"program": {"slots": SLOTS, "n_inner": N_INNER}},
        seconds=float(seconds), run_seconds=float(RUN_SECONDS), trace=False,
        devices=[], spans=common.Spans(), t_start=clock.now(), trace_dir="",
    )
    sched = ScriptedScheduler(
        clock, experts_hit=9.0 if kind == "serve_moe" else None)
    reqs = [sched.submit(r[1], r[2])
            for r in traffic_gen.ordered_requests(traffic)]
    return run, sched, reqs


def scripted_run(monkeypatch, clock, **kw):
    """One pass of the shared loop over the scripted scheduler."""
    run, sched, reqs = scripted(monkeypatch, clock, **kw)
    served = _serve_loop.serve(run, sched, reqs,
                               KV_ROWS[kw.get("kind", "serve")])
    return run, sched, reqs, served


def what_the_window_held(run, served):
    """Everything of a window but its times."""
    gaps, gap_w = run.info["token_gaps"]
    return {
        "ticks": [(n, d) for _, n, d in run.info["ticks"]],
        "gap_tokens": list(gap_w), "n_gaps": len(gaps),
        "attempted": run.attempted, "failed": run.failed,
        "kv_rows": run.info["mean_kv_rows_per_tick"],
        "streams": [(len(p), s.tolist()) for p, s in served.streams],
    }


# -- the window holds the same ticks whatever the clock does ---------------

CLOCKS = {
    # jitter, and where in the window (as a share of its ticks; under 0
    # the warm phase) one tick stalls for two seconds
    "jitter_half_a_percent": (0.005, None),
    "one_stall_of_2s_early": (0.0, 0.1),
    "one_stall_of_2s_in_the_last_tick": (0.0, 1.0),
    "one_stall_of_2s_in_the_warm_phase": (0.0, -0.3),
    "jitter_and_stall": (0.005, 0.6),
}


def stalling_clock(variant, first_tick: int, last_tick: int) -> Clock:
    jitter, where = CLOCKS[variant]
    if where is None:
        return Clock(jitter=jitter)
    return Clock(jitter=jitter, stall_at=first_tick + round(
        where * (last_tick - first_tick)))


@pytest.mark.parametrize("kind", ["serve", "serve_moe"])
@pytest.mark.parametrize("variant", CLOCKS)
def test_two_clocks_record_the_same_window(monkeypatch, variant, kind):
    a_run, a_sched, reqs, a = scripted_run(monkeypatch, Clock(),
                                           kind=kind)
    last = a_sched.tick_count
    first = last - len(a_run.info["ticks"]) + 1
    b_run, b_sched, _, b = scripted_run(
        monkeypatch, stalling_clock(variant, first, last),
        kind=kind)
    assert what_the_window_held(a_run, a) == what_the_window_held(b_run, b)
    assert a_sched.tick_count == b_sched.tick_count
    a_len = a_run.window[1] - a_run.window[0]
    b_len = b_run.window[1] - b_run.window[0]
    where = CLOCKS[variant][1]
    if where is None:       # the clocks did differ, by jitter alone
        assert a_len != b_len and b_len == pytest.approx(a_len, rel=0.01)
    elif where < 0:         # a stall before the window is set-up's
        assert b_len == pytest.approx(a_len, rel=0.01)
        assert (b_run.end_to_end["setup_s"]
                - a_run.end_to_end["setup_s"]) == pytest.approx(2.0, abs=0.05)
    else:
        assert b_len - a_len == pytest.approx(2.0, abs=0.05)
    assert a_run.attempted > 0 and a_run.failed == 0
    assert (a.experts_hit == 9.0) == (kind == "serve_moe")


def test_a_stall_moves_the_tail_and_not_what_it_is_the_tail_of(monkeypatch):
    a, sched, *_ = scripted_run(monkeypatch, Clock())
    b, *_ = scripted_run(monkeypatch,
                         Clock(stall_at=sched.tick_count - 5))
    assert sum(a.info["token_gaps"][1]) == sum(b.info["token_gaps"][1])
    assert max(b.info["token_gaps"][0]) > 10 * max(a.info["token_gaps"][0])
    assert a.end_to_end["serve_tok_s"] > b.end_to_end["serve_tok_s"] > 0


# -- where the window opens and closes -------------------------------------


@pytest.mark.parametrize("seconds,rounds", [(30, 6), (10, 2), (5, 1), (1, 1)])
def test_the_closing_tick_admits_the_marked_request(monkeypatch, seconds,
                                                    rounds):
    run, sched, reqs, _ = scripted_run(monkeypatch, Clock(),
                                       seconds=seconds)
    size, warm = TRAFFIC["round"], TRAFFIC["warm_rounds"]
    n_open, n_close = warm * size, (warm + rounds) * size
    assert _serve_loop.schedule_marks(TRAFFIC, seconds, RUN_SECONDS) == (
        rounds, n_open, n_close)
    # the last tick is the one that admitted request n_close, and the
    # first of the window the one after request n_open's
    assert reqs[n_close].admitted_tick == sched.tick_count
    assert reqs[n_close + 1].admitted_tick is None or (
        reqs[n_close + 1].admitted_tick == sched.tick_count)
    assert len(run.info["ticks"]) == (
        reqs[n_close].admitted_tick - reqs[n_open].admitted_tick)
    # R rounds of requests were admitted inside it
    admitted = [r for r in reqs if r.admitted_tick is not None
                and r.admitted_tick > reqs[n_open].admitted_tick]
    assert len(admitted) >= rounds * size - SLOTS
    assert sched.pending > 0


@pytest.mark.parametrize("mix,seconds,rounds", [
    ("chat_backlog", 30, 10), ("chat_backlog", 6, 2), ("chat_backlog", 1, 1),
    ("mixed_backlog", 30, 12), ("mixed_backlog", 6, 2),
    ("mixed_backlog", 1, 1),
])
def test_rounds_in_a_window_follow_the_seconds_asked(mix, seconds, rounds):
    traffic = committed_mix(mix)
    manifest = json.loads((REPO / "BENCHMARK.json").read_text())
    assert _serve_loop.window_rounds(
        traffic, seconds, manifest["run_seconds"]) == rounds
    R, n_open, n_close = _serve_loop.schedule_marks(
        traffic, seconds, manifest["run_seconds"])
    assert (R, n_open) == (rounds, 2 * 16)
    assert n_close == (2 + rounds) * 16 < 16 * traffic["rounds"]


@pytest.mark.parametrize("rounds,seconds", [(9, 30), (8, 30), (3, 30),
                                            (4, 10), (13, 90)])
def test_a_traffic_file_with_too_few_rounds_is_refused_before_a_tick(
        monkeypatch, rounds, seconds):
    run, sched, reqs = scripted(monkeypatch, Clock(), seconds=seconds,
                                traffic={**TRAFFIC, "rounds": rounds})
    with pytest.raises(ValueError, match="rounds"):
        _serve_loop.serve(run, sched, reqs, KV_ROWS["serve"])
    assert sched.tick_count == 0


def test_just_enough_rounds_are_taken(monkeypatch):
    enough = {**TRAFFIC, "rounds": 2 + 6 + 2}
    run, *_ = scripted_run(monkeypatch, Clock(), traffic=enough)
    assert run.attempted > 0


# -- what a run prints and leaves for the readers --------------------------


def test_the_lines_of_a_run_and_the_keys_the_readers_use(monkeypatch,
                                                         capsys):
    run, sched, _, served = scripted_run(monkeypatch, Clock(),
                                         kind="serve_moe")
    out = capsys.readouterr().out.splitlines()
    ticks = run.info["ticks"]
    window = next(ln for ln in out if ln.startswith("note window "))
    assert window.split()[:6] == [
        "note", "window", "rounds", "6", "ticks", str(len(ticks))]
    assert float(window.split()[7]) == pytest.approx(
        run.window[1] - run.window[0], abs=1e-3)
    note = next(ln for ln in out if ln.startswith("note ticks "))
    words = note.split()
    assert words[2] == str(len(ticks))
    assert int(words[4]) == sum(n for _, n, _ in ticks[1:])
    assert int(words[6]) == run.attempted
    assert int(words[8]) == sum(run.info["token_gaps"][1])
    assert "experts_hit_mean 9.00" in note
    series = next(ln for ln in out if ln.startswith("series tick_ms "))
    assert len(series.split(" ", 2)[2].split(",")) == len(ticks) - 1
    assert {"ticks", "slots", "n_inner", "token_gaps",
            "mean_kv_rows_per_tick", "kv_rows_by_tick"} <= set(run.info)
    # the rows the decoding slots attend, tick by tick: what the mean
    # is the mean of (``attn_rows_hbm_pct`` reads the traced ticks')
    rows = run.info["kv_rows_by_tick"]
    assert len(rows) == len(ticks) and any(rows)
    assert run.info["mean_kv_rows_per_tick"] == pytest.approx(
        sum(rows) / len(rows))
    assert set(run.end_to_end) == {"setup_s", "serve_tok_s", "itl_p95_ms"}
    # the rate is the whole window's: tokens after the first tick
    # boundary over the time to the last
    t_first, t_last = ticks[0][0], ticks[-1][0]
    assert run.end_to_end["serve_tok_s"] == pytest.approx(
        sum(n for _, n, _ in ticks[1:]) / (t_last - t_first))
    assert run.end_to_end["itl_p95_ms"] == pytest.approx(
        1e3 * common.weighted_percentile(*run.info["token_gaps"], 95.0))
    # the sample for the reference holds the longest finished stream
    assert 0 < len(served.streams) <= TRAFFIC["check_requests"]
    assert all(len(s) > 0 for _, s in served.streams)


# -- both kinds go through the one loop ------------------------------------


@pytest.mark.parametrize("name", ["serve", "serve_moe"])
def test_a_serving_runner_holds_no_loop_of_its_own(name):
    source = (REPO / "chipbench" / "runners" / f"{name}.py").read_text()
    tree = ast.parse(source)
    assert not [n for n in ast.walk(tree) if isinstance(n, ast.While)]
    names = {n.id for n in ast.walk(tree) if isinstance(n, ast.Name)}
    attrs = {n.attr for n in ast.walk(tree) if isinstance(n, ast.Attribute)}
    assert "after_tick" not in names | attrs
    for word in ("weighted_percentile", "seconds", "now", "ServingScheduler"):
        assert word not in names | attrs, word
    assert {"submit_backlog", "serve", "judge"} <= attrs


def test_the_loop_asks_for_the_seconds_once():
    source = (REPO / "chipbench" / "runners" / "_serve_loop.py").read_text()
    tree = ast.parse(source)
    uses = [n for n in ast.walk(tree) if isinstance(n, ast.Attribute)
            and n.attr == "seconds"]
    assert len(uses) == 1   # where R is computed


from test_serve_moe import moe_root  # noqa: E402,F401  (the fixture)


@pytest.mark.parametrize("cell,root_fixture", [
    ("tiny_serve", "tiny_root"), ("tiny_serve_moe", "moe_root")])
def test_both_kinds_go_through_the_one_loop(request, monkeypatch, capsys,
                                            cell, root_fixture):
    root = request.getfixturevalue(root_fixture)
    calls = []
    real = _serve_loop.serve

    def spy(run, sched, reqs, kv_rows):
        calls.append(run.config["kind"])
        return real(run, sched, reqs, kv_rows)

    monkeypatch.setattr(_serve_loop, "serve", spy)
    results = [
        bench.run_cell(root, cell, seed, 0.6, False, require_chip=False,
                       t_start=time.perf_counter())
        for seed in (3, 2**31 + 4)
    ]
    assert calls == [cell.replace("tiny_", "")] * 2
    assert all(r["correct"] and r["failed"] == 0 for r in results)
    # the real scheduler, two seeds, two clocks: the same window
    assert results[0]["attempted"] == results[1]["attempted"] > 0
    notes = [ln.split(" itl_ms")[0] for ln in capsys.readouterr().out.split("\n")
             if ln.startswith("note ticks ")]
    assert len(notes) == 2 and notes[0] == notes[1]
