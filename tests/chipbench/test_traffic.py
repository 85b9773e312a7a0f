"""The traffic generator: every seed offers the same requests, in the
same cyclic order, from another starting point. The serving mixes are
what the manifest says they are: the traffic of the cells whose
configuration is of a serving kind, on the committed benchmark and on
the guard's copy with a mix appended (tests/chipbench/_tiny.py)."""

from __future__ import annotations

import json
from collections import Counter
from pathlib import Path

import pytest

import _tiny
from chipbench import common, traffic_gen

REPO = Path(__file__).resolve().parents[2]


def mix(name, root=REPO):
    return json.loads(
        (Path(root) / "chipbench" / "traffic" / f"{name}.json").read_text())


COMMITTED = _tiny.serving_mixes(
    REPO, json.loads((REPO / "BENCHMARK.json").read_text()))
# (checkout, mix) for every serving mix either manifest names: the
# guard's copy has the committed ones and its own behind them
MIXES = [
    pytest.param(which, name, id=f"{which}-{name}")
    for which, names in (
        ("committed", COMMITTED),
        ("with_additions", COMMITTED + [_tiny.GUARD["traffic"]]))
    for name in names
]
each_mix = pytest.mark.parametrize("which,name", MIXES)


@each_mix
def test_the_traffic_is_one_round_repeated(which, name, root_of):
    t = mix(name, root_of(which))
    size = t["round"]
    reqs = traffic_gen.ordered_requests(t)
    base = traffic_gen.one_round(t)
    assert len(reqs) == size * t["rounds"]
    assert reqs[:size] == base
    for i in range(0, len(reqs), size):
        assert reqs[i:i + size] == base
    assert len(set(base)) > size // 2  # not one length, a spread


def test_every_serving_file_is_named_by_a_cell_and_every_mix_is_a_file(
        checkout):
    """No dead file: a file under ``chipbench/traffic/`` that states a
    ``round`` is some serving cell's traffic; and no cell names a mix
    that is not there."""
    manifest = json.loads((checkout / "BENCHMARK.json").read_text())
    files = sorted(
        p.stem for p in (checkout / "chipbench" / "traffic").glob("*.json")
        if "round" in json.loads(p.read_text()))
    named = _tiny.serving_mixes(checkout, manifest)
    assert files == sorted(named)
    # what the parametrised tests of this file ran on is that list
    which = "committed" if checkout == REPO else "with_additions"
    assert [p.values[1] for p in MIXES if p.values[0] == which] == named


@each_mix
def test_a_serving_file_states_its_window_in_rounds_and_outlasts_it(
        which, name, root_of):
    t = mix(name, root_of(which))
    for key in ("round", "rounds", "warm_rounds", "window_rounds"):
        assert isinstance(t[key], int) and t[key] >= 1, key
    # two rounds to spare: the slots are full when the window closes
    assert t["warm_rounds"] + t["window_rounds"] + 2 <= t["rounds"]
    assert t["check_requests"] <= t["round"] * t["window_rounds"]


WINDOW_TOKENS = 20000


@each_mix
def test_the_window_is_the_fewest_rounds_holding_20000_output_tokens(
        which, name, root_of):
    """``window_rounds`` follows from the file alone, not from any
    program's tick series: every serving cell's ``itl_p95_ms`` is the
    percentile of about as many token gaps, a thousand beyond it."""
    t = mix(name, root_of(which))
    per_round = sum(olen for _, _, olen in traffic_gen.one_round(t))
    assert t["window_rounds"] == -(-WINDOW_TOKENS // per_round)


def test_the_tiny_mix_states_its_window_too():
    import _tiny

    t = _tiny.TRAFFIC["tiny_backlog"]
    assert t["warm_rounds"] + t["window_rounds"] + 2 <= t["rounds"]


def test_every_seed_offers_the_same_lengths_and_other_contents():
    t = mix("mixed_backlog")
    reqs = traffic_gen.ordered_requests(t)[: t["round"]]
    a = traffic_gen.prompts_for(reqs, 49152, 1)
    b = traffic_gen.prompts_for(reqs, 49152, 2**31 + 12345)
    assert [len(x) for x in a] == [len(x) for x in b] == [r[1] for r in reqs]
    assert Counter(len(x) for x in a) == Counter(r[1] for r in reqs)
    assert any((x != y).any() for x, y in zip(a, b))


@each_mix
def test_a_round_holds_each_class_at_its_share_and_range(which, name,
                                                         root_of):
    t = mix(name, root_of(which))
    base = traffic_gen.one_round(t)
    count = Counter(r[0] for r in base)
    for ci, c in enumerate(t["classes"]):
        assert count[ci] == round(t["round"] * c["share"])
    for ci, plen, olen in base:
        c = t["classes"][ci]
        assert c["prompt"][0] <= plen <= c["prompt"][1]
        assert c["output"][0] <= olen <= c["output"][1]


def test_the_round_spreads_short_and_long():
    base = traffic_gen.one_round(mix("chat_backlog"))
    outs = [r[2] for r in base]
    half = len(outs) // 2
    # neither half of the round holds much more work than the other
    assert abs(sum(outs[:half]) - sum(outs[half:])) < 0.1 * sum(outs)


def test_shares_that_do_not_fill_the_round_are_refused():
    t = mix("mixed_backlog")
    # 0.75 and 0.25 of 6 requests round to 4 and 2, which is not... 6?
    # 4.5 -> 4 and 1.5 -> 2 happen to fill it; of 10 they give 8 + 2
    assert len(traffic_gen.one_round({**t, "round": 10})) == 10
    t["classes"][0]["share"] = 0.5
    with pytest.raises(ValueError):
        traffic_gen.one_round(t)


def test_quantile_points_are_mid_quantiles():
    # u = 1/8, 3/8, 5/8, 7/8 of the way from log 1 to log 256
    assert common.quantile_points(1, 256, 4, "log_uniform") == [2, 8, 32, 128]
    pts = common.quantile_points(32, 512, 4, "log_uniform")
    assert pts == sorted(pts) and 32 < pts[0] and pts[-1] < 512
    with pytest.raises(ValueError):
        common.quantile_points(0, 10, 5, "uniform")


def test_prompt_contents_follow_the_seed():
    t = mix("chat_backlog")
    reqs = traffic_gen.ordered_requests(t)[:4]
    a = traffic_gen.prompts_for(reqs, 1000, 5)
    b = traffic_gen.prompts_for(reqs, 1000, 5)
    c = traffic_gen.prompts_for(reqs, 1000, 6)
    assert all((x == y).all() for x, y in zip(a, b))
    assert any((x != y).any() for x, y in zip(a, c))
    assert [len(x) for x in a] == [r[1] for r in reqs]


def test_percentile_of_a_weighted_multiset():
    assert common.weighted_percentile([10, 20], [95, 5], 95) == 10
    assert common.weighted_percentile([10, 20], [94, 6], 95) == 20
