"""chipbench/counts_gdn_rule.py against hand-worked cases and against
the arithmetic of the configuration it was written for; the reader of
``gdn_rule_roofline_pct`` on hand-made spans and scope times."""

from __future__ import annotations

import json
import types
from pathlib import Path

import pytest

from chipbench import counts_gdn_rule as cr
from chipbench.metrics import _gdn_scopes, _program_spans as ps
from chipbench.metrics import gdn_rule_roofline_pct

REPO = Path(__file__).resolve().parents[2]
SMALL = dict(rows=8, key_heads=1, value_heads=2, key_dim=2, value_dim=3)


def test_bytes_by_hand():
    # 8 rows: q and k 2 x 8 x 1 x 2 = 32; v and o 2 x 8 x 2 x 3 = 96;
    # g and beta 2 x 8 x 2 = 32; S read and written 2 x 2 x 2 x 3 = 24;
    # float32
    assert cr.chunk_rule_bytes(**SMALL) == 4 * (32 + 96 + 32 + 24)


def test_flops_by_hand():
    # two sub-chunks of 4 rows. A key head: q.k and k.k 2 x 2 x 16 x 2
    # = 128. A value head: k.S and q.S 2 x 2 x 4 x 6 = 96; the system
    # 16 x 3 = 48; scores x updates 2 x 16 x 3 = 96; the state's update
    # 2 x 4 x 6 = 48: 288
    assert cr.chunk_rule_flops(sub=4, **SMALL) == 2 * (128 + 2 * 288)
    # 9 rows are three sub-chunks, the last padded
    assert cr.chunk_rule_flops(sub=4, **{**SMALL, "rows": 9}) == 3 * (
        128 + 2 * 288)


def shape_of(cfg):
    return dict(rows=cfg["program"]["prompt_chunk"],
                key_heads=cfg["linear_num_key_heads"],
                value_heads=cfg["linear_num_value_heads"],
                key_dim=cfg["linear_key_head_dim"],
                value_dim=cfg["linear_value_head_dim"])


@pytest.mark.parametrize("name", ["q3next-80b-a3b-serve",
                                  "q3next-80b-a3b-serve-long"])
def test_the_configurations_chunk(name):
    cfg = json.loads(
        (REPO / "chipbench/configs" / f"{name}.json").read_text())
    shape = shape_of(cfg)
    # PERF.md section 7 (PR 40): q and k 2.10 MB each, v and o 4.19 MB
    # each, S 2 x 2.10 MB: 16.78 MB; with g and beta (33 KB each) 16.84
    assert cr.chunk_rule_bytes(**shape) == 16_842_752
    # a value head's sub-chunk of 128 rows: 4.19 (its half of the key
    # head's scores) + 8.39 + 2.10 + 4.19 + 4.19 = 23.07 MFLOP
    flops = cr.chunk_rule_flops(**shape)
    assert flops == 2 * (16 * 8_388_608 + 32 * 18_874_368)
    assert flops / (2 * 32) == pytest.approx(23.07e6, rel=1e-3)
    floor_s, bound = cr.chunk_rule_floor_s(
        hbm_bytes_per_s=819e9, bf16_flops_per_s=197e12, **shape)
    # 1.476 GFLOP at a sixth of 197 TFLOP/s: 45.0 us, over the bytes'
    # 20.6 us: the MXU's passes bound it
    assert bound == "mxu" and floor_s == pytest.approx(44.97e-6, rel=1e-3)
    # at the plain bfloat16 peak the bytes would
    assert cr.chunk_rule_bytes(**shape) / 819e9 > flops / 197e12


def _run(chunks, gdn_rule_s, cfg_name="q3next-80b-a3b-serve"):
    spans = [ps.HostSpan("serving.prefill_chunk", 10 * i, 10 * i + 5,
                         {"chunks": n}) for i, n in enumerate(chunks)]
    loaded = ps.ProgramSpans((0.0, 1e9), spans, 0.0, {}, 0.0)
    return types.SimpleNamespace(
        summary=object(), trace_dir="",
        info={ps.CACHE_KEY: loaded,
              _gdn_scopes.CACHE_KEY + "_chunk": {
                  "whole": 1.0, "moves": 0.0, "runs": len(chunks),
                  "gdn_proj": 0.0, "gdn_conv": 0.0, "gdn_out": 0.0,
                  "gdn_rule": gdn_rule_s}},
        config=json.loads((REPO / "chipbench/configs"
                           / f"{cfg_name}.json").read_text()),
        peaks={"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12})


def test_the_reader_on_hand_made_spans(capsys):
    # programs of 4, 4 and 1 real chunks: 9 chunks in 3 delta layers at
    # 44.97 us each, over 2.5 ms under gdn_rule
    got = gdn_rule_roofline_pct.read(_run([4, 4, 1], 2.5e-3))
    assert got == pytest.approx(100 * 9 * 3 * 44.97e-6 / 2.5e-3, rel=1e-3)
    assert "bound mxu" in capsys.readouterr().out
    # PR 40's traced run by hand: 221 real chunks, 0.129 s
    got = gdn_rule_roofline_pct.read(_run([221], 0.129))
    assert got == pytest.approx(23.1, abs=0.1)


def test_the_reader_finds_nothing_where_there_is_nothing():
    none = types.SimpleNamespace(summary=None, info={}, config={},
                                 peaks=None)
    assert gdn_rule_roofline_pct.read(none) is None
    assert gdn_rule_roofline_pct.read(_run([], 1e-3)) is None
    assert gdn_rule_roofline_pct.read(_run([4], 0.0)) is None
    # a program whose prefill carries no gdn scope (another model)
    run = _run([4], 1e-3)
    run.info[_gdn_scopes.CACHE_KEY + "_chunk"] = None
    assert gdn_rule_roofline_pct.read(run) is None
