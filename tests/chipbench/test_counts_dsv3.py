"""chipbench/counts_dsv3.py against hand-worked cases, and against the
arithmetic of the configuration it was written for (the issue's, PERF.md
section 4)."""

from __future__ import annotations

import json
from pathlib import Path

import pytest

from chipbench import counts_dsv3
from chipbench.runners import serve_dsv3

REPO = Path(__file__).resolve().parents[2]
CONFIG = json.loads(
    (REPO / "chipbench/configs/dsv3-671b-a37b-serve.json").read_text())

# d=4, 2 heads; q rank 3, kv rank 5; head parts 2 | 2, value 3; dense
# width 6; a router over 8 experts of width 2, 3 held, 1 shared; vocab 10
TINY = dict(d_model=4, n_heads=2, q_rank=3, kv_rank=5, nope=2, rope=2, v=3,
            d_ff=6, d_expert=2, router_experts=8, held_experts=3,
            shared_experts=1, n_layers=3, n_dense_layers=1, vocab=10,
            mtp_depth=1)
MIXER = (12 + 24 + 28 + 50 + 24) + (8 + 3 + 5)  # test_counts_mla.py's


def test_parameters_by_hand():
    c = counts_dsv3.parameter_counts(**TINY)
    router = 4 * 8 + 8
    assert c["dense_layer"] == MIXER + 3 * 4 * 6
    assert c["expert_layer"] == MIXER + router + 3 * 4 * (3 + 1) * 2
    # the module: an expert layer, eh_proj 8 x 4 and three norms
    assert c["mtp"] == c["expert_layer"] + 32 + 12
    assert c["total"] == (c["dense_layer"] + 2 * c["expert_layer"]
                          + c["mtp"] + 2 * 40 + 4)
    # three routers (two layers' and the module's) are float32
    assert c["bytes"] == 2 * c["total"] + 2 * 3 * router
    assert counts_dsv3.parameter_counts(**{**TINY, "mtp_depth": 0})[
        "total"] == c["total"] - c["mtp"]


@pytest.mark.parametrize("hit,mtp_hit", [(1.5, 2.0), (3.0, 0.0)])
def test_a_drafting_steps_bytes_by_hand(hit, mtp_hit):
    router = 4 * (4 * 8 + 8)
    expert = 3 * 4 * 2 * 2  # one expert's three matrices, bfloat16
    layer = lambda h: 2 * (MIXER + 3 * 4 * 2) + router + h * expert
    model = (2 * (MIXER + 3 * 4 * 6) + 2 * layer(hit)
             + 2 * (10 * 4 + 4))
    module = layer(mtp_hit) + 2 * (2 * 4 * 4 + 3 * 4) + 2 * 10 * 4
    got = counts_dsv3.draft_step_weight_bytes(
        experts_hit=hit, mtp_experts_hit=mtp_hit, **TINY)
    assert got == model + module
    # the head is read twice a drafting step and once without the drafter
    assert counts_dsv3.draft_step_weight_bytes(
        experts_hit=hit, mtp_experts_hit=mtp_hit,
        **{**TINY, "mtp_depth": 0}) == model
    assert counts_dsv3.mtp_step_bytes(mtp_experts_hit=mtp_hit,
                                      **TINY) == module


def test_the_configurations_arithmetic():
    """The issue's numbers for the cut, to the digits it gives."""
    z = serve_dsv3.sizes(CONFIG)
    c = counts_dsv3.parameter_counts(**z)
    M = 1e6
    assert counts_dsv3._mixer(**z) / M == pytest.approx(187.1, abs=0.06)
    assert 3 * 7168 * 2048 / M == pytest.approx(44.04, abs=0.005)
    assert counts_dsv3.router_params(7168, 256) / M == pytest.approx(
        1.8, abs=0.05)
    assert c["dense_layer"] / M == pytest.approx(583.5, abs=0.06)
    assert c["expert_layer"] / M == pytest.approx(937.6, abs=0.06)
    assert c["mtp"] / M == pytest.approx(1040, abs=0.6)
    assert (c["mtp"] - c["expert_layer"]) / M == pytest.approx(102.8,
                                                               abs=0.06)
    assert 2 * c["embedding"] / M == pytest.approx(231.7, abs=0.06)
    assert c["total"] / M == pytest.approx(5606, abs=1)
    # 11.21 GB at 2 bytes a parameter, and 18 MB more for the five
    # routers' float32
    assert 2 * c["total"] / 1e9 == pytest.approx(11.21, abs=0.005)
    assert c["bytes"] / 1e9 == pytest.approx(11.23, abs=0.005)
    assert c["bytes"] / 16.9e9 == pytest.approx(0.66, abs=0.01)
    # a fifth expert layer: 13.09 GB (13.11 with the routers' float32)
    more = counts_dsv3.parameter_counts(**{**z, "n_layers": 6})
    assert 2 * more["total"] / 1e9 == pytest.approx(13.09, abs=0.005)
    # a drafting step at 10.1 experts hit everywhere against the
    # drafter-off step: about 8.6 and 5.5 GB (the issue's estimate)
    on = counts_dsv3.draft_step_weight_bytes(
        experts_hit=10.1, mtp_experts_hit=10.1, **z)
    off = counts_dsv3.draft_step_weight_bytes(
        experts_hit=6.4, mtp_experts_hit=0.0, **{**z, "mtp_depth": 0})
    assert on / 1e9 == pytest.approx(8.63, abs=0.01)
    assert off / 1e9 == pytest.approx(5.53, abs=0.01)
    assert counts_dsv3.mtp_step_bytes(
        mtp_experts_hit=10.1, **z) / on == pytest.approx(0.21, abs=0.01)
    # the latent cache: 584 B a row, 6 cache layers, 16 slots x 13 pages
    from chipbench.counts_mla import latent_row_bytes

    row = latent_row_bytes(kv_rank=512, rope=64, quantized=True)
    assert row == 584
    assert row * 6 * 16 * 13 * 64 / 1e6 == pytest.approx(46.6, abs=0.1)
