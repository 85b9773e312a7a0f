"""chipbench/counts_mla.py against hand-worked cases, and against the
arithmetic of the configuration it was written for."""

from __future__ import annotations

import json
from pathlib import Path

import pytest

from chipbench import counts_mla
from chipbench.runners import serve_mla

REPO = Path(__file__).resolve().parents[2]
CONFIG = json.loads(
    (REPO / "chipbench/configs/xing4-29b-a4b-serve.json").read_text())


def test_mixer_norms_and_mixing_by_hand():
    # d=4, 2 heads; q rank 3, kv rank 5; head parts 2 | 2, value 3
    # q down 4x3, q up 3x2x4, kv down 4x(5+2), kv up 5x2x(2+3), out 2x3x4
    assert counts_mla.mla_attention_params(4, 2, 3, 5, 2, 2, 3) == (
        12 + 24 + 28 + 50 + 24)
    assert counts_mla.mla_norm_params(4, 3, 5) == 8 + 3 + 5
    # 2 streams: phi (2x4) x (2 + 2 + 4), 3 scalars, 8 biases; two halves
    assert counts_mla.hc_params(4, 2) == 2 * (64 + 3 + 8)


def test_row_bytes_and_operations_by_hand():
    assert counts_mla.latent_row_bytes(
        kv_rank=5, rope=2, quantized=True) == 5 + 2 + 8
    assert counts_mla.latent_row_bytes(
        kv_rank=5, rope=2, quantized=False) == 14
    # 2 heads: scores over 7 dims and values over 5, a multiply-add as two
    assert counts_mla.absorbed_row_flops(
        n_heads=2, kv_rank=5, rope=2) == 2 * (14 + 10)


def test_step_weight_bytes_by_hand():
    # 2 layers, the first dense (width 6); 3 experts of width 2 + 1 shared;
    # vocab 10; 1.5 experts hit on average
    mixer = (12 + 24 + 28 + 50 + 24) + (8 + 3 + 5)
    mixing = 2 * (64 + 3 + 8)
    want = (2 * (2 * mixer + 3 * 4 * 6 + 3 * 4 * 2 + 10 * 4 + 4)
            + 4 * 2 * mixing
            + 1 * (4 * (4 * 3 + 3) + 1.5 * 3 * 4 * 2 * 2))
    sizes = dict(d_model=4, n_heads=2, q_rank=3, kv_rank=5, nope=2, rope=2,
                 v=3, d_ff=6, d_expert=2, n_experts=3, shared_experts=1,
                 n_layers=2, n_dense_layers=1, vocab=10, hc_mult=2)
    assert counts_mla.step_weight_bytes(experts_hit=1.5, **sizes) == want
    counts = counts_mla.parameter_counts(**sizes)
    assert counts["dense_layer"] == mixer + mixing + 72
    assert counts["expert_layer"] == mixer + mixing + 15 + 3 * 4 * 8
    assert counts["total"] == (counts["dense_layer"]
                               + counts["expert_layer"] + 2 * 40 + 4)
    assert counts["bytes"] == 2 * counts["total"] + 2 * (2 * mixing + 15)


def test_the_configurations_arithmetic():
    """The numbers PERF.md section 4 and the issue give for the cut."""
    z = serve_mla.sizes(CONFIG)
    assert counts_mla.mla_attention_params(
        z["d_model"], z["n_heads"], z["q_rank"], z["kv_rank"], z["nope"],
        z["rope"], z["v"]) == 28_409_856
    assert counts_mla.hc_params(z["d_model"], z["hc_mult"]) == 688_182
    counts = counts_mla.parameter_counts(**z)
    assert counts["dense_layer"] == pytest.approx(128.2e6, rel=1e-3)
    assert counts["expert_layer"] == pytest.approx(744.8e6, rel=1e-3)
    assert counts["embedding"] == counts["head"] == 131072 * 3584
    assert counts["total"] == pytest.approx(4048e6, rel=1e-3)
    assert counts["bytes"] == pytest.approx(8.10e9, rel=2e-3)
    assert counts_mla.latent_row_bytes(
        kv_rank=z["kv_rank"], rope=z["rope"], quantized=True) == 584
    # 32 heads uncompressed at int8 would keep 32 x (192 + 128 + 8)
    assert 32 * (192 + 128 + 8) == 10_496
    # a step with the 41 of 64 experts that 64 pairs hit: 5.1 GB
    assert counts_mla.step_weight_bytes(
        experts_hit=41, **z) == pytest.approx(5.14e9, rel=2e-3)
    # 119 operations a byte of int8 row
    assert counts_mla.absorbed_row_flops(
        n_heads=32, kv_rank=512, rope=64) / 584 == pytest.approx(119.2, abs=0.1)
    # the cache: 16 slots x 68 pages x 64 rows x 584 B x 5 layers
    prog = CONFIG["program"]
    pages = prog["max_context"] // prog["page_tokens"]
    assert pages == 68
    assert prog["slots"] * pages * 64 * 584 * 5 == pytest.approx(203e6,
                                                                 rel=3e-3)
