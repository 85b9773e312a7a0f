"""chipbench/counts_moe.py against hand-worked cases, and against the
arithmetic of the configuration it was written for."""

from __future__ import annotations

import json
from pathlib import Path

from chipbench import counts_moe

REPO = Path(__file__).resolve().parents[2]


def test_layer_pieces_by_hand():
    # d=4, 2 heads of 3 (a head size of its own), 1 kv head
    assert counts_moe.attention_params(4, 2, 1, 3) == 3 * 4 * 2 * 3 + 2 * 4 * 3
    assert counts_moe.gated_mlp_params(4, 5) == 60
    assert counts_moe.norm_params(4, 3) == 22


def test_step_weight_bytes_by_hand():
    # 2 layers, the first dense (width 8), the second with 4 experts of
    # width 2 and one shared expert; vocab 10; 1.5 experts hit on average
    attn = 3 * 4 * 2 * 3 + 2 * 4 * 3          # 96
    norms = 4 * 4 + 2 * 3                     # 22
    dense = 3 * 4 * 8                         # 96
    shared = 3 * 4 * 2                        # 24
    expert = 3 * 4 * 2                        # 24
    router = 4 * (4 * 4 + 4)                  # float32 bytes: 80
    want = 2 * (2 * (attn + norms) + dense + shared + 10 * 4 + 4) \
        + router + 1.5 * expert * 2
    got = counts_moe.step_weight_bytes(
        d_model=4, n_heads=2, kv_heads=1, head_dim=3, d_ff=8, d_expert=2,
        n_experts=4, shared_experts=1, n_layers=2, n_dense_layers=1,
        vocab=10, experts_hit=1.5)
    assert got == want
    assert counts_moe.experts_hit_bytes(1.5, d_model=4, d_expert=2) == 72.0


def test_kv_rows_and_bytes_by_layer_kind():
    # int8: (head_dim + one float32 scale) for K and for V of each head
    assert counts_moe.kv_layer_row_bytes(
        kv_heads=4, head_dim=128, quantized=True) == 1056
    assert counts_moe.kv_layer_row_bytes(
        kv_heads=4, head_dim=128, quantized=False) == 2048
    windows = (2048, 2048, 2048, 2048, None)
    # under the window every layer attends everything
    assert counts_moe.kv_layer_rows(100, windows) == 500
    # over it the four window layers stop at 2048 and the full one grows
    assert counts_moe.kv_layer_rows(3000, windows) == 4 * 2048 + 3000


def test_the_configurations_arithmetic():
    """The numbers PERF.md and the issue give for the configuration."""
    from chipbench.runners import serve_moe

    cfg = json.loads(
        (REPO / "chipbench/configs/trinity-mini-serve.json").read_text())
    z = serve_moe.sizes(cfg)
    attn = counts_moe.attention_params(2048, 32, 4, 128)
    assert attn == 27_262_976
    assert counts_moe.gated_mlp_params(2048, 6144) == 37_748_736
    assert counts_moe.gated_mlp_params(2048, 1024) == 6_291_456
    # all 128 experts hit: every weight of the 5 layers but the
    # embedding (a row a slot is read, not the table), 8.48 GB with it
    whole = counts_moe.step_weight_bytes(experts_hit=128, **z)
    assert 8.47e9 < whole + 2 * 200192 * 2048 < 8.49e9
    # 81 of 128 hit (what even routing of 16 x 8 pairs gives): the step
    # reads 5.3 GB, three quarters of it experts
    step = counts_moe.step_weight_bytes(experts_hit=81, **z)
    experts = 4 * counts_moe.experts_hit_bytes(
        81, d_model=2048, d_expert=1024)
    assert 5.2e9 < step < 5.4e9
    assert 0.75 < experts / step < 0.79
