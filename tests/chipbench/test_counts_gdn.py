"""chipbench/counts_gdn.py against hand-worked cases, and against the
arithmetic of the configuration it was written for."""

from __future__ import annotations

import json
from pathlib import Path

import pytest

from chipbench import counts_gdn

REPO = Path(__file__).resolve().parents[2]


def test_mixer_and_state_by_hand():
    # d=4; 1 key head of 2 serving 2 value heads of 3; 4 taps
    # q/k/v/z: 4 x (2 + 2 + 6 + 6); b/a: 4 x 4; conv: 4 x (2 + 2 + 6);
    # the norm over a value head: 3; out: 6 x 4
    assert counts_gdn.gdn_matrix_params(4, 1, 2, 2, 3, 4) == (
        64 + 16 + 40 + 3 + 24)
    state = dict(key_heads=1, value_heads=2, key_dim=2, value_dim=3, conv=4)
    # S: 2 x 2 x 3 float32; 3 rows of 10 channels in 2 bytes
    assert counts_gdn.gdn_state_bytes(**state) == (48, 60)
    # 5 slots, 3 layers, read and written
    assert counts_gdn.step_state_bytes(
        slots=5, gdn_layers=3, **state) == 2 * 5 * 3 * 108


def test_step_weight_bytes_by_hand():
    # 2 layers, the first a delta-rule layer; 2 heads of 3, 1 kv head;
    # 8 router outputs; experts of width 2, shared of width 2; vocab 10;
    # 1.5 held experts hit on average
    attn = 3 * 4 * 2 * 3 + 2 * 4 * 3 + 2 * 3      # q, gate, out, k, v, qk norms
    gdn = 64 + 16 + 40 + 3 + 24
    shared = 3 * 4 * 2 + 4                        # and its gate
    expert = 3 * 4 * 2
    want = (2 * (attn + gdn + 2 * (2 * 4 + shared) + 10 * 4 + 4)
            + 4 * 2 * 2                           # A_log, dt_bias float32
            + 2 * (4 * 4 * 8 + 1.5 * expert * 2))
    got = counts_gdn.step_weight_bytes(
        d_model=4, n_heads=2, kv_heads=1, head_dim=3, key_heads=1,
        value_heads=2, key_dim=2, value_dim=3, conv=4, d_expert=2,
        d_shared=2, router_experts=8, n_layers=2, gdn_layers=1, vocab=10,
        experts_hit=1.5)
    assert got == want


def test_delta_rule_chunk_flops_by_hand():
    # one sub-chunk of 4 rows, 1 head, Dk 2, Dv 3:
    # k.k and q.k 2 x 2 x 16 x 2 = 128; the solve 16 x 5 = 80;
    # three products with the state 3 x 2 x 4 x 6 = 144; scores x updates
    # 2 x 16 x 3 = 96
    assert counts_gdn.delta_rule_chunk_flops(
        rows=4, value_heads=1, key_dim=2, value_dim=3, sub=4) == 448
    # 9 rows are three sub-chunks of 4 (the last padded), 2 heads
    assert counts_gdn.delta_rule_chunk_flops(
        rows=9, value_heads=2, key_dim=2, value_dim=3, sub=4) == 6 * 448


def test_the_configurations_arithmetic():
    """The numbers PERF.md and the issue give for the configuration."""
    from chipbench import counts_moe
    from chipbench.runners import serve_gdn

    cfg = json.loads(
        (REPO / "chipbench/configs/q3next-80b-a3b-serve.json").read_text())
    z = serve_gdn.sizes(cfg)
    assert z["gdn_layers"] == 3 and z["n_layers"] == 4
    attn = counts_moe.attention_params(2048, 16, 2, 256)
    assert attn == 27_262_976                       # 27.26M
    gdn = counts_gdn.gdn_matrix_params(2048, 16, 32, 128, 128, 4)
    assert round(gdn / 1e6, 2) == 33.72
    expert = counts_moe.gated_mlp_params(2048, 512)
    assert expert == 3_145_728                      # 6.29 MB in bfloat16
    # this chip: 256 experts a layer, one period, the whole vocabulary
    held = 4 * 256 * expert
    rest = (attn + 3 * gdn + 4 * (expert + 2048 * 512))
    total = held + rest + 2 * 151936 * 2048
    assert round(total / 1e6) == 3989               # 7.98 GB
    s, conv = counts_gdn.gdn_state_bytes(
        **{k: z[k] for k in ("key_heads", "value_heads", "key_dim",
                             "value_dim", "conv")})
    assert (s, conv) == (2_097_152, 49_152)         # 2.10 MB and 48 KB
    assert counts_gdn.step_state_bytes(
        slots=16, gdn_layers=3, **{k: z[k] for k in (
            "key_heads", "value_heads", "key_dim", "value_dim", "conv")}
    ) == pytest.approx(0.206e9, rel=0.01)           # 0.20 GB a step
    # 69 held experts hit a layer: about 2.9 GB a step
    step = counts_gdn.step_weight_bytes(experts_hit=69.0, **z)
    assert 2.6e9 < step < 2.9e9
    # a position of the attention layer's cache: 1,040 bytes
    assert counts_moe.kv_layer_row_bytes(
        kv_heads=2, head_dim=256, quantized=True) == 1040
    # a 256-row chunk's delta rule, three layers: about 4.0 GFLOP
    flops = 3 * counts_gdn.delta_rule_chunk_flops(
        rows=256, value_heads=32, key_dim=128, value_dim=128)
    assert flops == 3 * 4 * 32 * 10_485_760
