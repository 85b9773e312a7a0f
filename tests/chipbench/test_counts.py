"""The operation and byte counts against hand-worked cases."""

from __future__ import annotations

from chipbench import counts


def test_attended_pairs_by_hand():
    # seq 4, window 2: queries see 1, 2, 2, 2 keys
    assert counts.attended_pairs(4, 2) == 7
    assert counts.attended_pairs(4, None) == 10
    assert counts.attended_pairs(4, 9) == 10
    # the cell: 8192 positions, window 4096 -> three quarters of causal
    assert counts.attended_pairs(8192, 4096) == (
        4096 * 4097 // 2 + 4096 * 4096)


def test_train_flops_by_hand():
    # one layer, d=4, 2 heads of 2, 1 kv head, d_ff=8, vocab 16,
    # batch 1 x seq 4, window 2
    matmul_params = 4 * 4 + 2 * 4 * 2 + 4 * 4 + 2 * 4 * 8  # 112
    assert counts.layer_matmul_params(4, 2, 1, 8) == matmul_params
    attn = 4 * 1 * 2 * 2 * 7  # 4 * B * H * Dh * pairs
    forward = 2 * 4 * matmul_params + attn + 2 * 4 * 4 * 16
    assert counts.transformer_train_flops(
        batch=1, seq=4, d_model=4, n_heads=2, kv_heads=1, d_ff=8,
        n_layers=1, vocab=16, window=2) == 3 * forward
    # the band matters: full causal attention counts more
    assert counts.transformer_train_flops(
        batch=1, seq=4, d_model=4, n_heads=2, kv_heads=1, d_ff=8,
        n_layers=1, vocab=16, window=None) == 3 * (forward - attn + 4 * 4 * 10)
    assert counts.flash_train_flops(
        batch=1, seq=4, n_heads=2, head_dim=2, n_layers=1,
        window=2) == 3 * attn


def test_train_flops_at_the_cell():
    sizes = dict(d_model=3072, n_heads=24, kv_heads=2, d_ff=12288,
                 vocab=49152)
    f7 = counts.transformer_train_flops(
        batch=2, seq=8192, n_layers=7, window=4096, **sizes)
    # PR 22's traced run: 66.76% of 197 TFLOP/s at 712.47 ms (ledger)
    assert abs(f7 / (0.71247 * 197e12) - 0.6676) < 0.01
    full = counts.transformer_train_flops(
        batch=2, seq=8192, n_layers=7, window=None, **sizes)
    assert full > f7  # what model_flops_per_step would have counted


def test_decode_step_bytes_by_hand():
    # d=4, 2 heads, 1 kv head, d_ff=8, 1 layer, vocab 16, 2-byte weights
    per_layer = 112 + 8 + 5 * 4
    assert counts.serving_weight_bytes(
        d_model=4, n_heads=2, kv_heads=1, d_ff=8, n_layers=1,
        vocab=16) == 2 * (per_layer + 16 * 4 + 2 * 4)
    # int8 K and V of one position: 2 * heads * (Dh + 4-byte scale)
    assert counts.kv_row_bytes(kv_heads=1, head_dim=2, n_layers=1,
                               quantized=True) == 2 * (2 + 4)
    assert counts.kv_row_bytes(kv_heads=2, head_dim=128, n_layers=30,
                               quantized=True) == 2 * 2 * 132 * 30
    assert counts.kv_row_bytes(kv_heads=1, head_dim=2, n_layers=1,
                               quantized=False) == 2 * 2 * 2
    assert counts.decode_step_bytes(
        weight_bytes=1000, kv_rows=10, row_bytes=12) == 1120


def test_serving_weights_at_the_cell_are_about_six_gigabytes():
    b = counts.serving_weight_bytes(
        d_model=3072, n_heads=24, kv_heads=2, d_ff=12288, n_layers=30,
        vocab=49152)
    assert 6.0e9 < b < 6.2e9

