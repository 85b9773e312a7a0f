"""Operations and bytes that the algorithms need, from shapes alone.

These are the yardstick's counts: a roofline or utilization share
divides them by a device time from the trace and by a published peak
(chipbench/peaks.json). Each is checked against a hand-worked case in
tests/chipbench/test_counts.py.

``transformer_train_flops`` is ``benchmarks/transformer_train_bench.py``'s
``model_flops_per_step`` with one correction: that function counts
attention as ``2*B*L*L*D`` whatever the window, although with
``attn_window=W < L`` only the band is computed. Here the attended
(query, key) pairs are counted.
"""

from __future__ import annotations


def attended_pairs(seq: int, window: int | None) -> int:
    """(query, key) pairs of one causal sequence in which a query sees
    itself and the ``window - 1`` positions before it."""
    if window is None or window >= seq:
        return seq * (seq + 1) // 2
    return window * (window + 1) // 2 + (seq - window) * window


def layer_matmul_params(d_model: int, n_heads: int, kv_heads: int,
                        d_ff: int) -> int:
    """Weights of one block that a token is multiplied by: q, k, v, out
    projection and the two MLP matrices."""
    head_dim = d_model // n_heads
    return (
        d_model * n_heads * head_dim          # wq
        + 2 * d_model * kv_heads * head_dim   # wk, wv
        + n_heads * head_dim * d_model        # wo
        + 2 * d_model * d_ff                  # w1, w2
    )


def attention_forward_flops(batch: int, seq: int, n_heads: int,
                            head_dim: int, window: int | None) -> int:
    """Scores and weighted values: 2*Dh each for every attended pair of
    every head."""
    return 4 * batch * n_heads * head_dim * attended_pairs(seq, window)


def transformer_train_flops(*, batch: int, seq: int, d_model: int,
                            n_heads: int, kv_heads: int, d_ff: int,
                            n_layers: int, vocab: int,
                            window: int | None) -> int:
    """Forward plus backward (twice the forward) of one step; nothing
    recomputed is counted."""
    tokens = batch * seq
    per_layer = (
        2 * tokens * layer_matmul_params(d_model, n_heads, kv_heads, d_ff)
        + attention_forward_flops(batch, seq, n_heads,
                                  d_model // n_heads, window)
    )
    forward = n_layers * per_layer + 2 * tokens * d_model * vocab
    return 3 * forward


def flash_train_flops(*, batch: int, seq: int, n_heads: int,
                      head_dim: int, n_layers: int,
                      window: int | None) -> int:
    """What the attention kernels of one step must compute: the forward
    (scores, weighted values) and twice that for the backward (dV, dP,
    dQ, dK). The scores that the backward kernels compute again are not
    counted."""
    return 3 * n_layers * attention_forward_flops(
        batch, seq, n_heads, head_dim, window
    )


def serving_weight_bytes(*, d_model: int, n_heads: int, kv_heads: int,
                         d_ff: int, n_layers: int, vocab: int,
                         bytes_per_weight: int = 2) -> int:
    """Bytes of weights one decode step reads once: every block's
    matrices and biases, and the tied embedding as the output head."""
    per_layer = (
        layer_matmul_params(d_model, n_heads, kv_heads, d_ff)
        + d_ff + 5 * d_model   # b1, b2 and the two LayerNorms
    )
    return bytes_per_weight * (
        n_layers * per_layer + vocab * d_model + 2 * d_model
    )


def kv_row_bytes(*, kv_heads: int, head_dim: int, n_layers: int,
                 quantized: bool, bytes_per_value: int = 2) -> int:
    """Bytes of K and V of one cached position over all layers: int8
    values with one float32 scale per head, or plain values."""
    per_head = head_dim + 4 if quantized else head_dim * bytes_per_value
    return 2 * kv_heads * per_head * n_layers


def decode_step_bytes(*, weight_bytes: int, kv_rows: float,
                      row_bytes: int) -> float:
    """What one decode step must read: the weights once and the cached
    rows that its active slots attend (``kv_rows``: summed over the
    slots, each at most the window)."""
    return weight_bytes + kv_rows * row_bytes

