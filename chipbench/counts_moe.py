"""Bytes that a decode step of a model with expert layers and layers of
more than one attention span must read, from shapes and from the count
of experts that got a token. The same yardstick rules as
chipbench/counts.py: a share divides these by a device time from the
trace and a published peak; each is checked against a hand-worked case
in tests/chipbench/test_counts_moe.py.
"""

from __future__ import annotations


def attention_params(d_model: int, n_heads: int, kv_heads: int,
                     head_dim: int) -> int:
    """Weights of one attention half with an output gate: q, gate and
    out projection (each d_model x n_heads x head_dim), k and v."""
    return (3 * d_model * n_heads * head_dim
            + 2 * d_model * kv_heads * head_dim)


def gated_mlp_params(d_model: int, width: int) -> int:
    """Gate, up and down matrix of one gated feed-forward."""
    return 3 * d_model * width


def norm_params(d_model: int, head_dim: int) -> int:
    """Scales of one layer: a norm before and after each half, and the
    q and k norms over a head."""
    return 4 * d_model + 2 * head_dim


def experts_hit_bytes(experts_hit: float, *, d_model: int, d_expert: int,
                      bytes_per_weight: int = 2) -> float:
    """What the grouped product of ONE expert layer must read in one
    step: the three matrices of every expert that got a token."""
    return experts_hit * gated_mlp_params(d_model, d_expert) * bytes_per_weight


def step_weight_bytes(*, d_model: int, n_heads: int, kv_heads: int,
                      head_dim: int, d_ff: int, d_expert: int,
                      n_experts: int, shared_experts: int, n_layers: int,
                      n_dense_layers: int, vocab: int, experts_hit: float,
                      bytes_per_weight: int = 2) -> float:
    """Bytes of weights one decode step reads once: every layer's
    attention half and norms; the dense layers' feed-forward; in each
    expert layer the router (float32, with its bias), the shared expert
    and the ``experts_hit`` experts that got a token; the final norm and
    the untied output head. The embedding rows of the step's tokens are
    left out (a row a slot)."""
    n_expert_layers = n_layers - n_dense_layers
    per_layer = (attention_params(d_model, n_heads, kv_heads, head_dim)
                 + norm_params(d_model, head_dim))
    dense = gated_mlp_params(d_model, d_ff)
    shared = gated_mlp_params(d_model, shared_experts * d_expert)
    router_bytes = 4 * (d_model * n_experts + n_experts)
    return (
        bytes_per_weight * (
            n_layers * per_layer + n_dense_layers * dense
            + n_expert_layers * shared + vocab * d_model + d_model
        )
        + n_expert_layers * (
            router_bytes + experts_hit_bytes(
                experts_hit, d_model=d_model, d_expert=d_expert,
                bytes_per_weight=bytes_per_weight)
        )
    )


def kv_layer_row_bytes(*, kv_heads: int, head_dim: int, quantized: bool,
                       bytes_per_value: int = 2) -> int:
    """Bytes of K and V of one cached position in ONE layer: int8 values
    with one float32 scale per head, or plain values."""
    per_head = head_dim + 4 if quantized else head_dim * bytes_per_value
    return 2 * kv_heads * per_head


def kv_layer_rows(length: int, windows) -> int:
    """Cached rows that one stream of ``length`` positions attends in a
    step, summed over the layers: a sliding-window layer at most its
    window, a full-attention layer (None) all of them."""
    return sum(length if w is None else min(w, length) for w in windows)

