"""Bytes and operations of a model whose layers are gated delta-rule
mixers beside gated attention, each with experts of which a share is
held here: what a decode step reads (weights, given the held experts
that got a token; the recurrent state, read and written), and what the
chunked form of the delta rule computes for a prefill chunk. The same
yardstick rules as chipbench/counts.py; each is checked against a
hand-worked case in tests/chipbench/test_counts_gdn.py.
"""

from __future__ import annotations

from chipbench.counts_moe import (
    attention_params,
    experts_hit_bytes,
    gated_mlp_params,
)


def gdn_matrix_params(d_model: int, key_heads: int, value_heads: int,
                      key_dim: int, value_dim: int, conv: int) -> int:
    """Weights of one gated delta-rule mixer kept in the model's type:
    the q/k/v/z projection, the b/a projection, the depthwise conv's
    taps, the scale of the norm over a value head, the out-projection."""
    kw, vw = key_heads * key_dim, value_heads * value_dim
    return (d_model * (2 * kw + 2 * vw) + d_model * 2 * value_heads
            + conv * (2 * kw + vw) + value_dim + vw * d_model)


def gdn_state_bytes(*, key_heads: int, value_heads: int, key_dim: int,
                    value_dim: int, conv: int,
                    bytes_per_value: int = 2) -> tuple[int, int]:
    """``(S, conv rows)``: bytes of one request's state in ONE delta-rule
    layer: ``S`` (value heads x key dim x value dim) float32, and the
    last ``conv - 1`` rows of the q/k/v channels in the model's type."""
    chans = 2 * key_heads * key_dim + value_heads * value_dim
    return (4 * value_heads * key_dim * value_dim,
            bytes_per_value * (conv - 1) * chans)


def step_state_bytes(*, slots: int, gdn_layers: int, **state) -> int:
    """What one decode step moves of recurrent state: every slot's
    ``S`` and conv rows in every delta-rule layer, read and written."""
    return 2 * slots * gdn_layers * sum(gdn_state_bytes(**state))


def step_weight_bytes(*, d_model: int, n_heads: int, kv_heads: int,
                      head_dim: int, key_heads: int, value_heads: int,
                      key_dim: int, value_dim: int, conv: int,
                      d_expert: int, d_shared: int, router_experts: int,
                      n_layers: int, gdn_layers: int, vocab: int,
                      experts_hit: float,
                      bytes_per_weight: int = 2) -> float:
    """Bytes of weights one decode step reads once: each layer's mixer
    (attention with its gate and q/k norms, or the delta rule with its
    float32 ``A_log`` and ``dt_bias``), its two norms, the router
    (float32, over ALL experts), the shared expert with its gate and
    the ``experts_hit`` held experts that got a token; the final norm
    and the untied head. Embedding rows are left out (a row a slot)."""
    attn = attention_params(d_model, n_heads, kv_heads, head_dim) \
        + 2 * head_dim
    gdn = gdn_matrix_params(d_model, key_heads, value_heads, key_dim,
                            value_dim, conv)
    shared = gated_mlp_params(d_model, d_shared) + d_model
    return (
        bytes_per_weight * (
            (n_layers - gdn_layers) * attn + gdn_layers * gdn
            + n_layers * (2 * d_model + shared) + vocab * d_model + d_model
        )
        + gdn_layers * 4 * 2 * value_heads
        + n_layers * (
            4 * d_model * router_experts + experts_hit_bytes(
                experts_hit, d_model=d_model, d_expert=d_expert,
                bytes_per_weight=bytes_per_weight)
        )
    )


def delta_rule_chunk_flops(*, rows: int, value_heads: int, key_dim: int,
                           value_dim: int, sub: int = 64) -> int:
    """Operations of the chunked form of the delta rule on ``rows``
    rows of ONE layer (models/transformer.py ``_delta_rule_chunks``),
    a multiply-add as two: per sub-chunk of ``sub`` rows and value
    head, k.k and q.k (``2 sub^2 Dk`` each), the unit-triangular solve
    for ``Dv + Dk`` right-hand sides (``sub^2`` each), the three
    products with the carried state (``2 sub Dk Dv`` each) and the
    scores times the updates (``2 sub^2 Dv``)."""
    n_sub = -(-rows // sub)
    per = (2 * 2 * sub * sub * key_dim
           + sub * sub * (value_dim + key_dim)
           + 3 * 2 * sub * key_dim * value_dim
           + 2 * sub * sub * value_dim)
    return n_sub * value_heads * per
