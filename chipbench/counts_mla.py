"""Parameters, bytes and operations of a model whose layers are latent
attention under a residual path of several streams, over a dense
feed-forward in the leading layers and experts behind them: what the
chip holds, what a decode step reads (weights, given the experts that
got a token; the latent rows the decoding slots attend) and what the
absorbed form of latent attention computes a row. The same yardstick
rules as chipbench/counts.py; each is checked against a hand-worked
case in tests/chipbench/test_counts_mla.py.
"""

from __future__ import annotations

from chipbench.counts_moe import experts_hit_bytes, gated_mlp_params


def mla_attention_params(d_model: int, n_heads: int, q_rank: int,
                         kv_rank: int, nope: int, rope: int, v: int) -> int:
    """Matrices of one latent-attention half: q down and up, the
    latent's down-projection with the shared rotated key beside it, the
    keys' and values' up-projection, the out-projection."""
    return (d_model * q_rank + q_rank * n_heads * (nope + rope)
            + d_model * (kv_rank + rope) + kv_rank * n_heads * (nope + v)
            + n_heads * v * d_model)


def mla_norm_params(d_model: int, q_rank: int, kv_rank: int) -> int:
    """Scales of one layer: a norm before each half, the q bottleneck's
    and the latent's."""
    return 2 * d_model + q_rank + kv_rank


def hc_params(d_model: int, hc_mult: int) -> int:
    """The residual mixing of one layer, float32: for each of its two
    halves ``phi`` (n d x (2 n + n n)), three scalars and 2 n + n n
    biases."""
    n, f = hc_mult, 2 * hc_mult + hc_mult * hc_mult
    return 2 * (n * d_model * f + 3 + f)


def latent_row_bytes(*, kv_rank: int, rope: int, quantized: bool,
                     bytes_per_value: int = 2) -> int:
    """Bytes of one cached position in ONE latent layer: int8 values
    with a float32 scale for the latent and one for the rotated key,
    or plain values."""
    if quantized:
        return kv_rank + rope + 2 * 4
    return (kv_rank + rope) * bytes_per_value


def parameter_counts(*, d_model: int, n_heads: int, q_rank: int,
                     kv_rank: int, nope: int, rope: int, v: int, d_ff: int,
                     d_expert: int, n_experts: int, shared_experts: int,
                     n_layers: int, n_dense_layers: int, vocab: int,
                     hc_mult: int) -> dict:
    """``{"dense_layer", "expert_layer", "embedding", "head", "total",
    "bytes"}``: parameters a layer of each kind has, and the bytes the
    whole holds (2 a parameter; 4 for the routers with their bias and
    the residual mixing)."""
    f32 = hc_params(d_model, hc_mult)
    mixer = (mla_attention_params(d_model, n_heads, q_rank, kv_rank, nope,
                                  rope, v)
             + mla_norm_params(d_model, q_rank, kv_rank))
    router = d_model * n_experts + n_experts
    dense = mixer + f32 + gated_mlp_params(d_model, d_ff)
    expert = (mixer + f32 + router
              + gated_mlp_params(d_model, (n_experts + shared_experts)
                                 * d_expert))
    n_expert_layers = n_layers - n_dense_layers
    total = (n_dense_layers * dense + n_expert_layers * expert
             + 2 * vocab * d_model + d_model)
    four = n_layers * f32 + n_expert_layers * router
    return {"dense_layer": dense, "expert_layer": expert,
            "embedding": vocab * d_model, "head": vocab * d_model,
            "total": total, "bytes": 2 * (total - four) + 4 * four}


def step_weight_bytes(*, d_model: int, n_heads: int, q_rank: int,
                      kv_rank: int, nope: int, rope: int, v: int, d_ff: int,
                      d_expert: int, n_experts: int, shared_experts: int,
                      n_layers: int, n_dense_layers: int, vocab: int,
                      hc_mult: int, experts_hit: float,
                      bytes_per_weight: int = 2) -> float:
    """Bytes of weights one decode step reads once: every layer's
    latent attention, norms and residual mixing (float32); the dense
    layers' feed-forward; in each expert layer the router (float32,
    with its bias), the shared expert and the ``experts_hit`` experts
    that got a token; the final norm and the untied output head. The
    embedding rows of the step's tokens are left out (a row a slot)."""
    n_expert_layers = n_layers - n_dense_layers
    mixer = (mla_attention_params(d_model, n_heads, q_rank, kv_rank, nope,
                                  rope, v)
             + mla_norm_params(d_model, q_rank, kv_rank))
    return (
        bytes_per_weight * (
            n_layers * mixer
            + n_dense_layers * gated_mlp_params(d_model, d_ff)
            + n_expert_layers * gated_mlp_params(
                d_model, shared_experts * d_expert)
            + vocab * d_model + d_model
        )
        + 4 * n_layers * hc_params(d_model, hc_mult)
        + n_expert_layers * (
            4 * (d_model * n_experts + n_experts) + experts_hit_bytes(
                experts_hit, d_model=d_model, d_expert=d_expert,
                bytes_per_weight=bytes_per_weight)
        )
    )


def absorbed_row_flops(*, n_heads: int, kv_rank: int, rope: int) -> int:
    """Operations of absorbed decode on ONE cached row of one layer, a
    multiply-add as two: every head's score over the whole row and its
    probability times the row's latent part. 69,632 at the published
    sizes: 119 a byte of int8 row against the chip's ridge of 240."""
    return n_heads * (2 * (kv_rank + rope) + 2 * kv_rank)
