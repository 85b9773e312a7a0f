"""Parameters and bytes of a model whose layers are latent attention on
one residual stream over a dense feed-forward in the leading layers and
group-limited experts behind them, of which this chip holds a share,
with a multi-token-prediction module behind the model: what the chip
holds, and what a DRAFTING decode step reads (the model's layers over
two rows a slot, then the module's block, its projection and the head's
second read). The same yardstick rules as chipbench/counts.py; each is
checked against a hand-worked case and against the issue's arithmetic
in tests/chipbench/test_counts_dsv3.py.
"""

from __future__ import annotations

from chipbench.counts_mla import mla_attention_params, mla_norm_params
from chipbench.counts_moe import experts_hit_bytes, gated_mlp_params


def _mixer(*, d_model, n_heads, q_rank, kv_rank, nope, rope, v, **_) -> int:
    """One layer's latent attention with the layer's four norm scales."""
    return (mla_attention_params(d_model, n_heads, q_rank, kv_rank, nope,
                                 rope, v)
            + mla_norm_params(d_model, q_rank, kv_rank))


def router_params(d_model: int, router_experts: int) -> int:
    """The router over ALL the experts, with its selection bias (both
    float32)."""
    return d_model * router_experts + router_experts


def mtp_own_params(d_model: int) -> int:
    """What the module has beside its block: ``eh_proj`` (2 d x d), the
    two norms of its input and its final norm. The embedding and the
    head it reads are the model's."""
    return 2 * d_model * d_model + 3 * d_model


def parameter_counts(*, d_model: int, n_heads: int, q_rank: int,
                     kv_rank: int, nope: int, rope: int, v: int, d_ff: int,
                     d_expert: int, router_experts: int, held_experts: int,
                     shared_experts: int, n_layers: int, n_dense_layers: int,
                     vocab: int, mtp_depth: int) -> dict:
    """``{"dense_layer", "expert_layer", "mtp", "embedding", "head",
    "total", "bytes"}``: parameters of a layer of each kind with the
    experts HELD, of the module (its block is an expert layer), and the
    bytes the whole holds (2 a parameter; 4 for the routers with their
    bias)."""
    mixer = _mixer(**locals())
    router = router_params(d_model, router_experts)
    dense = mixer + gated_mlp_params(d_model, d_ff)
    expert = (mixer + router + gated_mlp_params(
        d_model, (held_experts + shared_experts) * d_expert))
    mtp = mtp_depth * (expert + mtp_own_params(d_model))
    n_expert_layers = n_layers - n_dense_layers
    total = (n_dense_layers * dense + n_expert_layers * expert + mtp
             + 2 * vocab * d_model + d_model)
    four = (n_expert_layers + mtp_depth) * router
    return {"dense_layer": dense, "expert_layer": expert, "mtp": mtp,
            "embedding": vocab * d_model, "head": vocab * d_model,
            "total": total, "bytes": 2 * (total - four) + 4 * four}


def expert_layer_step_bytes(experts_hit: float, *, d_model: int,
                            d_expert: int, router_experts: int,
                            shared_experts: int, mixer: int,
                            bytes_per_weight: int = 2) -> float:
    """What one expert layer's step reads: latent attention and norms,
    the router (float32), the shared expert and the ``experts_hit``
    held experts that got a row."""
    return (bytes_per_weight * (mixer + gated_mlp_params(
                d_model, shared_experts * d_expert))
            + 4 * router_params(d_model, router_experts)
            + experts_hit_bytes(experts_hit, d_model=d_model,
                                d_expert=d_expert,
                                bytes_per_weight=bytes_per_weight))


def mtp_step_bytes(*, mtp_experts_hit: float, d_model: int, vocab: int,
                   bytes_per_weight: int = 2, **sizes) -> float:
    """Bytes of weights the MODULE reads in one drafting step: its
    projection and three norms, its block (with the experts of its own
    layer that got a row) and the head, once, for the draft's logits."""
    return (bytes_per_weight * (mtp_own_params(d_model) + vocab * d_model)
            + expert_layer_step_bytes(
                mtp_experts_hit, d_model=d_model, mixer=_mixer(
                    d_model=d_model, **sizes),
                d_expert=sizes["d_expert"],
                router_experts=sizes["router_experts"],
                shared_experts=sizes["shared_experts"],
                bytes_per_weight=bytes_per_weight))


def draft_step_weight_bytes(*, experts_hit: float, mtp_experts_hit: float,
                            d_model: int, d_ff: int, n_layers: int,
                            n_dense_layers: int, vocab: int, mtp_depth: int,
                            bytes_per_weight: int = 2, **sizes) -> float:
    """Bytes of weights one decode step reads once. The model's layers
    (``experts_hit``: the mean, an expert layer of the model, of held
    experts that got one of the step's rows, two a slot when it drafts),
    the final norm and the head; with a drafter the module on top
    (:func:`mtp_step_bytes`, the head's second read in it). The
    embedding rows of the step's tokens are left out."""
    mixer = _mixer(d_model=d_model, **sizes)
    layer = expert_layer_step_bytes(
        experts_hit, d_model=d_model, mixer=mixer,
        d_expert=sizes["d_expert"], router_experts=sizes["router_experts"],
        shared_experts=sizes["shared_experts"],
        bytes_per_weight=bytes_per_weight)
    model = (bytes_per_weight * (
                 n_dense_layers * (mixer + gated_mlp_params(d_model, d_ff))
                 + vocab * d_model + d_model)
             + (n_layers - n_dense_layers) * layer)
    if not mtp_depth:
        return model
    return model + mtp_step_bytes(
        mtp_experts_hit=mtp_experts_hit, d_model=d_model, vocab=vocab,
        bytes_per_weight=bytes_per_weight, **sizes)
