"""Parameters, bytes and operations of a model whose every layer holds
attention AND a Mamba-2 state-space mixer side by side over a dense
gated feed-forward (the ``falcon_h1`` block): the parameters, what a
decode step reads of weights, of recurrent state and of K/V rows, and
the operations of a prefill chunk, from the configuration's sizes
alone, whatever implements them. The same yardstick rules as
chipbench/counts.py (what the mechanism needs, not what an
implementation happens to do); each is checked against a hand-worked
case and against the runner's shapes at the published widths in
tests/chipbench/test_counts_ssm.py.
"""

from __future__ import annotations

from chipbench.counts_moe import gated_mlp_params


def attention_params(d_model: int, n_heads: int, kv_heads: int,
                     head_dim: int) -> int:
    """Weights of one attention mixer: q and the out-projection (each
    d_model x n_heads x head_dim), k and v; no gate, no bias."""
    return 2 * d_model * head_dim * (n_heads + kv_heads)


def ssm_proj_width(*, ssm_heads: int, ssm_head_dim: int, ssm_state: int,
                   ssm_groups: int) -> int:
    """Columns of the in-projection ``[z | x | B | C | dt]``."""
    return (2 * ssm_heads * ssm_head_dim + 2 * ssm_groups * ssm_state
            + ssm_heads)


def ssm_conv_channels(*, ssm_heads: int, ssm_head_dim: int, ssm_state: int,
                      ssm_groups: int) -> int:
    """Channels of the depthwise conv: ``[x | B | C]``."""
    return ssm_heads * ssm_head_dim + 2 * ssm_groups * ssm_state


def ssm_params(d_model: int, *, ssm_conv: int, **ssm) -> tuple[int, int]:
    """``(parameters kept in the model's type, float32 ones)`` of one
    state-space mixer: the in- and out-projection, the conv's taps and
    bias, the gated norm's scale; ``A_log``, ``dt_bias`` and ``D``, a
    head each."""
    wide = ssm["ssm_heads"] * ssm["ssm_head_dim"]
    chans = ssm_conv_channels(**ssm)
    return (d_model * ssm_proj_width(**ssm) + wide * d_model
            + (ssm_conv + 1) * chans + wide, 3 * ssm["ssm_heads"])


def ssm_state_bytes(*, ssm_heads: int, ssm_head_dim: int,
                    ssm_state: int) -> int:
    """One request's ``S`` in ONE layer: heads x head dim x state dim,
    float32."""
    return 4 * ssm_heads * ssm_head_dim * ssm_state


def step_state_bytes(*, slots: int, n_layers: int, ssm_heads: int,
                     ssm_head_dim: int, ssm_state: int) -> int:
    """What one decode step moves of recurrent state: every slot's
    ``S`` in every layer, read and written (the conv's three rows a
    slot, 30 KB beside 4.19 MB, are left out)."""
    return 2 * slots * n_layers * ssm_state_bytes(
        ssm_heads=ssm_heads, ssm_head_dim=ssm_head_dim, ssm_state=ssm_state)


def _ssm(sizes: dict) -> dict:
    return {k: sizes[k] for k in ("ssm_heads", "ssm_head_dim", "ssm_state",
                                  "ssm_groups")}


def layer_params(**sizes) -> tuple[int, int]:
    """``(model-type, float32)`` parameters of one layer: both mixers,
    the two norms, the gated feed-forward."""
    d = sizes["d_model"]
    typed, f32 = ssm_params(d, ssm_conv=sizes["ssm_conv"], **_ssm(sizes))
    return (attention_params(d, sizes["n_heads"], sizes["kv_heads"],
                             sizes["head_dim"])
            + typed + 2 * d + gated_mlp_params(d, sizes["d_ff"]), f32)


def model_params(**sizes) -> int:
    """Every parameter of the model: the layers, the final norm, the
    embedding and the untied head."""
    typed, f32 = layer_params(**sizes)
    return (sizes["n_layers"] * (typed + f32) + sizes["d_model"]
            + 2 * sizes["vocab"] * sizes["d_model"])


def step_weight_bytes(*, bytes_per_weight: int = 2, **sizes) -> int:
    """Bytes of weights one decode step reads once: every layer, the
    final norm and the head. Embedding rows are left out (a row a
    slot)."""
    typed, f32 = layer_params(**sizes)
    return (bytes_per_weight * (sizes["n_layers"] * typed + sizes["d_model"]
                                + sizes["vocab"] * sizes["d_model"])
            + 4 * sizes["n_layers"] * f32)


def chunk_flops(rows: int, *, ssm_chunk: int, **sizes) -> dict:
    """Operations (2 a multiply-add) of one prefill chunk of ``rows``
    rows: ``dense`` (every layer's projections and feed-forward: two a
    weight and row; the head runs once a request, not here), ``ssm``
    (the chunked recurrence in sub-chunks of ``ssm_chunk`` rows: a
    group's ``C B^T``, each head's masked product with its rows, the
    carried state read and the state's update)."""
    typed, _ = layer_params(**sizes)
    d, L = sizes["d_model"], sizes["n_layers"]
    H, P, N, G = (sizes["ssm_heads"], sizes["ssm_head_dim"],
                  sizes["ssm_state"], sizes["ssm_groups"])
    matrices = typed - 2 * d - (sizes["ssm_conv"] + 1) * ssm_conv_channels(
        **_ssm(sizes)) - H * P
    c = min(ssm_chunk, rows)
    per_sub = 2 * (G * c * c * N + H * c * c * P + 2 * H * c * N * P)
    return {"dense": 2 * rows * L * matrices,
            "ssm": L * -(-rows // c) * per_sub}
