"""Parameters and bytes of a model whose layers hold a Mamba-2
state-space mixer ALONE (state and no K/V row) or attention alone, by
its list of layer types, and behind every mixer small gated experts of
which a share is held here beside one always-on gated MLP (the
``granitemoehybrid`` block): the parameters by part, and what a decode
step reads of weights outside the experts, of the experts that got a
token, of recurrent state (read and written) and of the head, from the
configuration's sizes alone, whatever implements them. The same
yardstick rules as chipbench/counts.py (what the mechanism needs, not
what an implementation happens to do); each is checked against a
hand-worked case and against the runner's shapes at the published
widths in tests/chipbench/test_serve_ssm_moe.py.

``sizes`` everywhere is the runner's (runners/serve_ssm_moe.py
``sizes``): ``d_model``, ``n_heads``, ``kv_heads``, ``head_dim``,
``d_expert``, ``d_shared``, ``router_experts``, ``experts_held``,
``n_layers``, ``ssm_layers`` (the layers whose mixer is the state-space
one), ``vocab`` and the mixer's ``ssm_heads``, ``ssm_head_dim``,
``ssm_state``, ``ssm_groups``, ``ssm_conv``.
"""

from __future__ import annotations

from chipbench import counts_ssm
from chipbench.counts_moe import experts_hit_bytes, gated_mlp_params


def _ssm(sizes: dict) -> dict:
    return {k: sizes[k] for k in ("ssm_heads", "ssm_head_dim", "ssm_state",
                                  "ssm_groups")}


def ssm_proj_params(**sizes) -> int:
    """The in- and the out-projection of ONE state-space mixer: the two
    matrices a step reads whole (4096 x 16,768 and 8,192 x 4096 at the
    published widths: 102.2M, 204.5 MB in bfloat16)."""
    d = sizes["d_model"]
    wide = sizes["ssm_heads"] * sizes["ssm_head_dim"]
    return d * counts_ssm.ssm_proj_width(**_ssm(sizes)) + wide * d


def feed_forward_params(**sizes) -> tuple[int, int, int]:
    """``(shared MLP, router, one expert)`` of one layer's feed-forward;
    the router is float32 in the program and scores ALL the experts."""
    d = sizes["d_model"]
    return (gated_mlp_params(d, sizes["d_shared"]),
            d * sizes["router_experts"],
            gated_mlp_params(d, sizes["d_expert"]))


def layer_params(mixer: str, **sizes) -> tuple[int, int]:
    """``(model-type, float32)`` parameters of one layer OUTSIDE its
    experts: the mixer (``"ssm"``: in- and out-projection, conv, gated
    norm's scale, and float32 ``A_log``, ``dt_bias``, ``D``;
    ``"attn"``: q, k, v and the out-projection), the two norms, the
    shared MLP; the router (float32)."""
    d = sizes["d_model"]
    shared, router, _ = feed_forward_params(**sizes)
    if mixer == "ssm":
        typed, f32 = counts_ssm.ssm_params(
            d, ssm_conv=sizes["ssm_conv"], **_ssm(sizes))
    else:
        typed, f32 = counts_ssm.attention_params(
            d, sizes["n_heads"], sizes["kv_heads"], sizes["head_dim"]), 0
    return typed + 2 * d + shared, f32 + router


def model_params(**sizes) -> dict:
    """Every parameter held on this chip, by part: ``ssm_layers`` and
    ``attn_layers`` (outside their experts), ``experts`` (the held ones
    of every layer), ``embedding`` (the tied head is the same array),
    ``final_norm``, and ``total``."""
    n_ssm = sizes["ssm_layers"]
    n_attn = sizes["n_layers"] - n_ssm
    out = {
        "ssm_layers": n_ssm * sum(layer_params("ssm", **sizes)),
        "attn_layers": n_attn * sum(layer_params("attn", **sizes)),
        "experts": sizes["n_layers"] * sizes["experts_held"]
        * feed_forward_params(**sizes)[2],
        "embedding": sizes["vocab"] * sizes["d_model"],
        "final_norm": sizes["d_model"],
    }
    out["total"] = sum(out.values())
    return out


def step_bytes(*, experts_hit: float, slots: int,
               bytes_per_weight: int = 2, **sizes) -> dict:
    """What one decode step moves, by part, K/V rows aside (the
    readers add them from the requests' lengths): ``outside_experts``
    (every layer's mixer, norms, shared MLP and float32 router and
    scalars, read once), ``experts`` (the ``experts_hit`` held experts
    of each layer that got a token), ``state`` (every slot's ``S`` in
    every state-space layer, read and written), ``head`` (the tied
    embedding as the head, and the final norm) and ``ssm_proj`` (the
    part of ``outside_experts`` that is the state-space layers' in- and
    out-projection). Embedding rows are left out (a row a slot)."""
    n_ssm = sizes["ssm_layers"]
    n_attn = sizes["n_layers"] - n_ssm
    outside = 0
    for n, mixer in ((n_ssm, "ssm"), (n_attn, "attn")):
        typed, f32 = layer_params(mixer, **sizes)
        outside += n * (bytes_per_weight * typed + 4 * f32)
    return {
        "outside_experts": outside,
        "experts": sizes["n_layers"] * experts_hit_bytes(
            experts_hit, d_model=sizes["d_model"],
            d_expert=sizes["d_expert"], bytes_per_weight=bytes_per_weight),
        "state": counts_ssm.step_state_bytes(
            slots=slots, n_layers=n_ssm, ssm_heads=sizes["ssm_heads"],
            ssm_head_dim=sizes["ssm_head_dim"],
            ssm_state=sizes["ssm_state"]),
        "head": bytes_per_weight * (sizes["vocab"] + 1) * sizes["d_model"],
        "ssm_proj": n_ssm * bytes_per_weight * ssm_proj_params(**sizes),
    }

