"""Flagship model: decoder-only transformer, SPMD over a dp x sp x tp mesh.

The reference has no model code of any kind (SURVEY §2: "the library has
no model code at all") — its workloads are conventions written by users.
This framework ships model families as first-class components; the
transformer is the flagship long-context workload, exercising every
parallel mechanism the framework provides in one train step:

* **dp** — batch data parallelism: batch sharded over ``dp``; gradient
  averaging is the ``psum`` XLA inserts when the loss mean crosses the
  axis.
* **sp** — sequence/context parallelism: activations sharded over the
  sequence axis; attention is exact ring attention
  (parallel/ring_attention.py) whose K/V blocks ride ICI via
  ``ppermute``, or Ulysses all-to-all. This is the long-context story:
  per-device activation memory is O(L / sp).
* **tp** — Megatron-style tensor parallelism: attention heads and the
  MLP hidden dimension sharded over ``tp``; one ``psum`` after the
  attention out-projection and one after the MLP down-projection.
* **ep** — expert parallelism (``n_experts > 0``): the FFN becomes a
  top-1-routed mixture of experts (models/moe.py), experts sharded
  over ``ep``, the batch sharded over ``(dp, ep)``, token routing via
  one tiled ``all_to_all`` each way. Expert hidden dims additionally
  shard over ``tp``.

Pipeline parallelism over a ``pp`` axis is a separate program shape —
see parallel/pipeline.py and :func:`make_pipeline_train_step` there.

The whole train step is a single ``shard_map`` program under ``jit`` —
collectives are explicit where they are structural (ring ppermute, tp
psum) and compiler-inserted where they are incidental (loss mean). RoPE
positions are computed from the global offset ``sp_index * L_local``, so
sequence sharding is invisible to the math.

Weight layout (TPU-first): projections keep (d_model, heads, head_dim)
so the contracted dim is leading and heads*head_dim tile the MXU lanes;
everything defaults to float32 with a ``dtype`` knob for bfloat16
compute on real chips.
"""

from __future__ import annotations

import dataclasses
import functools
from functools import lru_cache, partial, wraps
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..obs.timeline import annotate
from ..ops.delta_rule import (
    chunked_delta_rule,
    delta_rule_step,
    delta_rule_viable,
    delta_step_viable,
)
from ..ops.ssm_step import (
    lane_pack,
    ssm_state_shape,
    ssm_step,
    ssm_step_viable,
)
from ..parallel.ring_attention import (
    _flash_interpreted,
    resolve_attention_impl,
    ring_self_attention,
    ulysses_attention,
)
from .moe import (
    init_moe_layer,
    init_topk_layer,
    moe_ffn_dense,
    moe_ffn_sharded,
    moe_ffn_topk,
    moe_layer_specs,
)

__all__ = [
    "TransformerConfig",
    "init_params",
    "param_specs",
    "forward_dense",
    "forward_dense_mtp",
    "make_forward",
    "make_train_step",
    "make_optax_train_step",
    "optax_step",
    "shard_params",
    "batch_axes",
    "data_spec",
]


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab: int = 256
    d_model: int = 64
    n_heads: int = 4
    # grouped-query attention: number of K/V heads; None = n_heads (MHA),
    # 1 = MQA. Q heads h and h+1.. share kv head h // (n_heads //
    # n_kv_heads) — the grouping every kernel (reference, flash, ring,
    # Ulysses) implements natively, so K/V projections, the KV cache and
    # the ring/all_to_all K/V traffic all shrink by the group factor.
    n_kv_heads: int | None = None
    n_layers: int = 2
    d_ff: int = 256
    attn: str = "ring"  # "ring" | "ulysses" | used inside shard_map
    # per-device attention kernel: "reference" (materializing oracle) or
    # "flash" (fused Pallas kernel, ops/flash_attention.py) — applies to
    # the dense forward and to the local attention inside Ulysses
    attn_impl: str = "reference"
    # sliding-window attention (Mistral-style): each position attends
    # the previous `attn_window` positions only (None = full causal).
    # Flows through every kernel — the reference oracle, the flash
    # kernels (which SKIP blocks left of the band), ring, Ulysses —
    # and the KV-cache decode path masks the same band.
    attn_window: int | None = None
    # n_experts > 0 replaces every layer's dense MLP with a top-1-routed
    # MoE (models/moe.py) whose experts shard over an "ep" mesh axis
    n_experts: int = 0
    capacity_factor: float = 2.0
    # Switch load-balance aux-loss weight; 0 keeps the sharded loss
    # bit-identical to the dense oracle (local vs global token means
    # differ), nonzero is what real training wants
    moe_aux_coef: float = 0.0
    # remat=True wraps every transformer layer in jax.checkpoint: the
    # backward recomputes the layer's activations instead of keeping
    # them resident — the standard FLOPs-for-HBM trade for long
    # sequences / deep stacks. Same math: loss matches exactly and
    # gradients to float tolerance (rtol 1e-6, since the recomputed
    # backward may fuse/order differently — tests/test_transformer.py).
    remat: bool = False
    dtype: Any = jnp.float32
    # -- the block as data ------------------------------------------------
    # Everything below defaults to the block above (pre-LayerNorm,
    # biased tanh-GELU MLP, tied head, one window, rotary everywhere).
    # The dense forward, the incremental forward (models/decode.py) and
    # the serving tick (models/serving.py) read the same fields through
    # the same three functions (:func:`attn_qkv`, :func:`attn_merge`,
    # :func:`ffn_half`); the sharded programs take the default block
    # only (:func:`require_plain_block`).
    d_head: int | None = None       # head size; None = d_model // n_heads
    norm: str = "layernorm"         # | "rmsnorm" (a scale, no bias)
    norm_eps: float = 1e-5
    ffn: str = "gelu"               # | "swiglu" (gated, no bias)
    tie_head: bool = True           # False: params["head"] (vocab, d_model)
    qk_norm: bool = False           # norm over each head of q and k
    attn_gate: bool = False         # o * sigmoid(h @ wog) before wo
    post_norm: bool = False         # a norm after each half, too
    emb_scale: float = 1.0          # x0 = emb[tok] * emb_scale
    # per-layer attention span: an int is a sliding window, None every
    # earlier position. None for the whole field = ``attn_window`` in
    # every layer.
    layer_windows: tuple | None = None
    rope_full: bool = True          # rotary on full-attention layers
    # per-layer feed-forward: True = dropless top-k experts
    # (models/moe.py ``moe_ffn_topk``) of ``n_experts`` at width
    # ``d_expert``, ``experts_per_token`` a token, beside
    # ``shared_experts`` always-on ones; False = the dense ``ffn``.
    # None for the whole field keeps ``n_experts``'s old meaning (the
    # top-1 Switch layer in every layer).
    layer_experts: tuple | None = None
    experts_per_token: int = 1
    d_expert: int | None = None
    shared_experts: int = 0
    route_scale: float = 1.0
    # positions a full-attention layer's serving cache holds: a request
    # whose prompt and answer could pass it is refused at submit
    max_context: int | None = None
    # rotary: the base, and how many leading dims of a head rotate
    # (None = the whole head; pairs (i, i + rope_dims / 2))
    rope_theta: float = 10000.0
    rope_dims: int | None = None
    # per-layer token mixer: "attn" (softmax attention over cached K/V
    # rows) or "gdn" (gated delta rule: a causal depthwise conv
    # and a recurrence whose per-request state is one fixed block, no
    # row a token; :func:`gdn_half`). None for the whole field =
    # attention everywhere.
    layer_mixers: tuple | None = None
    gdn_key_heads: int = 0          # each serves value_heads / key_heads
    gdn_value_heads: int = 0
    gdn_key_dim: int = 128          # head sizes of q/k and of v
    gdn_value_dim: int = 128
    gdn_conv: int = 4               # taps of the depthwise causal conv
    # the dropless router's scores: "sigmoid" with a selection bias, or
    # "softmax" over all experts (no bias), the k largest renormalised
    route_score: str = "sigmoid"
    shared_gate: bool = False       # shared expert * sigmoid(h @ ws_sgate)
    # this chip's share of an expert layer: experts [lo, hi) of
    # ``n_experts`` are held here. The router scores all ``n_experts``;
    # the pairs that fall on a held expert are computed, the rest are
    # left out (what a further chip would add). None = all held.
    experts_held: tuple | None = None
    # latent attention (``layer_mixers`` value "mla"; DeepSeek-V2's
    # multi-head latent attention): queries through a rank
    # ``mla_q_rank`` bottleneck to ``n_heads`` heads of ``mla_nope_dim +
    # mla_rope_dim``; keys and values through ONE row a position, the
    # normalised latent (``mla_kv_rank``) beside one rotated key of
    # ``mla_rope_dim`` that all heads share, from which each head's
    # ``mla_nope_dim`` of key and ``mla_v_dim`` of value are read
    # (:func:`mla_project`). ``head_dim`` is then the q/k head's
    # ``mla_nope_dim + mla_rope_dim``.
    mla_q_rank: int = 0
    mla_kv_rank: int = 0
    mla_nope_dim: int = 0
    mla_rope_dim: int = 0
    mla_v_dim: int = 0
    # rotary frequencies as a table, one per rotated pair
    # (:func:`yarn_rope_table`; None = ``rope_theta ** (-i / pairs)``),
    # and the softmax scale (None = ``head_dim ** -0.5``)
    rope_table: tuple | None = None
    attn_scale: float | None = None
    # the residual path as ``hc_mult`` streams (manifold-constrained
    # hyper-connections, arXiv:2512.24880): each half reads a mix of
    # the streams and its result goes back through matrices made from
    # the token itself, the stream-to-stream one doubly stochastic by
    # ``hc_sinkhorn_iters`` Sinkhorn iterations (:func:`hc_pre`,
    # :func:`hc_post`). 1 = the one stream, ``x + half(norm(x))``.
    hc_mult: int = 1
    hc_sinkhorn_iters: int = 20
    # group-limited routing (DeepSeek-V3, arXiv:2412.19437): the
    # experts as ``route_groups`` equal groups, a group's score the sum
    # of its two largest ``s + bias``; only the ``route_topk_groups``
    # best groups' experts stand for the top-k (``moe.topk_route``).
    # 1 = no limit.
    route_groups: int = 1
    route_topk_groups: int = 1
    # multi-token prediction (the same paper, section 2.2): one more
    # block of the last layer's kind behind the model, fed ``[norm(h_i)
    # ; norm(emb(t_{i+1}))] eh_proj`` and read through the model's own
    # head after a final norm of its own: its row i predicts token
    # i + 2 (:func:`mtp_input`, :func:`mtp_logits`). The block keeps a
    # cache layer of its own behind the model's (``cache_layers``).
    # ``ServingScheduler(draft="mtp")`` drafts with it; no forward of
    # the model itself reads it.
    mtp_depth: int = 0
    # decayed linear attention (``layer_mixers`` value "la"; Lightning
    # Attention, arXiv:2401.04658): ``la_heads`` heads of
    # ``la_head_dim``, each keeping ``S_t = lam S_(t-1) + k_t^T v_t``
    # (a fixed block a request, no row a token; :func:`la_half`) with
    # ``lam`` a constant a head that the layer holds as data
    # (``la_slope``: ``lam = exp(-slope)``)
    la_heads: int = 0
    la_head_dim: int = 128
    # a selection of key blocks in the full-attention layers (InfLLM
    # v2, arXiv:2506.07900; ``sparse_block`` 0 = every row): a query
    # that sees more than ``sparse_dense_len`` rows attends the first
    # ``sparse_init_blocks`` blocks of ``sparse_block`` rows, the
    # blocks that hold its last ``sparse_window`` rows and the
    # ``sparse_topk`` best of the others, scored by the query against
    # means of ``sparse_kernel`` keys every ``sparse_stride`` rows
    # (:func:`sparse_pick`)
    sparse_block: int = 0
    sparse_topk: int = 0
    sparse_kernel: int = 0
    sparse_stride: int = 0
    sparse_init_blocks: int = 0
    sparse_window: int = 0
    sparse_dense_len: int = 0
    # a constant on each half's result before it joins the residual,
    # and one on the final norm's output before the head
    residual_scale: float = 1.0
    head_scale: float = 1.0
    # a state-space mixer BESIDE attention in one layer (``layer_mixers``
    # value "attn_ssm"; Falcon-H1's block, the mixer Mamba-2's,
    # arXiv:2405.21060): both read the layer's one normed input and
    # their results join the residual in ONE add (:func:`ssm_half`).
    # ``ssm_heads`` heads of ``ssm_head_dim``, each keeping ``S_t = a_t
    # S_(t-1) + dt_t B_t x_t^T`` (``ssm_state`` x ``ssm_head_dim``
    # float32 a request) with ``a_t`` the token's own scalar a head and
    # B, C shared by the heads of one of ``ssm_groups`` groups, behind a
    # causal depthwise conv of ``ssm_conv`` taps; ``ssm_chunk`` rows a
    # sub-chunk of the chunked form. The layer keeps K/V rows AND state.
    # The same mixer ALONE in a layer is the value "ssm" (Granite-4.0-H's
    # mamba layers): the layer keeps its state and no row a position, and
    # the mixer's result joins the residual itself (:func:`state_half`).
    ssm_heads: int = 0
    ssm_head_dim: int = 64
    ssm_state: int = 128
    ssm_groups: int = 1
    ssm_conv: int = 4
    ssm_chunk: int = 128
    # constants of the block, each applied where its source applies it
    # (1.0 = absent from the program): on the normed input of the
    # attention and of the state-space mixer, on the keys before rotary,
    # on each mixer's result, on the five spans ``[z | x | B | C | dt]``
    # of the state-space mixer's in-projection, on the gated
    # feed-forward's gate before its activation and on its result
    attn_in_scale: float = 1.0
    attn_out_scale: float = 1.0
    key_scale: float = 1.0
    ssm_in_scale: float = 1.0
    ssm_out_scale: float = 1.0
    ssm_scales: tuple = (1.0, 1.0, 1.0, 1.0, 1.0)
    ffn_gate_scale: float = 1.0
    ffn_down_scale: float = 1.0

    def __post_init__(self):
        if self.attn == "ring" and self.attn_impl == "flash":
            # ring attention accumulates block-wise itself; flash only
            # applies to the per-device full-sequence attention (dense
            # forward / inside Ulysses). Accepting the combination would
            # silently run ring without flash while the dense oracle
            # diverged to a different kernel.
            raise ValueError(
                'attn_impl="flash" requires attn="ulysses" (ring '
                "attention has no per-device full-sequence kernel)"
            )
        if self.d_head is None and self.d_model % self.n_heads != 0:
            raise ValueError(
                f"d_model {self.d_model} not divisible by n_heads "
                f"{self.n_heads}"
            )
        if self.head_dim % 2 != 0:
            raise ValueError(
                f"RoPE requires even head_dim, got {self.head_dim}"
            )
        if self.norm not in ("layernorm", "rmsnorm"):
            raise ValueError(f"unknown norm {self.norm!r}")
        if self.ffn not in ("gelu", "swiglu"):
            raise ValueError(f"unknown ffn {self.ffn!r}")
        for name in ("layer_windows", "layer_experts", "layer_mixers"):
            pat = getattr(self, name)
            if pat is not None and len(pat) != self.n_layers:
                raise ValueError(
                    f"{name} names {len(pat)} layers, n_layers is "
                    f"{self.n_layers}"
                )
        if self.layer_windows is not None and any(
            w is not None and w < 1 for w in self.layer_windows
        ):
            raise ValueError("every window in layer_windows must be >= 1")
        if self.layer_experts is not None and any(self.layer_experts):
            if not 1 <= self.experts_per_token <= self.n_experts:
                raise ValueError(
                    f"experts_per_token {self.experts_per_token} must "
                    f"lie in [1, n_experts={self.n_experts}]"
                )
        if self.attn_window is not None and self.attn_window < 1:
            raise ValueError(
                f"attn_window must be >= 1, got {self.attn_window}"
            )
        if self.rope_dims is not None and not (
            0 < self.rope_dims <= self.head_dim and self.rope_dims % 2 == 0
        ):
            raise ValueError(
                f"rope_dims {self.rope_dims} must be even and at most "
                f"head_dim {self.head_dim}"
            )
        if self.layer_mixers is not None:
            if any(m not in ("attn", "gdn", "mla", "la", "attn_ssm", "ssm")
                   for m in self.layer_mixers):
                raise ValueError(
                    f"layer_mixers holds 'attn' or 'gdn' or 'mla' or "
                    f"'la' or 'attn_ssm' or 'ssm', got {self.layer_mixers}"
                )
            if self.ssm_layers and (
                self.ssm_heads < 1 or self.ssm_groups < 1
                or self.ssm_heads % self.ssm_groups or self.ssm_conv < 1
                or self.ssm_chunk < 1 or len(self.ssm_scales) != 5
            ):
                raise ValueError(
                    "a state-space mixer needs ssm_groups >= 1 dividing "
                    "ssm_heads, ssm_conv and ssm_chunk >= 1 and five "
                    f"ssm_scales, got {self.ssm_heads} heads in "
                    f"{self.ssm_groups} groups, conv {self.ssm_conv}, "
                    f"chunk {self.ssm_chunk}, scales {self.ssm_scales}"
                )
            if "la" in self.layer_mixers and (
                self.la_heads < 1 or self.la_head_dim < 2
                or self.la_head_dim % 2
            ):
                raise ValueError(
                    "a linear-attention layer needs la_heads >= 1 heads "
                    "of an even la_head_dim, got "
                    f"{self.la_heads} and {self.la_head_dim}"
                )
            if "mla" in self.layer_mixers:
                sizes = (self.mla_q_rank, self.mla_kv_rank,
                         self.mla_nope_dim, self.mla_rope_dim,
                         self.mla_v_dim)
                if min(sizes) < 1 or self.mla_rope_dim % 2 or (
                    self.head_dim != self.mla_nope_dim + self.mla_rope_dim
                ):
                    raise ValueError(
                        "a latent-attention layer needs its five sizes "
                        "(mla_q_rank, mla_kv_rank, mla_nope_dim, an even "
                        "mla_rope_dim, mla_v_dim) and d_head = "
                        f"mla_nope_dim + mla_rope_dim, got {sizes} and "
                        f"head_dim {self.head_dim}"
                    )
                if any(w is not None for w, m in
                       zip(self.windows, self.layer_mixers) if m == "mla"):
                    raise ValueError(
                        "a latent-attention layer attends every earlier "
                        "position: it takes no window"
                    )
            if "gdn" in self.layer_mixers and (
                self.gdn_key_heads < 1
                or self.gdn_value_heads % self.gdn_key_heads != 0
                or self.gdn_value_heads < 1 or self.gdn_conv < 1
            ):
                raise ValueError(
                    "a gated delta-rule layer needs gdn_key_heads >= 1 "
                    "dividing gdn_value_heads, got "
                    f"{self.gdn_key_heads} and {self.gdn_value_heads}"
                )
        if self.sparse_block:
            b, st, ks = (self.sparse_block, self.sparse_stride,
                         self.sparse_kernel)
            if (st < 1 or b % st or ks % st or ks < st
                    or self.sparse_topk < 1 or self.sparse_init_blocks < 0
                    or self.sparse_window < 1
                    or self.sparse_dense_len < ks):
                raise ValueError(
                    "a selection of key blocks needs sparse_stride "
                    "dividing sparse_block and sparse_kernel, sparse_topk "
                    ">= 1, sparse_window >= 1 and sparse_dense_len >= "
                    f"sparse_kernel, got block {b}, stride {st}, kernel "
                    f"{ks}, topk {self.sparse_topk}, window "
                    f"{self.sparse_window}, dense_len "
                    f"{self.sparse_dense_len}"
                )
            if any(self.windows[li] is not None
                   for li in range(self.n_layers) if self.sparse(li)):
                raise ValueError(
                    "a selection of key blocks is made among every "
                    "earlier row: its attention layers take no window"
                )
            if self.latent_layers or self.mtp_depth or self.hc_mult > 1:
                raise ValueError(
                    "a selection of key blocks is written for K/V rows "
                    "under one residual stream, with no latent layer "
                    "and no multi-token-prediction module"
                )
        if self.hc_mult < 1 or self.hc_sinkhorn_iters < 1:
            raise ValueError(
                f"hc_mult {self.hc_mult} and hc_sinkhorn_iters "
                f"{self.hc_sinkhorn_iters} must be >= 1"
            )
        if self.route_score not in ("sigmoid", "softmax"):
            raise ValueError(f"unknown route_score {self.route_score!r}")
        if self.route_groups != 1 or self.route_topk_groups != 1:
            g, kg = self.route_groups, self.route_topk_groups
            if (g < 1 or self.n_experts % g or not 1 <= kg <= g
                    or self.n_experts // g < 2
                    or self.experts_per_token > kg * (self.n_experts // g)):
                raise ValueError(
                    f"route_groups {g} must divide n_experts "
                    f"{self.n_experts} into groups of at least two, and "
                    f"route_topk_groups {kg} of them must hold "
                    f"experts_per_token {self.experts_per_token} experts"
                )
        if self.mtp_depth not in (0, 1):
            raise ValueError(
                f"mtp_depth is 0 or 1 (one module, one token ahead), got "
                f"{self.mtp_depth}"
            )
        if self.mtp_depth and (self.hc_mult > 1 or self.state_layers):
            raise ValueError(
                "the multi-token-prediction module reads ONE residual "
                "stream's last block output and keeps rows a position; "
                "this configuration has "
                + ("several residual streams" if self.hc_mult > 1
                   else "layers that keep recurrent state")
            )
        if self.experts_held is not None:
            lo, hi = self.experts_held
            if not 0 <= lo < hi <= self.n_experts:
                raise ValueError(
                    f"experts_held {self.experts_held} must be a range "
                    f"inside [0, n_experts={self.n_experts})"
                )
        if self.n_kv_heads is not None and (
            self.n_kv_heads < 1 or self.n_heads % self.n_kv_heads != 0
        ):
            raise ValueError(
                f"n_kv_heads {self.n_kv_heads} must divide n_heads "
                f"{self.n_heads}"
            )

    @property
    def head_dim(self) -> int:
        if self.d_head is not None:
            return self.d_head
        return self.d_model // self.n_heads

    @property
    def windows(self) -> tuple:
        """Attention span of every layer (None = every position)."""
        if self.layer_windows is not None:
            return tuple(self.layer_windows)
        return (self.attn_window,) * self.n_layers

    @property
    def cache_layers(self) -> int:
        """Layers that keep a cache: the model's, then the
        multi-token-prediction module's block."""
        return self.n_layers + self.mtp_depth

    def _like(self, li: int) -> int:
        """A cache layer's index among the model's layers: its own, or
        for the multi-token-prediction module's block the last one's
        (the block is of the last layer's kind)."""
        return min(li, self.n_layers - 1)

    def dropless(self, li: int) -> bool:
        """Is layer ``li``'s feed-forward the dropless top-k experts?"""
        return bool(self.layer_experts is not None
                    and self.layer_experts[self._like(li)])

    def rope_at(self, li: int) -> bool:
        return self.rope_full or self.windows[li] is not None

    def mixer(self, li: int) -> str:
        """Layer ``li``'s token mixer: "attn", "gdn", "mla", "la",
        "attn_ssm" (attention and a state-space mixer side by side) or
        "ssm" (a state-space mixer alone)."""
        return ("attn" if self.layer_mixers is None
                else self.layer_mixers[self._like(li)])

    def gdn(self, li: int) -> bool:
        """Is layer ``li``'s token mixer the gated delta rule?"""
        return self.mixer(li) == "gdn"

    def state(self, li: int) -> bool:
        """Does layer ``li`` keep recurrent state, one fixed block a
        request (the gated delta rule, decayed linear attention, a
        state-space mixer, beside attention or alone)?"""
        return self.mixer(li) in ("gdn", "la", "attn_ssm", "ssm")

    def rows(self, li: int) -> bool:
        """Does layer ``li`` keep a row a position in its cache? Every
        attention does, also the one that stands beside a state-space
        mixer: that layer's cache is rows AND a state. A state-space
        mixer alone in its layer keeps a state and NO row."""
        return self.mixer(li) in ("attn", "mla", "attn_ssm")

    def ssm(self, li: int) -> bool:
        """Does layer ``li`` hold a state-space mixer BESIDE its
        attention (rows AND state; the mixer's result joins the
        attention's)? False for the mixer alone in a layer, which is a
        state layer like the others (:meth:`state` and not
        :meth:`rows`); :meth:`ssm_mixer` is true for both."""
        return self.mixer(li) == "attn_ssm"

    def ssm_mixer(self, li: int) -> bool:
        """Is a state-space mixer among layer ``li``'s token mixers,
        beside attention or alone? (Whose leaves and state the layer
        has; who joins its result to the residual is :meth:`ssm`'s
        question.)"""
        return self.mixer(li) in ("attn_ssm", "ssm")

    @property
    def ssm_layers(self) -> int:
        """Layers that hold a state-space mixer."""
        return sum(self.ssm_mixer(li) for li in range(self.n_layers))

    def sparse(self, li: int) -> bool:
        """Does layer ``li`` attend a selection of its key blocks?"""
        return bool(self.sparse_block) and self.mixer(li) == "attn"

    @property
    def counts_rows(self) -> bool:
        """Must a padded chunk be told how many of its rows are real?
        A recurrence, or a sum of keys, would swallow the padding that
        attention never reads."""
        return self.state_layers or self.sparse_layers

    @property
    def sparse_layers(self) -> bool:
        return any(self.sparse(li) for li in range(self.n_layers))

    @property
    def sparse_cells(self) -> int:
        """Pooled cells (means of ``sparse_stride`` keys) a block has."""
        return self.sparse_block // self.sparse_stride

    def mla(self, li: int) -> bool:
        """Is layer ``li``'s token mixer latent attention?"""
        return self.mixer(li) == "mla"

    def cache_heads(self, li: int) -> int:
        """Heads a position's row has in layer ``li``'s cache: the K/V
        heads, or the one row a latent layer keeps."""
        return 1 if self.mla(li) else self.kv_heads

    @property
    def latent_width(self) -> int:
        """Width of a latent layer's row: ``[latent | rotated key]``."""
        return self.mla_kv_rank + self.mla_rope_dim

    @property
    def softmax_scale(self) -> float:
        if self.attn_scale is not None:
            return self.attn_scale
        return self.head_dim ** -0.5

    def layer_like(self, li: int) -> int:
        """The first layer that is treated as ``li`` is: the same token
        mixer, window (and so rotary) and kind of feed-forward, which
        is all a forward reads of a layer here. Two such layers differ
        in their weights alone, so a program may trace one and call it
        for the other (``decode._grouped_layer``)."""
        traits = lambda j: (self.mixer(j), self.windows[j],
                            self.dropless(j))
        return next(j for j in range(li + 1) if traits(j) == traits(li))

    @property
    def state_layers(self) -> bool:
        """Does any layer keep recurrent state (no row a token)?"""
        return any(self.state(li) for li in range(self.n_layers))

    @property
    def latent_layers(self) -> bool:
        """Does any layer keep one latent row a position (no K/V heads)?"""
        return any(self.mla(li) for li in range(self.n_layers))

    @property
    def held_experts(self) -> int:
        """Experts whose weights are here (``experts_held``, or all)."""
        if self.experts_held is None:
            return self.n_experts
        return self.experts_held[1] - self.experts_held[0]

    @property
    def plain_block(self) -> bool:
        """The default block in every layer (pre-LayerNorm, GELU MLP or
        Switch experts, tied head, one attention span): what the
        sharded programs (train step, ``make_generate``,
        ``make_serving_scan``) and ``param_specs`` are written for."""
        return (
            len(set(self.windows)) == 1
            and not (self.layer_experts and any(self.layer_experts))
            and self.norm == "layernorm" and self.ffn == "gelu"
            and self.tie_head and self.d_head is None
            and not (self.qk_norm or self.attn_gate or self.post_norm)
            and self.emb_scale == 1.0 and self.rope_full
            and self.rope_dims is None and not self.state_layers
            and not self.latent_layers and self.hc_mult == 1
            and self.rope_table is None and self.attn_scale is None
            and not self.mtp_depth and not self.sparse_block
            and self.residual_scale == 1.0 and self.head_scale == 1.0
        )

    def expert_width(self) -> int:
        return self.d_ff if self.d_expert is None else self.d_expert

    @property
    def kv_heads(self) -> int:
        """Resolved K/V head count (n_heads when n_kv_heads is None)."""
        return self.n_heads if self.n_kv_heads is None else self.n_kv_heads


def init_params(cfg: TransformerConfig, seed: int = 0) -> dict:
    """Plain pytree-of-arrays parameters (replicable / shardable). The
    leaves follow the configuration's block: a LayerNorm has ``_s`` and
    ``_b``, an RMSNorm ``_s`` alone; the GELU MLP ``w1 b1 w2 b2``, the
    gated one ``w_gate w_up w_down``; a dropless expert layer
    (:func:`~.moe.init_topk_layer`) its router, experts and shared
    expert; an untied head ``params["head"]``; a multi-token-prediction
    module ``params["mtp"]`` (``mtp_depth``)."""
    rng = np.random.default_rng(seed)
    D, H, Dh, F = cfg.d_model, cfg.n_heads, cfg.head_dim, cfg.d_ff
    Hkv = cfg.kv_heads
    sd = lambda *s: jnp.asarray(
        rng.standard_normal(s) / np.sqrt(s[0]), cfg.dtype
    )

    def norm(name, width=D):
        out = {name + "_s": jnp.ones((width,), cfg.dtype)}
        if cfg.norm == "layernorm":
            out[name + "_b"] = jnp.zeros((width,), cfg.dtype)
        return out

    def one_layer(li):
        if cfg.gdn(li):
            layer = {**norm("ln1"), **init_gdn_layer(rng, cfg),
                     **norm("ln2")}
        elif cfg.mixer(li) == "la":
            layer = {**norm("ln1"), **init_la_layer(rng, cfg, li),
                     **norm("ln2")}
        elif cfg.mla(li):
            layer = {**norm("ln1"), **init_mla_layer(rng, cfg),
                     **norm("ln2")}
        elif cfg.ssm_mixer(li) and not cfg.rows(li):  # the mixer alone
            layer = {**norm("ln1"), **init_ssm_layer(rng, cfg),
                     **norm("ln2")}
        else:
            layer = {
                **norm("ln1"),
                **(init_ssm_layer(rng, cfg) if cfg.ssm(li) else {}),
                "wq": sd(D, H, Dh),
                "wk": sd(D, Hkv, Dh),
                "wv": sd(D, Hkv, Dh),
                # NB float(): an np.float64 scalar would silently
                # promote the param to f64 under jax_enable_x64
                "wo": sd(H, Dh, D) / float(np.sqrt(cfg.n_layers)),
                **norm("ln2"),
            }
            if cfg.attn_gate:
                layer["wog"] = sd(D, H, Dh)
            if cfg.qk_norm:
                layer["qn_s"] = jnp.ones((Dh,), cfg.dtype)
                layer["kn_s"] = jnp.ones((Dh,), cfg.dtype)
        if cfg.post_norm:
            layer.update(norm("ln1p"))
            layer.update(norm("ln2p"))
        if cfg.hc_mult > 1:
            layer.update(init_hc_layer(rng, cfg, li))
        if cfg.dropless(li):
            layer.update(init_topk_layer(rng, cfg))
        elif cfg.n_experts and cfg.layer_experts is None:
            layer.update(
                init_moe_layer(
                    rng, D, F, cfg.n_experts, cfg.n_layers, cfg.dtype
                )
            )
        elif cfg.ffn == "swiglu":
            layer.update(
                {
                    "w_gate": sd(D, F),
                    "w_up": sd(D, F),
                    "w_down": sd(F, D) / float(np.sqrt(cfg.n_layers)),
                }
            )
        else:
            layer.update(
                {
                    "w1": sd(D, F),
                    "b1": jnp.zeros((F,), cfg.dtype),
                    "w2": sd(F, D) / float(np.sqrt(cfg.n_layers)),
                    "b2": jnp.zeros((D,), cfg.dtype),
                }
            )
        return layer

    layers = [one_layer(li) for li in range(cfg.n_layers)]
    params = {
        "emb": jnp.asarray(
            rng.standard_normal((cfg.vocab, D)) * 0.02, cfg.dtype
        ),
        "layers": layers,
        **norm("lnf"),
    }
    if not cfg.tie_head:
        params["head"] = jnp.asarray(
            rng.standard_normal((cfg.vocab, D)) * 0.02, cfg.dtype
        )
    if cfg.mtp_depth:
        # drawn last, so the model's own leaves are what they are
        # without the module: the two norms of its input, the
        # projection of their concatenation, one more block of the last
        # layer's kind and a final norm of its own; the embedding and
        # the head it reads are the model's arrays
        params["mtp"] = {
            **norm("hn"), **norm("en"), "eh_proj": sd(2 * D, D),
            "block": one_layer(cfg.n_layers), **norm("lnf"),
        }
    return params


def require_plain_block(cfg: TransformerConfig, what: str) -> None:
    """The sharded programs are written for one kind of layer. Refuse,
    by mechanism, a configuration they cannot run."""
    if cfg.plain_block:
        return
    why = []
    if any(map(cfg.gdn, range(cfg.n_layers))):
        why.append("gated delta-rule layers (recurrent state)")
    if "la" in (cfg.layer_mixers or ()):
        why.append("decayed linear-attention layers (recurrent state)")
    if cfg.ssm_layers:
        why.append("layers that hold a state-space mixer, beside their "
                   "attention or alone (recurrent state)")
    if cfg.sparse_block:
        why.append("attention layers that read a selection of their "
                   "key blocks")
    if cfg.latent_layers:
        why.append("latent-attention layers (one row a position, no "
                   "K/V heads to shard)")
    if cfg.hc_mult > 1:
        why.append(f"a residual path of {cfg.hc_mult} streams")
    if len(set(cfg.windows)) > 1:
        why.append("layers of more than one cache width")
    if cfg.layer_experts and any(cfg.layer_experts):
        why.append("dropless top-k expert layers")
    if cfg.mtp_depth:
        why.append("a multi-token-prediction module")
    if not why:
        why.append("a block other than pre-LayerNorm / GELU MLP / "
                   "tied head at head_dim = d_model // n_heads")
    raise ValueError(
        f"{what} runs the default block in every layer; this "
        f"configuration has {' and '.join(why)}. One chip serves it "
        "through ServingScheduler, forward_dense and the dense "
        "decode functions"
    )


def _kv_tp_sharded(cfg: TransformerConfig, mesh: Mesh | None) -> bool:
    """Whether the K/V projections shard their (narrower) head dim over
    ``tp``. With GQA/MQA the kv-head count can drop below the tp degree;
    then wk/wv stay replicated and each tp member slices the one kv head
    its q-head shard reads (``_forward_local``). Requires kv_heads % tp
    == 0 or tp % kv_heads == 0 — anything else has no aligned grouping."""
    if mesh is None or "tp" not in mesh.axis_names:
        return True
    tp = mesh.shape["tp"]
    if cfg.n_heads % tp != 0:
        raise ValueError(
            f"n_heads {cfg.n_heads} must divide over tp={tp}"
        )
    if cfg.kv_heads % tp == 0:
        return True
    if tp % cfg.kv_heads == 0:
        return False
    raise ValueError(
        f"kv_heads {cfg.kv_heads} and tp={tp} need one to divide the "
        "other (grouped q-head shards must align to whole kv heads)"
    )


def param_specs(cfg: TransformerConfig, mesh: Mesh | None = None) -> dict:
    """PartitionSpecs matching :func:`init_params`: heads and d_ff over
    ``tp`` (Megatron split), everything else replicated. Pass ``mesh``
    so GQA configs whose kv_heads < tp degree fall back to replicated
    K/V projections (see :func:`_kv_tp_sharded`)."""
    require_plain_block(cfg, "param_specs (the sharded layout)")
    kv = P(None, "tp", None) if _kv_tp_sharded(cfg, mesh) else P()
    layer = {
        "ln1_s": P(), "ln1_b": P(),
        "wq": P(None, "tp", None),
        "wk": kv,
        "wv": kv,
        "wo": P("tp", None, None),
        "ln2_s": P(), "ln2_b": P(),
    }
    if cfg.n_experts:
        layer.update(moe_layer_specs())
    else:
        layer.update(
            {
                "w1": P(None, "tp"),
                "b1": P("tp"),
                "w2": P("tp", None),
                "b2": P(),
            }
        )
    return {
        "emb": P(),
        "layers": [dict(layer) for _ in range(cfg.n_layers)],
        "lnf_s": P(),
        "lnf_b": P(),
    }


def _ln(x, s, b, eps=1e-5):
    x = x.astype(jnp.float32)
    mu = x.mean(-1, keepdims=True)
    var = x.var(-1, keepdims=True)
    return ((x - mu) * jax.lax.rsqrt(var + eps)).astype(s.dtype) * s + b


def _rms(x, s, eps=1e-5):
    """RMSNorm over the last axis (a scale, no bias), in float32."""
    xf = x.astype(jnp.float32)
    ms = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(ms + eps)).astype(s.dtype) * s


def _norm(x, p, name, cfg):
    """The configuration's norm with the leaves ``<name>_s`` (and
    ``<name>_b`` for a LayerNorm) of ``p``."""
    if cfg.norm == "rmsnorm":
        return _rms(x, p[name + "_s"], cfg.norm_eps)
    return _ln(x, p[name + "_s"], p[name + "_b"], cfg.norm_eps)


def _rope_freqs(half: int, theta: float, table):
    """A rotary pair's angle a position: ``theta ** (-i / half)``, or
    ``table`` (``cfg.rope_table``: one entry a pair)."""
    if table is None:
        return 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    if len(table) != half:
        raise ValueError(
            f"rope_table has {len(table)} entries, the head rotates "
            f"{half} pairs")
    return jnp.asarray(table, jnp.float32)


def yarn_rope_table(dims: int, theta: float, factor: float,
                    original_max: int, beta_fast: float = 32.0,
                    beta_slow: float = 1.0) -> tuple:
    """YaRN's frequencies for ``dims`` rotated dims (``dims / 2``
    pairs), as ``TransformerConfig(rope_table=...)`` takes them: pair i
    of base frequency ``f_i = theta ** (-2i / dims)`` keeps ``f_i``
    where it turns more than ``beta_fast`` times inside
    ``original_max`` positions, takes ``f_i / factor`` where it turns
    fewer than ``beta_slow`` times, and a linear blend over the pairs
    between (the ramp's ends are the floor and the ceiling of the two
    correction dims, as the published implementations have them)."""
    half = dims // 2
    f = theta ** (-np.arange(half, dtype=np.float64) / half)
    turn = lambda r: (dims * np.log(original_max / (r * 2 * np.pi))
                      / (2 * np.log(theta)))
    lo = max(int(np.floor(turn(beta_fast))), 0)
    hi = min(int(np.ceil(turn(beta_slow))), dims - 1)
    ramp = np.clip((np.arange(half) - lo) / max(hi - lo, 1e-3), 0.0, 1.0)
    return tuple(float(v) for v in f * (1.0 - ramp) + f / factor * ramp)


def _rope(x, pos, theta: float = 10000.0, table=None):
    """Rotary embedding at base ``theta`` (or at ``table``'s
    frequencies); pos carries GLOBAL token positions (L,)."""
    B, L, H, Dh = x.shape
    half = Dh // 2
    freqs = _rope_freqs(half, theta, table)
    ang = pos.astype(jnp.float32)[:, None] * freqs[None, :]  # (L, half)
    cos = jnp.cos(ang)[None, :, None, :].astype(x.dtype)
    sin = jnp.sin(ang)[None, :, None, :].astype(x.dtype)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1
    )


def _rope_leading(rope, t, dims: int | None):
    """``rope`` on the ``dims`` leading dims of each head of ``t`` (None:
    the whole head); the rest pass through."""
    if dims is None or dims >= t.shape[-1]:
        return rope(t)
    return jnp.concatenate([rope(t[..., :dims]), t[..., dims:]], axis=-1)


# The block, written once. Every forward (dense, sharded, incremental,
# serving tick) is: attn_qkv -> its own attention over its own K/V
# store -> attn_merge -> ffn_half. What a forward brings of its own is
# where K/V live and how positions reach the rotary (``rope``).


def _res(a, cfg):
    """A half's result times ``cfg.residual_scale``, before it joins
    the residual."""
    if cfg.residual_scale == 1.0:
        return a
    return a * jnp.asarray(cfg.residual_scale, a.dtype)


def _scaled(a, scale: float):
    """``a`` times a constant of the block in ``a``'s own type; 1.0 is
    absent from the program."""
    if scale == 1.0:
        return a
    return a * jnp.asarray(scale, a.dtype)


def attn_qkv(x, lp, cfg, li, rope, kv_slice=None):
    """First part of layer ``li``'s attention half on (B, L, D): norm,
    projections, the q/k norms, rotary. ``rope(t)`` rotates a
    (B, L, H, Dh) tensor at the caller's positions. Returns
    ``(q, k, v, gate)``; ``gate`` (None without ``attn_gate``) goes to
    :func:`attn_merge`. Of a head, ``cfg.rope_dims`` leading dims rotate
    (None: all). ``kv_slice`` post-selects kv heads from
    tp-replicated K/V projections (the GQA kv_heads < tp case — see
    :func:`_kv_tp_sharded`)."""
    with jax.named_scope("attn_qkv"):
        h = _scaled(_norm(x, lp, "ln1", cfg), cfg.attn_in_scale)
        q = jnp.einsum("bld,dhk->blhk", h, lp["wq"])
        k = _scaled(jnp.einsum("bld,dhk->blhk", h, lp["wk"]), cfg.key_scale)
        v = jnp.einsum("bld,dhk->blhk", h, lp["wv"])
        if kv_slice is not None:
            k, v = kv_slice(k), kv_slice(v)
        if cfg.qk_norm:
            q = _rms(q, lp["qn_s"], cfg.norm_eps)
            k = _rms(k, lp["kn_s"], cfg.norm_eps)
        if cfg.rope_at(li):
            q = _rope_leading(rope, q, cfg.rope_dims)
            k = _rope_leading(rope, k, cfg.rope_dims)
        gate = None
        if cfg.attn_gate:
            gate = jnp.einsum("bld,dhk->blhk", h, lp["wog"])
        return q, k, v, gate


def attn_merge(x, o, gate, lp, cfg, tp_psum=False, mix=None, beside=None):
    """Second part of the attention half: the output gate, the
    out-projection (summed over ``tp`` when the heads were a shard),
    the norm after the half, the residual (``mix``: :func:`hc_pre`'s,
    where the residual path is streams). ``beside``: the result of the
    mixer that stands beside the attention in this layer
    (:func:`ssm_half`); the two are summed and join the residual in one
    add."""
    with jax.named_scope("attn_out"):
        if gate is not None:
            o = o * jax.nn.sigmoid(gate.astype(jnp.float32)).astype(o.dtype)
        a = jnp.einsum("blhk,hkd->bld", o, lp["wo"])
        if tp_psum:
            a = jax.lax.psum(a, "tp")
        a = _scaled(a, cfg.attn_out_scale)
        if beside is not None:
            a = beside + a
        if cfg.post_norm:
            a = _norm(a, lp, "ln1p", cfg)
        return hc_post(x, _res(a, cfg), mix)


# The residual path, written once. With ``hc_mult`` = 1 a half is
# ``x + half(norm(x))``. With n > 1 the stream is n streams (B, L, n,
# D): a half reads ``h = sum_j Hpre[j] X[j]`` and its result y goes back
# as ``X'[i] = sum_j Hres[i, j] X[j] + Hpost[i] y``, the three made from
# the token's own streams (manifold-constrained hyper-connections,
# arXiv:2512.24880). Every place that adds a half to the stream calls
# :func:`hc_pre` before it and hands :func:`hc_post` what that returned.

# the implementation's two guards (the published ``hc_eps`` and
# ``mhc_h_res_clamp_min / _max``): what a Sinkhorn round adds to a sum
# before dividing by it, and the range the stream-to-stream matrix's
# exponent is held to
HC_EPS = 1e-6
HC_RES_CLAMP = (-30.0, 30.0)


def init_hc_layer(rng: np.random.Generator, cfg: TransformerConfig,
                  li: int) -> dict:
    """The mixing's leaves for both halves of layer ``li`` (``hc1``: the
    token mixer's, ``hc2``: the feed-forward's), all float32: ``phi``
    (n D, 2 n + n * n) laid out ``[pre | post | res]``, the three
    scalars ``alpha`` and the biases ``b`` in the same layout
    (:func:`hc_bias`). ``alpha`` is one (away from zero: the matrices
    follow the token)."""
    n, D = cfg.hc_mult, cfg.d_model
    out = {}
    for k, half in enumerate(("hc1", "hc2")):
        out[half + "_phi"] = jnp.asarray(
            rng.standard_normal((n * D, 2 * n + n * n)) / np.sqrt(n * D),
            jnp.float32)
        out[half + "_alpha"] = jnp.ones((3,), jnp.float32)
        out[half + "_b"] = jnp.asarray(hc_bias(n, 2 * li + k), jnp.float32)
    return out


def hc_bias(n: int, k: int) -> np.ndarray:
    """Where hyper-connections (arXiv:2409.19606) start their static
    matrices, as the biases of the sigmoid and of the exponential: the
    model's ``k``-th half reads mostly stream ``k mod n`` (+2 there, -2
    elsewhere: 0.88 against 0.12), its result goes back to every stream
    alike (0: ``Hpost`` 1), and a stream mostly keeps to itself (2 on
    the diagonal of the stream-to-stream part: 0.7 after the rounds).
    With every half reading every stream alike the streams would be
    interchangeable and the stream-to-stream matrix would move
    nothing."""
    pre = np.where(np.arange(n) == k % n, 2.0, -2.0)
    return np.concatenate([pre, np.zeros(n), 2.0 * np.eye(n).ravel()])


def hc_pre(x, lp, cfg, half: str):
    """What a half reads and how its result goes back: ``(h, mix)``
    from the streams x (B, L, n, D) and the leaves ``<half>_phi /
    _alpha / _b`` of ``lp``. With one stream ``(x, None)``. The token's
    matrices, float32: ``u = RMSNorm(vec(X))`` over the n D values;
    ``Hpre = sigmoid(a_pre u phi_pre + b_pre)``, ``Hpost = 2
    sigmoid(a_post u phi_post + b_post)``, ``Hres`` the clamped
    exponential of ``a_res u phi_res + b_res`` after
    ``hc_sinkhorn_iters`` rounds of columns, then rows, divided by
    their sums + ``HC_EPS``. The matrices are laid out entries first,
    (n, n, B, L) with the tokens on the minor axes, and the rounds are
    ONE ``fori_loop`` whose body is two sums over a leading axis and
    two divisions: unrolled in Python the same rounds are a graph in
    which every value has five readers twenty levels deep, which the
    compiler's fusion pass does not come back from."""
    if cfg.hc_mult == 1:
        return x, None
    n = cfg.hc_mult
    B, L, _, D = x.shape
    with jax.named_scope("hc_mix"):
        xf = x.astype(jnp.float32)
        ms = jnp.mean(xf * xf, axis=(-2, -1), keepdims=True)
        u = (xf * jax.lax.rsqrt(ms + cfg.norm_eps)).reshape(B, L, n * D)
        c = jnp.einsum("blc,cf->fbl", u, lp[half + "_phi"], precision=_HI)
        alpha = jnp.repeat(lp[half + "_alpha"], np.array([n, n, n * n]))
        c = alpha[:, None, None] * c + lp[half + "_b"][:, None, None]
        pre = jax.nn.sigmoid(c[:n])
        post = 2.0 * jax.nn.sigmoid(c[n:2 * n])
        res = jnp.exp(jnp.clip(c[2 * n:], *HC_RES_CLAMP)).reshape(
            n, n, B, L)

        def sinkhorn(_, m):  # m[i, j]: columns over i, rows over j
            m = m / (m.sum(axis=0, keepdims=True) + HC_EPS)
            return m / (m.sum(axis=1, keepdims=True) + HC_EPS)

        res = jax.lax.fori_loop(0, cfg.hc_sinkhorn_iters, sinkhorn, res)
        h = sum(pre[j][..., None] * xf[:, :, j] for j in range(n))
    return h.astype(x.dtype), (xf, res, post)


def hc_post(x, y, mix):
    """The half's result y back into the stream: ``x + y``, or with
    streams (``mix`` from :func:`hc_pre`; x is then the mix the half
    read and is not used) ``X'[i] = sum_j Hres[i][j] X[j] + Hpost[i]
    y``."""
    if mix is None:
        return x + y
    xf, res, post = mix
    n = len(post)
    with jax.named_scope("hc_mix"):
        yf = y.astype(jnp.float32)
        out = [sum(res[i, j][..., None] * xf[:, :, j] for j in range(n))
               + post[i][..., None] * yf for i in range(n)]
        return jnp.stack(out, axis=2).astype(y.dtype)


def hc_fold(x, cfg):
    """The streams folded into one before the final norm: their sum."""
    if cfg.hc_mult == 1:
        return x
    return x.astype(jnp.float32).sum(axis=2).astype(x.dtype)


# Latent attention, written once like the two other mixers. The dense
# forward attends in the expanded form (every position's keys and
# values for all heads out of its latent); a cache keeps the ONE row a
# position and is attended in the absorbed form (:func:`mla_absorb`:
# the key's up-projection folded into the query, the value's applied to
# the result), so a cached row is never expanded to heads.


def init_mla_layer(rng: np.random.Generator, cfg: TransformerConfig) -> dict:
    """Leaves of one latent-attention mixer: ``mla_wdq`` (D, q rank)
    and its norm's scale, ``mla_wuq`` (q rank, H, nope + rope);
    ``mla_wdkv`` (D, kv rank + rope, laid out ``[latent | key's rotated
    part]``) and the latent norm's scale, ``mla_wukv`` (kv rank, H,
    nope + v, ``[key | value]`` a head); ``wo`` (H, v, D)."""
    D, H = cfg.d_model, cfg.n_heads
    sd = lambda *s: jnp.asarray(
        rng.standard_normal(s) / np.sqrt(s[0]), cfg.dtype
    )
    return {
        "mla_wdq": sd(D, cfg.mla_q_rank),
        "mla_qn_s": jnp.ones((cfg.mla_q_rank,), cfg.dtype),
        "mla_wuq": sd(cfg.mla_q_rank, H, cfg.head_dim),
        "mla_wdkv": sd(D, cfg.latent_width),
        "mla_kvn_s": jnp.ones((cfg.mla_kv_rank,), cfg.dtype),
        "mla_wukv": sd(cfg.mla_kv_rank, H, cfg.mla_nope_dim + cfg.mla_v_dim),
        "wo": sd(H, cfg.mla_v_dim, D) / float(np.sqrt(cfg.n_layers)),
    }


def mla_project(x, lp, cfg, rope):
    """First part of a latent-attention half on (B, L, D): norm, both
    down-projections with their norms, the query heads, rotary. Returns
    ``(qn (B, L, H, nope), qr (B, L, H, rope) rotated, row (B, L, 1,
    kv rank + rope))``; ``row`` is what a cache keeps of the position:
    the normalised latent beside the one rotated key all heads share."""
    R, nope = cfg.mla_kv_rank, cfg.mla_nope_dim
    h = _norm(x, lp, "ln1", cfg)
    with jax.named_scope("mla_q"):
        cq = _rms(jnp.einsum("bld,dr->blr", h, lp["mla_wdq"]),
                  lp["mla_qn_s"], cfg.norm_eps)
        q = jnp.einsum("blr,rhk->blhk", cq, lp["mla_wuq"])
        qn, qr = q[..., :nope], rope(q[..., nope:])
    with jax.named_scope("mla_kv"):
        ckr = jnp.einsum("bld,dr->blr", h, lp["mla_wdkv"])[:, :, None]
        ckv = _rms(ckr[..., :R], lp["mla_kvn_s"], cfg.norm_eps)
        row = jnp.concatenate([ckv, rope(ckr[..., R:])], axis=-1)
    return qn, qr, row


def mla_absorb(qn, qr, lp, cfg):
    """The absorbed query ``[qn Wuk^T | qr]`` (B, L, H, kv rank +
    rope): its product with a cached row is the head's whole score."""
    with jax.named_scope("mla_q"):
        qa = jnp.einsum("blhk,rhk->blhr", qn,
                        lp["mla_wukv"][..., :cfg.mla_nope_dim])
        return jnp.concatenate([qa, qr], axis=-1)


def mla_expanded(qn, qr, row, lp, cfg):
    """Causal attention of a whole sequence over itself in the expanded
    form: every position's keys and values for all heads out of its
    latent. Returns (B, L, H, v)."""
    R, nope = cfg.mla_kv_rank, cfg.mla_nope_dim
    L = row.shape[1]
    kv = jnp.einsum("blr,rhk->blhk", row[:, :, 0, :R], lp["mla_wukv"])
    s = jnp.einsum("bqhd,bkhd->bhqk", qn, kv[..., :nope],
                   preferred_element_type=jnp.float32)
    s = s + jnp.einsum("bqhd,bkd->bhqk", qr, row[:, :, 0, R:],
                       preferred_element_type=jnp.float32)
    seen = jnp.arange(L)[None, :] <= jnp.arange(L)[:, None]
    p = jax.nn.softmax(
        jnp.where(seen[None, None], s * cfg.softmax_scale, -1e30), axis=-1)
    return jnp.einsum("bhqk,bkhv->bqhv", p.astype(qn.dtype), kv[..., nope:])


def mla_merge(x, o, lp, cfg, mix=None, latent=False):
    """Second part of the latent-attention half: the out-projection
    and the residual. ``latent``: ``o`` is the absorbed form's (B, L,
    H, kv rank) and goes through the value's up-projection first;
    else the expanded form's (B, L, H, v)."""
    with jax.named_scope("mla_out"):
        if latent:
            o = jnp.einsum("blhr,rhv->blhv", o.astype(x.dtype),
                           lp["mla_wukv"][..., cfg.mla_nope_dim:])
        a = jnp.einsum("blhk,hkd->bld", o, lp["wo"])
    return hc_post(x, _res(a, cfg), mix)


# The gated delta-rule half, written once like the attention half: the
# dense forward (the whole sequence from a zero state), a prefill chunk
# and a decode step all call :func:`gdn_half` and differ in the state
# they hand it. A layer's state is a fixed block a request: ``S``
# (value heads, key dim, value dim) float32 and the last ``conv - 1``
# rows that went into the depthwise conv. No leaf has a row a token.

GDN_SUBCHUNK = 64  # rows the chunked form of the recurrence takes at once
_HI = jax.lax.Precision.HIGHEST


def init_gdn_layer(rng: np.random.Generator, cfg: TransformerConfig) -> dict:
    """Leaves of one gated delta-rule mixer: ``gdn_wqkvz`` (D, 2 *
    key + 2 * value width, laid out ``[q | k | v | z]``), ``gdn_wba``
    (D, 2 * value heads, ``[b | a]``), the depthwise conv taps
    ``gdn_conv_w`` (taps, q + k + v channels), ``gdn_A_log`` and
    ``gdn_dt_bias`` (float32, a value head each, drawn as the published
    initialisation does: A uniform in (0, 16], dt log-uniform in
    [0.001, 0.1] with ``dt_bias`` its inverse softplus), the scale of
    the norm over a value head and the out-projection."""
    D = cfg.d_model
    Hk, Hv = cfg.gdn_key_heads, cfg.gdn_value_heads
    kw, vw = Hk * cfg.gdn_key_dim, Hv * cfg.gdn_value_dim
    sd = lambda *s: jnp.asarray(
        rng.standard_normal(s) / np.sqrt(s[0]), cfg.dtype
    )
    A = 16.0 * (1.0 - rng.random(Hv))
    dt = np.exp(rng.uniform(np.log(1e-3), np.log(1e-1), Hv))
    return {
        "gdn_wqkvz": sd(D, 2 * kw + 2 * vw),
        "gdn_wba": sd(D, 2 * Hv),
        "gdn_conv_w": jnp.asarray(
            rng.uniform(-0.5, 0.5, (cfg.gdn_conv, 2 * kw + vw)), cfg.dtype),
        "gdn_A_log": jnp.asarray(np.log(A), jnp.float32),
        "gdn_dt_bias": jnp.asarray(dt + np.log(-np.expm1(-dt)),
                                   jnp.float32),
        "gdn_norm_s": jnp.ones((cfg.gdn_value_dim,), cfg.dtype),
        "gdn_wout": sd(vw, D) / float(np.sqrt(cfg.n_layers)),
    }


def gdn_zero_state(cfg: TransformerConfig, B: int) -> dict:
    """The state of ``B`` requests that have seen no token."""
    Hk, Hv = cfg.gdn_key_heads, cfg.gdn_value_heads
    chans = 2 * Hk * cfg.gdn_key_dim + Hv * cfg.gdn_value_dim
    return {
        "S": jnp.zeros((B, Hv, cfg.gdn_key_dim, cfg.gdn_value_dim),
                       jnp.float32),
        "conv": jnp.zeros((B, cfg.gdn_conv - 1, chans), cfg.dtype),
    }


def _delta_rule_step(q, k, v, g, beta, S):
    """One token of the recurrence, float32: q, k (B, H, Dk), v
    (B, H, Dv), g, beta (B, H), S (B, H, Dk, Dv)."""
    S = S * jnp.exp(g)[..., None, None]
    mem = (S * k[..., None]).sum(axis=-2)            # S'^T k
    delta = (v - mem) * beta[..., None]
    S = S + k[..., None] * delta[..., None, :]
    return (S * q[..., None]).sum(axis=-2), S        # S^T q


def _delta_rule_chunks(q, k, v, g, beta, S):
    """The same recurrence over T rows, ``GDN_SUBCHUNK`` at a time:
    q, k (B, T, H, Dk), v (B, T, H, Dv), g, beta (B, T, H), S
    (B, H, Dk, Dv), all float32. Inside a sub-chunk with G the running
    sum of g, the rows' updates u solve the unit lower-triangular
    system ``(I + A) u = beta (v - exp(G) k S0)``, ``A[t, s] = beta_t
    exp(G_t - G_s) k_t.k_s`` (s < t); then ``o = exp(G) q S0 +
    [exp(G_t - G_s) q_t.k_s]_{s <= t} u`` and ``S = exp(G_c) S0 + (k
    exp(G_c - G))^T u``. A scan over the sub-chunks carries S. A row
    with g = 0 and beta = 0 leaves S as it was (padding)."""
    B, T, H, Dk = q.shape
    c = min(GDN_SUBCHUNK, T)
    pad = -T % c
    if pad:
        padt = lambda a: jnp.pad(
            a, [(0, 0), (0, pad)] + [(0, 0)] * (a.ndim - 2))
        q, k, v, g, beta = (padt(a) for a in (q, k, v, g, beta))
    n = (T + pad) // c
    def rows(a):  # (B, T, H[, D]) -> (n, B, H, c[, D]): scanned axis first
        a = jnp.moveaxis(a.reshape((B, n, c) + a.shape[2:]), 1, 0)
        return jnp.swapaxes(a, 2, 3)

    q, k, v, g, beta = (rows(a) for a in (q, k, v, g, beta))
    tri = jnp.tril(jnp.ones((c, c), bool))

    def sub(S, xs):
        q, k, v, g, beta = xs  # (B, H, c, D*), (B, H, c)
        G = jnp.cumsum(g, axis=-1)
        # exp(G_t - G_s) on and below the diagonal, 0 above: masked
        # before the exponential (above it the difference is positive)
        decay = jnp.exp(jnp.where(
            tri, G[..., :, None] - G[..., None, :], -jnp.inf))
        kk = jnp.einsum("bhtd,bhsd->bhts", k, k, precision=_HI)
        eG = jnp.exp(G)[..., None]
        rhs = jnp.concatenate([v, k * eG], axis=-1) * beta[..., None]
        # I + A: the solve takes the diagonal as ones and reads the
        # strict lower triangle alone
        sol = jax.scipy.linalg.solve_triangular(
            kk * decay * beta[..., None], rhs, lower=True,
            unit_diagonal=True)
        u = sol[..., :v.shape[-1]] - jnp.einsum(
            "bhtk,bhkv->bhtv", sol[..., v.shape[-1]:], S, precision=_HI)
        qk = jnp.einsum("bhtd,bhsd->bhts", q, k, precision=_HI) * decay
        o = eG * jnp.einsum("bhtk,bhkv->bhtv", q, S, precision=_HI)
        o = o + jnp.einsum("bhts,bhsv->bhtv", qk, u, precision=_HI)
        last = jnp.exp(G[..., -1:] - G)[..., None]
        S = S * jnp.exp(G[..., -1])[..., None, None] + jnp.einsum(
            "bhtk,bhtv->bhkv", k * last, u, precision=_HI)
        return S, o

    S, o = jax.lax.scan(sub, S, (q, k, v, g, beta))
    o = jnp.moveaxis(jnp.swapaxes(o, 2, 3), 0, 1).reshape(B, n * c, H, -1)
    return o[:, :T], S


def _causal_conv(rows, kept, w, valid, bias=None):
    """The causal depthwise conv both recurrent mixers run before their
    rule: ``rows`` (B, T, channels) behind the ``taps - 1`` rows
    ``kept`` from the call before, taps ``w`` (taps, channels), an
    optional ``bias``, silu, in float32. Returns ``(y (B, T, channels),
    tail)``: ``tail`` is what the next call's conv reaches back to, the
    last ``taps - 1`` rows that went in, padding not counted (``valid``:
    see :func:`gdn_half`)."""
    T, taps = rows.shape[1], w.shape[0]
    seen = jnp.concatenate([kept, rows.astype(kept.dtype)], axis=1)
    if valid is None:
        tail = seen[:, T:]
    elif jnp.ndim(valid):
        tail = jax.vmap(lambda a, n: jax.lax.dynamic_slice_in_dim(
            a, n, taps - 1))(seen, valid)
    else:
        tail = jax.lax.dynamic_slice_in_dim(seen, valid, taps - 1, axis=1)
    w = w.astype(jnp.float32)
    seen = seen.astype(jnp.float32)
    y = sum(seen[:, j:j + T] * w[j] for j in range(taps))
    if bias is not None:
        y = y + bias.astype(jnp.float32)
    return jax.nn.silu(y), tail


def gdn_rule_route(cfg: TransformerConfig, T: int) -> str:
    """The form the recurrence takes over a call of T rows, from what
    the shapes say: ``"kernel"`` (ops/delta_rule.py, at head sizes of
    whole lane tiles, the published widths: for T > 1 the chunked
    kernel, whole sub-chunks of its own, 128 rows; for one token the
    kernel that updates S where it lies) or ``"xla"``
    (:func:`_delta_rule_chunks`, :func:`_delta_rule_step`).
    :func:`gdn_half` asks it, and the serving scheduler for the
    ``gdn_rule`` of ``serving.prefill_chunk`` and ``serving.decode``."""
    heads = (cfg.gdn_key_heads, cfg.gdn_value_heads, cfg.gdn_key_dim,
             cfg.gdn_value_dim)
    viable = (delta_step_viable(*heads) if T == 1
              else delta_rule_viable(T, *heads))
    return "kernel" if viable else "xla"


def gdn_half(x, lp, state, cfg, valid=None, mix=None):
    """Layer's gated delta-rule half on (B, T, D) from ``state``
    (:func:`gdn_zero_state`'s leaves): norm, projections, the causal
    depthwise conv, the recurrence, the gated norm over each value
    head, the out-projection, the residual. Returns ``(x, state)``.
    ``valid`` (a traced count, None = T) says how many leading rows are
    real: the rows after them are a padded prompt's tail and leave
    ``S`` and the conv rows as the last real row left them. A vector
    ``(B,)`` gives every row of the batch its own count (the chunks of
    several requests in one program, ``decode._grouped_hidden``).
    ``mix``: :func:`hc_pre`'s, where the residual path is streams."""
    B, T, _ = x.shape
    Hk, Hv = cfg.gdn_key_heads, cfg.gdn_value_heads
    Dk, Dv = cfg.gdn_key_dim, cfg.gdn_value_dim
    kw, vw = Hk * Dk, Hv * Dv
    h = _norm(x, lp, "ln1", cfg)
    with jax.named_scope("gdn_proj"):
        qkvz = jnp.einsum("bld,dc->blc", h, lp["gdn_wqkvz"])
        qkv, z = qkvz[..., :2 * kw + vw], qkvz[..., 2 * kw + vw:]
        ba = jnp.einsum("bld,dc->blc", h, lp["gdn_wba"],
                        preferred_element_type=jnp.float32)
    with jax.named_scope("gdn_conv"):
        y, tail = _causal_conv(qkv, state["conv"], lp["gdn_conv_w"], valid)
    with jax.named_scope("gdn_rule"):
        # at widths of whole lane tiles a chunk of whole sub-chunks
        # goes through the chunked kernel, which reads q, k and v out
        # of y as they lie, and one token through the kernel that
        # updates S where it lies, a key head serving its value heads
        # there; anything else (tiny widths, an odd length) through the
        # plain forms below, q and k repeated to the value heads
        kernel = gdn_rule_route(cfg, T) == "kernel"
        if not kernel or T == 1:
            q = y[..., :kw].reshape(B, T, Hk, Dk)
            k = y[..., kw:2 * kw].reshape(B, T, Hk, Dk)
            v = y[..., 2 * kw:].reshape(B, T, Hv, Dv)
            l2 = lambda a: a * jax.lax.rsqrt(
                (a * a).sum(-1, keepdims=True) + 1e-6)
            q, k = l2(q) * Dk ** -0.5, l2(k)
            if Hv != Hk and not kernel:
                # key head j serves value heads [j * r, (j + 1) * r)
                q = jnp.repeat(q, Hv // Hk, axis=2)
                k = jnp.repeat(k, Hv // Hk, axis=2)
        beta = jax.nn.sigmoid(ba[..., :Hv])
        g = -jnp.exp(lp["gdn_A_log"]) * jax.nn.softplus(
            ba[..., Hv:] + lp["gdn_dt_bias"])
        if valid is not None:
            real = ((jnp.arange(T) < valid[:, None])[..., None]
                    if jnp.ndim(valid) else
                    (jnp.arange(T) < valid)[None, :, None])
            g, beta = jnp.where(real, g, 0.0), jnp.where(real, beta, 0.0)
        if T == 1:
            step = delta_rule_step if kernel else _delta_rule_step
            o, S = step(q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0],
                        state["S"])
            o = o[:, None]
        elif kernel:
            o, S = chunked_delta_rule(y, g, beta, state["S"], Hk=Hk, Hv=Hv,
                                      Dk=Dk, Dv=Dv)
            o = o.reshape(B, T, Hv, Dv)
        else:
            o, S = _delta_rule_chunks(q, k, v, g, beta, state["S"])
    with jax.named_scope("gdn_out"):
        o = _rms(o, lp["gdn_norm_s"].astype(jnp.float32), cfg.norm_eps)
        o = o * jax.nn.silu(z.astype(jnp.float32)).reshape(B, T, Hv, Dv)
        a = jnp.einsum("blc,cd->bld", o.reshape(B, T, vw).astype(x.dtype),
                       lp["gdn_wout"])
    return hc_post(x, _res(a, cfg), mix), {"S": S, "conv": tail}


# Decayed linear attention, written once like the delta rule: the dense
# forward (the whole sequence from a zero state), a prefill chunk and a
# decode step all call :func:`la_half` and differ in the state and the
# rotary they hand it. A layer's state is ``S`` (heads, head dim, head
# dim) float32 and nothing else: no conv, no gate that follows the
# data, and so no solve. Over C rows it is all products.

LA_SUBCHUNK = 256  # rows the chunked form takes at once


def la_slopes(heads: int, layer: int, layers: int) -> np.ndarray:
    """Lightning Attention's decay exponents (arXiv:2401.04658;
    MiniMax-01, arXiv:2501.08313): head h forgets at ``exp(-s_h)`` a
    token, ``s_h = 2 ** (-8 (h + 1) / heads)`` times the layer's
    factor ``1 - layer / (layers - 1) + 1e-5`` (``layer`` of
    ``layers``: the deeper the layer, the longer it remembers)."""
    base = 2.0 ** (-8.0 * (np.arange(heads) + 1) / heads)
    return base * (1.0 - layer / max(layers - 1, 1) + 1e-5)


def init_la_layer(rng: np.random.Generator, cfg: TransformerConfig,
                  li: int) -> dict:
    """Leaves of one linear-attention mixer: ``la_wq`` / ``la_wk`` /
    ``la_wv`` (D, heads, head dim), the q/k norms' scales, the gate's
    projection ``la_wz`` (D, heads * head dim), the decay exponents
    ``la_slope`` (heads, float32: :func:`la_slopes` at this layer's
    index; data, so that a deployment's own constants can stand in
    them), the scale of the norm over the joined heads and the
    out-projection."""
    D, H, Dh = cfg.d_model, cfg.la_heads, cfg.la_head_dim
    sd = lambda *s: jnp.asarray(
        rng.standard_normal(s) / np.sqrt(s[0]), cfg.dtype
    )
    return {
        "la_wq": sd(D, H, Dh), "la_wk": sd(D, H, Dh), "la_wv": sd(D, H, Dh),
        "la_qn_s": jnp.ones((Dh,), cfg.dtype),
        "la_kn_s": jnp.ones((Dh,), cfg.dtype),
        "la_wz": sd(D, H * Dh),
        "la_slope": jnp.asarray(la_slopes(H, li, cfg.n_layers),
                                jnp.float32),
        "la_norm_s": jnp.ones((H * Dh,), cfg.dtype),
        "la_wo": sd(H * Dh, D) / float(np.sqrt(cfg.n_layers)),
    }


def la_zero_state(cfg: TransformerConfig, B: int) -> dict:
    """The state of ``B`` requests that have seen no token."""
    return {"S": jnp.zeros((B, cfg.la_heads, cfg.la_head_dim,
                            cfg.la_head_dim), jnp.float32)}


def zero_state(cfg: TransformerConfig, li: int, B: int) -> dict:
    """Layer ``li``'s recurrent state for ``B`` requests that have seen
    no token (``cfg.state(li)``: the delta rule's, linear attention's
    or a state-space mixer's, beside attention or alone)."""
    if cfg.ssm_mixer(li):
        return ssm_zero_state(cfg, B)
    return (gdn_zero_state(cfg, B) if cfg.gdn(li)
            else la_zero_state(cfg, B))


def _la_chunks(q, k, v, slope, S, valid=None):
    """``S_t = lam S_(t-1) + k_t^T v_t``, ``o_t = q_t S_t`` over T rows,
    ``LA_SUBCHUNK`` at a time, all float32: q, k, v (B, T, H, D), slope
    (H,) with ``lam = exp(-slope)``, S (B, H, D, D). Inside a sub-chunk
    of c rows ``O = ((Q K^T) * L) V + diag(lam^(1..c)) Q S0`` with
    ``L[t, i] = lam^(t - i)`` on and below the diagonal, and ``S = lam^c
    S0 + (K * lam^(c - 1 - i))^T V``: every exponent of ``lam`` is a
    count of rows, none is negative. ``valid`` (B,) says how many
    leading rows are real; the rows behind them leave S as the last
    real row left it."""
    B, T, H, D = q.shape
    c = min(LA_SUBCHUNK, T)
    pad = -T % c
    if pad:
        padt = lambda a: jnp.pad(a, [(0, 0), (0, pad), (0, 0), (0, 0)])
        q, k, v = padt(q), padt(k), padt(v)
    n = (T + pad) // c
    if valid is None:
        valid = jnp.full((B,), T, jnp.int32)
    rows = lambda a: jnp.moveaxis(
        a.reshape(B, n, c, H, D), 1, 0).swapaxes(2, 3)  # (n, B, H, c, D)
    q, k, v = rows(q), rows(k), rows(v)
    t = jnp.arange(c)
    slope = slope.astype(jnp.float32)[:, None]          # (H, 1)
    below = t[:, None] >= t[None, :]
    L = jnp.exp(jnp.where(below, -slope[..., None]
                          * (t[:, None] - t[None, :]), -jnp.inf))  # (H,c,c)
    into = jnp.exp(-slope * (t + 1))[..., None]         # lam^(t+1): (H,c,1)

    def sub(S, xs):
        q, k, v, at = xs                # (B, H, c, D); rows before: at
        real = jnp.clip(valid - at, 0, c)               # (B,)
        live = (t[None, :] < real[:, None])[:, None, :, None]
        k = jnp.where(live, k, 0.0)
        # lam^(real - 1 - i) for the real rows, masked before the
        # exponential (behind them the exponent would be negative)
        back = (real[:, None, None] - 1 - t[None, None, :])
        out = jnp.exp(jnp.where(back >= 0, -slope[None] * back,
                                -jnp.inf))[..., None]   # (B, H, c, 1)
        qk = jnp.einsum("bhtd,bhsd->bhts", q, k, precision=_HI) * L
        o = jnp.einsum("bhts,bhsd->bhtd", qk, v, precision=_HI)
        o = o + into * jnp.einsum("bhtk,bhkv->bhtv", q, S, precision=_HI)
        S = (S * jnp.exp(-slope * real[:, None, None])[..., None]
             + jnp.einsum("bhtk,bhtv->bhkv", k * out, v, precision=_HI))
        return S, o

    S, o = jax.lax.scan(sub, S, (q, k, v, jnp.arange(n) * c))
    o = jnp.moveaxis(o.swapaxes(2, 3), 0, 1).reshape(B, n * c, H, D)
    return o[:, :T], S


def la_rule_route(cfg: TransformerConfig, T: int) -> str:
    """The form the recurrence takes over a call of T > 1 rows:
    ``"xla"`` (:func:`_la_chunks`: products the compiler schedules;
    there is no kernel). :func:`la_half`'s note, and the serving
    scheduler's ``serving.prefill_chunk`` argument ``la_rule``."""
    return "xla"


def la_half(x, lp, state, cfg, rope, valid=None, mix=None):
    """Layer's linear-attention half on (B, T, D) from ``state``
    (:func:`la_zero_state`'s leaf): norm, projections, the norm over
    each head of q and k, rotary over the whole head (``rope(t)`` at
    the caller's positions), the recurrence in float32, the norm over
    the joined heads, the sigmoid gate, the out-projection, the
    residual. Returns ``(x, state)``. Of ``valid`` and ``mix`` see
    :func:`gdn_half`."""
    B, T, _ = x.shape
    H, Dh = cfg.la_heads, cfg.la_head_dim
    h = _norm(x, lp, "ln1", cfg)
    with jax.named_scope("la_proj"):
        q = jnp.einsum("bld,dhk->blhk", h, lp["la_wq"])
        k = jnp.einsum("bld,dhk->blhk", h, lp["la_wk"])
        v = jnp.einsum("bld,dhk->blhk", h, lp["la_wv"])
        z = jnp.einsum("bld,dc->blc", h, lp["la_wz"])
        q = rope(_rms(q, lp["la_qn_s"], cfg.norm_eps))
        k = rope(_rms(k, lp["la_kn_s"], cfg.norm_eps))
    with jax.named_scope("la_rule"):
        q, k, v = (a.astype(jnp.float32) for a in (q, k, v))
        slope, S = lp["la_slope"], state["S"]
        if T == 1 and valid is None:
            S = S * jnp.exp(-slope)[:, None, None] + (
                k[:, 0, :, :, None] * v[:, 0, :, None, :])
            o = (S * q[:, 0, :, :, None]).sum(axis=-2)[:, None]
        else:
            if valid is not None and not jnp.ndim(valid):
                valid = jnp.full((B,), valid, jnp.int32)
            o, S = _la_chunks(q, k, v, slope, S, valid)
        o = o * Dh ** -0.5
    with jax.named_scope("la_out"):
        o = _rms(o.reshape(B, T, H * Dh),
                 lp["la_norm_s"].astype(jnp.float32), cfg.norm_eps)
        o = o * jax.nn.sigmoid(z.astype(jnp.float32))
        a = jnp.einsum("blc,cd->bld", o.astype(x.dtype), lp["la_wo"])
    return hc_post(x, _res(a, cfg), mix), {"S": S}


def state_half(x, lp, state, cfg, li, rope, valid=None, mix=None):
    """Layer ``li``'s recurrent half (``cfg.state(li)`` and no rows),
    whichever it is: ``(x, state)``. ``rope`` is read by linear
    attention alone. A state-space mixer alone in its layer
    (``layer_mixers`` value "ssm") is :func:`ssm_half`'s body, the one
    the mixer beside an attention runs, joined to the residual here as
    every half's result is."""
    if cfg.gdn(li):
        return gdn_half(x, lp, state, cfg, valid, mix=mix)
    if cfg.ssm_mixer(li):
        a, state = ssm_half(x, lp, state, cfg, valid)
        return hc_post(x, _res(a, cfg), mix), state
    return la_half(x, lp, state, cfg, rope, valid, mix=mix)


# A state-space mixer, beside attention (``layer_mixers`` value
# "attn_ssm") or alone in its layer ("ssm"), written once like the
# other recurrences: the dense forward (the whole sequence from a zero
# state), a prefill chunk and a decode step all call :func:`ssm_half`
# on the layer's input; beside attention they hand what it returns to
# :func:`attn_merge` as ``beside``, alone :func:`state_half` joins it to
# the residual. A layer's state is ``S`` (heads, state dim, head dim)
# float32, the state dim down the rows so that a head's x, decay and
# result lie along the lanes and the group's B and C scale whole rows
# (ops/ssm_step.py), and the last ``ssm_conv - 1`` rows that went into
# the depthwise conv. Where the step kernel takes a head narrower than
# a lane tile, k heads share one: ``S`` is kept (heads / k, state dim,
# k head dims) (:func:`ssm_state_heads` gives the heads back).


def ssm_widths(cfg: TransformerConfig) -> tuple[int, int, int]:
    """``(heads * head dim, groups * state dim, in-projection's
    width)``: the in-projection is laid out ``[z | x | B | C | dt]``,
    the conv runs over ``[x | B | C]``."""
    wide, gn = cfg.ssm_heads * cfg.ssm_head_dim, cfg.ssm_groups * cfg.ssm_state
    return wide, gn, 2 * wide + 2 * gn + cfg.ssm_heads


def init_ssm_layer(rng: np.random.Generator, cfg: TransformerConfig) -> dict:
    """Leaves of one state-space mixer: ``ssm_win`` (D, ``[z | x | B |
    C | dt]``), the depthwise conv's taps and bias over ``[x | B | C]``,
    ``ssm_A_log``, ``ssm_dt_bias`` and ``ssm_D`` (float32, a head each,
    drawn as Mamba-2's reference initialisation does: A uniform in [1,
    16], dt log-uniform in [0.001, 0.1] with ``dt_bias`` its inverse
    softplus, D one), the gated norm's scale and the out-projection."""
    D, H = cfg.d_model, cfg.ssm_heads
    wide, gn, proj = ssm_widths(cfg)
    sd = lambda *s: jnp.asarray(
        rng.standard_normal(s) / np.sqrt(s[0]), cfg.dtype
    )
    dt = np.exp(rng.uniform(np.log(1e-3), np.log(1e-1), H))
    return {
        "ssm_win": sd(D, proj),
        "ssm_conv_w": jnp.asarray(
            rng.uniform(-0.5, 0.5, (cfg.ssm_conv, wide + 2 * gn)), cfg.dtype),
        "ssm_conv_b": jnp.asarray(
            rng.uniform(-0.5, 0.5, (wide + 2 * gn,)), cfg.dtype),
        "ssm_A_log": jnp.asarray(np.log(rng.uniform(1.0, 16.0, H)),
                                 jnp.float32),
        "ssm_dt_bias": jnp.asarray(dt + np.log(-np.expm1(-dt)), jnp.float32),
        "ssm_D": jnp.ones((H,), jnp.float32),
        "ssm_norm_s": jnp.ones((wide,), cfg.dtype),
        "ssm_wout": sd(wide, D) / float(np.sqrt(cfg.n_layers)),
    }


def _ssm_shape(cfg: TransformerConfig) -> tuple[int, int, int, int]:
    return cfg.ssm_heads, cfg.ssm_groups, cfg.ssm_state, cfg.ssm_head_dim


def ssm_zero_state(cfg: TransformerConfig, B: int) -> dict:
    """The state of ``B`` requests that have seen no token; ``S`` in
    the layout the step kernel keeps (``ssm_step.ssm_state_shape``:
    (heads, state dim, head dim), or k heads a lane tile)."""
    wide, gn, _ = ssm_widths(cfg)
    return {
        "S": jnp.zeros((B,) + ssm_state_shape(*_ssm_shape(cfg)),
                       jnp.float32),
        "conv": jnp.zeros((B, cfg.ssm_conv - 1, wide + 2 * gn), cfg.dtype),
    }


def ssm_state_heads(S, cfg: TransformerConfig):
    """A kept ``S`` (B, heads / k, N, k P) as (B, heads, N, P), a head
    its own block: what the plain forms work on. ``S`` itself where no
    heads share a lane tile (k = 1)."""
    k = lane_pack(*_ssm_shape(cfg))
    if k == 1:
        return S
    B, Hk, N, _ = S.shape
    return S.reshape(B, Hk, N, k, cfg.ssm_head_dim).swapaxes(2, 3).reshape(
        B, Hk * k, N, cfg.ssm_head_dim)


def ssm_state_kept(S, cfg: TransformerConfig):
    """:func:`ssm_state_heads`'s inverse: (B, heads, N, P) as the
    cache keeps it."""
    k = lane_pack(*_ssm_shape(cfg))
    if k == 1:
        return S
    B, H, N, P = S.shape
    return S.reshape(B, H // k, k, N, P).swapaxes(2, 3).reshape(
        B, H // k, N, k * P)


def _ssm_step(x, Bm, Cm, dA, dt, S):
    """One token of the recurrence, float32: x (B, H, P), Bm, Cm (B, G,
    N), dA (the log decay) and dt (B, H), S (B, H, N, P). Returns ``(y
    (B, H, P), S)``, ``y = S_t C_t`` without the skip."""
    r = x.shape[1] // Bm.shape[1]
    Bh, Ch = jnp.repeat(Bm, r, axis=1), jnp.repeat(Cm, r, axis=1)
    S = S * jnp.exp(dA)[..., None, None] + (
        Bh[..., :, None] * (dt[..., None] * x)[..., None, :])
    return (S * Ch[..., :, None]).sum(axis=-2), S


def _ssm_chunks(x, Bm, Cm, dA, dt, S, c: int):
    """The same recurrence over T rows, ``c`` at a time, all float32: x
    (B, T, H, P), Bm, Cm (B, T, G, N), dA, dt (B, T, H), S (B, H, N,
    P). Inside a sub-chunk with ``L`` the running sum of dA, ``y_t =
    sum_{s <= t} exp(L_t - L_s) (C_t . B_s) dt_s x_s + exp(L_t) C_t
    S0`` and ``S = exp(L_c) S0 + sum_s exp(L_c - L_s) B_s (dt_s
    x_s)^T``: :func:`_la_chunks` with a decay that is the token's own
    scalar a head and B, C shared by a group's heads (their ``C B^T``
    is made once a group). A scan over the sub-chunks carries S. A row
    with dA = 0 and dt = 0 leaves S as it was (padding)."""
    B, T, H, P = x.shape
    G = Bm.shape[2]
    c = min(c, T)
    pad = -T % c
    if pad:
        padt = lambda a: jnp.pad(
            a, [(0, 0), (0, pad)] + [(0, 0)] * (a.ndim - 2))
        x, Bm, Cm, dA, dt = (padt(a) for a in (x, Bm, Cm, dA, dt))
    n = (T + pad) // c

    def rows(a):  # (B, T, H[, D]) -> (n, B, H, c[, D]): scanned axis first
        a = jnp.moveaxis(a.reshape((B, n, c) + a.shape[2:]), 1, 0)
        return jnp.swapaxes(a, 2, 3)

    xs = rows(x * dt[..., None])
    tri = jnp.tril(jnp.ones((c, c), bool))
    heads = lambda a: a.reshape((B, G, H // G) + a.shape[2:])

    def sub(S, xs):
        u, Bm, Cm, dA = xs        # (B, H, c, P), (B, G, c, N) x 2, (B, H, c)
        L = jnp.cumsum(dA, axis=-1)
        # exp(L_t - L_s) on and below the diagonal, 0 above: masked
        # before the exponential (above it the difference is positive)
        decay = jnp.exp(jnp.where(
            tri, L[..., :, None] - L[..., None, :], -jnp.inf))
        cb = jnp.einsum("bgtn,bgsn->bgts", Cm, Bm, precision=_HI)
        w = heads(decay) * cb[:, :, None]                # (B, G, r, c, c)
        y = jnp.einsum("bgrts,bgrsp->bgrtp", w, heads(u), precision=_HI)
        y = y + heads(jnp.exp(L))[..., None] * jnp.einsum(
            "bgtn,bgrnp->bgrtp", Cm, heads(S), precision=_HI)
        last = heads(jnp.exp(L[..., -1:] - L))[..., None]
        S = S * jnp.exp(L[..., -1])[..., None, None] + jnp.einsum(
            "bgsn,bgrsp->bgrnp", Bm, heads(u) * last,
            precision=_HI).reshape(S.shape)
        return S, y.reshape(u.shape)

    S, y = jax.lax.scan(sub, S, (xs, rows(Bm), rows(Cm), rows(dA)))
    y = jnp.moveaxis(jnp.swapaxes(y, 2, 3), 0, 1).reshape(B, n * c, H, P)
    return y[:, :T], S


def ssm_rule_route(cfg: TransformerConfig, T: int) -> str:
    """The form the recurrence takes over a call of T rows, from what
    the shapes say: for one token ``"kernel"`` (ops/ssm_step.py, which
    updates S where it lies, at a head size of whole lane tiles or one
    that divides a tile: the published widths) or ``"xla"``
    (:func:`_ssm_step`); over T > 1 rows
    ``"xla"`` (:func:`_ssm_chunks`: products the compiler schedules;
    there is no kernel). :func:`ssm_half` asks it, and the serving
    scheduler for the ``ssm_rule`` of its spans."""
    if T == 1 and ssm_step_viable(*_ssm_shape(cfg)):
        return "kernel"
    return "xla"


def ssm_half(x, lp, state, cfg, valid=None):
    """The state-space mixer of a layer that holds one, beside its
    attention or alone, on the layer's input (B, T, D) from ``state``
    (:func:`ssm_zero_state`'s leaves): the layer's norm (the one an
    attention beside it reads), ``ssm_in_scale``, the in-projection times
    ``ssm_scales`` over its spans ``[z | x | B | C | dt]``, the causal
    depthwise conv with its bias and silu over ``[x | B | C]``, the
    recurrence in float32 (``dt = softplus(dt + dt_bias)``, ``a =
    exp(dt * -exp(A_log))``, ``S = a S + dt B x^T``, ``y = S C + D x``),
    the gate ``y * silu(z)`` and THEN an RMSNorm over each group's
    share of the joined heads, the out-projection, ``ssm_out_scale``.
    Returns ``(s, state)``: ``s`` (B, T, D) is NOT joined to the
    residual; :func:`attn_merge` takes it as ``beside``, and
    :func:`state_half` joins the lone mixer's. Of ``valid`` see
    :func:`gdn_half`."""
    B, T, _ = x.shape
    H, P, N, G = (cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state,
                  cfg.ssm_groups)
    wide, gn, proj = ssm_widths(cfg)
    h = _scaled(_norm(x, lp, "ln1", cfg), cfg.ssm_in_scale)
    with jax.named_scope("ssm_proj"):
        zxbcdt = jnp.einsum("bld,dc->blc", h, lp["ssm_win"])
        if any(s != 1.0 for s in cfg.ssm_scales):
            zxbcdt = zxbcdt * jnp.asarray(np.repeat(
                np.asarray(cfg.ssm_scales, np.float32),
                [wide, wide, gn, gn, H]), zxbcdt.dtype)
        z, xbc = zxbcdt[..., :wide], zxbcdt[..., wide:proj - H]
        dt = zxbcdt[..., proj - H:].astype(jnp.float32)
    with jax.named_scope("ssm_conv"):
        y, tail = _causal_conv(xbc, state["conv"], lp["ssm_conv_w"], valid,
                               lp["ssm_conv_b"])
    with jax.named_scope("ssm_rule"):
        xs = y[..., :wide].reshape(B, T, H, P)
        Bm = y[..., wide:wide + gn].reshape(B, T, G, N)
        Cm = y[..., wide + gn:].reshape(B, T, G, N)
        dt = jax.nn.softplus(dt + lp["ssm_dt_bias"])
        if valid is not None:
            real = ((jnp.arange(T) < valid[:, None])[..., None]
                    if jnp.ndim(valid) else
                    (jnp.arange(T) < valid)[None, :, None])
            dt = jnp.where(real, dt, 0.0)
        dA = -jnp.exp(lp["ssm_A_log"]) * dt
        if T == 1:
            step = (ssm_step if ssm_rule_route(cfg, 1) == "kernel"
                    else _ssm_step)
            o, S = step(xs[:, 0], Bm[:, 0], Cm[:, 0], dA[:, 0], dt[:, 0],
                        state["S"])
            o = o[:, None]
        else:
            # the plain form takes a head's S as a block of its own
            o, S = _ssm_chunks(xs, Bm, Cm, dA, dt,
                               ssm_state_heads(state["S"], cfg),
                               cfg.ssm_chunk)
            S = ssm_state_kept(S, cfg)
        o = o + lp["ssm_D"][:, None] * xs
    with jax.named_scope("ssm_out"):
        o = o.reshape(B, T, wide) * jax.nn.silu(z.astype(jnp.float32))
        o = _rms(o.reshape(B, T, G, wide // G),
                 lp["ssm_norm_s"].astype(jnp.float32).reshape(G, wide // G),
                 cfg.norm_eps).reshape(B, T, wide)
        a = jnp.einsum("blc,cd->bld", o.astype(x.dtype), lp["ssm_wout"])
        a = _scaled(a, cfg.ssm_out_scale)
    return a, {"S": S, "conv": tail}


# A selection of key blocks (``cfg.sparse_block``), written once: every
# forward keeps, beside its K/V rows, the means of every
# ``sparse_stride`` keys (a "cell": :func:`pool_cells`), from which a
# pooling window's mean is the mean of its ``sparse_kernel /
# sparse_stride`` cells, and asks :func:`sparse_pick` which blocks each
# query attends.


def pool_cells(k, cfg):
    """Cell means of whole cells of rows: k (..., T, Hkv, D) with T a
    whole number of ``sparse_stride`` -> (..., T / stride, Hkv, D)
    float32, each the sum of its rows over the stride (rows of zeros
    count nothing, so a cell that is not full yet holds what it has so
    far)."""
    st = cfg.sparse_stride
    kf = k.astype(jnp.float32)
    T = k.shape[-3]
    shape = k.shape[:-3] + (T // st, st) + k.shape[-2:]
    return kf.reshape(shape).sum(axis=-3) / st


def sparse_pick(q, cells, n, cfg, n_blocks: int):
    """Which key blocks each query attends: q (R, H, D), the R queries
    of ONE request; ``cells`` (>= n_blocks * cells a block, Hkv, D)
    float32, its pooled cells; ``n`` (R,) the rows each query sees
    (its position + 1). Returns ``(stands, score)``: (R, Hkv,
    n_blocks) bool and the blocks' scores float32.

    A query with ``n <= sparse_dense_len`` attends every block it
    sees. Else: window j is the mean of the keys ``[j stride, j stride
    + kernel)`` and counts while it lies whole inside the n rows; ``p_h
    = softmax_j(q_h . c_j * scale)`` per query head, ``s[j]`` its sum
    over the query heads of a K/V head; a block's score is the largest
    ``s[j]`` over the windows that TOUCH it (a window across two blocks
    counts for both); the first ``sparse_init_blocks`` blocks and the
    blocks that hold the last ``sparse_window`` rows stand, and of the
    others the ``sparse_topk`` best (equal scores: the earlier
    block). A caller opens the scope ``sparse_select`` around its call
    (around the ``vmap`` where it maps this over requests: a scope
    opened under a ``vmap`` is traced as ``vmap(sparse_select)``)."""
    st, blk = cfg.sparse_stride, cfg.sparse_block
    m, r = cfg.sparse_kernel // st, blk // st  # cells a window, a block
    R, H, D = q.shape
    Hkv = cells.shape[1]
    nc = n_blocks * r
    cells = cells[:nc]
    t = jnp.einsum("rhgd,chd->hgrc",
                   q.reshape(R, Hkv, H // Hkv, D).astype(jnp.float32),
                   cells, precision=_HI)
    nw = nc - m + 1
    w = sum(t[..., i:i + nw] for i in range(m)) * (
        cfg.softmax_scale / m)
    whole = (jnp.arange(nw) * st + cfg.sparse_kernel) <= n[:, None]
    p = jax.nn.softmax(jnp.where(whole, w, -1e30), axis=-1)
    s = jnp.where(whole, p.sum(axis=1), -1.0)       # (Hkv, R, nw)
    # block b is touched by the windows [b r - (m - 1), (b + 1) r):
    # r + m - 1 strided reads of s, padded by m - 1 at both ends
    s = jnp.pad(s, [(0, 0), (0, 0), (m - 1, m - 1)],
                constant_values=-1.0)
    score = functools.reduce(jnp.maximum, (
        s[..., d:d + nc:r] for d in range(r + m - 1)))
    score = score.transpose(1, 0, 2)                # (R, Hkv, blocks)
    b = jnp.arange(n_blocks)
    sees = b <= ((n - 1) // blk)[:, None]           # (R, blocks)
    held = (b < cfg.sparse_init_blocks) | (
        b >= (jnp.maximum(n - cfg.sparse_window, 0) // blk)[:, None])
    held = (held & sees)[:, None]
    open_ = (sees[:, None] & ~held)
    vals, idx = jax.lax.top_k(jnp.where(open_, score, -1.0),
                              min(cfg.sparse_topk, n_blocks))
    best = jnp.zeros((R, Hkv, n_blocks), bool).at[
        jnp.arange(R)[:, None, None], jnp.arange(Hkv)[None, :, None],
        idx].set(vals >= 0.0)
    stands = jnp.where((n > cfg.sparse_dense_len)[:, None, None],
                       held | best, sees[:, None])
    return stands, score


def sparse_counts(n, cfg: TransformerConfig):
    """``(blocks attended, blocks visible)`` of ONE K/V head, summed
    over the queries that see ``n`` rows (a host array of counts) and
    more than ``sparse_dense_len`` of them: what :func:`sparse_pick`
    makes stand follows from the lengths alone, so a scheduler counts
    it without a read from the device."""
    n = np.asarray(n, np.int64).reshape(-1)
    n = n[n > cfg.sparse_dense_len]
    blk = cfg.sparse_block
    sees = (n - 1) // blk + 1
    first = np.maximum(n - cfg.sparse_window, 0) // blk
    held = (sees - first) + np.minimum(cfg.sparse_init_blocks, first)
    attended = held + np.minimum(cfg.sparse_topk, sees - held)
    return int(attended.sum()), int(sees.sum())


def sparse_attention_dense(q, k, v, cfg):
    """Causal attention of a whole sequence over itself with every
    query's own selection of key blocks (:func:`sparse_pick`), the
    scores of all pairs materialised: the dense forward's form. q (B,
    T, H, D), k, v (B, T, Hkv, D) -> (B, T, H, D)."""
    B, T, H, D = q.shape
    Hkv, blk, st = k.shape[2], cfg.sparse_block, cfg.sparse_stride
    nb = -(-T // blk)
    pad = nb * blk - T
    with jax.named_scope("sparse_pool"):
        cells = pool_cells(
            jnp.pad(k, [(0, 0), (0, pad), (0, 0), (0, 0)]), cfg)
    n = jnp.arange(T) + 1
    with jax.named_scope("sparse_select"):
        stands = jax.vmap(lambda qb, cb: sparse_pick(
            qb, cb, n, cfg, nb)[0])(q, cells)           # (B, T, Hkv, nb)
    rows = jnp.take(stands, jnp.arange(T) // blk, axis=-1)  # (B,T,Hkv,T)
    seen = rows & (jnp.arange(T)[None, :] <= jnp.arange(T)[:, None])[
        None, :, None, :]
    seen = jnp.repeat(seen.transpose(0, 2, 1, 3), H // Hkv, axis=1)
    g = H // Hkv
    s = jnp.einsum("bqhd,bkhd->bhqk", q, jnp.repeat(k, g, axis=2),
                   preferred_element_type=jnp.float32)
    p = jax.nn.softmax(jnp.where(seen, s * cfg.softmax_scale, -1e30),
                       axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p.astype(q.dtype),
                      jnp.repeat(v, g, axis=2))


def _mlp(x, lp):
    a = jax.nn.gelu(jnp.einsum("bld,df->blf", x, lp["w1"]) + lp["b1"])
    return jnp.einsum("blf,fd->bld", a, lp["w2"])


def _swiglu(x, w_gate, w_up, w_down, gate_scale: float = 1.0):
    a = jax.nn.silu(_scaled(jnp.einsum("bld,df->blf", x, w_gate),
                            gate_scale))
    return jnp.einsum("blf,fd->bld", a * jnp.einsum("bld,df->blf", x, w_up),
                      w_down)


def ffn_half(x, lp, cfg, li, *, tp_psum=False):
    """Layer ``li``'s feed-forward half on (B, L, D). Returns
    ``(x, aux, hit)``: the Switch layer's load-balance loss (0
    elsewhere) and, for a dropless expert layer, how many experts got
    at least one token (None elsewhere). ``tp_psum`` is the sharded
    programs' (plain block only): hidden widths are ``tp`` shards.
    Where the residual path is streams, x is the streams."""
    with jax.named_scope("ffn"):
        x, mix = hc_pre(x, lp, cfg, "hc2")
        h = _norm(x, lp, "ln2", cfg)
        aux, hit = jnp.float32(0.0), None
        if cfg.dropless(li):
            y, hit = moe_ffn_topk(h, lp, cfg)
        elif cfg.n_experts and cfg.layer_experts is None:
            if tp_psum:
                # expert hidden dims are tp shards; bias rides outside
                # the psum (it is tp-replicated, see moe_ffn_sharded)
                y, ybias, aux = moe_ffn_sharded(h, lp, cfg.capacity_factor)
                y = jax.lax.psum(y, "tp") + ybias
            else:
                y, aux = moe_ffn_dense(h, lp, cfg.capacity_factor)
        elif cfg.ffn == "swiglu":
            y = _scaled(_swiglu(h, lp["w_gate"], lp["w_up"], lp["w_down"],
                                cfg.ffn_gate_scale), cfg.ffn_down_scale)
        else:
            y = _mlp(h, lp)
            if tp_psum:
                y = jax.lax.psum(y, "tp")  # d_ff shard partial-sum
            if (not cfg.post_norm and mix is None
                    and cfg.residual_scale == 1.0):
                return x + y + lp["b2"], aux, hit  # b2 replicated
            y = y + lp["b2"]
        if cfg.post_norm:
            y = _norm(y, lp, "ln2p", cfg)
        return hc_post(x, _res(y, cfg), mix), aux, hit


def embed(params, tokens, cfg):
    with jax.named_scope("embed"):
        x = params["emb"][tokens]
        if cfg.emb_scale != 1.0:
            x = x * jnp.asarray(cfg.emb_scale, x.dtype)
        if cfg.hc_mult > 1:  # every stream starts as the embedding
            x = jnp.broadcast_to(
                x[:, :, None], x.shape[:2] + (cfg.hc_mult, x.shape[-1]))
        return x


def head_logits(params, x, cfg):
    """Final norm and the output head on (B, L, D) (streams already
    folded, :func:`hc_fold`): the tied embedding, or ``params["head"]``."""
    with jax.named_scope("head"):
        x = _norm(x, params, "lnf", cfg)
        if cfg.head_scale != 1.0:
            x = x * jnp.asarray(cfg.head_scale, x.dtype)
        w = params["emb"] if cfg.tie_head else params["head"]
        return jnp.einsum("bld,vd->blv", x, w)


# The multi-token-prediction module (``cfg.mtp_depth``), written once:
# every caller (the dense forward below, a prefill chunk, the first
# token's program and the drafting tick of models/serving.py) makes the
# block's input with :func:`mtp_input`, runs ``params["mtp"]["block"]``
# as it runs the model's last layer (its own cache layer behind the
# model's), and reads the result with :func:`mtp_logits`.


def mtp_input(params, h, nxt, cfg):
    """The module's block input at positions i: ``[norm_h(h_i) ;
    norm_e(emb(t_{i+1}))] eh_proj`` from the model's last block output
    ``h`` (B, L, D), before the final norm, and the tokens ``nxt``
    (B, L) that FOLLOW those positions. Which half comes first is a
    relabelling of ``eh_proj``'s rows."""
    mp = params["mtp"]
    e = embed(params, nxt, cfg)
    with jax.named_scope("mtp_proj"):
        x = jnp.concatenate(
            [_norm(h, mp, "hn", cfg), _norm(e, mp, "en", cfg)], axis=-1)
        return jnp.einsum("blc,cd->bld", x, mp["eh_proj"])


def mtp_logits(params, x, cfg):
    """The module's final norm and the MODEL'S head on its block's
    output (B, L, D): row i's logits are for token i + 2. Its own
    scope, not ``head``: a reader of the model's head reads one product
    a step."""
    with jax.named_scope("mtp_head"):
        x = _norm(x, params["mtp"], "lnf", cfg)
        w = params["emb"] if cfg.tie_head else params["head"]
        return jnp.einsum("bld,vd->blv", x, w)


def make_kv_slice(cfg: TransformerConfig):
    """GQA with kv_heads < tp (call inside shard_map): wk/wv arrive
    tp-REPLICATED (:func:`_kv_tp_sharded`); this device's q-head shard
    [t*H/tp, (t+1)*H/tp) reads exactly one kv head, t*kv_heads // tp —
    the returned callable slices it so the attention kernels see the
    aligned local grouping (all local q heads -> local kv head 0).
    Returns None when kv heads shard evenly (nothing to slice). Shared
    by the training forward and the decode path (models/decode.py) so
    the index math cannot drift between them."""
    tp = jax.lax.axis_size("tp")
    if cfg.kv_heads % tp == 0:
        return None

    def kv_slice(a):
        idx = jax.lax.axis_index("tp") * cfg.kv_heads // tp
        return jax.lax.dynamic_slice_in_dim(a, idx, 1, axis=2)

    return kv_slice


def _local_attention(cfg: TransformerConfig):
    """The per-device (unsharded) attention kernel selected by config."""
    return partial(
        resolve_attention_impl(cfg.attn_impl), causal=True,
        window=cfg.attn_window,
    )


def _mixer_dense(x, lp, cfg, li: int, rope, impl):
    """Layer ``li``'s token mixer over a whole sequence: attention, the
    gated delta rule from a zero state, or latent attention in its
    expanded form."""
    x, mix = hc_pre(x, lp, cfg, "hc1")
    beside = None
    if cfg.ssm(li):
        beside = ssm_half(x, lp, zero_state(cfg, li, x.shape[0]), cfg)[0]
    elif cfg.state(li):
        return state_half(x, lp, zero_state(cfg, li, x.shape[0]), cfg, li,
                          rope, mix=mix)[0]
    if cfg.mla(li):
        qn, qr, row = mla_project(x, lp, cfg, rope)
        with jax.named_scope("mla_attn"):
            o = mla_expanded(qn, qr, row, lp, cfg)
        return mla_merge(x, o, lp, cfg, mix)
    q, k, v, gate = attn_qkv(x, lp, cfg, li, rope)
    if cfg.sparse(li):
        o = sparse_attention_dense(q, k, v, cfg)
    else:
        o = impl(q, k, v, causal=True, window=cfg.windows[li],
                 scale=cfg.softmax_scale)
    return attn_merge(x, o, gate, lp, cfg, mix=mix, beside=beside)


def forward_dense(params: dict, tokens: jax.Array, cfg: TransformerConfig):
    """Unsharded oracle forward: full attention, no collectives. The
    sharded program must agree with this bit-for-float."""
    return _forward_dense_aux(params, tokens, cfg)[0]


def forward_dense_mtp(params, tokens, cfg: TransformerConfig):
    """The dense forward with the multi-token-prediction module behind
    it: ``(logits (B, L, V), mtp_logits (B, L - 1, V))``. Row i of the
    second is the module's guess of token i + 2 from the model's last
    block output at i and token i + 1: the oracle of what
    ``ServingScheduler(draft="mtp")`` drafts."""
    if not cfg.mtp_depth:
        raise ValueError("forward_dense_mtp needs TransformerConfig("
                         "mtp_depth=1) and params['mtp']")
    logits, _, h = _forward_dense_aux(params, tokens, cfg, hidden=True)
    L, li = tokens.shape[1] - 1, cfg.n_layers - 1
    rope = partial(_rope, pos=jnp.arange(L), theta=cfg.rope_theta,
                   table=cfg.rope_table)
    block = params["mtp"]["block"]
    with jax.named_scope("mtp"):
        x = mtp_input(params, h[:, :L], tokens[:, 1:], cfg)
        x = _mixer_dense(x, block, cfg, li, rope,
                         resolve_attention_impl(cfg.attn_impl))
        x, _, _ = ffn_half(x, block, cfg, li)
        return logits, mtp_logits(params, x, cfg)


def _forward_dense_aux(params, tokens, cfg: TransformerConfig,
                       hidden: bool = False):
    """Dense forward returning (logits, summed MoE aux loss) and, with
    ``hidden``, the last block's output before the final norm."""
    pos = jnp.arange(tokens.shape[1])
    x = embed(params, tokens, cfg)
    rope = partial(_rope, pos=pos, theta=cfg.rope_theta,
                   table=cfg.rope_table)
    impl = resolve_attention_impl(cfg.attn_impl)

    def one_layer(x, lp, li):
        x = _mixer_dense(x, lp, cfg, li, rope, impl)
        x, a, _ = ffn_half(x, lp, cfg, li)
        return x, a

    layer_fn = (jax.checkpoint(one_layer, static_argnums=(2,))
                if cfg.remat else one_layer)
    aux = jnp.float32(0.0)
    for li, lp in enumerate(params["layers"]):
        x, a = layer_fn(x, lp, li)
        aux = aux + a
    x = hc_fold(x, cfg)
    out = head_logits(params, x, cfg), aux
    return out + (x,) if hidden else out


def _forward_local(params, tokens, cfg: TransformerConfig):
    """Per-shard forward: tokens are the batch/sequence-local chunk,
    params the tp/ep-local shards. Returns (local logits (B', L', V),
    summed MoE aux loss)."""
    require_plain_block(cfg, "the sharded forward")
    Lc = tokens.shape[1]
    pos = jax.lax.axis_index("sp") * Lc + jnp.arange(Lc)
    if cfg.attn == "ring":
        attn = partial(
            ring_self_attention, axis="sp", causal=True,
            window=cfg.attn_window,
        )
    elif cfg.attn == "ulysses":
        attn = partial(
            ulysses_attention, axis="sp", causal=True,
            impl=cfg.attn_impl, window=cfg.attn_window,
        )
    else:
        raise ValueError(f"unknown sharded attention kind {cfg.attn!r}")
    kv_slice = make_kv_slice(cfg)
    x = embed(params, tokens, cfg)
    rope = partial(_rope, pos=pos)

    def one_layer(x, lp):
        q, k, v, gate = attn_qkv(x, lp, cfg, 0, rope, kv_slice)
        # tp combine: heads were a shard, the out-projection partial-sums
        x = attn_merge(x, attn(q, k, v), gate, lp, cfg, tp_psum=True)
        x, a, _ = ffn_half(x, lp, cfg, 0, tp_psum=True)
        return x, a

    # remat recomputes each layer's activations in the backward — the
    # collectives inside (tp psum, ring ppermute / ulysses all_to_all,
    # MoE all_to_all) replay under jax.checkpoint like any other op
    layer_fn = jax.checkpoint(one_layer) if cfg.remat else one_layer
    aux = jnp.float32(0.0)
    for lp in params["layers"]:
        x, a = layer_fn(x, lp)
        aux = aux + a
    return head_logits(params, x, cfg), aux


def batch_axes(cfg: TransformerConfig) -> tuple[str, ...]:
    """Mesh axes the batch/sequence is sharded over: MoE adds ``ep`` as
    an extra batch-sharding axis so every ep member routes distinct
    tokens (GShard layout)."""
    return ("dp", "ep", "sp") if cfg.n_experts else ("dp", "sp")


def data_spec(cfg: TransformerConfig) -> P:
    """PartitionSpec of global (B, L) token arrays."""
    return P(("dp", "ep"), "sp") if cfg.n_experts else P("dp", "sp")


def nll_loss(logits, targets, axes):
    """Mean token NLL over all devices of the batch-sharding ``axes``;
    call inside shard_map (shared by the flat and pipeline programs).

    Written in logsumexp form (``lse - logits[target]``) rather than
    ``log_softmax`` + gather: same math, same gradient (softmax minus
    one-hot), but the full (B, L, V) normalized array is never
    materialized in f32 — only the reductions are. On the chip the
    scope ``loss`` is 2.4 ms of ``train_sc2_8k``'s 794 ms step, all of
    it forward (PERF.md section 5, PR 36): the backward's softmax minus
    one-hot is fused into the head's two backward products and reads
    under ``head``."""
    with jax.named_scope("loss"):
        logits = logits.astype(jnp.float32)
        lse = jax.nn.logsumexp(logits, axis=-1)
        tl = jnp.take_along_axis(
            logits, targets[..., None], axis=-1)[..., 0]
        nll = lse - tl
        total = jax.lax.psum(nll.sum(), axes)
        count = jax.lax.psum(jnp.asarray(nll.size, jnp.float32), axes)
        return total / count


def sgd_step(loss_fn, *, lr: float, donate: bool = False):
    """Jitted (params, tokens, targets) -> (params, loss) SGD step over
    any shard_map loss; XLA propagates the NamedShardings through the
    update (shared by the flat and pipeline train steps).

    ``donate=True`` donates the incoming params to the update so XLA
    writes the new params into the same HBM buffers — the layout for
    iterated training loops (the bench chains steps this way); the
    caller must not reuse a donated pytree after the call."""
    return sgd_step_from_grads(
        _value_and_grad3(loss_fn), lr=lr, donate=donate
    )


def _loss_local(params, tokens, targets, cfg: TransformerConfig):
    logits, aux = _forward_local(params, tokens, cfg)
    axes = batch_axes(cfg)
    loss = nll_loss(logits, targets, axes)
    if cfg.n_experts and cfg.moe_aux_coef:
        # mean of the per-member aux losses (each over local tokens)
        members = jax.lax.psum(jnp.float32(1.0), axes)
        loss = loss + cfg.moe_aux_coef * jax.lax.psum(aux, axes) / members
    return loss


def make_forward(cfg: TransformerConfig, mesh: Mesh):
    """Jitted sharded forward over global (B, L) token arrays."""

    def fwd_local(params, tokens):
        return _forward_local(params, tokens, cfg)[0]

    f = jax.shard_map(
        fwd_local,
        mesh=mesh,
        in_specs=(param_specs(cfg, mesh), data_spec(cfg)),
        out_specs=data_spec(cfg),
        # interpret-mode Pallas (flash attn on the CPU test mesh) trips
        # the vma checker — see parallel/ring_attention._make_wrapped;
        # compiled-on-TPU flash keeps the check on
        check_vma=not _flash_interpreted(cfg.attn_impl),
    )
    return jax.jit(f)


def optax_step(loss_fn, tx, *, donate: bool = False):
    """Jitted (params, opt_state, tokens, targets) -> (params,
    opt_state, loss) step for any optax GradientTransformation over a
    shard_map loss. Build the optimizer state with
    :func:`make_opt_init`'s ``init_state`` — NOT bare
    ``jax.jit(tx.init)``, which does not propagate the params'
    shardings to the moments (see :func:`make_opt_init`).
    ``donate=True`` donates params AND opt_state for in-place HBM
    updates in iterated loops."""
    return optax_step_from_grads(
        _value_and_grad3(loss_fn), tx, donate=donate
    )


def _value_and_grad3(loss_fn):
    def grad_fn(params, tokens, targets):
        return jax.value_and_grad(loss_fn)(params, tokens, targets)

    return grad_fn


def sgd_step_from_grads(grad_fn, *, lr: float, donate: bool = False):
    """SGD update over any ``grad_fn(params, tokens, targets) ->
    (loss, grads)`` — the shared body of :func:`sgd_step` and the
    pipeline train steps (parallel/pipeline.py), so the update rule
    lives in exactly one place."""

    def step(params, tokens, targets):
        loss, grads = grad_fn(params, tokens, targets)
        with jax.named_scope("sgd_update"):
            params = jax.tree.map(
                lambda p, g: p - lr * g.astype(p.dtype), params, grads
            )
        return params, loss

    return jax.jit(step, donate_argnums=(0,) if donate else ())


def optax_step_from_grads(grad_fn, tx, *, donate: bool = False):
    """Optax update over any ``grad_fn(params, tokens, targets) ->
    (loss, grads)`` (shared by :func:`optax_step` and the pipeline
    optax step)."""
    import optax

    def step(params, opt_state, tokens, targets):
        loss, grads = grad_fn(params, tokens, targets)
        updates, opt_state = tx.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return params, opt_state, loss

    return jax.jit(step, donate_argnums=(0, 1) if donate else ())


def _make_loss_fn(cfg: TransformerConfig, mesh: Mesh):
    """The sharded scalar loss both train-step flavors differentiate
    (one place for the spec wiring and the interpreted-flash vma
    exemption — see make_forward)."""
    return jax.shard_map(
        partial(_loss_local, cfg=cfg),
        mesh=mesh,
        in_specs=(param_specs(cfg, mesh), data_spec(cfg), data_spec(cfg)),
        out_specs=P(),
        check_vma=not _flash_interpreted(cfg.attn_impl),
    )


def make_optax_train_step(
    cfg: TransformerConfig, mesh: Mesh, tx, *, donate: bool = False,
):
    """Like :func:`make_train_step` but stepping any optax optimizer
    (Adam/AdamW/etc.) instead of plain SGD. Returns ``(step,
    init_state)``; calling ``init_state(params)`` builds the optimizer
    state under jit so every state leaf inherits its param's
    NamedSharding (tp-sharded weights get tp-sharded moments — no
    replicated extra model copies in HBM):

    >>> tx = optax.adamw(3e-4)
    >>> step, init_state = make_optax_train_step(cfg, mesh, tx)
    >>> opt_state = init_state(params)
    >>> params, opt_state, loss = step(params, opt_state, inp, tgt)

    The reference has no optimizer layer at all (its workloads are
    user conventions); this is framework surface the flagship model
    family needs.
    """
    step = optax_step(_make_loss_fn(cfg, mesh), tx, donate=donate)
    return step, make_opt_init(tx)


def make_opt_init(tx):
    """(params) -> optimizer state whose param-like leaves (moments)
    carry their parameter's sharding FROM INIT, not only after the
    first step. ``jax.jit(tx.init)`` alone does NOT propagate input
    shardings to its outputs (measured: every moment lands
    single-device; the round-3 assertion only passed because it ran
    after a step had resharded the state). The state's sharding pytree
    is built up front (param-like leaves take their parameter's
    sharding via ``optax.tree_map_params`` over an ``eval_shape``
    skeleton, step counts replicate) and passed as jit
    ``out_shardings`` — so the state MATERIALIZES sharded and no
    unsharded copy ever exists, which matters at exactly the scale
    where sharded moments are the point."""
    import optax

    def init_state(params):
        shardings = [
            p.sharding for p in jax.tree.leaves(params)
            if isinstance(p, jax.Array)
            and isinstance(p.sharding, NamedSharding)
        ]
        if not shardings:
            return jax.jit(tx.init)(params)  # dense/single-device
        replicated = NamedSharding(shardings[0].mesh, P())
        skeleton = jax.eval_shape(tx.init, params)
        out_shardings = optax.tree_map_params(
            tx,
            lambda _, p: p.sharding,
            skeleton,
            params,
            transform_non_params=lambda _: replicated,
        )
        return jax.jit(tx.init, out_shardings=out_shardings)(params)

    return init_state


def make_train_step(
    cfg: TransformerConfig, mesh: Mesh, *, lr: float = 1e-2,
    donate: bool = False,
):
    """Jitted (params, tokens, targets) -> (params, loss) SGD step.

    The loss/grad runs as one shard_map program (explicit ring/tp
    collectives inside); the parameter update stays in plain jit where
    XLA propagates the NamedShardings.

    Each call's dispatch is inside the host span ``train.step``
    (obs/timeline.py: annotate; written where a profiler session is
    open, an atomic check where none is), whose arguments are
    :func:`_train_step_counts`. The program keeps its name,
    ``jit_step``, and what is returned keeps ``.lower``.
    """
    step = sgd_step(_make_loss_fn(cfg, mesh), lr=lr, donate=donate)

    @wraps(step)
    def train_step(params, tokens, targets):
        with annotate("train.step",
                      **_train_step_counts(cfg, tokens.shape)):
            return step(params, tokens, targets)

    train_step.lower = step.lower
    return train_step


@lru_cache(maxsize=32)
def _train_step_counts(cfg: TransformerConfig, shape: tuple) -> dict:
    """``train.step``'s arguments, counts the program knows from its
    shapes alone, made once a shape: ``tokens`` (B x L of the global
    batch) and, where the step's attention is the flash kernels over
    the whole sequence (Ulysses, ``attn_impl="flash"``), what one
    (batch x head) forward sweep of their grid does
    (ops/flash_attention.py ``block_plan``, every key of it):
    ``flash_block``, ``flash_tile``, ``flash_grid_steps``,
    ``flash_tile_steps``, ``flash_run_steps``, ``flash_interior_steps``,
    ``flash_pairs_run``, ``flash_pairs_band``."""
    B, L = shape
    counts = {"tokens": B * L}
    if cfg.attn == "ulysses" and cfg.attn_impl == "flash":
        from ..ops.flash_attention import block_plan

        plan = block_plan(
            L, L, causal=True, window=cfg.attn_window,
            head_dim=cfg.head_dim, itemsize=jnp.dtype(cfg.dtype).itemsize)
        counts.update({f"flash_{k}": v for k, v in plan.items()})
    return counts


def shard_params(params: dict, cfg: TransformerConfig, mesh: Mesh) -> dict:
    """Place a replicated param pytree onto the mesh per param_specs."""
    return jax.tree.map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)),
        params,
        param_specs(cfg, mesh),
    )
