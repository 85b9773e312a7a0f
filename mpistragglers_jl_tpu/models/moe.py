"""Mixture-of-experts FFN with expert parallelism over an ``"ep"`` axis.

The reference has no model code and exactly one parallelism strategy
(coordinator/worker data-parallel map — SURVEY §2 "Parallelism
strategies"); expert parallelism is a north-star capability this
framework adds so the flagship transformer exercises every axis of a
modern TPU mesh (dp, sp, tp, ep) in one program.

Design (TPU-first, GShard/Switch lineage):

* **Top-1 routing with static capacity.** Every shape is static: each
  token picks its argmax expert, takes a slot among that expert's
  ``capacity`` slots (computed by a cumsum over the one-hot dispatch —
  no sort, no dynamic shapes), and tokens beyond capacity are dropped
  (they ride the residual connection, the standard Switch behavior).
  The router gradient flows through the gate probability that scales
  the combined expert output.
* **Dispatch/combine as gather/scatter.** Routing materializes a
  static (experts, capacity) token-index table
  (:func:`switch_route_indices`); dispatch is one gather, combine one
  scatter-add — O(E*C*D) HBM traffic and no MXU work. The classic
  Mesh-TF one-hot einsum formulation (:func:`switch_route`) is kept as
  the oracle the gather form is tested equal against: its (T, E*C, D)
  dispatch matmuls are quadratic in token count and cost more than the
  expert FFNs themselves at flagship token counts (round 4: earlier
  installation, not repeated on this one).
* **Expert parallelism = all_to_all over ``"ep"``.** Experts are
  sharded over the ``ep`` mesh axis and the *batch* is sharded over
  ``(dp, ep)`` — every ep member holds distinct tokens, so the tiled
  ``all_to_all`` exchanges "my tokens for your experts" in one ICI
  collective each way, the expert FFN runs on local experts only, and
  a second all_to_all restores token ownership.
* **tp composes.** Each expert's hidden dim is additionally sharded
  over ``tp`` (Megatron split); the caller psums the down-projection
  over ``tp`` exactly like the dense MLP path.

The dense path (:func:`moe_ffn_dense`) runs identical routing math with
all experts resident — it is the correctness oracle for the sharded
path and the single-chip execution mode.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "init_moe_layer",
    "moe_layer_specs",
    "switch_route",
    "switch_route_indices",
    "moe_ffn_dense",
    "moe_ffn_sharded",
    "init_topk_layer",
    "topk_route",
    "group_tiling",
    "grouped_matmul",
    "moe_ffn_topk",
]


def init_moe_layer(rng: np.random.Generator, d_model: int, d_ff: int,
                   n_experts: int, n_layers: int, dtype) -> dict:
    """Per-layer MoE params: router + stacked expert FFN weights.

    Expert weights carry a leading (n_experts,) axis — the axis the
    ``ep`` PartitionSpec shards.
    """
    E, D, F = n_experts, d_model, d_ff
    sd = lambda *s: jnp.asarray(
        rng.standard_normal(s) / np.sqrt(s[-2]), dtype
    )
    return {
        # the router stays f32 at ANY model dtype: wg is only (D, E) —
        # E columns of weights, bytes that round to zero next to the
        # expert FFNs — while routing decisions (argmax over logits,
        # gate magnitudes, the load-balance loss) are exactly the
        # quantities bf16 rounding perturbs first. tests/test_moe.py
        # pins bf16-activation routing against the f32 router.
        "wg": jnp.asarray(rng.standard_normal((D, E)) * 0.02,
                          jnp.float32),
        "we1": sd(E, D, F),
        "be1": jnp.zeros((E, F), dtype),
        # float(): np.float64 scalars promote f32 params under x64
        "we2": sd(E, F, D) / float(np.sqrt(n_layers)),
        "be2": jnp.zeros((E, D), dtype),
    }


def moe_layer_specs():
    """PartitionSpecs for :func:`init_moe_layer`: experts over ``ep``,
    the expert hidden dim over ``tp``, router replicated."""
    from jax.sharding import PartitionSpec as P

    return {
        "wg": P(),
        "we1": P("ep", None, "tp"),
        "be1": P("ep", "tp"),
        "we2": P("ep", "tp", None),
        "be2": P("ep", None),
    }


def switch_route(x2d: jax.Array, wg: jax.Array, capacity: int):
    """Top-1 routing of (T, D) tokens over E = wg.shape[1] experts.

    Returns ``(dispatch, combine, aux)``:

    * ``dispatch`` — (T, E, C) 0/1 float: token t occupies slot c of
      expert e. At most ``capacity`` tokens per expert (cumsum slot
      assignment in arrival order); overflow rows are all-zero.
    * ``combine`` — ``dispatch`` scaled by the token's gate probability;
      contracting expert outputs against it yields the MoE output (and
      routes the gradient into the router).
    * ``aux`` — Switch load-balance loss ``E * sum_e f_e * p_e`` where
      ``f_e`` is the dispatched-token fraction and ``p_e`` the mean
      router probability of expert e; 1.0 at perfect balance.
    """
    E = wg.shape[1]
    expert, slot, gate, aux = _route(x2d, wg)
    onehot = jax.nn.one_hot(expert, E, dtype=jnp.float32)  # (T, E)
    dispatch = onehot[:, :, None] * jax.nn.one_hot(
        slot, capacity, dtype=jnp.float32
    )[:, None, :]  # (T, E, C); one_hot(slot >= C) is all-zero = dropped
    combine = dispatch * gate[:, None, None].astype(jnp.float32)
    return dispatch, combine, aux


def _route(x2d: jax.Array, wg: jax.Array):
    """The router core shared by both routing forms: top-1 expert,
    cumsum slot (in token order), gate probability, Switch aux loss.
    Returns ``(expert (T,), slot (T,), gate (T,) f32, aux)``."""
    # f32 ACCUMULATION without materializing an f32 copy of the whole
    # (T, D) activation (the astype form wrote+read 2x64 MB per layer
    # for a 4-column matmul — the single largest routing cost measured;
    # earlier installation, not repeated on this one). The router WEIGHT is not
    # downcast to the activation dtype: wg stays f32 (it is only
    # (D, E)) and the mixed-precision dot accumulates in f32 via
    # preferred_element_type — bf16 rounding touches the activations
    # once (they already are bf16), never the router's parameters.
    logits = jnp.einsum(
        "td,de->te", x2d, wg,
        preferred_element_type=jnp.float32,
    )  # (T, E) f32
    probs = jax.nn.softmax(logits, axis=-1)
    expert = jnp.argmax(probs, axis=-1)  # (T,)
    gate = jnp.take_along_axis(probs, expert[:, None], axis=1)[:, 0]
    onehot = jax.nn.one_hot(expert, wg.shape[1], dtype=jnp.float32)
    # slot within the chosen expert, in token order; >= capacity drops
    slot = (jnp.cumsum(onehot, axis=0) - onehot) * onehot  # (T, E)
    slot = slot.sum(axis=1).astype(jnp.int32)  # (T,)
    frac = onehot.mean(axis=0)
    aux = wg.shape[1] * jnp.sum(frac * probs.mean(axis=0))
    return expert, slot, gate, aux


def switch_route_indices(x2d: jax.Array, wg: jax.Array, capacity: int):
    """Top-1 routing as static-shape INDEX TABLES (the gather/scatter
    form of :func:`switch_route`).

    The one-hot ``dispatch``/``combine`` tensors of the Mesh-TF
    formulation turn routing into (T, E*C, D) matmuls — quadratic in
    token count (T=16k tokens at the flagship rung shape is ~0.7
    TFLOP per layer of pure dispatch, more than the expert FFNs
    themselves). This form replaces them with a (E, C) token-index
    table: dispatch is a gather, combine is a scatter-add — O(E*C*D)
    HBM traffic, zero MXU work, identical semantics (same cumsum slot
    assignment, same capacity drops; measured-equal to the one-hot
    path in tests/test_moe.py).

    Returns ``(table, expert, gate, aux)``: ``table[e, c]`` is the
    token index occupying slot c of expert e, or ``T`` (a sentinel one
    past the last token) for empty slots; ``expert`` (T,) each token's
    chosen expert; ``gate`` (T,) f32 router probabilities of the chosen
    expert; ``aux`` the Switch load-balance loss.
    """
    table, expert, _, gate, aux = _route_tables(x2d, wg, capacity)
    return table, expert, gate, aux


def _route_tables(x2d: jax.Array, wg: jax.Array, capacity: int):
    """:func:`switch_route_indices` plus the per-token ``slot`` — the
    inverse seating map the gather-form backward passes need.

    The (E, C) table is built by a STABLE SORT of token indices by
    expert, not a scatter: a (T,)-element scatter serializes on the
    TPU and measured as a chip-rate-invariant ~ms-scale floor in the
    MoE step (the step barely moved when the chip's minute-rate did —
    r5). Sort keeps token order within each expert group, so sorted
    position == the cumsum slot and the two constructions agree
    exactly (pinned against the one-hot oracle in tests)."""
    T = x2d.shape[0]
    E = wg.shape[1]
    expert, slot, gate, aux = _route(x2d, wg)
    # tokens grouped by expert, token order preserved within a group
    _, sorted_tok = jax.lax.sort(
        (expert, jnp.arange(T, dtype=jnp.int32)), num_keys=1,
        is_stable=True,
    )
    counts = jnp.sum(
        jax.nn.one_hot(expert, E, dtype=jnp.int32), axis=0
    )  # (E,)
    start = jnp.cumsum(counts) - counts  # exclusive prefix
    c_idx = jnp.arange(capacity, dtype=jnp.int32)[None, :]  # (1, C)
    flat = start[:, None] + c_idx  # (E, C) indices into sorted_tok
    seated = jnp.take(
        sorted_tok, jnp.minimum(flat, T - 1), axis=0
    )
    valid = c_idx < counts[:, None]
    table = jnp.where(valid, seated, T)
    return table, expert, slot, gate, aux


# Dispatch and combine are the SAME bijection between kept tokens and
# their (expert, slot) seats, applied in opposite directions — so both
# directions of both ops are GATHERS. Left to autodiff, the transpose
# of each gather is a scatter-add, and TPU scatter-adds (plus the
# sentinel row's duplicate indices) measured as the dominant routing
# cost in the r4 rung (earlier installation, not repeated on this
# one); the custom
# VJPs below express each backward as the inverse gather instead,
# eliminating every (T-or-EC, D)-scale scatter from the layer.


def _int_zero(a):
    """float0 cotangent for an integer primal (custom_vjp contract)."""
    return np.zeros(a.shape, dtype=jax.dtypes.float0)


def _seat_gather(x2d, table):
    T = x2d.shape[0]
    safe = jnp.minimum(table, T - 1)
    return x2d[safe] * (table < T)[..., None].astype(x2d.dtype)


def _token_gather(w_ecd, expert, slot):
    E, C, D = w_ecd.shape
    kept = (slot < C)[:, None].astype(w_ecd.dtype)
    idx = expert * C + jnp.minimum(slot, C - 1)
    return w_ecd.reshape(E * C, D)[idx] * kept


@jax.custom_vjp
def _gather_dispatch(x2d, table, expert, slot):
    """(T, D) tokens -> (E, C, D) expert slots; empty slots are zeros.
    ``expert``/``slot`` ((T,), from :func:`_route`) are the inverse
    seating map driving the gather-form backward."""
    return _seat_gather(x2d, table)


def _gather_dispatch_fwd(x2d, table, expert, slot):
    return _seat_gather(x2d, table), (table, expert, slot)


def _gather_dispatch_bwd(res, g):
    table, expert, slot = res
    return (_token_gather(g, expert, slot), _int_zero(table),
            _int_zero(expert), _int_zero(slot))


_gather_dispatch.defvjp(_gather_dispatch_fwd, _gather_dispatch_bwd)


@jax.custom_vjp
def _combine_per_token(w_ecd, table, expert, slot):
    """(E, C, D) weighted slots -> (T, D): each token reads its own
    seat (dropped tokens read zero). Equal to the scatter-add combine
    because the seating is a bijection; both directions — like both
    directions of :func:`_gather_dispatch` — are gathers."""
    return _token_gather(w_ecd, expert, slot)


def _combine_per_token_fwd(w_ecd, table, expert, slot):
    return _token_gather(w_ecd, expert, slot), (table, expert, slot)


def _combine_per_token_bwd(res, g):
    table, expert, slot = res
    # dw[e, c] = dy[token seated at (e, c)], zero for empty seats —
    # exactly the dispatch gather applied to the cotangent
    return (_seat_gather(g, table), _int_zero(table),
            _int_zero(expert), _int_zero(slot))


_combine_per_token.defvjp(_combine_per_token_fwd, _combine_per_token_bwd)


def _scatter_combine(weighted, table, T):
    """Scatter-add oracle for :func:`_combine_per_token` (kept for the
    equivalence test; the hot paths use the gather form)."""
    E, C, D = weighted.shape
    y = jnp.zeros((T + 1, D), weighted.dtype)
    y = y.at[table.reshape(-1)].add(weighted.reshape(E * C, D))
    return y[:T]


def _expert_ffn(xe, mp):
    """Per-expert FFN on dispatched tokens xe (E_local, C', D); weights
    carry matching local leading axis."""
    a = jax.nn.gelu(
        jnp.einsum("ecd,edf->ecf", xe, mp["we1"]) + mp["be1"][:, None, :]
    )
    return jnp.einsum("ecf,efd->ecd", a, mp["we2"])


def moe_ffn_dense(x: jax.Array, mp: dict, capacity_factor: float):
    """Oracle/single-chip MoE FFN on (B, L, D); all experts resident.

    Returns ``(y, aux)``; dropped tokens contribute zeros to y (the
    caller's residual connection carries them through). ``be2`` is added
    via the combine weights so dropped tokens see no bias — the sharded
    path reproduces this exactly.
    """
    B, L, D = x.shape
    E = mp["wg"].shape[1]
    T = B * L
    C = _capacity(T, E, capacity_factor)
    x2d = x.reshape(T, D)
    table, expert, slot, gate, aux = _route_tables(x2d, mp["wg"], C)
    xe = _gather_dispatch(x2d, table, expert, slot)
    ye = _expert_ffn(xe, mp) + mp["be2"][:, None, :]
    # per-token combine (gather form); the gate multiply stays outside
    # the custom-vjp op so the router gradient flows through it
    yt = _combine_per_token(ye, table, expert, slot)
    kg = jnp.where(slot < C, gate, 0.0).astype(x.dtype)  # dropped -> 0
    y = yt * kg[:, None]
    return y.reshape(B, L, D), aux


def moe_ffn_sharded(x: jax.Array, mp: dict, capacity_factor: float,
                    *, ep_axis: str = "ep", tp_axis: str = "tp"):
    """Expert-parallel MoE FFN; call inside shard_map.

    ``x`` is the (B_local, L_local, D) activation chunk (batch sharded
    over (dp, ep), sequence over sp); ``mp`` holds the ep x tp-local
    expert shards per :func:`moe_layer_specs`. Routing and capacity are
    computed over *local* tokens (GShard convention). One tiled
    all_to_all ships dispatched tokens to their expert's owner, the
    expert FFN runs on (E/ep) local experts, and the inverse all_to_all
    ships results home. The caller must ``psum`` the returned y over
    ``tp`` (matching the dense-MLP Megatron pattern); the tp-replicated
    ``be2`` is folded in *after* that psum via the returned ``ybias``.

    Returns ``(y_partial, ybias, aux)`` with
    ``y = psum(y_partial, tp) + ybias``.
    """
    ep = jax.lax.axis_size(ep_axis)
    B, L, D = x.shape
    E_local = mp["we1"].shape[0]
    E = E_local * ep
    T = B * L
    C = _capacity(T, E, capacity_factor)
    x2d = x.reshape(T, D)
    # router: wg is replicated; logits over ALL E experts. Gather-form
    # dispatch (see switch_route_indices) — the (E, C, D) slot tensor
    # the all_to_all ships is built by a gather, not a T x E*C matmul.
    table, expert, slot, gate, aux = _route_tables(x2d, mp["wg"], C)
    xe = _gather_dispatch(x2d, table, expert, slot)
    # (E, C, D) -> ship expert-group j to ep member j; receive my
    # E_local experts' slots from every member: (E_local, ep*C, D)
    xe = jax.lax.all_to_all(
        xe, ep_axis, split_axis=0, concat_axis=1, tiled=True
    )
    ye = _expert_ffn(xe, mp)  # tp-partial over the d_ff shard
    # inverse: split the capacity axis back per source, return home
    ye = jax.lax.all_to_all(
        ye, ep_axis, split_axis=1, concat_axis=0, tiled=True
    )  # (E, C, D), tp-partial
    yt = _combine_per_token(ye, table, expert, slot)  # (T, D) tp-partial
    kg = jnp.where(slot < C, gate, 0.0).astype(x.dtype)  # dropped -> 0
    y = yt * kg[:, None]
    # be2 is replicated over tp, so it must bypass the caller's tp psum.
    # It is a rank-1 per-token quantity: kept-gate[t] * be2[expert[t]]
    # (one row gather — review r4; the kept mask is just slot < C now).
    be2 = jax.lax.all_gather(mp["be2"], ep_axis, axis=0, tiled=True)
    ybias = kg[:, None] * be2[expert]
    return y.reshape(B, L, D), ybias.reshape(B, L, D), aux


def _capacity(tokens: int, n_experts: int, capacity_factor: float) -> int:
    return max(1, int(np.ceil(tokens / n_experts * capacity_factor)))


# --------------------------------------------------------------------------
# dropless top-k experts beside shared ones (serving and the dense forward)
# --------------------------------------------------------------------------
#
# The Switch layer above seats tokens in a fixed number of slots per
# expert and drops the overflow, so what a token gets depends on which
# other tokens are in the call. The layer below never drops: every
# token is multiplied by exactly its own ``k`` experts, whatever the
# rest of the batch chose. It is therefore a per-token function, and a
# prompt prefilled in chunks, a decode step and the whole forward all
# give a token the same result. That is what lets the serving
# scheduler take it.


def init_topk_layer(rng: np.random.Generator, cfg) -> dict:
    """Per-layer params of the dropless layer: the router (float32, as
    the Switch router and for the same reason) with its per-expert
    selection bias, ``n_experts`` gated experts stacked on a leading
    axis, and ``shared_experts`` always-on ones as one gated MLP of
    their summed width. The bias is small and not zero, so that
    "selects but does not weigh" shows in every test that uses it."""
    D, E, F = cfg.d_model, cfg.n_experts, cfg.expert_width()
    sd = lambda *s: jnp.asarray(
        rng.standard_normal(s) / np.sqrt(s[-2]), cfg.dtype
    )
    out = {
        "router": jnp.asarray(
            rng.standard_normal((D, E)) / np.sqrt(D), jnp.float32),
    }
    if cfg.route_score == "sigmoid":
        out["router_bias"] = jnp.asarray(
            rng.standard_normal((E,)) * 0.02, jnp.float32)
    # the router scores all E experts; the matrices are the held ones'
    Eh = cfg.held_experts
    out.update({
        "we_gate": sd(Eh, D, F),
        "we_up": sd(Eh, D, F),
        # float(): np.float64 scalars promote f32 params under x64
        "we_down": sd(Eh, F, D) / float(np.sqrt(cfg.n_layers)),
    })
    Fs = cfg.shared_experts * F
    if Fs:
        out.update({
            "ws_gate": sd(D, Fs),
            "ws_up": sd(D, Fs),
            "ws_down": sd(Fs, D) / float(np.sqrt(cfg.n_layers)),
        })
        if cfg.shared_gate:
            out["ws_sgate"] = sd(D, 1)
    return out


def topk_route(x2d: jax.Array, router: jax.Array, bias, k: int,
               scale: float, score: str = "sigmoid", groups: int = 1,
               topk_groups: int = 1):
    """Top-k routing of (T, D) tokens: scores ``s = sigmoid(x @
    router)`` (or, ``score="softmax"``, the softmax over all experts)
    in float32; the ``k`` experts with the largest ``s + bias`` are
    chosen (the bias selects and does not weigh; None: no bias); their
    weights are ``s`` itself, normalised to sum to one and multiplied
    by ``scale``. With ``groups`` > 1 the choice is group-limited: the
    experts are ``groups`` equal runs, a group's score is the sum of
    its two largest ``s + bias``, and only the experts of the
    ``topk_groups`` best groups stand for the top-k (a token's experts
    then lie on at most that many nodes). Returns ``(idx (T, k) int32,
    w (T, k) float32)``."""
    logits = jnp.einsum(
        "td,de->te", x2d, router, preferred_element_type=jnp.float32,
    )
    if score == "softmax":
        s = jax.nn.softmax(logits, axis=-1)
    else:
        s = jax.nn.sigmoid(logits)
    sel = s if bias is None else s + bias
    if groups > 1:
        T, E = sel.shape
        by_group = sel.reshape(T, groups, E // groups)
        _, keep = jax.lax.top_k(
            jax.lax.top_k(by_group, 2)[0].sum(axis=-1), topk_groups)
        kept = jnp.zeros((T, groups), bool).at[
            jnp.arange(T)[:, None], keep].set(True)
        sel = jnp.where(kept[:, :, None], by_group, -jnp.inf).reshape(T, E)
    _, idx = jax.lax.top_k(sel, k)
    w = jnp.take_along_axis(s, idx, axis=1)
    w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20) * scale
    return idx, w


# m, k and n tile of the grouped product. Read on one v5e chip at the
# widths this was written for (128 experts, 2048 -> 1024 -> 2048,
# bfloat16; PR 26, PERF.md section 6): the three products of a
# 2048-row prefill chunk took 2.58 ms at this tiling against 3.33 ms
# at (128, 512, 512) and 5.38 ms through ``jax.lax.ragged_dot``; of a
# 128-row decode step, 1.50 against 1.91 and 1.90 ms.
# Where K or N is no multiple of 1024 (3584 = 3.5 x 1024 is the first)
# the tile follows the width (:func:`_width_tile`: 1792). It need not:
# ``megablox.gmm`` masks both operands of a ragged last k step in
# float32 and lets the last n block hang over. Read on one v5e chip
# (my chip runs, PR 34) at 64 experts, 3584 -> 1024 -> 3584, bfloat16,
# the three products with the 3584-wide dimension tiled at the divisors
# 512 / 896 / 1792 / at the ragged 1024: a decode step's 64 rows 1.232
# / 1.244 / 1.243 / 1.248 ms; a chunk's 1024 rows 2.282 / 2.254 /
# 2.241 / 2.349 ms; a group of four chunks' 4096 rows 3.100 / 3.023 /
# 2.978 / 3.134 ms. End to end on ``serve_xing4_mixed``, 1792 against
# the ragged 1024 on four shared seeds: ``serve_tok_s`` 935.2, 928.2,
# 923.3, 928.0 against 922.8, 912.4, 915.5, 926.4 (every pair, +0.2 to
# +1.7%), and the median token gap 13.29 to 13.32 ms in its four runs
# against 13.40 to 13.47 in the ragged tile's eight; in the traced
# windows the prefill programs' ``gmm`` takes 0.502 against 0.538 s of
# 4 s and the tick's the same (PERF.md section 6, PR 34).
# Over more than two row tiles the k tile is all of K
# (:func:`group_tiling`). Read on one v5e chip (my chip run, PR 35;
# bfloat16, every token's experts drawn evenly, the three products of
# one layer) at this tile / with K whole / with every group's rows
# padded to whole row tiles under this tile (the way not taken: its
# rows, and the row-wise work around the products that is not in
# these readings, grow from M to M + E * 127):
#   Trinity-Mini, 128 experts, 2048 -> 1024 -> 2048, top 8: a decode
#   step's 128 rows 1.583 / 1.580 ms (the same tile); a chunk's 2048
#   rows 2.579 / 2.417 / 2.762; a group of four chunks' 8192 rows
#   3.471 / 3.027 / 2.876 (the bytes of 128 experts: 1.97 ms);
#   Xing4.0, 64 experts, 3584 -> 1024 -> 3584, top 4: 64 rows 1.260 /
#   1.263; 1024 rows 2.223 / 2.097 / 2.348; 4096 rows 2.964 / 2.610 /
#   2.356 (the bytes: 1.72 ms);
#   Qwen3-Next, 256 held of 512 experts, 2048 -> 512 -> 2048, top 10:
#   160 rows 0.704 / 0.700; 2560 rows 2.524 / 2.318 / 3.189; 10240
#   rows 2.851 / 2.534 / 3.329 (the bytes: 1.97 ms).
# The gate product alone at Trinity's 8192 rows: 1.266 / 1.007 /
# 0.911 ms where its bytes are 0.66: with K whole each expert is read
# once (191 visits, 128 reads; this tile reads at all 191), and what is
# left is the kernel's: a visit of a group already in VMEM computes
# with no read beside it to hide behind, and every visit moves its row
# and output blocks. A row tile of 256 with K whole read the same or
# worse (3.043, 2.615, 2.855 at the groups' rows), an n tile of 512 at
# K 2048 worse (3.135). Not taken: with K whole the down product
# (K 1024 or 512) could have all of N as its n tile too, 1.150 ->
# 1.086 ms at Trinity's 8192 rows and 0.961 -> 0.911 at Qwen3-Next's
# 10240 (0.25 ms a program of four, under what the pairs can show).
# End to end: PERF.md section 6, PR 35.
_GROUP_TILE = (128, 1024, 1024)


def _width_tile(dim: int, cap: int) -> int:
    """The k or n tile of a width ``dim``: ``cap`` where it divides
    ``dim`` (or all of a narrower one), else the largest multiple of
    128 lanes up to twice ``cap`` that does (3584 at 1024: 1792), else
    ``cap`` with a ragged last tile."""
    if dim <= cap or dim % cap == 0:
        return min(dim, cap)
    return max((t for t in range(128, 2 * cap + 1, 128) if dim % t == 0),
               default=cap)


# Bytes of one right-hand block where the k tile is all of K. The
# pipeline holds two of them beside two row blocks, two output blocks
# and the float32 sums, inside the 16 MiB of scoped VMEM a kernel gets
# by default (``gmm`` hands no limit on): at (128, 2048, 1024) in
# bfloat16 that is 8 + 1 + 0.5 + 0.5 MiB.
_WHOLE_K_BLOCK = 4 << 20


def group_tiling(M: int, K: int, N: int, itemsize: int):
    """The (m, k, n) tile :func:`grouped_matmul` hands the kernel for
    ``M`` rows against (K, N) matrices of ``itemsize`` bytes a number.

    Up to two row tiles (a decode step's pairs): ``_GROUP_TILE``, each
    width's tile from :func:`_width_tile`. Over more row tiles (a
    prefill chunk's pairs, a group of chunks') many groups straddle a
    row tile's edge and are visited once per row tile they touch. The
    kernel fetches a right-hand block at every grid step whose block
    index differs from the step before: with two k tiles that is
    every visit, with ONE the visits of a group share its block and
    its matrix is read once per n tile. So there the k tile is all of
    K, and the n tile the widest that keeps the block within
    ``_WHOLE_K_BLOCK`` (K 2048: 1024; 3584: 512). A K too wide for
    even 128 columns of that keeps the first rule."""
    tm, ck, cn = _GROUP_TILE
    tm = tm if M >= tm else -(-M // 8) * 8
    fit = _WHOLE_K_BLOCK // (K * itemsize) // 128 * 128
    if M > 2 * tm and fit:
        return tm, K, _width_tile(N, min(cn, fit))
    return tm, _width_tile(K, ck), _width_tile(N, cn)


def grouped_matmul(xs: jax.Array, w: jax.Array, sizes: jax.Array,
                   out_dtype) -> jax.Array:
    """``xs[rows of group e] @ w[e]`` for every group: ``xs`` (M, K)
    holds each group's rows together, in group order, ``w`` is
    (E, K, N) and ``sizes`` (E,) int32 counts each group's rows (zero
    allowed). One Pallas call (``megablox.gmm``) that visits, tile by
    tile, only the (row tile, group) pairs that exist: it reads the
    matrices of the groups that have rows and multiplies each row once
    (:func:`group_tiling`: once a group, too, where the rows are many).
    Rows are padded to the kernel's row tile; the padding belongs to
    no group and is cut off again."""
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    from ..ops.flash_attention import _use_interpret

    M, K = xs.shape
    tiling = group_tiling(M, K, w.shape[2], w.dtype.itemsize)
    pad = -M % tiling[0]
    if pad:
        xs = jnp.pad(xs, ((0, pad), (0, 0)))
    out = gmm(
        xs, w, sizes, preferred_element_type=out_dtype, tiling=tiling,
        interpret=_use_interpret(),
    )
    return out[:M] if pad else out


def moe_ffn_topk(h: jax.Array, lp: dict, cfg):
    """Dropless top-k experts plus the shared expert on (B, L, D).

    The ``T * k`` (token, expert) pairs are sorted by expert, each
    expert's rows are multiplied by that expert's matrices alone
    (:func:`grouped_matmul`, one call per matrix: it reads the experts
    that have rows and does ``2 * rows * D * F`` operations, not
    ``n_experts`` times that), and the weighted results go back to
    their tokens. Returns ``(y, hit)``: ``hit`` counts the experts that
    got at least one row.

    With ``cfg.experts_held = (lo, hi)`` the matrices here are those of
    experts ``[lo, hi)`` alone. The router still scores all
    ``n_experts``, and a token's weights are normalised over its ``k``
    chosen wherever they live; the pairs that fall on a held expert are
    computed and the others left out (a further chip's part of the
    sum). ``hit`` is then ``[held experts that got a row, pairs that
    fell on held experts]``.
    """
    B, L, D = h.shape
    T, k, E = B * L, cfg.experts_per_token, cfg.n_experts
    x = h.reshape(T, D)
    held = cfg.experts_held
    with jax.named_scope("moe_route"):
        idx, w = topk_route(x, lp["router"], lp.get("router_bias"), k,
                            cfg.route_scale, cfg.route_score,
                            cfg.route_groups, cfg.route_topk_groups)
        idx = idx.reshape(-1)
        if held is not None:
            # held experts count from 0; a pair for any other expert
            # sorts behind them all, into no group
            lo, E = held[0], held[1] - held[0]
            here = (idx >= lo) & (idx < lo + E)
            idx = jnp.where(here, idx - lo, E)
        # pair p = (token p // k, its (p % k)-th expert), by expert
        expert, pair = jax.lax.sort(
            (idx, jnp.arange(T * k, dtype=jnp.int32)),
            num_keys=1, is_stable=True,
        )
        if held is None:
            sizes = jnp.zeros((E,), jnp.int32).at[expert].add(1)
            hit = jnp.sum(sizes > 0)
        else:
            sizes = jnp.zeros((E + 1,), jnp.int32).at[expert].add(1)[:E]
            hit = jnp.stack([jnp.sum(sizes > 0), jnp.sum(sizes)])
    with jax.named_scope("moe_experts"):
        xs = jnp.take(x, pair // k, axis=0)  # (T*k, D), grouped
        a = grouped_matmul(xs, lp["we_gate"], sizes, x.dtype)
        b = grouped_matmul(xs, lp["we_up"], sizes, x.dtype)
        ys = grouped_matmul(jax.nn.silu(a) * b, lp["we_down"], sizes,
                            jnp.float32)
        ys = ys * jnp.take(w.reshape(-1), pair)[:, None]
        if held is not None:
            # rows of no group were never written by the product
            ys = jnp.where((expert < E)[:, None], ys, 0.0)
        # back to token order: pair p's row sits at sorted position
        # inv[p]; a token's k rows are then adjacent
        inv = jnp.zeros((T * k,), jnp.int32).at[pair].set(
            jnp.arange(T * k, dtype=jnp.int32))
        y = jnp.take(ys, inv, axis=0).reshape(T, k, D).sum(axis=1)
    if "ws_gate" in lp:
        with jax.named_scope("moe_shared"):
            a = jax.nn.silu(jnp.einsum("td,df->tf", x, lp["ws_gate"]))
            a = a * jnp.einsum("td,df->tf", x, lp["ws_up"])
            ysh = jnp.einsum(
                "tf,fd->td", a, lp["ws_down"],
                preferred_element_type=jnp.float32,
            )
            if "ws_sgate" in lp:
                ysh = ysh * jax.nn.sigmoid(jnp.einsum(
                    "td,do->to", x, lp["ws_sgate"],
                    preferred_element_type=jnp.float32))
            y = y + ysh
    return y.astype(h.dtype).reshape(B, L, D), hit
