"""Continuous batching: a multi-request serving scheduler.

The reference is transport-only (src/MPIAsyncPools.jl:1-226 — no model,
no serving); this is north-star serving scope (VERDICT r4 next-#1),
converting the round-4 serving inventory (ring cache, GQA decode, int8
KV, hedged) from single-request features into aggregate
throughput. At B=1 a decode step is weight-read-bound — the HBM traffic
is the parameters, amortized over one token (PERF.md section 5). Batching S
concurrent requests into one step amortizes the same weight reads over
S tokens; until the KV-cache reads dominate, aggregate tokens/s scales
near-linearly with S. That economics is the whole point of this module.

Design (TPU-first):

* **Fixed slots, static shapes.** The scheduler owns ``S`` serving
  slots. A slot's per-layer state is an O(W) ring (the ring layout of
  models/decode.py: position ``p`` at ring slot ``p % W``) however
  long its request runs, kept as pages of ``page_tokens`` ring slots in
  one pool a layer (:class:`PagePool`, :func:`_fresh_pages`) and read
  through the slot's row of a page table, so slot reuse is a table
  write, never a reallocation, and one compiled program serves every
  scheduler tick.
* **A width per layer.** The block is data (``TransformerConfig``):
  a layer's ring is as wide as its own attention span
  (``decode.ring_widths``) — the window of a sliding-window layer, the
  context budget ``max_context`` of a full-attention layer, a ring
  that never wraps inside the budget, so the one ring invariant serves
  both. Layers of one width are one *kind*: each kind has its
  own :class:`PagePool` and page table (``_PageKind``), and a request
  holds pages of every kind. Dropless top-k expert layers
  (``models/moe.py`` ``moe_ffn_topk``) are per token, so chunks and
  decode steps give what the whole forward gives.
* **A second kind of state.** A gated delta-rule layer
  (``TransformerConfig(layer_mixers=...)``) keeps no row a token: its
  cache is one fixed block a slot (``S`` and the last rows of its
  conv, ``transformer.gdn_zero_state``), beside the other layers'
  pages. A prefill chunk carries it through the arena and is told how
  many of its rows are real (a recurrence would swallow the padding
  that attention never reads); placement writes the block over the
  slot's old one, so a reused slot starts from the new prompt's state.
  The state at a page boundary is kept nowhere, so such a
  configuration shares no prefix page (``shares_prefixes`` is False),
  and ``qos=``, ``cache=``, page migration and ``make_serving_scan``
  refuse it by mechanism.
* **A third kind of leaf.** A latent-attention layer
  (``layer_mixers`` value ``"mla"``) keeps ONE row a position for all
  its heads, the normalised latent beside one rotated key, and no
  ``v`` (``decode._latent_leaves``): int8 with a scale for each of the
  two parts, in pages like any row cache (``_fresh_pages``: the row in
  whole lane tiles), prefix pages shared. Keys and values are both
  read from it in the absorbed form (``decode._latent_attend``). The
  paged tick writes the step's row into its page in place and reads
  the pages a slot has filled where they lie, through the latent form
  of the paged kernel (``ops.decode_attention.latent_decode_attention``;
  ``decode._paged_kernel_possible`` says when: int8 rows, a latent of
  whole lane tiles, every layer latent), a drafting step's two queries
  a slot as two rows of the kernel; what the kernel cannot take (rows
  in the model's dtype, the tests' latents of 24) takes the gather
  route (``_serving_scan_paged`` with ``use_kernel`` False: every
  slot's ring gathered once a tick). The sharded tick and migration
  refuse it by mechanism, as they do a residual path of several
  streams (``hc_mult``), of which nothing is cached.
* **One or two tokens a step.** With ``draft="mtp"`` and a
  configuration that carries a multi-token-prediction module
  (``TransformerConfig(mtp_depth=1)``) a decode step runs two rows a
  slot, the last certain token and the module's draft of the next,
  delivers two tokens where the model's own pick is the draft and one
  where it is not, and lets the module draft again
  (:func:`_draft_step`, whose note has the argument for the stale row
  a rejected draft leaves). The stream is the drafter-off stream token
  for token, greedy or sampled. Window layers, state layers, several
  streams, ``qos=``, ``cache=`` and migration refuse it by mechanism,
  and no prefix page is shared under it.
* **Per-row positions.** Unlike ``decode_step_ring_dense`` (one scalar
  position for the whole batch), every slot decodes at its own global
  position: RoPE angles, ring-slot writes, and the ``kpos >= 0``
  validity mask are all computed per row (``_rope_rows``,
  ``_ring_write_rows``, ``_ring_attention_rows``). The masks make slot
  reuse safe: a freshly admitted row's unwritten slots have
  ``kpos < 0`` and self-mask, so the previous occupant's K/V are
  unreachable even before they are overwritten.
* **Inner scan, host ticks.** Each scheduler tick runs ``n_inner``
  decode steps for all S slots inside one ``lax.scan`` program — one
  host round trip per ``S x n_inner`` tokens (per-token host control
  would put a device-to-host fetch between every two steps).
* **Chunked prefill interleaved with decode.** Admission does not
  stall in-flight requests behind a long prompt: each tick advances
  every admitting request by ONE C-token prefill chunk (through the
  cached-attention path, exactly ``make_extend``'s semantics: the
  chunk attends the key blocks its rows can see,
  ``decode._chunk_attention``, so its work follows the prompt's
  length so far and not the arena's) and then runs the decode scan.
  With ``quantize_kv=True`` each chunk
  attends the already-quantized cache — the only math available once
  earlier chunks' raw K/V are gone — and per-position absmax
  quantization makes the chunk size invisible, so the stream is
  IDENTICAL at any ``prompt_chunk`` and equals the quantized oracle
  (``generate_ring_dense(quantize_kv=True)``, whose prefill runs the
  same cached-attention math; both the equality and its
  chunk-invariance premise are pinned by tests/test_serving.py at the
  tests' sizes, and ``ServingScheduler``'s docstring says what that is
  worth on a real model). A request's prefill lands in a
  transient positional cache; on the last chunk the final-W window
  becomes ring rows (``ring_from_cache`` math with a traced length:
  the cache as it lies where ``max_prompt`` cannot wrap the ring, a
  gather by position where it can), of which placement writes the
  pages the prompt covers into the slot's pages, and the
  first token comes from the head applied to
  ONE row of the last chunk's hidden state, the prompt's last
  position: a chunk program stops at the last layer's output, so the
  head's weights are read once a request. Decode stall per tick is
  bounded by the tick's prefill programs, not by a prompt. The chunks
  that are due in one tick (every admitting slot's next, and the first
  of each request the tick admits) run as ONE program over their
  concatenated rows where they can, up to ``_chunk_group_cap`` of them
  a program (``_extend_chunk_group``, ``decode._grouped_hidden``):
  every weight, every expert, is read once for all of them, while each
  request's K/V goes into its own arena at its own offset and its
  queries walk that arena alone. Grouping leaves the schedule as it
  was: the same chunks run between the same ticks, and a request's
  admission still ends (first token, pages registered) before the next
  request is planned wherever its end can change that plan
  (``_due_at_once``). Where such a program's rows are nearly free (a
  group cap above 1: chunks wait for the weights' bytes) AND the
  deployment serves documents (``max_prompt // prompt_chunk > slots``:
  a prompt can hold its slot through more ticks of prefill than there
  are slots), ONE request a tick takes a whole grouped program's rows
  for itself: the one admitted first that has that many whole chunks
  left before its last advances them all in the program
  ``serving_prefill_chunk_w<cap>`` (``_extend_chunk_wide``: the lone
  chunk's body at that width), the other due chunks are grouped as
  ever, and a tick that goes wide runs one more prefill program at
  most (``ServingScheduler._wide_slot``). Documents then finish one
  after another and not side by side, each holding its slot a quarter
  as long before its first token; the tokens are the same. Every other
  scheduler builds no such program and runs the schedule it ran.
* **A tick's admissions are planned behind the tick before.** Where a
  request can only end by its length (no ``eos_id``, no drafter:
  ``ServingScheduler._ends_known``) a decoding slot gains exactly
  ``n_inner`` tokens a tick, so the host knows which slots a tick ends
  before its tokens are back. ``step`` then dispatches the tick, frees
  those slots, runs the NEXT tick's whole admit phase (the same plan,
  the same chunks in the same programs, stamped as that tick's) while
  the chip runs this one, and only then fetches and harvests: the
  programs queue behind the tick by their data, and the chip goes from
  a tick straight into the next one's prefill. A first token stays a
  device value there (placement has put it into the slot's row) and
  is read with the fetch of its request's first tick; no read of a
  device value stands between two dispatches. The admit phase at the
  top of ``step`` stays for what arrives between two steps.
* **EOS retirement + slot reuse.** Rows that emit ``eos_id`` keep
  emitting it on-device (static shapes; ``_eos_clamp``); the host
  strips the tail, retires the request (EOS or its ``max_new`` budget),
  and hands the slot to the next queued request. Retirement has two
  halves: the slot's (the row done, its pages back, its table row
  nulled) and the request's (tokens trimmed, ``finished``, ``reason``,
  ``retired_tick``). In order they run together in the harvest; planned
  ahead the slot's half runs by count right behind the dispatch and
  the request's half in the harvest. With ``eos_id`` an end is a
  token's VALUE, with a drafter a step's yield is: both keep the order
  admit, tick, harvest and read a first token where it is made.

Greedy decoding per row equals the single-request oracle
(:func:`~.decode.generate_ring_dense`) token-for-token — the batched
per-row step is the same math evaluated at S independent (row,
position) points; tests/test_serving.py pins every admitted request
against its oracle stream, including staggered admissions and reuse.
One precision caveat: "same math" means same at exact f32 — at the
TPU's DEFAULT matmul precision (bf16 MXU passes) the batched and
single-request program shapes round differently and greedy argmax
TIES can flip between them (set
``jax.config.update("jax_default_matmul_precision", "highest")`` for
cross-shape exactness; examples/continuous_batching.py demonstrates).

``make_serving_scan(cfg, mesh=...)`` is the sharded variant of the
decode tick (slots over ``dp``, heads over ``tp``, the training path's
psum placement) over one ring a slot — the multi-chip serving program
the driver dryrun compiles and checks against the dense per-row step
(:func:`serving_decode_step_dense`).
"""

from __future__ import annotations

import dataclasses
import functools
import itertools
import time
from collections import deque
from typing import Any, Sequence

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from ..obs.timeline import annotate as _annotate
from .decode import (
    STATE_LEAVES,
    _NEG,
    _cache_pv,
    _cache_scores,
    _check_ring_cfg,
    _check_sampling_params,
    _decode_kernel_interpreted,
    _chunk_rows_seen,
    _eos_clamp,
    _grouped_hidden,
    _incremental_hidden,
    _is_quantized,
    _kernel_possible,
    _latent_attend,
    _latent_leaves,
    _incremental_layer,
    _kernel_viable,
    _kv_quantize,
    _paged_kernel_possible,
    _pick_token,
    _pool_cells_for,
    _select_rows,
    _split_state,
    _ring_from_cache,
    _route_kernel,
    _row_widths,
    _zero_latent_layer,
    ring_widths,
)
from .moe import group_tiling
from .paging import (
    NULL_PAGE,
    PagePool,
    PagePoolExhausted,
    prefix_page_digests,
)
from ..qos import DeficitScheduler, TenantRegistry
from .transformer import (
    TransformerConfig,
    _rope_freqs,
    attn_merge,
    attn_qkv,
    embed,
    ffn_half,
    gdn_rule_route,
    hc_fold,
    hc_pre,
    head_logits,
    make_kv_slice,
    mtp_input,
    mtp_logits,
    la_rule_route,
    param_specs,
    require_plain_block,
    sparse_counts,
    sparse_pick,
    ssm_half,
    ssm_rule_route,
    state_half,
    zero_state,
)

__all__ = [
    "Request",
    "ServingScheduler",
    "make_serving_scan",
    "serving_decode_step_dense",
    "PagePool",
    "PagePoolExhausted",
]


@functools.lru_cache(maxsize=32)
def _fresh_arena(cfg: TransformerConfig, B: int, L: int,
                 quantize_kv: bool):
    """Jitted ``serving_fresh_arena() -> [per-layer dict]``: every leaf
    of a zeroed ``(B, L, kv_heads, head_dim)`` cache out of ONE
    dispatch. An eager ``jnp.zeros`` per leaf is a program launch and
    an allocation each, four a layer, while the device has nothing
    queued: admission's whole host cost at 30 layers."""
    kvdt = jnp.int8 if quantize_kv else cfg.dtype

    def layer(li):
        if not cfg.rows(li):  # the layer's fixed block of state alone
            return zero_state(cfg, li, B)
        if cfg.mla(li):  # one row a position: [latent | rotated key]
            return _zero_latent_layer(B, L, cfg, quantize_kv)
        shape = (B, L, cfg.kv_heads, cfg.head_dim)
        out = {"k": jnp.zeros(shape, kvdt), "v": jnp.zeros(shape, kvdt)}
        if cfg.ssm(li):  # rows AND the state of the mixer beside them
            out.update(zero_state(cfg, li, B))
        if quantize_kv:
            out["k_s"] = jnp.zeros(shape[:3], jnp.float32)
            out["v_s"] = jnp.zeros(shape[:3], jnp.float32)
        if cfg.sparse(li):  # the selector's pooled cells (decode.py)
            out["kp"] = jnp.zeros(
                (B, _pool_cells_for(L, cfg)) + shape[2:], jnp.float32)
        return out

    @jax.jit
    def serving_fresh_arena():
        return [layer(li) for li in range(cfg.cache_layers)]

    return serving_fresh_arena


def _fresh_cache(cfg: TransformerConfig, B: int, L: int,
                 quantize_kv: bool = False) -> list[dict]:
    """Zeroed positional/ring cache with DISTINCT buffers per leaf,
    made by one program (:func:`_fresh_arena`). decode.py's
    ``_zero_cache_layer`` aliases one zeros array for k and v (fine
    undonated); the serving programs donate their caches, and donating
    the same buffer twice is an XLA execution error — a program's
    outputs are buffers of their own, which tests/test_serving_arena.py
    holds it to."""
    return _fresh_arena(cfg, B, L, bool(quantize_kv))()


@functools.partial(jax.jit, donate_argnums=(0,), keep_unused=True)
def serving_reset_arena(arena):
    """Zero a dead prefill arena IN PLACE: the arena is donated and
    every output takes its input's buffer (``keep_unused`` keeps the
    unread inputs in the program, so the donation has something to
    alias), one dispatch and no allocation. What comes back is
    byte for byte what :func:`_fresh_cache` makes."""
    return jax.tree.map(jnp.zeros_like, arena)


def paged_scale_lanes(P: int) -> int:
    """The minor axis of a pool's scale leaves at page size ``P``: the
    kernel's rule (ops/decode_attention.py), imported where it is used
    like the kernel itself."""
    from ..ops.decode_attention import paged_scale_lanes as lanes

    return lanes(P)


def paged_row_lanes(width: int) -> int:
    """The minor axis of a latent layer's pool at a row of ``width``
    values: the kernel's rule too (ops/decode_attention.py)."""
    from ..ops.decode_attention import paged_row_lanes as lanes

    return lanes(width)


def _fresh_pages(cfg: TransformerConfig, n_pages, P: int,
                 quantize_kv: bool = False, slots: int = 0) -> list[dict]:
    """Zeroed per-layer PAGE POOL, shared by every slot, in the layout
    the paged decode kernel's blocks have (ops/decode_attention.py), so
    that a tick reads and writes pages where they lie and nothing of
    the pool's size is ever re-laid out: K/V ``(n_pages, P, kv_heads *
    head_dim)``, a page one contiguous block of P rows; int8 scales
    ``(n_pages, kv_heads, lanes)`` with a page's P positions on the
    minor axis, as the scores want them, and that axis padded to whole
    128-lane rows (``paged_scale_lanes``; the padding is never read):
    the device stores a leaf with a narrow minor axis transposed and
    re-lays it out at every program's door, and this shape it stores
    as the kernel reads it. ``n_pages``: one count for all layers, or a
    tuple with one per layer (layers of one cache width share a page
    table, and so a count). Page 0 is the reserved null page
    (:data:`~.paging.NULL_PAGE`): rows nothing reads unmasked, the
    landing zone for retired-but-still-ticking rows. A gated
    delta-rule layer has no pages: its leaf is the fixed block of state
    of each of the ``slots`` (its page count is not read); a layer that
    holds a state-space mixer beside its attention has pages AND that
    block (``decode.STATE_LEAVES`` name the leaves that are state). A latent
    layer's pages hold its one row a position, ``k`` ``(n_pages, P,
    lanes)``, the ``latent + rope`` values of a row and zeros up to
    whole 128-lane tiles behind them (``paged_row_lanes``: what the
    device stores either way, and what the latent kernel can copy), and
    the two scales of a row as two "heads" of ``k_s``. A layer that
    attends a selection of its key blocks keeps a page's pooled cells
    beside its rows: ``kp`` ``(n_pages, P / sparse_stride, kv_heads *
    head_dim)`` float32."""
    counts = ((n_pages,) * cfg.cache_layers if isinstance(n_pages, int)
              else tuple(n_pages))
    kvdt = jnp.int8 if quantize_kv else cfg.dtype

    def layer(li, n):
        if not cfg.rows(li):
            return zero_state(cfg, li, slots)
        if cfg.mla(li):
            out = {"k": jnp.zeros(
                (n, P, paged_row_lanes(cfg.latent_width)), kvdt)}
            if quantize_kv:
                out["k_s"] = jnp.zeros((n, 2, paged_scale_lanes(P)),
                                       jnp.float32)
            return out
        shape = (n, P, cfg.kv_heads * cfg.head_dim)
        out = {"k": jnp.zeros(shape, kvdt), "v": jnp.zeros(shape, kvdt)}
        if quantize_kv:
            sshape = (n, cfg.kv_heads, paged_scale_lanes(P))
            out["k_s"] = jnp.zeros(sshape, jnp.float32)
            out["v_s"] = jnp.zeros(sshape, jnp.float32)
        if cfg.sparse(li):
            out["kp"] = jnp.zeros((n, P // cfg.sparse_stride, shape[2]),
                                  jnp.float32)
        if cfg.ssm(li):  # pages, and beside them each slot's state
            out.update(zero_state(cfg, li, slots))
        return out

    return [layer(li, n) for li, n in enumerate(counts)]


def _rows_to_pages(kk: str, x, P: int, stride: int | None = None,
                   lanes: int | None = None):
    """Cache rows to pool-layout page blocks (:func:`_fresh_pages`):
    K/V ``(..., n * P, Hkv, D) -> (..., n, P, Hkv * D)``, a scale leaf
    (``kk`` ends in ``_s``) ``(..., n * P, Hkv) -> (..., n, Hkv,
    lanes)``, zeros in the lanes past P; a layer's pooled cells
    (``kp``) ``(..., n * c, Hkv, D) -> (..., n, c, Hkv * D)``, c = P /
    ``stride`` cells a page. ``lanes``: the pool leaf's minor axis,
    where it is wider than the rows (a latent layer's: zeros behind)."""
    if kk == "kp":
        lead, (L, H, D) = x.shape[:-3], x.shape[-3:]
        c = P // stride
        return x.reshape(lead + (L // c, c, H * D))
    if kk.endswith("_s"):
        lead, (L, H) = x.shape[:-2], x.shape[-2:]
        x = jnp.swapaxes(x.reshape(lead + (L // P, P, H)), -1, -2)
        pad = [(0, 0)] * (x.ndim - 1) + [(0, paged_scale_lanes(P) - P)]
        return jnp.pad(x, pad)
    lead, (L, H, D) = x.shape[:-3], x.shape[-3:]
    x = x.reshape(lead + (L // P, P, H * D))
    if lanes is not None and lanes > H * D:
        x = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, lanes - H * D)])
    return x


def _pages_to_rows(kk: str, blk, Hkv: int, P: int,
                   width: int | None = None):
    """Inverse of :func:`_rows_to_pages`: ``(..., n, P, Hkv * D) ->
    (..., n * P, Hkv, D)`` and ``(..., n, H, lanes) -> (..., n * P,
    H)`` (a scale leaf says itself how many scales a position has).
    ``width``: the values a row has, where the pool's minor axis is
    wider (a latent layer's)."""
    lead, n = blk.shape[:-3], blk.shape[-3]
    if kk == "kp":  # a page's pooled cells, however many it has
        return blk.reshape(lead + (n * blk.shape[-2], Hkv,
                                   blk.shape[-1] // Hkv))
    if kk.endswith("_s"):
        blk = jnp.swapaxes(blk[..., :P], -1, -2)
        return blk.reshape(lead + (n * P, blk.shape[-1]))
    if width is not None:
        blk = blk[..., :width]
    return blk.reshape(lead + (n * P, Hkv, blk.shape[-1] // Hkv))


def _row_values(cfg: TransformerConfig, li: int) -> int | None:
    """The values a row of layer ``li``'s pool holds where the pool's
    minor axis is wider than they are (a latent layer's row in whole
    lane tiles, :func:`_fresh_pages`); None: the row is the axis."""
    return cfg.latent_width if cfg.mla(li) else None


def _layer_kinds(cfg: TransformerConfig) -> tuple[tuple[int, ...],
                                                  tuple[int, ...]]:
    """``(kinds, kind_of_layer)``: the distinct cache widths of the
    configuration's layers, narrowest first, and each layer's index
    into them. Layers of one width share one page table and one
    :class:`~.paging.PagePool`; a configuration of sliding-window
    layers alone has one kind. A layer that keeps recurrent state has
    no rows, no width and no kind (None)."""
    widths = _row_widths(cfg)
    kinds = tuple(sorted({w for w in widths if w is not None}))
    return kinds, tuple(
        None if w is None else kinds.index(w) for w in widths)


def _layer_tables(cfg: TransformerConfig, pt) -> list:
    """Each layer's page table (or page-table row) out of ``pt``: one
    array where all layers share a width, else a tuple with one per
    kind in :func:`_layer_kinds` order."""
    tables = tuple(pt) if isinstance(pt, (tuple, list)) else (pt,)
    _, kind_of = _layer_kinds(cfg)
    if len(tables) == 1:
        return [tables[0]] * cfg.cache_layers
    return [None if k is None else tables[k] for k in kind_of]


# --------------------------------------------------------------------------
# per-row primitives (each slot at its own global position)
# --------------------------------------------------------------------------


def _rope_rows(x, pos, theta: float = 10000.0, table=None):
    """Rotary embedding for a slot's own rows: x (S, T, H, D), pos
    (S, T) global positions ((S,) for the one row of a plain step) —
    the per-row counterpart of transformer._rope (which shares one
    position vector across the batch), at its base (or its table of
    frequencies)."""
    Dh = x.shape[-1]
    half = Dh // 2
    freqs = _rope_freqs(half, theta, table)
    # (S, half), or (S, T, half) to (S, T, 1, half)
    ang = pos.astype(jnp.float32)[..., None] * freqs[None, :]
    heads = ((slice(None), None, None, slice(None)) if pos.ndim == 1
             else (slice(None), slice(None), None, slice(None)))
    cos = jnp.cos(ang)[heads].astype(x.dtype)
    sin = jnp.sin(ang)[heads].astype(x.dtype)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1
    )


def _ring_write_rows(cache_l: dict, k, v, slot, latent=None):
    """Write each slot's own rows of K/V at their own ring slots:
    k, v (S, T, Hkv, D), slot (S, T) ((S,) for T = 1) — a per-row
    scatter on the slot axis (decode.py's ``_cache_write`` writes one
    shared offset). A latent layer (``latent``: the latent's width)
    writes its one row a position, ``k``."""
    rows = jnp.arange(k.shape[0])

    def put(c, u):
        if slot.ndim == 1:
            return c.at[rows, slot].set(u[:, 0].astype(c.dtype))
        return c.at[rows[:, None], slot].set(u.astype(c.dtype))

    if latent is not None:
        return {kk: put(cache_l[kk], u) for kk, u in _latent_leaves(
            k, latent, _is_quantized(cache_l)).items()}
    pooled = {}
    if "kp" in cache_l:
        # the row into its pooled cell (a ring as wide as the context
        # budget: slot s is position s), one row a slot
        kp = cache_l["kp"]
        stride = cache_l["k"].shape[1] // kp.shape[1]
        with jax.named_scope("sparse_pool"):
            pooled["kp"] = kp.at[rows, slot // stride].add(
                k[:, 0].astype(jnp.float32) / stride)
    if not _is_quantized(cache_l):
        return {"k": put(cache_l["k"], k), "v": put(cache_l["v"], v),
                **pooled}
    kq, ks = _kv_quantize(k)
    vq, vs = _kv_quantize(v)
    return {
        "k": put(cache_l["k"], kq),
        "v": put(cache_l["v"], vq),
        "k_s": put(cache_l["k_s"], ks),
        "v_s": put(cache_l["v_s"], vs),
        **pooled,
    }


def _ring_attention_rows(q, cache_l, pos, scale, use_kernel=False,
                         latent=None, select=None):
    """Single-query ring attention with a per-row position: the same
    ``kpos(s) = pos - ((pos - s) mod W), valid iff kpos >= 0`` invariant
    as decode.py's ``_ring_cached_attention``, evaluated rowwise. The
    mask is simultaneously causal bound, sliding-window bound, warmup
    guard, AND slot-reuse guard (a reused slot's stale rows sit at
    kpos < 0 for the new occupant until overwritten).

    ``use_kernel=True`` routes int8 caches through the Pallas decode
    kernel's ring mode (per-row positions ride SMEM): ONE kernel call
    serves all S slots, so the scan/custom_call boundary cost that
    sinks the kernel at B=1 is paid once per S tokens — the batched
    regime is where int8 finally converts its byte win into time
    (PERF.md section 6, PR 27). Default False: this function is also the dense
    ORACLE step (``serving_decode_step_dense``), which stays einsum so
    kernel-vs-einsum parity is testable against it. ``latent`` (the
    latent's width): q is the absorbed query, the ring a latent
    layer's one row a slot, the result (S, 1, H, latent). ``pos``
    (S, T): the slot's T queries, each at its own position, over the
    einsum (a drafting step's two rows, :func:`_draft_step`).
    ``select`` (``(stands (S, Hkv, blocks), cfg)``): each slot attends
    the blocks that stand for it alone (:func:`_slot_blocks`)."""
    W = cache_l["k"].shape[1]
    if use_kernel and _kernel_viable(q, cache_l):
        from ..ops.decode_attention import quantized_decode_attention

        return quantized_decode_attention(
            q, cache_l, pos, scale, ring=True
        )
    s = _cache_scores(q, cache_l, scale, latent)  # (S, H, T, W) f32
    kpos = pos[..., None] - jnp.mod(
        pos[..., None] - jnp.arange(W)[None, :], W
    )  # (S, W), or (S, T, W)
    seen = kpos >= 0
    seen = seen[:, None, None, :] if pos.ndim == 1 else seen[:, None]
    if select is not None:
        seen = seen & _select_rows(
            select[0][:, None], jnp.arange(W), select[1],
            q.shape[2] // cache_l["k"].shape[2])
    s = jnp.where(seen, s, _NEG)
    p = jax.nn.softmax(s, axis=-1)
    o = _cache_pv(p, cache_l, latent)
    return o.astype(q.dtype)


def _slot_blocks(q, cells, pos, cfg, n_blocks: int):
    """Which key blocks each slot's one query attends
    (``transformer.sparse_pick`` a slot): q (S, 1, H, D), ``cells`` (S,
    cells, Hkv, D) each slot's pooled cells in position order, ``pos``
    (S,) -> (S, Hkv, n_blocks) bool."""
    with jax.named_scope("sparse_select"):
        return jax.vmap(lambda qs, cs, p: sparse_pick(
            qs, cs, p[None] + 1, cfg, n_blocks)[0][0])(q, cells, pos)


def _paged_write_rows(cache_l: dict, k, v, pt, slot, P: int):
    """Write each row's single-token K/V through its page table:
    ring slot ``slot[i]`` of row i is row ``slot % P`` of pool page
    ``pt[i, slot // P]``. The scheduler's pre-tick COW pass guarantees
    every page written here is exclusively owned (or the null page,
    for retired rows) — the device program never has to know pages
    can be shared."""
    S = k.shape[0]
    page = pt[jnp.arange(S), slot // P]  # (S,)
    off = slot % P
    put = functools.partial(_put_page_row, page=page, off=off)
    put_s = functools.partial(_put_page_scales, page=page, off=off)

    pooled = {}
    if "kp" in cache_l:  # untouched here: :func:`_paged_pool_rows`
        pooled["kp"] = cache_l["kp"]
    if not _is_quantized(cache_l):
        return {"k": put(cache_l["k"], k), "v": put(cache_l["v"], v),
                **pooled}
    kq, ks = _kv_quantize(k)
    vq, vs = _kv_quantize(v)
    return {
        "k": put(cache_l["k"], kq),
        "v": put(cache_l["v"], vq),
        "k_s": put_s(cache_l["k_s"], ks),
        "v_s": put_s(cache_l["v_s"], vs),
        **pooled,
    }


def _put_page_row(c, u, *, page, off):
    """K/V: each row's one ``(Hkv * D,)`` row ``u`` (S, 1, ...) into
    row ``off`` of its page of the pool leaf ``c``."""
    S = u.shape[0]
    return c.at[page, off].set(u[:, 0].reshape(S, -1).astype(c.dtype))


def _put_page_scales(c, u, *, page, off):
    """Scales: one position of each head's row of its page. Whole
    (Hkv, lanes) blocks out and back (a few KB), the position replaced
    on the way: an element scatter into this leaf makes the compiler
    keep it heads-minor through the scan, and re-lay it out for every
    kernel call."""
    blk = jnp.where(
        jnp.arange(c.shape[2]) == off[:, None, None],
        u[:, 0, :, None].astype(c.dtype), jnp.take(c, page, axis=0),
    )
    return c.at[page].set(blk)


def _paged_write_latent(cache_l: dict, row, pt, slot, P: int, R: int):
    """:func:`_paged_write_rows` for a latent layer's int8 pages: each
    slot's T rows ``row`` (S, T, 1, R + rope) through its page table,
    the row's values with zeros up to the pool's lanes behind them and
    its two scales as the two "heads" of ``k_s``. One row of every slot
    at a time: a drafting step's two rows may share a page, and the
    second's block of scales must hold the first's."""
    S, T = row.shape[:2]
    slot = slot.reshape(S, T)
    leaves = _latent_leaves(row, R, True)
    k, ks = cache_l["k"], cache_l["k_s"]
    zeros = k.shape[-1] - row.shape[-1]
    kq = jnp.pad(leaves["k"], ((0, 0), (0, 0), (0, 0), (0, zeros)))
    for t in range(T):
        at = dict(page=pt[jnp.arange(S), slot[:, t] // P],
                  off=slot[:, t] % P)
        k = _put_page_row(k, kq[:, t:t + 1], **at)
        ks = _put_page_scales(ks, leaves["k_s"][:, t:t + 1], **at)
    return {"k": k, "k_s": ks}


def _paged_latent_rows(q, cache_l, pt, pos, scale, P: int, R: int):
    """The absorbed queries q (S, T, H, R + rope) at positions ``pos``
    ((S,) for T = 1, else (S, T)) over a latent layer's pages, through
    the latent form of the paged kernel (ops/decode_attention.py):
    ``S * T`` rows with their own positions, a slot's table row once
    for each of its queries. Returns (S, T, H, R)."""
    from ..ops.decode_attention import latent_decode_attention

    S, T = q.shape[:2]
    o = latent_decode_attention(
        q.reshape((S * T, 1) + q.shape[2:]), cache_l, pos.reshape(S * T),
        jnp.repeat(pt, T, axis=0) if T > 1 else pt, scale=scale, P=P, R=R)
    return o.reshape((S, T) + o.shape[2:])


def _paged_pool_rows(cache_l: dict, k, pt, slot, P: int) -> dict:
    """Each row's key into its pooled cell, through the page table:
    cell ``(slot % P) // stride`` of pool page ``pt[i, slot // P]``
    (``kp``, :func:`_fresh_pages`). A page's cells were zeroed or
    copied in when the request was placed."""
    kp = cache_l["kp"]
    stride = P // kp.shape[1]
    page = pt[jnp.arange(k.shape[0]), slot // P]
    with jax.named_scope("sparse_pool"):
        kp = kp.at[page, (slot % P) // stride].add(
            k[:, 0].reshape(k.shape[0], -1).astype(jnp.float32) / stride)
    return {**cache_l, "kp": kp}


def sparse_table_width(cfg: TransformerConfig) -> int:
    """The most blocks a query attends: every block of
    ``sparse_dense_len`` rows, or past it the first blocks, the top-k
    and the blocks that hold the window (one more where it straddles)."""
    blk = cfg.sparse_block
    return max(-(-cfg.sparse_dense_len // blk),
               cfg.sparse_init_blocks + cfg.sparse_topk
               + -(-cfg.sparse_window // blk) + 1)


def _paged_select(q, cache_l, pt, pos, cfg, P: int):
    """The selection as the paged kernel reads it: for every slot and
    K/V head the pool pages of the blocks that stand, in position order
    (a block is a page: ``sparse_block == P``), and the position the
    slot's query has among THOSE rows. Returns ``(pages (S, Hkv, n),
    at (S, Hkv))``: the kernel walks ``pages[s, h]`` as it walks a
    page table, rows ``<= at[s, h]`` live. n is the most blocks a
    query attends (:func:`sparse_table_width`). The block the query's
    own row lies in always stands and is the last, so every entry
    before it is a whole page and the last is live up to the row."""
    max_pages = pt.shape[1]
    kp = cache_l["kp"]
    S, Hkv = q.shape[0], cfg.kv_heads
    with jax.named_scope("sparse_select"):
        cells = jnp.take(kp, pt, axis=0)        # (S, pages, c, Hkv * D)
        cells = cells.reshape(S, max_pages * kp.shape[1], Hkv, -1)
        stands = _slot_blocks(q, cells, pos, cfg, max_pages)
        n = min(max_pages, sparse_table_width(cfg))
        b = jnp.arange(max_pages)
        order = jnp.sort(jnp.where(stands, b, max_pages + b), axis=-1)
        count = stands.sum(axis=-1).astype(jnp.int32)       # (S, Hkv)
        ids = jnp.minimum(order[..., :n], max_pages - 1)
        pages = jnp.take_along_axis(pt[:, None, :], ids, axis=-1)
        at = (count - 1) * P + (pos % P)[:, None]
    return pages, at


def _paged_gather(cache_l: dict, pt, Hkv: int, P: int,
                  width: int | None = None):
    """Materialize every slot's W-row ring view out of the page pool:
    one PAGE-BLOCK ``jnp.take`` per leaf — ``(S, max_pages)`` indices
    moving whole pages. Page p's rows are ring slots ``[j*P, (j+1)*P)``
    in offset order, so the gathered blocks, read as rows
    (:func:`_pages_to_rows`), are EXACTLY one ring a slot,
    ``(S, W, Hkv, ...)``, and the einsum path runs the unchanged ring
    math on it — ring and paged decode are the identical math by
    construction, which is what the CPU parity tests lean on (``width``:
    the row's values, where the pool's minor axis is wider). Speed
    note: this gather runs once per TICK (hoisted out of the decode
    scan — see ``_serving_scan_paged``). Null page-table entries
    resolve to page 0,
    whose rows are only ever reached by ``kpos < 0`` (masked) slots."""
    return {
        # (the state beside a layer's rows is no page: it goes through)
        kk: a if kk in STATE_LEAVES else _pages_to_rows(
            kk, jnp.take(a, pt, axis=0), Hkv, P, width)
        for kk, a in cache_l.items()
    }


def _paged_scatter(cache_l: dict, view_l: dict, pt, P: int,
                   stride: int | None = None):
    """Write a tick's updated ring views back through the page table —
    the inverse of :func:`_paged_gather`, one page-block scatter per
    leaf. Duplicate table entries (a prefix page shared by several
    slots) all write the SAME bytes: any page a tick writes is
    exclusively owned (the pre-tick COW pass), so shared pages come
    back exactly as they went out. Null entries dump into page 0,
    which nothing reads unmasked."""
    return {
        kk: view_l[kk] if kk in STATE_LEAVES else a.at[pt].set(
            _rows_to_pages(kk, view_l[kk], P, stride,
                           a.shape[-1]).astype(a.dtype))
        for kk, a in cache_l.items()
    }


def _paged_attention_rows(q, cache_l, pt, pos, scale, P):
    """Single-query ring attention THROUGH the page table — the Pallas
    paged KERNEL route only (ops/decode_attention.py): the per-slot
    page-index row rides scalar-prefetch SMEM next to the per-row
    positions and the kernel copies in the pages a row has filled,
    straight out of the pool, so HBM traffic is the live rows and a
    table entry no position has reached costs nothing. The einsum tick
    never reads
    through the table per step — ``_serving_scan_paged`` hoists the
    gather out of the scan instead (``_paged_gather`` + the unchanged
    dense ring math). Routing is resolved at scheduler construction
    (``_paged_kernel_possible``); there is no trace-time re-gate."""
    from ..ops.decode_attention import quantized_decode_attention

    return quantized_decode_attention(
        q, cache_l, pos, scale, ring=True, page_table=pt,
        page_tokens=P,
    )


def _serving_layer(x, lp, cache_l, pos, cfg, li, *, kv_slice=None,
                   tp_psum=False, use_kernel=False, paged=None):
    """Layer ``li`` of the per-row serving step: models/transformer.py's
    block with per-row positions and the K/V store of this module.
    ``paged`` = (page_table, W, PAGE_TOKENS) switches the cache
    write/read to the page-pool layout; None is one ring a slot (the
    gathered views, the sharded tick, ``serving_decode_step_dense``).
    Returns ``(x, cache_l, hit)``; ``hit`` is the number of experts
    that got a row in a dropless expert layer, None elsewhere. A gated
    delta-rule layer's ``cache_l`` is every slot's state: one step of
    the recurrence a row, no position and no page. ``pos`` (S, T) with
    x (S, T, D): T rows a slot, each written before any is attended (a
    drafting step's two: the gathered views, and a latent layer's
    pages)."""
    h, mix = hc_pre(x, lp, cfg, "hc1")
    rope = functools.partial(_rope_rows, pos=pos, theta=cfg.rope_theta,
                             table=cfg.rope_table)
    beside, state = None, {}
    if cfg.ssm(li):
        # the state-space mixer beside the attention: one step of its
        # recurrence a row, every slot's S updated where it lies; its
        # result joins the attention's below
        cache_l, state = _split_state(cache_l)
        beside, state = ssm_half(h, lp, state, cfg)
    elif cfg.state(li):
        x, cache_l = state_half(h, lp, cache_l, cfg, li, rope, mix=mix)
        with jax.named_scope("decode_mlp"):
            x, _, hit = ffn_half(x, lp, cfg, li, tp_psum=tp_psum)
        return x, cache_l, hit
    if cfg.mla(li):
        R, scale = cfg.mla_kv_rank, cfg.softmax_scale
        if paged is not None:
            # kernel route: the row into its page in place, the pages a
            # slot has filled read where they lie
            pt, W, P = paged
            write = lambda row: _paged_write_latent(
                cache_l, row, pt, jnp.mod(pos, W), P, R)
            attend = lambda q, cl: _paged_latent_rows(
                q, cl, pt, pos, scale, P, R)
        else:
            # every slot's ring (the gather route: its view of its
            # pages, gathered once a tick, ``_serving_scan_paged``):
            # the row goes to slot ``pos`` of a ring as wide as the
            # context budget, which never wraps
            W = cache_l["k"].shape[1]
            write = lambda row: _ring_write_rows(
                cache_l, row, None, jnp.mod(pos, W), R)
            attend = lambda q, cl: _ring_attention_rows(
                q, cl, pos, scale, latent=R)
        x, cache_l = _latent_attend(h, lp, cfg, rope, mix, write, attend)
        with jax.named_scope("decode_mlp"):
            x, _, hit = ffn_half(x, lp, cfg, li)
        return x, cache_l, hit
    q, k, v, gate = attn_qkv(h, lp, cfg, li, rope, kv_slice)
    scale = cfg.softmax_scale
    # scopes name the K/V traffic (cache write, scores, softmax, p @ v)
    # and the MLP in a device trace; the projections are under
    # ``attn_qkv`` / ``attn_out``, which models/transformer.py's block
    # opens itself, and the feed-forward's ``ffn`` nests in ``decode_mlp``
    # a layer that attends a selection of its key blocks: the row into
    # its pooled cell first (``sparse_pool``), then the pick
    # (``sparse_select``), both beside ``decode_attn`` and not in it
    select = None
    if cfg.sparse(li) and paged is not None:
        pt, W, P = paged
        cache_l = _paged_pool_rows(cache_l, k, pt, jnp.mod(pos, W), P)
        select = _paged_select(q, cache_l, pt, pos, cfg, P)
    with jax.named_scope("decode_attn"):
        if paged is not None:
            # kernel route only: the einsum paged tick runs THIS
            # function with paged=None over per-tick gathered ring
            # views instead (see _serving_scan_paged)
            pt, W, P = paged
            cache_l = _paged_write_rows(cache_l, k, v, pt,
                                        jnp.mod(pos, W), P)
            if select is None:
                o = _paged_attention_rows(q, cache_l, pt, pos, scale, P)
            else:
                from ..ops.decode_attention import paged_select_attention

                o = paged_select_attention(
                    q, {kk: a for kk, a in cache_l.items() if kk != "kp"},
                    select[1], select[0], scale=scale, P=P)
        else:
            W = cache_l["k"].shape[1]
            cache_l = _ring_write_rows(cache_l, k, v, jnp.mod(pos, W))
            if cfg.sparse(li):
                select = (_slot_blocks(q, cache_l["kp"], pos, cfg,
                                       W // cfg.sparse_block), cfg)
            o = _ring_attention_rows(q, cache_l, pos, scale,
                                     use_kernel=use_kernel, select=select)
    x = attn_merge(h, o, gate, lp, cfg, tp_psum=tp_psum, mix=mix,
                   beside=beside)
    with jax.named_scope("decode_mlp"):
        x, _, hit = ffn_half(x, lp, cfg, li, tp_psum=tp_psum)
    return x, {**cache_l, **state}, hit


def _paged_layer(paged, li: int):
    """Cache layer ``li``'s ``(page table, ring width, PAGE_TOKENS)``
    out of a tick's ``(per-layer page tables, PAGE_TOKENS)``."""
    pt = paged[0][li]
    return pt, pt.shape[1] * paged[1], paged[1]


def _serving_hidden(params, tok, pos, caches, cfg, *, kv_slice=None,
                    tp_psum=False, use_kernel=False, paged=None):
    """The model's layers on T rows a slot: (tok (S, T), pos (S,) for
    T = 1 or (S, T), caches) -> (the last block's output (S, T, d)
    with the streams folded, the model's layers' caches, hits).
    ``paged``: ``(per-layer page tables, PAGE_TOKENS)``. ``hits`` sums,
    over the dropless expert layers, the experts that got a row this
    step (None for a configuration without such a layer)."""
    x = embed(params, tok, cfg)  # (S, T, d)
    new = []
    hits = None
    for li, (lp, cl) in enumerate(zip(params["layers"], caches)):
        paged_l = None
        if paged is not None and cfg.rows(li):
            paged_l = _paged_layer(paged, li)
        x, cl, hit = _serving_layer(
            x, lp, cl, pos, cfg, li, kv_slice=kv_slice, tp_psum=tp_psum,
            use_kernel=use_kernel, paged=paged_l,
        )
        new.append(cl)
        if hit is not None:
            hits = hit if hits is None else hits + hit
    return hc_fold(x, cfg), new, hits


def _serving_forward(params, tok, pos, caches, cfg, **kw):
    """(tok (S,), pos (S,), caches) -> (logits (S, V), caches, hits):
    :func:`_serving_hidden` on one row a slot, and the head."""
    x, new, hits = _serving_hidden(params, tok[:, None], pos, caches, cfg,
                                   **kw)
    return head_logits(params, x, cfg)[:, 0], new, hits


def serving_decode_step_dense(params, tok, pos, caches,
                              cfg: TransformerConfig):
    """One batched serving decode step, dense: every slot at its own
    position. Returns (logits (S, V), caches). The single-position
    sibling is :func:`~.decode.decode_step_ring_dense`. Always the
    einsum path — this is the reference step the kernelized tick is
    pinned against."""
    ring_widths(cfg)
    return _serving_forward(params, tok, pos, caches, cfg)[:2]


def _pick_rows(lg, pos, keys, temperature, top_k, dtype):
    """Per-row token choice: greedy at temperature 0 (static), else
    per-row keyed sampling — each row evaluated as row 0 of its own
    B=1 stream THROUGH ``decode._pick_token`` itself (vmapped), so the
    fold/truncation discipline has one source of truth and a slot's
    sampled stream equals ``generate_ring_dense(..., key=key_row)``
    for the same request key by construction."""
    if temperature == 0.0:
        return jnp.argmax(lg, axis=-1).astype(dtype)
    return jax.vmap(
        lambda k, p, ll: _pick_token(
            ll[None], p, k, temperature, top_k, dtype
        )[0]
    )(keys, pos, lg)


def _scan_body(params, tok, pos, done, caches, cfg, eos_id, n_inner,
               keys, *, temperature=0.0, top_k=None,
               kv_slice=None, tp_psum=False, use_kernel=False,
               paged=None):
    """``n_inner`` decode steps for all S slots under one scan (greedy,
    or per-row keyed sampling when ``temperature > 0``; ``keys`` is
    required — a silent shared-default key would couple every
    scheduler's streams).
    Returns (tok, pos, done, caches, toks (S, n_inner)). A
    configuration with dropless expert layers gets one more ROW on
    ``toks``, (S + 1, n_inner): step by step, the experts that got a
    row, summed over those layers — the ``experts_hit`` counter rides
    home in the fetch that brings the tokens. Where the layers hold a
    share of their experts (``experts_held``) a second row follows:
    the pairs that fell on held experts."""

    def step(carry, _):
        tok, pos, done, caches = carry
        lg, caches, hits = _serving_forward(
            params, tok, pos, caches, cfg, kv_slice=kv_slice,
            tp_psum=tp_psum, use_kernel=use_kernel, paged=paged,
        )
        # the compiler fuses the pick into the head's product; one
        # scope over both keeps that fusion's time under ``head``
        with jax.named_scope("head"):
            nxt = _pick_rows(lg, pos, keys, temperature, top_k, tok.dtype)
            nxt, done = _eos_clamp(nxt, tok, done, eos_id)
        out = nxt if hits is None else (nxt, hits.astype(nxt.dtype))
        return (nxt, pos + 1, done, caches), out

    (tok, pos, done, caches), toks = jax.lax.scan(
        step, (tok, pos, done, caches), None, length=n_inner
    )
    if isinstance(toks, tuple):
        toks, hits = toks
        if hits.ndim == 1:  # one counter a step; a share of experts, two
            hits = hits[:, None]
        toks = jnp.concatenate([toks, hits], axis=1)
    return tok, pos, done, caches, toks.swapaxes(0, 1)


# A drafting step (``TransformerConfig(mtp_depth=1)`` under
# ``ServingScheduler(draft="mtp")``; DeepSeek-V3's multi-token
# prediction, arXiv:2412.19437 section 2.2, served). A slot holds its
# certain tokens up to position p and a draft d of the token at p + 1.
#
# * The model runs TWO rows a slot, (t_p, d) at (p, p + 1), each layer
#   writing both cache rows before it attends. ``x1 = pick(logits_p)``
#   is the token at p + 1 whatever the draft was. If ``x1 == d`` the
#   second row was computed on the right token and ``x2 =
#   pick(logits_{p+1})`` is the token at p + 2: the step delivers two
#   tokens for one read of the weights. Else it delivers ``x1`` alone.
# * ``pick`` is the scheduler's own (``_pick_rows``): greedy, or
#   ``argmax(logits / T + Gumbel(key folded with the position))``. The
#   draft was picked with the key of the position it is a draft FOR, so
#   draft and verification add the same noise and agree wherever their
#   logits do: the delivered stream is, token for token, the stream the
#   same scheduler delivers with the drafter off, at temperature 0 and
#   above it. No acceptance probability is a parameter anywhere.
# * The module then runs over the positions just made certain, rows
#   (h_p, x1) and (h_{p+1}, x2) at (p, p + 1) of its own cache layer,
#   and its row at the last certain position is the next step's draft.
# * A rejected draft leaves a stale row at p + 1 in every layer's
#   cache, the module's too. The next step starts at p + 1 and writes
#   that row before it attends, and until then no query above p exists:
#   the stale row is overwritten before it is read. A slot therefore
#   writes one row past its certain cursor, and a tick ``2 * n_inner``
#   rows at most: the page budget and the context check count that
#   (``ServingScheduler._tick_rows``).


def _draft_step(params, tok, pos, done, caches, cfg, eos_id, keys,
                temperature, top_k, paged=None):
    """One drafting step for all S slots on ring caches (or gathered
    views; ``paged``, :func:`_serving_hidden`'s: on latent layers'
    pages in place): ``tok`` (S, 2) is ``[t_p, d]``. Returns ``(tok,
    pos, done, caches)`` advanced by one or two positions a slot and
    ``(out, hits, mtp_hits)``: ``out`` (S, 4) int32 holds ``[x1, x2,
    accepted, d]`` and the hits are ``_serving_layer``'s, summed over
    the model's expert layers and of the module's."""
    n, dt = cfg.n_layers, tok.dtype
    pos2 = pos[:, None] + jnp.arange(2, dtype=pos.dtype)
    x, new, hits = _serving_hidden(params, tok, pos2, caches, cfg,
                                   paged=paged)
    lg = head_logits(params, x, cfg)  # (S, 2, V)
    pick = functools.partial(_pick_rows, keys=keys, temperature=temperature,
                             top_k=top_k, dtype=dt)
    with jax.named_scope("head"), jax.named_scope("verify"):
        x1, done1 = _eos_clamp(pick(lg[:, 0], pos), tok[:, 0], done, eos_id)
        x2, done2 = _eos_clamp(pick(lg[:, 1], pos + 1), x1, done1, eos_id)
        # a stream that has ended delivers nothing behind its end
        accept = x1 == tok[:, 1]
        if eos_id is not None:
            accept = accept & ~done2
    with jax.named_scope("mtp"):
        block = params["mtp"]["block"]
        h = mtp_input(params, x, jnp.stack([x1, x2], axis=1), cfg)
        h, cl, mtp_hits = _serving_layer(
            h, block, caches[n], pos2, cfg, n - 1,
            paged=None if paged is None else _paged_layer(paged, n))
        new.append(cl)
        # the head once, on the row at the last certain position
        last = jnp.where(accept[:, None, None], h[:, 1:], h[:, :1])
        q = mtp_logits(params, last, cfg)[:, 0]
        pos = pos + 1 + accept.astype(pos.dtype)
        with jax.named_scope("mtp_head"):
            draft = pick(q, pos)
    out = jnp.stack([x1, x2, accept.astype(dt), tok[:, 1]], axis=1)
    tok = jnp.stack([jnp.where(accept, x2, x1), draft], axis=1)
    done = jnp.where(accept, done2, done1)
    return (tok, pos, done, new), (out, hits, mtp_hits)


def _scan_body_draft(params, tok, pos, done, caches, cfg, eos_id, n_inner,
                     keys, *, temperature=0.0, top_k=None,
                     use_kernel=False, paged=None):
    """:func:`_scan_body` for a drafting scheduler: ``n_inner``
    :func:`_draft_step` under one scan. Over the einsum on rings and
    gathered views; with ``paged`` on latent layers' pages, where the
    kernel takes a slot's two queries as two rows (the K/V kernels take
    one query a slot: ``use_kernel`` without ``paged`` is refused).
    Returns (tok (S, 2), pos, done, caches, (out (S, n_inner, 4),
    counters (n_inner, c) int32)); the counters are, step by step, the
    model's expert layers' hits (two columns where the layers hold a
    share of their experts: the pairs that fell on held ones) and then
    the module's, the same columns."""

    assert (paged is not None) == bool(use_kernel)

    def step(carry, _):
        carry, (out, hits, mtp_hits) = _draft_step(
            params, *carry, cfg, eos_id, keys, temperature, top_k, paged)
        count = [jnp.atleast_1d(h).astype(jnp.int32)
                 for h in (hits, mtp_hits) if h is not None]
        return carry, (out, jnp.concatenate(count) if count
                       else jnp.zeros((0,), jnp.int32))

    (tok, pos, done, caches), (out, counters) = jax.lax.scan(
        step, (tok, pos, done, caches), None, length=n_inner)
    return tok, pos, done, caches, (out.swapaxes(0, 1), counters)


def _tick_body(cfg: TransformerConfig):
    """The scan a tick runs: the drafting one where the configuration
    carries the module (a scheduler with the drafter off drops it from
    its configuration, ``ServingScheduler.__init__``)."""
    return _scan_body_draft if cfg.mtp_depth else _scan_body


@functools.lru_cache(maxsize=32)
def _serving_scan_paged(cfg: TransformerConfig, n_inner: int,
                        eos_id: int | None, temperature: float,
                        top_k: int | None, use_kernel: bool, P: int):
    """Jitted tick: (params, tok, pos, done, caches, keys, pt) ->
    (tok, pos, done, caches, toks); ``pt`` is the ``(S, max_pages)``
    int32 page table (a loop-invariant input — the tick writes pages,
    never the table; COW retargeting happens host-side between ticks).
    The page pool is donated: the tick updates it in place in HBM.
    ``W = max_pages * P`` is recovered from the table shape so one
    compiled program serves any pool size at a given (cfg, P).
    ``use_kernel`` is the scheduler's RESOLVED int8-kernel routing.

    ``use_kernel=True`` (the int8 route: K/V layers of whole GQA
    groups at a lane-aligned head size, or latent layers alone with a
    latent of whole lane tiles, drafting or not;
    ``decode._paged_kernel_possible``) reads pages IN PLACE every
    step — the Pallas page-table mode's whole point. The einsum
    fallback (any cache in the model's dtype, widths off the lane
    tile, latent layers beside K/V layers, fewer slots than
    ``KERNEL_MIN_BATCH``) instead hoists the indirection OUT of the scan: the table
    is tick-invariant, so each layer's W-row ring view gathers ONCE,
    the unchanged ring scan (:func:`_scan_body`) runs on the views,
    and one scatter writes the views back through the table (inside
    the scan XLA re-materializes the view every step; hoisted, the
    gather amortizes over ``n_inner`` steps). The trade is a transient
    ``(S, W)``-row view per layer during the tick — active-slot bytes,
    not pool bytes; the kernel route has no such transient (PERF.md
    section 4: 4.83 GB reserved against 0.69)."""

    @functools.partial(jax.jit, donate_argnums=(4,))
    def serving_tick_paged(params, tok, pos, done, caches, keys, pt):
        # one table for all layers, or one per cache width: each layer
        # reads through its own kind's (``_layer_tables``)
        pts = _layer_tables(cfg, pt)
        if use_kernel:
            return _tick_body(cfg)(
                params, tok, pos, done, caches, cfg, eos_id, n_inner,
                keys, temperature=temperature, top_k=top_k,
                use_kernel=True, paged=(pts, P),
            )
        with jax.named_scope("kv_page_gather"):
            # (a recurrent layer's state is no page: it goes through)
            views = [
                cl if not cfg.rows(li)
                else _paged_gather(cl, t, cfg.cache_heads(li), P,
                                   _row_values(cfg, li))
                for li, (cl, t) in enumerate(zip(caches, pts))
            ]
        tok, pos, done, views, toks = _tick_body(cfg)(
            params, tok, pos, done, views, cfg, eos_id, n_inner, keys,
            temperature=temperature, top_k=top_k, use_kernel=False,
        )
        with jax.named_scope("kv_page_scatter"):
            caches = [
                vw if not cfg.rows(li)
                else _paged_scatter(cl, vw, t, P, cfg.sparse_stride)
                for li, (cl, vw, t) in enumerate(zip(caches, views, pts))
            ]
        return tok, pos, done, caches, toks

    return serving_tick_paged


@functools.lru_cache(maxsize=32)
def _seed_admit_paged(cfg: TransformerConfig, R: int, P: int):
    """Seed rows ``[0, ell)`` of a transient positional prefill cache
    from shared prefix pages: ring slot s of a within-window prefix
    holds position s, so the page rows ARE the positional rows and
    admission can skip recomputing them. ``R`` (static) bounds the
    gather at ``min(W, Lmax)``; rows at and past ``ell`` stay zero —
    exactly the arena admission hands to prefill (zeros out of
    :func:`_fresh_cache` or, recycled, :func:`serving_reset_arena`). The
    seeded bytes are the pages' bytes, which are the bytes this very
    prefill would have produced (pinned by the paged parity tests), so
    the oracle identity survives the skip. Cache donated; the page
    pool is only read."""

    @functools.partial(jax.jit, donate_argnums=(0,))
    def serving_seed_prefix(cache, pages, pt_row, ell):
        valid = jnp.arange(R) < ell
        nb = -(-R // P)  # pages that cover rows [0, R)

        def seed(kk, c, pg, row, li):
            g = _pages_to_rows(
                kk, jnp.take(pg, row[:nb], axis=0), cfg.cache_heads(li),
                P, _row_values(cfg, li),
            )[:R]  # (R, ...)
            g = jnp.where(
                valid.reshape((R,) + (1,) * (g.ndim - 1)), g, 0
            )
            return jax.lax.dynamic_update_slice_in_dim(
                c, g[None].astype(c.dtype), 0, axis=1
            )

        return [
            {kk: seed(kk, cl[kk], pl[kk], row, li) for kk in cl}
            for li, (cl, pl, row) in enumerate(zip(
                cache, pages, _layer_tables(cfg, pt_row)))
        ]

    return serving_seed_prefix


@functools.lru_cache(maxsize=32)
def _gather_ring_paged(cfg: TransformerConfig, P: int):
    """Materialize ONE slot's full W-row ring view out of the page
    pool — the capture half of a KV-page migration (models/disagg.py):
    the gathered leaves are fresh device buffers, so the source
    scheduler can free (and reuse) the slot's pages the moment this
    returns while the view stays valid for the destination's
    :func:`_place_paged` scatter. Shapes match ``_finish_admit_dense``'s
    ring output exactly — adoption IS a re-placement. The pool is only
    read (no donation)."""

    @jax.jit
    def serving_gather_ring(caches, pt_row):
        return [
            _paged_gather(cl, row[None], cfg.cache_heads(li), P,
                          _row_values(cfg, li))
            for li, (cl, row) in enumerate(zip(
                caches, _layer_tables(cfg, pt_row)))
        ]

    return serving_gather_ring


# Table entries one step of placement's loop writes: a scatter of that
# many page blocks a leaf, so a chat prompt's 1 to 8 pages are one step
# and a window-filling one W / P / 8 of them.
_PLACE_STEP = 8


@functools.lru_cache(maxsize=32)
def _place_paged(cfg: TransformerConfig, P: int):
    """Paged install: write the pages the request's rows have reached
    into the pool through its page-table row, and set the row state:
    first token, start position, key, ``done`` off. ``ring`` holds the
    request's ``W`` ring rows a layer; of them the page blocks ``0 ..
    ceil(pos0 / P) - 1`` go to their pages (all ``W / P`` once ``pos0``
    has passed ``W``: a wrapped ring, which only a migration places),
    ``_PLACE_STEP`` table entries a step of one loop whose trip count
    follows ``pos0``. A page behind them keeps what the pool held:
    every reader bounds its rows by the slot's position, the mask that
    is also the slot-reuse guard (:func:`_ring_attention_rows`), and the
    tick writes a row before a position can reach it. The one leaf that
    is ADDED to and not written, a selecting layer's pooled cells
    (``kp``, :func:`_paged_pool_rows`), needs zeros behind the prompt
    and is scattered whole, every table entry, those past the request's
    page budget into the null page. Everything donated — admission is
    an in-place write. Shared prefix pages get bytes IDENTICAL to what
    they already hold (the seed op put those very bytes into the
    transient cache), so the write never perturbs a sharer."""

    @functools.partial(jax.jit, donate_argnums=(0, 2, 3, 4))
    def serving_place_pages(caches, ring, tok, pos, done, keys, pt_row,
                            s, tok0, pos0, key):
        rows = _layer_tables(cfg, pt_row)
        held = -(-pos0 // P)  # pages the rows have reached, of any width

        def blocks(c, kk, x):  # ring rows x as the pool's page blocks
            return _rows_to_pages(kk, x, P, cfg.sparse_stride,
                                  c[kk].shape[-1]).astype(c[kk].dtype)

        def step(i, caches):
            # table entries [i * _PLACE_STEP, (i + 1) * _PLACE_STEP) of
            # each width's row, as the slice that lies inside the row;
            # an entry before the step's first or behind the last page
            # held goes nowhere (an index past the pool is dropped)
            out = []
            for c, r, row in zip(caches, ring, rows):
                paged = [kk for kk in c
                         if kk not in STATE_LEAVES and kk != "kp"]
                new = dict(c)
                if paged:
                    n = min(_PLACE_STEP, row.shape[0])
                    at = jnp.minimum(i * _PLACE_STEP, row.shape[0] - n)
                    j = at + jnp.arange(n)
                    to = jnp.where(
                        (j >= i * _PLACE_STEP) & (j < held),
                        jax.lax.dynamic_slice_in_dim(row, at, n),
                        c[paged[0]].shape[0])
                for kk in paged:
                    new[kk] = c[kk].at[to].set(blocks(
                        c, kk, jax.lax.dynamic_slice_in_dim(
                            r[kk][0], at * P, n * P)), mode="drop")
                out.append(new)
            return out

        most = max((row.shape[0] for row in rows if row is not None),
                   default=0)
        caches = jax.lax.fori_loop(
            0, -(-jnp.minimum(held, most) // _PLACE_STEP), step, caches)
        caches = [
            # a block of recurrent state goes over slot s's (a reused
            # slot starts from the new prompt's state); pooled cells
            # whole, zeros behind the prompt
            {kk: c[kk].at[s].set(r[kk][0].astype(c[kk].dtype))
             if kk in STATE_LEAVES
             else c[kk].at[row].set(blocks(c, kk, r[kk][0])) if kk == "kp"
             else c[kk] for kk in c}
            for c, r, row in zip(caches, ring, rows)
        ]
        return (caches, tok.at[s].set(tok0), pos.at[s].set(pos0),
                done.at[s].set(False), keys.at[s].set(key))

    return serving_place_pages


@functools.lru_cache(maxsize=32)
def _copy_pages_paged(cfg: TransformerConfig, P: int):
    """BATCHED COW page copies across every layer and leaf: all of a
    tick's ``src -> dst`` pairs in ONE jitted call (one dispatch on
    the tick's critical path however many sharers diverge at once,
    review r11), donated so the pool updates in place. Every src block
    is gathered BEFORE any dst block writes, so a page appearing as
    src twice (three-way sharing, two writers in one tick) reads its
    pre-copy bytes both times; dst pages are freshly allocated and
    never coincide with a src. The scheduler pads the pair lists to a
    power-of-two length with null-page self-copies (page 0 -> page 0,
    bytes nothing reads unmasked) to bound compile count."""

    @functools.partial(jax.jit, donate_argnums=(0,))
    def serving_copy_pages(caches, src, dst):
        def cp(a):
            return a.at[dst].set(jnp.take(a, src, axis=0))

        return [{kk: cp(cl[kk]) for kk in cl} for cl in caches]

    return serving_copy_pages


def _refuse_switch_experts(cfg: TransformerConfig) -> None:
    """The top-1 Switch layer seats tokens by capacity per CALL, so a
    prompt's chunks and a decode step drop differently from the whole
    forward; ``make_generate`` serves it. Dropless top-k expert layers
    (``layer_experts``) are per token and are served here."""
    if cfg.n_experts and cfg.layer_experts is None:
        raise ValueError(
            "the serving tick covers dense-FFN layers and dropless "
            "top-k expert layers (TransformerConfig(layer_experts=...)); "
            "the top-1 Switch layer drops by capacity per call "
            "(models/decode.py prefill caveat) and is served via "
            "make_generate"
        )


def _refuse_state_layers(cfg: TransformerConfig, what: str,
                         why: str) -> None:
    """``what`` is written for caches that are rows of K/V; refuse, by
    mechanism, a configuration with recurrent layers."""
    if cfg.state_layers:
        raise ValueError(
            f"{what}: this configuration has gated delta-rule layers "
            f"(or decayed linear attention, or a state-space mixer "
            f"beside attention or alone in its layer), whose "
            f"per-request state is one fixed block and no row a "
            f"token; {why}"
        )


def _refuse_sparse_layers(cfg: TransformerConfig, what: str,
                          why: str) -> None:
    """``what`` is written for layers that attend every row they keep;
    refuse, by mechanism, a configuration that attends a selection of
    its key blocks."""
    if cfg.sparse_layers:
        raise ValueError(
            f"{what}: this configuration's attention layers read a "
            f"selection of their key blocks, made from pooled keys kept "
            f"beside the rows; {why}"
        )


def _refuse_latent_layers(cfg: TransformerConfig, what: str,
                          why: str) -> None:
    """``what`` is written for caches that are rows of K and of V, a
    head each; refuse, by mechanism, a configuration with
    latent-attention layers."""
    if cfg.latent_layers:
        raise ValueError(
            f"{what}: this configuration has latent-attention layers, "
            f"whose cache is one row a position and no K/V head; {why}"
        )


def make_serving_scan(cfg: TransformerConfig, mesh: Mesh, n_inner: int,
                      *, eos_id: int | None = None,
                      quantize_kv: bool = False,
                      temperature: float = 0.0,
                      top_k: int | None = None):
    """Sharded serving tick: slots over ``dp``, heads over ``tp``
    (psum placement of the training path — the serving counterpart of
    :func:`~.decode.make_decode_step` with per-row positions).
    Returns ``f(params, tok, pos, done, caches, keys)`` jitted over
    ``mesh`` with the caches donated (``keys``: per-slot PRNG keys,
    used only at ``temperature > 0``). ``quantize_kv=True`` serves an int8 ring
    cache (scale leaves shard like their K/V; the per-row write/score
    paths detect the layout)."""
    require_plain_block(cfg, "make_serving_scan (the sharded tick)")
    _check_ring_cfg(cfg)
    _check_sampling_params(temperature, top_k)
    _refuse_switch_experts(cfg)
    tp = int(mesh.shape["tp"])
    if cfg.kv_heads % tp != 0 and tp % cfg.kv_heads != 0:
        raise ValueError(
            f"kv_heads {cfg.kv_heads} and tp {tp} must nest (one "
            "divide the other) for the sharded serving tick's cache "
            "layout"
        )
    # kv_heads < tp uses decode.py's replicated-groups layout: the
    # cache's global head axis has `tp` slots, slot t holding kv head
    # t*kv_heads//tp (each device computes its slot locally from the
    # tp-replicated K/V projections via make_kv_slice — no extra
    # collectives). Callers size the cache head axis with
    # `_cache_heads_global(cfg, mesh)` exactly like make_ring_generate.
    cspec = P("dp", None, "tp", None)
    layer_spec = {"k": cspec, "v": cspec}
    if quantize_kv:
        sspec = P("dp", None, "tp")
        layer_spec["k_s"], layer_spec["v_s"] = sspec, sspec
    cspecs = [dict(layer_spec) for _ in range(cfg.n_layers)]

    def local(params, tok, pos, done, caches, keys):
        # resolve at this shard's slot count: one ring-kernel call per
        # layer serves every local slot, so the gate compares the
        # per-call boundary cost against S_local amortizing rows
        routed = (
            _kernel_possible(cfg, quantize_kv)
            and _route_kernel(tok.shape[0])
        )
        return _scan_body(
            params, tok, pos, done, caches, cfg, eos_id, n_inner,
            keys, temperature=temperature, top_k=top_k,
            kv_slice=make_kv_slice(cfg), tp_psum=True,
            use_kernel=routed,
        )

    sharded = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(param_specs(cfg, mesh), P("dp"), P("dp"), P("dp"),
                  cspecs, P("dp")),
        out_specs=(P("dp"), P("dp"), P("dp"), cspecs,
                   P("dp", None)),
        # quantize_kv can route the int8 ring kernel inside the tick —
        # interpreted Pallas needs the same vma carve-out as
        # decode.py's make_decode_step; einsum-only programs keep
        # varying-axes checking on
        check_vma=not _decode_kernel_interpreted(cfg, quantize_kv),
    )

    def serving_tick_sharded(params, tok, pos, done, caches, keys):
        return sharded(params, tok, pos, done, caches, keys)

    return jax.jit(serving_tick_sharded, donate_argnums=(4,))


# --------------------------------------------------------------------------
# admission programs (chunked prefill -> ring window -> slot)
# --------------------------------------------------------------------------


@functools.lru_cache(maxsize=32)
def _extend_chunk_dense(cfg: TransformerConfig, C: int, Lmax: int):
    """One C-token prefill chunk into a (1, Lmax) transient positional
    cache at dynamic ``offset`` (make_extend semantics, dense B=1):
    (params, chunk (1, C), cache, offset) -> (hidden (1, C, d), cache).
    The chunk attends the key blocks its rows can see
    (``decode._chunk_attention``: work follows ``offset + C``, not
    ``Lmax``) and stops at the last layer's output: the head runs in
    :func:`_finish_admit_dense`, on the one row a request reads. One
    program per ``(cfg, C, Lmax)``; ``offset`` is traced. Cache
    donated: chunks stream through one arena. ``valid`` (a traced
    count; a configuration with recurrent layers passes it, no other)
    is how many of the chunk's rows are the prompt's: the padding
    after them must leave a recurrent layer's state alone. ``nxt``
    (1, C; a configuration with a multi-token-prediction module passes
    it, no other): the tokens that follow the chunk's, from which the
    module's own cache layer gets its rows."""

    @functools.partial(jax.jit, donate_argnums=(2,))
    def serving_prefill_chunk(params, chunk, cache, offset, valid=None,
                              nxt=None):
        return _incremental_hidden(
            params, chunk, cache, offset, cfg, prefill=False, valid=valid,
            nxt=nxt,
        )

    return serving_prefill_chunk


# Rows that must share one read of a weight before its product runs at
# the chip's arithmetic pace and no longer at its memory's: bfloat16
# operations a byte of HBM at the peaks (v5e: 197 TFLOP/s over 819 GB/s
# = 240).
_RIDGE_ROWS = 256


def _chunk_group_cap(cfg: TransformerConfig, C: int, slots: int) -> int:
    """How many requests' chunks one prefill program takes (1: each
    chunk is a program of its own, as it always was). A chunk gives a
    dense layer's weights ``C`` rows a read and an expert's ``C *
    experts_per_token / n_experts`` (Trinity-Mini 16, Qwen3-Next 5):
    below ``_RIDGE_ROWS`` the chunk waits for the weights' bytes, and a
    program over the rows of several requests reads them once for all.
    At or above it (StarCoder2-3B's dense chunk of 256) width buys
    nothing; measured, it cost a fifth more set-up for +0.3% (PERF.md
    section 6, PR 33). One size beside 1, because every program a
    scheduler holds costs 1.5 to 2.6 s of set-up whether a tick ever
    needs it or not: a group that does not fill it is padded
    (:meth:`ServingScheduler._run_chunk_group`), which a chunk that
    waits for bytes hardly feels. At most 4: a backlog of mixed lengths
    has 2 to 4 chunks due in nine of ten of 16 slots' ticks. The same
    number is the width, in chunks, of the one further program a
    scheduler that serves documents holds (:func:`_extend_chunk_wide`):
    as many rows as the grouped program has, for the same reason."""
    share = min(
        (cfg.experts_per_token / cfg.n_experts if cfg.dropless(li) else 1.0
         for li in range(cfg.n_layers)), default=1.0)
    return 1 if C * share >= _RIDGE_ROWS else min(4, slots)


@functools.lru_cache(maxsize=32)
def _extend_chunk_group(cfg: TransformerConfig, C: int, Lmax: int, n: int):
    """:func:`_extend_chunk_dense` for the chunks of ``n`` > 1 requests
    in one program (``decode._grouped_hidden``): (params, chunks (n, C),
    the n requests' arenas, offsets (n,)[, valid (n,)]) -> (n hidden
    states (1, C, d), the n arenas). Each weight is read once for all n
    chunks; each request's K/V goes into its own arena at its own
    offset and its queries walk that arena alone. All arenas donated.
    One program per ``(cfg, C, Lmax, n)``, named
    ``serving_prefill_chunk_x<n>``; a scheduler has the one of its
    ``_chunk_group_cap``."""

    def chunk_group(params, chunks, caches, offsets, valid=None, nxt=None):
        x, caches = _grouped_hidden(params, chunks, caches, offsets, cfg,
                                    valid, nxt)
        return tuple(x[i:i + 1] for i in range(n)), tuple(caches)

    chunk_group.__name__ = chunk_group.__qualname__ = (
        f"serving_prefill_chunk_x{n}")
    return jax.jit(chunk_group, donate_argnums=(2,))


@functools.lru_cache(maxsize=32)
def _extend_chunk_wide(cfg: TransformerConfig, C: int, Lmax: int, g: int):
    """:func:`_extend_chunk_dense` at ``g`` chunks' rows of ONE request:
    (params, chunk (1, g * C), cache, offset[, valid][, nxt]) -> (hidden
    (1, g * C, d), cache), the lone chunk's body at another width (the
    chunk's walk lies on absolute positions and a recurrent layer takes
    any whole number of its sub-chunks, so the arena and the state come
    out as ``g`` chunks one after another leave them). A program of the
    grouped one's rows, all of them one prompt's: a document advances
    ``g`` chunks where a chunk waits for the weights' bytes
    (:meth:`ServingScheduler._wide_slot`). One program per ``(cfg, C,
    Lmax, g)``, named ``serving_prefill_chunk_w<g>``."""

    def chunk_wide(params, chunk, cache, offset, valid=None, nxt=None):
        return _incremental_hidden(
            params, chunk, cache, offset, cfg, prefill=False, valid=valid,
            nxt=nxt,
        )

    chunk_wide.__name__ = chunk_wide.__qualname__ = (
        f"serving_prefill_chunk_w{g}")
    return jax.jit(chunk_wide, donate_argnums=(2,))


@functools.lru_cache(maxsize=32)
def _finish_admit_dense(cfg: TransformerConfig, Lmax: int,
                        temperature: float = 0.0,
                        top_k: int | None = None):
    """The last-W window of a filled positional cache as ring rows
    (:func:`~.decode._ring_from_cache`, a layer at a time: where
    ``Lmax <= W`` no prompt wraps the ring and the arena's rows are the
    ring's, zeroed behind the prompt; a narrower ring is gathered by
    position) + pick the first token: the head on row ``true_len - 1 -
    last_off`` of the last chunk's hidden state, the prompt's last
    position (greedy, or sampled with the request's key there —
    decode.py's fold discipline), so the head's weights are read once a
    request:
    (params, cache, last_hidden (1, C, d), true_len, last_off, key) ->
    (tok0 (), ring leaves (1, W, ...)). A recurrent layer's "ring" is
    its state as the last chunk left it. With a multi-token-prediction
    module ``tok0`` is (2,), the first token and the first DRAFT: the
    module's row at the prompt's last position needs the token that
    follows it, which is the first token, so that one row is run here
    (into the arena's copy, before the window is gathered) and its
    logits give the draft of the token behind the first."""
    widths = _row_widths(cfg)

    @jax.jit
    def serving_first_token(params, cache, last_hidden, true_len,
                            last_off, key):
        def window(cache):
            return [cl if W is None else _ring_from_cache(
                cl, true_len, W, cfg.sparse_stride or None)
                    for cl, W in zip(cache, widths)]

        ring = None if cfg.mtp_depth else window(cache)
        row = jax.lax.dynamic_slice_in_dim(
            last_hidden, true_len - 1 - last_off, 1, axis=1
        )
        lg = head_logits(params, row, cfg)[:, 0]  # (1, V)
        pick = functools.partial(_pick_rows, keys=key[None],
                                 temperature=temperature, top_k=top_k,
                                 dtype=jnp.int32)
        with jax.named_scope("head"):  # as in ``_scan_body``
            tok0 = pick(lg, (true_len - 1)[None])[0]
        if ring is not None:
            return tok0, ring
        n = cfg.n_layers
        with jax.named_scope("mtp"):
            h, cl = _incremental_layer(
                mtp_input(params, row, tok0[None, None], cfg),
                params["mtp"]["block"], cache[n], (true_len - 1)[None],
                cfg, n - 1, chunk_attn=None, kv_slice=None, tp_psum=False)
            q = mtp_logits(params, h, cfg)[:, 0]
            with jax.named_scope("mtp_head"):
                draft = pick(q, true_len[None])[0]
        return jnp.stack([tok0, draft]), window(cache[:n] + [cl])

    return serving_first_token


# --------------------------------------------------------------------------
# observability: the tick's phase boundaries go to the profiler always;
# the obs/ registry + timeline are strictly opt-in
# --------------------------------------------------------------------------
#
# Every phase of ``ServingScheduler.step`` is one ``with`` block around a
# ``jax.profiler.TraceAnnotation`` (obs/timeline.py: annotate), entered
# whether or not anything is attached: an open profiler session is the
# switch, and with none open a boundary costs an atomic check. Names are
# fixed strings; what varies rides in the arguments. docs/API.md
# ("Scheduler phases in a profiler trace") is the operator's table.


class _LitPhase:
    """A phase boundary of a LIT tick: the annotation every tick
    enters, plus the two clock reads from which the span recorder, the
    registry series and the flight ring are cut — one set of
    boundaries, not a second set of stamps. Dark ticks enter the bare
    annotation and read no clock."""

    __slots__ = ("_ann", "t0", "t1")

    def __init__(self, name: str, **args):
        self._ann = _annotate(name, **args)
        self.t0 = self.t1 = 0.0

    def __enter__(self) -> "_LitPhase":
        self._ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        self.t1 = time.perf_counter()
        self._ann.__exit__(*exc)

    def set_metadata(self, **args) -> None:
        self._ann.set_metadata(**args)


class _ServingObs:
    """Instrument bundle for one scheduler, resolved ONCE at
    construction so the tick path only increments/observes. Built only
    when a registry or span recorder is attached — a dark scheduler's
    tick does no observability work beyond ``is not None`` checks and
    the profiler's phase annotations (the tracer's opt-in contract,
    utils/trace.py), which the no-op overhead tests in
    tests/test_obs.py and tests/test_serving_spans.py pin.
    """

    def __init__(self, sched: "ServingScheduler", registry, spans):
        self.registry = registry
        self.spans = spans
        # tokens delivered in the CURRENT tick (admission first-tokens
        # + trimmed decode harvest — the same population as
        # serving_tokens_total, so the per-tick rate and the running
        # counter always cross-check)
        self._tick_toks = 0
        # last published page-pool tallies (delta counters)
        self._last_share = 0
        self._last_cow = 0
        self._r = registry is not None
        if not self._r:
            return
        registry.gauge(
            "serving_slots", help="configured serving slots"
        ).set(sched.S)
        self.m_queue = registry.gauge(
            "serving_queue_depth",
            help="requests queued, not yet admitted",
        )
        self.m_active = registry.gauge(
            "serving_active_slots", help="slots decoding or admitting"
        )
        self.m_ticks = registry.counter("serving_ticks_total")
        self.m_tick_s = registry.histogram(
            "serving_tick_seconds", help="scheduler tick wall clock"
        )
        self.m_tokens = registry.counter(
            "serving_tokens_total",
            help="tokens delivered into request streams (first tokens "
            "+ decode harvest, post-retirement trim)",
        )
        self.m_tok_rate = registry.gauge(
            "serving_tokens_per_s",
            help="tokens delivered / tick wall, last tick",
        )
        self.m_ttft = registry.histogram(
            "serving_ttft_seconds", help="submit -> first token"
        )
        self.m_intertoken = registry.histogram(
            "serving_intertoken_seconds",
            help="mean per-token gap, one sample per (slot, tick)",
        )
        self.m_admitted = registry.counter("serving_admitted_total")
        self.m_retired = {
            "eos": registry.counter(
                "serving_retired_total", reason="eos"
            ),
            "length": registry.counter(
                "serving_retired_total", reason="length"
            ),
        }
        self.m_prefill = registry.counter(
            "serving_prefill_chunks_total",
            help="admission prefill chunks advanced",
        )
        # (only a scheduler that has the wide program has the series)
        self.m_prefill_wide = None if sched._extend_wide is None else (
            registry.counter(
                "serving_prefill_wide_chunks_total",
                help="those of them that one request advanced together, "
                "in the wide prefill program",
            ))
        # the route resolved for THIS scheduler (fixed
        # at construction against its slot count — see use_kernel);
        # incremented once per decode tick, so the series records when
        # the kernel route actually fired, not just that it could
        self.m_route = registry.counter(
            "serving_kernel_route_total",
            help="decode ticks by resolved int8-kernel route",
            route="kernel" if sched.use_kernel else "einsum",
        )
        # page-pool series: pool occupancy gauges plus prefix-share /
        # COW counters published as deltas of the pool's lifetime
        # tallies, so the registry stays monotone however often the
        # pool is sampled
        self.m_pages_free = registry.gauge(
            "serving_cache_pages_free",
            help="KV cache pages on the free list",
        )
        self.m_pages_used = registry.gauge(
            "serving_cache_pages_used",
            help="KV cache pages allocated to slots",
        )
        # tier-labeled (cache/ package): hbm = local share, the
        # only tier a fleet-less scheduler ever increments;
        # dram/peer appear lazily via fleet_hit when a fleet
        # cache serves the page instead
        self.m_share = registry.counter(
            "serving_prefix_share_hits_total",
            help="prompt prefix pages whose prefill was skipped "
            "at admission, by serving tier (hbm = local share, "
            "dram = host page store, peer = replica fetch)",
            tier="hbm",
        )
        self._share_tier: dict[str, Any] = {"hbm": self.m_share}
        self.m_cow = registry.counter(
            "serving_cow_copies_total",
            help="copy-on-write page copies (a slot wrote a page "
            "another slot still reads)",
        )
        # QoS series (qos= schedulers only): per-tenant admission
        # counters plus deficit / page-quota-usage gauges, series
        # created lazily per tenant and cached (the _RouterObs
        # per-labelset pattern — label churn is bounded by the
        # registry's tenant count)
        self._qos = getattr(sched, "_qos", None)
        if self._qos is not None:
            self._q_admit: dict[str, Any] = {}
            self._q_deficit: dict[str, Any] = {}
            self._q_quota: dict[str, Any] = {}

    # -- hooks (each guards its own registry half) ----------------------
    def qos_admitted(self, sched: "ServingScheduler",
                     tenant: str) -> None:
        if not self._r or self._qos is None:
            return
        c = self._q_admit.get(tenant)
        if c is None:
            cls = (self._qos.get(tenant).cls
                   if tenant in self._qos else "unknown")
            c = self._q_admit[tenant] = self.registry.counter(
                "qos_admitted_total",
                help="requests admitted into slots, by tenant and "
                "SLO class (DRR order)",
                tenant=tenant, cls=cls,
            )
        c.inc()

    def qos_gauges(self, sched: "ServingScheduler") -> None:
        """Per-tenant deficit + quota-usage gauges, refreshed once per
        tick (tick_done)."""
        drr = sched._drr
        for contract in self._qos:
            t = contract.name
            g = self._q_deficit.get(t)
            if g is None:
                g = self._q_deficit[t] = self.registry.gauge(
                    "qos_deficit",
                    help="carried DRR credit (tokens) per tenant",
                    tenant=t,
                )
            g.set(drr.deficit(t))
            q = self._q_quota.get(t)
            if q is None:
                q = self._q_quota[t] = self.registry.gauge(
                    "qos_pages_quota_used",
                    help="KV pages attributed to the tenant "
                    "(hot refs + cold cache) against its quota",
                    tenant=t,
                )
            q.set(sched._tenant_usage(t))

    def first_token(self, req: "Request", t: float) -> None:
        self._tick_toks += 1
        if self._r:
            self.m_admitted.inc()
            self.m_tokens.inc()
            if req._t_submit is not None:
                self.m_ttft.observe(t - req._t_submit)
        req._t_last_tok = t

    def tokens_emitted(self, req: "Request", n: int, t: float) -> None:
        self._tick_toks += n
        if self._r:
            self.m_tokens.inc(n)
            last = req._t_last_tok
            # (a first token handed over with its tick's tokens is
            # stamped with that tick's fetch: no gap to sample)
            if last is not None and n and t > last:
                self.m_intertoken.observe((t - last) / n)
        req._t_last_tok = t

    def prefill_chunk(self, n: int = 1, wide: bool = False) -> None:
        if self._r:
            self.m_prefill.inc(n)
            if wide:
                self.m_prefill_wide.inc(n)

    def fleet_hit(self, tier: str) -> None:
        """One prefix page served from the fleet cache (``dram`` |
        ``peer``) instead of prefilled — the same family as the local
        share counter, so tier shares read off one query."""
        if not self._r:
            return
        c = self._share_tier.get(tier)
        if c is None:
            c = self._share_tier[tier] = self.registry.counter(
                "serving_prefix_share_hits_total", tier=tier,
            )
        c.inc()

    def tick_done(
        self, sched: "ServingScheduler", retired, tick: _LitPhase,
        phases: list[tuple[str, _LitPhase]], ahead: bool,
    ) -> None:
        """The closed phases of the tick just run, in their order:
        ``serving.tick`` and, by the recorder's names, ``admit`` /
        ``decode`` / ``retire`` (``serving.harvest``). A tick in which
        no slot decoded has its one ``admit``; a tick that planned the
        next one's admissions behind its own program has a second
        ``admit`` between two ``decode`` spans (``ahead``: this tick's
        own admissions were planned that way)."""
        wall = tick.t1 - tick.t0
        n_toks, self._tick_toks = self._tick_toks, 0
        if self._r:
            self.m_ticks.inc()
            self.m_tick_s.observe(wall)
            self.m_queue.set(sched.pending)
            self.m_active.set(sched.active)
            self.m_tok_rate.set(n_toks / wall if wall > 0 else 0.0)
            if any(name == "decode" for name, _ in phases):
                self.m_route.inc()
            for req in retired:
                self.m_retired[req.reason].inc()
            pool = sched.pool
            self.m_pages_free.set(pool.free)
            self.m_pages_used.set(pool.used)
            self.m_share.inc(pool.share_hits - self._last_share)
            self._last_share = pool.share_hits
            self.m_cow.inc(pool.cow_copies - self._last_cow)
            self._last_cow = pool.cow_copies
            if self._qos is not None:
                self.qos_gauges(sched)
        sp = self.spans
        if sp is not None:
            sp.add(
                f"tick {sched.tick_count}", tick.t0, wall,
                track="scheduler", queue=sched.pending,
                active=sched.active, tokens=n_toks,
                retired=len(retired), ahead=int(ahead),
            )
            for name, ph in phases:
                sp.add(name, ph.t0, ph.t1 - ph.t0, track="scheduler")
            sp.count("queue_depth", sched.pending, t=tick.t1)
            sp.count("active_slots", sched.active, t=tick.t1)
            sp.count("pages_used", sched.pool.used, t=tick.t1)


# --------------------------------------------------------------------------
# the scheduler
# --------------------------------------------------------------------------


class Request:
    """One generation request: ``prompt`` (1D int tokens) in,
    ``tokens`` (the generated ids, EOS kept if emitted) out.
    ``finished`` flips at retirement; ``reason`` is ``"eos"``,
    ``"length"``, or ``"cancelled"`` (withdrawn via
    :meth:`ServingScheduler.cancel` — the router's losing hedge leg).
    ``tenant`` names the contract the request is billed to (the QoS
    plane, ``qos/``); None = untenanted (the default on schedulers
    without ``qos=``)."""

    _next_id = 0

    def __init__(self, prompt, max_new: int, key=None,
                 tenant: str | None = None):
        self.id = Request._next_id
        Request._next_id += 1
        # per-request PRNG key (sampling schedulers); None -> id-derived
        self.key = key
        self.tenant = tenant
        self.prompt = np.asarray(prompt, np.int32).reshape(-1)
        if self.prompt.size == 0:
            raise ValueError("empty prompt")
        if max_new < 1:
            raise ValueError(f"max_new must be >= 1, got {max_new}")
        self.max_new = int(max_new)
        self.tokens: list[int] = []
        # under ``ServingScheduler(draft=...)``: every draft a decode
        # step verified for this request, ``(index in tokens of the
        # token it guessed, the draft, whether it was accepted)``; the
        # token's position is ``len(prompt) + index``
        self.drafts: list[tuple[int, int, bool]] = []
        self.finished = False
        self.reason: str | None = None
        # filled by the scheduler: admission tick and retirement tick,
        # the observability hooks the tests and bench read
        self.admitted_tick: int | None = None
        self.retired_tick: int | None = None
        # the first token while it is still a device value: made when
        # admission ended, handed over with the fetch of the request's
        # first tick (scheduler-internal: ``_ends_known``)
        self._first = None
        # latency stamps (perf_counter), set only by an instrumented
        # scheduler (registry=/spans=): submit time and last-token time
        self._t_submit: float | None = None
        self._t_last_tok: float | None = None
        # incremental EOS-scan state (scheduler-internal): index of the
        # first EOS if found, and how many tokens were already scanned
        self._eos_at: int | None = None
        self._scanned = 0
        # causal tracing (round 22): the TraceBook id following this
        # request across planes (None = dark). _trace_owned marks a
        # trace MINTED at this scheduler's door — terminal events are
        # stamped by the owner only (a router-managed leg's terminals
        # belong to the router, obs/tracing.py docstring)
        self.trace: int | None = None
        self._trace_owned = False


class _PageKind:
    """One cache width's share of the paged arena: the layers that have
    it, their :class:`PagePool`, the host-authoritative page table
    (``(slots, W // P)``) those layers read through, and per slot
    whether the resident request's lifetime can wrap a ring this wide
    (its departure must then drop the wrapper count on every page it
    holds — paging.py). A configuration of sliding-window layers alone
    has one kind; window layers beside full-attention layers have two,
    and a request holds pages of both."""

    def __init__(self, name: str, W: int, P: int, n_pages: int,
                 slots: int, layers: tuple[int, ...]):
        self.name = name
        self.W = W
        self.max_pages = W // P
        self.layers = layers
        self.pool = PagePool(n_pages, P)
        self.pt_host = np.full((slots, self.max_pages), NULL_PAGE,
                               np.int32)
        self.slot_wraps = [False] * slots


class _Admitting:
    """Per-slot chunked-prefill state machine: the transient positional
    cache (the arena: taken from the scheduler's free list, back on it
    when the admission ends), the chunk cursor and the page plan:
    ``base`` (tokens of shared prefix
    whose prefill is SKIPPED — chunk i runs at offset ``base + i*C``),
    ``pids`` (the slot's full page-table row of every cache width, in
    the scheduler's kind order, installed into the device tables only
    at finish — until then the row's stale writes land in the null
    page), ``digests``/``n_cover`` (prefix digests to register at
    finish) and ``wraps`` (per width, whether this request can wrap
    that ring — registered pages are then volatile)."""

    def __init__(self, req: Request, cache, padded, n_chunks: int, *,
                 base: int, pids, digests, n_cover: int, wraps):
        self.req = req
        self.cache = cache
        self.padded = padded  # (1, n_chunks * C) int32, on the host
        self.n_chunks = n_chunks
        self.next_chunk = 0
        self.last_hidden = None  # (1, C, d) of the newest chunk
        self.base = base
        self.pids = pids
        self.digests = digests
        self.n_cover = n_cover
        self.wraps = wraps


class ServingScheduler:
    """Continuous-batching scheduler over ``slots`` fixed serving
    slots (dense single-device programs; the sharded tick is
    :func:`make_serving_scan`).

    >>> sched = ServingScheduler(params, cfg, slots=8, eos_id=2,
    ...                          page_tokens=64)
    >>> r = sched.submit(prompt, max_new=64)   # any time, any order
    >>> sched.run()                            # or step() per tick
    >>> r.tokens                               # greedy == oracle

    Each ``step()`` tick: (1) advance every admitting request by one
    prefill chunk (where a wide program exists, the module note, the
    oldest document by ``_chunk_group_cap`` chunks), installing
    finished ones into their slot; (2) admit
    queued requests into free slots, each running its first chunk
    (chunks due together share their programs); (3) run ``n_inner``
    decode steps for all slots in one device program; (4) harvest
    tokens, retire
    rows that emitted EOS or exhausted their budget, free their slots.
    Without ``eos_id`` (and without a drafter, ``qos=`` or ``cache=``)
    a tick's ends follow from lengths, and (1) and (2) of the NEXT tick
    run between this tick's dispatch and the fetch of its tokens,
    behind it on the device (:meth:`step` has the order): the same
    schedule and streams, with the host's work hidden behind the
    chip's; ``ticks_ahead`` counts the ticks planned that way.
    Greedy by default; ``temperature > 0`` (optionally ``top_k``)
    samples each slot with its request's own key (``submit(...,
    key=...)``; id-derived when omitted) — a sampled stream equals
    ``generate_ring_dense(..., key=request_key)`` exactly, like the
    greedy==oracle contract. With ``quantize_kv=True`` that equality is
    a reading, not an identity: admission's chunks and the oracle's
    whole prompt both attend the already-quantized cache, but they are
    different program shapes, and what the tests pin on their tiny
    float32 configurations can round apart on a real model or a long
    prompt.

    ``prompt_chunk`` bounds the decode stall a long prompt can inject
    into in-flight requests (a tick runs one chunk of each admitting
    request, as many a program as share one; where the scheduler has
    the wide program, at most one request a tick advances a grouped
    program's rows instead, and that tick runs at most one program
    more: ``prefill_chunks`` and ``wide_chunks`` count both);
    ``max_prompt`` sizes the transient prefill arena (one compile for
    all prompt lengths) and, with ``slots``, says whether the
    deployment serves documents (the module note).

    The cache is a PAGE POOL (docs/API.md "Paged serving cache"):
    per-layer K/V live in ``cache_pages`` fixed-size pages of
    ``page_tokens=P`` ring slots managed by a host-side
    :class:`PagePool` (free list + refcounts), each slot reading
    through a ``(max_pages,)`` page-index row; ``P`` divides every
    cache width, and one page a window (``P = W``) is a ring a slot.
    What the pool does (the streams are the oracle's whatever ``P`` —
    the paged parity tests pin it):

    * **Right-sized residency.** A request holds only the pages its
      lifetime can touch (``ceil(min(W, Tp + max_new + n_inner) / P)``),
      never a full window it will not fill, and ``cache_pages`` (not
      ``slots``) is the capacity knob. Admission defers when the pool
      cannot cover a request's whole budget, so mid-decode exhaustion
      cannot happen.
      The DEFERRAL UNIT is the admission-order contract: FIFO (the
      default) defers the head of the one queue — no reordering, a
      large request cannot be starved by later small ones; under
      ``qos=`` the deficit-round-robin hook defers only that TENANT's
      queue while the rotation tries the next, so one tenant's
      unplannable head never blocks another tenant's admission.

    **Multi-tenant QoS** (``qos=`` a :class:`~..qos.TenantRegistry`,
    docs/API.md "Multi-tenant QoS"): ``submit`` then requires
    ``tenant=`` (unknown tenants refused by name) and admission order
    comes from a :class:`~..qos.DeficitScheduler` over per-tenant
    queues — weighted, work-conserving, deficits carried — instead of
    FIFO. Each contract's page QUOTA is enforced at plan time, with
    COW-aware graceful reclaim: a retiring request's
    still-registered, refcount-1 prefix pages go COLD
    (resident for future sharers, attributed to the tenant) instead
    of freeing, and reclaim evicts cold pages oldest-first — an
    over-quota tenant's first — while a page shared with any live
    holder (refcount > 1) is never touched.
    * **Prefix sharing.** Admission hashes the prompt's page-aligned
      prefix (chained digests — page j's key covers ``prompt[:(j+1) *
      P]``, the exact content determinant) and shares resident pages
      by refcount, SKIPPING their prefill entirely: N users on one
      system prompt pay its prefill and residency once while any
      sharer is resident.
    * **Copy-on-write.** Writers never touch a shared page: the
      pre-tick pass copies any page the next ``n_inner`` steps would
      write while its refcount > 1 (reserved at admission for
      window-wrapping requests), so a reader's bytes are immutable.

    The decode tick reads K/V through the page table: the einsum path
    gathers each slot's W-row ring view (``jnp.take``, then the ring
    math: the CPU-testable fallback); int8 caches route
    the Pallas kernel's page-table mode, where the per-slot page row
    rides scalar-prefetch SMEM and block index maps gather pages
    directly (no materialized ring view at all).

    Observability is strictly opt-in (the tracer contract): pass
    ``registry=`` (an :class:`~..obs.MetricsRegistry`) for tick/queue/
    slot/tokens-per-s series, TTFT and inter-token histograms, and
    kernel-route counters, and/or ``spans=`` (an
    :class:`~..obs.SpanRecorder`) for per-tick admit/decode/retire
    spans in the merged Perfetto timeline
    (:func:`~..obs.dump_merged_chrome_trace`); ``flight=`` (an
    :class:`~..obs.FlightRecorder`) for per-tick spans in the bounded
    postmortem ring plus the ``last_tick_at`` liveness stamp a flight
    watchdog probes; ``exporter=`` (an :class:`~..obs.ObsServer`) to
    register the tick-freshness ``/healthz`` check and the span
    recorder as a ``/trace`` source. With none of them, the tick path
    reads no clock and builds no registry object. What every tick does,
    attached or not, is enter one ``jax.profiler.TraceAnnotation`` per
    phase (``serving.tick`` around ``serving.admit`` / ``.decode`` /
    ``.harvest``, a second ``.admit`` between two ``.decode`` where the
    next tick is planned ahead; the table is in docs/API.md): an open profiler
    session sees them on the device trace's clock, and with none open
    each costs an atomic check. ``spans=`` and ``flight=`` cut their
    spans at the same boundaries.

    ``draft="mtp"``: decode steps of one or two tokens a slot, drafted
    by the configuration's own multi-token-prediction module (the
    module docstring; ``Request.drafts``, ``drafted`` / ``accepted`` on
    ``serving.tick``). Without it a configuration that carries the
    module is served as if it did not.
    """

    def __init__(self, params, cfg: TransformerConfig, *, slots: int = 8,
                 n_inner: int = 8, eos_id: int | None = None,
                 prompt_chunk: int = 256, max_prompt: int = 2048,
                 quantize_kv: bool = False, temperature: float = 0.0,
                 top_k: int | None = None, page_tokens: int,
                 cache_pages: int | None = None,
                 qos: TenantRegistry | None = None,
                 max_queue: int | None = None, registry=None,
                 spans=None, flight=None, exporter=None, trace=None,
                 cache=None, draft: str | None = None):
        cfg = self._resolve_draft(params, cfg, draft, qos, cache)
        self.draft = draft
        # rows a slot runs in a decode step, and the most a tick writes
        # past a slot's position when it begins (a drafting step writes
        # the draft's row one past the certain one)
        self._rows = 2 if draft else 1
        self._tick_rows = int(n_inner) * self._rows
        # every layer's ring width, and the distinct ones ("kinds",
        # narrowest first): W is the narrowest, which is the whole
        # story for a configuration of sliding-window layers alone
        widths = _row_widths(cfg)
        kinds, kind_of = _layer_kinds(cfg)
        if not kinds:
            raise ValueError(
                "every layer keeps recurrent state and none K/V rows: "
                "the scheduler's positions, pages and context budget "
                "are those of an attention layer, and there is none"
            )
        W = kinds[0]
        _check_sampling_params(temperature, top_k)
        _refuse_switch_experts(cfg)
        if qos is not None or cache is not None:
            _refuse_state_layers(
                cfg, "page quotas (qos=) and the fleet prefix cache "
                "(cache=)", "both count and move prefix pages, and a "
                "prefix page is no use without the state at its "
                "boundary, which is kept nowhere")
        # a prompt's resident prefix pages let admission skip their
        # prefill; the recurrent state at the page boundary exists
        # nowhere, so with state layers nothing is shared or registered.
        # Nor with a drafter: its module's row at a page's last
        # position is made from the token BEHIND the page, which no
        # prefix digest covers
        # Nor under a selection of key blocks: the seed of a prefill
        # arena from shared pages copies rows, and is not written for
        # the pooled cells a page keeps beside them
        self.shares_prefixes = (not cfg.state_layers
                                and not cfg.sparse_layers
                                and draft is None)
        if qos is not None or cache is not None:
            _refuse_sparse_layers(
                cfg, "page quotas (qos=) and the fleet prefix cache "
                "(cache=)", "both count and move prefix pages, which "
                "this configuration does not share")
        if cfg.sparse_layers and int(page_tokens) != cfg.sparse_block:
            raise ValueError(
                f"a selection of key blocks is a selection of pages: "
                f"page_tokens {page_tokens} must be sparse_block "
                f"{cfg.sparse_block}")
        if slots < 1 or n_inner < 1:
            raise ValueError("slots and n_inner must be >= 1")
        if prompt_chunk > max_prompt:
            raise ValueError("prompt_chunk must be <= max_prompt")
        if len(kinds) > 1 and (qos is not None or cache is not None):
            raise ValueError(
                "page quotas (qos=) and the fleet prefix cache (cache=) "
                "count and move pages of one pool; this configuration "
                "has layers of more than one cache width "
                f"({list(kinds)}), a pool each"
            )
        self.P = int(page_tokens)
        if self.P < 1 or any(w % self.P for w in kinds):
            raise ValueError(
                f"page_tokens must divide the attention window "
                f"(W={W}) and every other cache width "
                f"({list(kinds)}), got {page_tokens}"
            )
        self.max_pages = W // self.P
        self.params = params
        self.cfg = cfg
        self.S = int(slots)
        self.W = W
        # the narrowest width that is a context BUDGET and not a
        # window: a request that could write past it is refused at
        # submit (a ring that wide must never wrap)
        self._context = min(
            (w for w, span in zip(widths, cfg.windows)
             if span is None and w is not None),
            default=None,
        )
        # dropless expert layers: the tick counts the experts that got
        # a row, and the count comes home with the tokens
        self._expert_layers = sum(
            cfg.dropless(li) for li in range(cfg.n_layers))
        self.experts_hit: float | None = None
        # where the layers hold a share of their experts: the pairs
        # (token, chosen expert) that fell on held experts, the same mean
        self.pairs_local: float | None = None
        self.n_inner = int(n_inner)
        self.eos_id = eos_id
        self.C = int(prompt_chunk)
        self.Lmax = int(max_prompt)
        self.quantize_kv = bool(quantize_kv)
        # queue-depth ceiling (chaos plane): the scheduler's own
        # bounded-queue backstop. The ROUTER is the shed point (it
        # refuses by name with a reason before a replica ever sees the
        # request); this ceiling is the hard assertion behind it — a
        # submit past it is a caller bug surfaced by name, never an
        # unbounded deque.
        if max_queue is not None and max_queue < 1:
            raise ValueError(
                f"max_queue must be >= 1 or None, got {max_queue}"
            )
        self.max_queue = None if max_queue is None else int(max_queue)
        self._queue: deque[Request] = deque()
        # multi-tenant QoS (opt-in): admission order moves from the
        # FIFO deque to a weighted deficit-round-robin scheduler over
        # per-tenant queues, and paged admission enforces page quotas
        # with cold-page reclaim (class docstring; qos/ package)
        self._qos = qos
        self._drr = DeficitScheduler(qos) if qos is not None else None
        if qos is not None and len(qos) == 0:
            raise ValueError(
                "qos= needs at least one TenantContract registered: "
                "an empty registry can admit nothing"
            )
        if qos is not None:
            # per-tenant page accounting: hot refs (pages the tenant's
            # resident slots hold) + cold pages (retired prefix pages
            # kept resident, attributed to the tenant that landed
            # them); quota usage is their sum
            self._tenant_pages: dict[str, int] = {}
            self._cold: dict[int, str] = {}  # pid -> tenant, oldest first
            self._cold_count: dict[str, int] = {}
        self._slot_req: list[Request | None] = [None] * self.S
        self._admitting: dict[int, _Admitting] = {}  # slot -> state
        # dead prefill arenas awaiting their next admission: one comes
        # back at every exit of admission (_release_arena) and a new
        # one is made only while this list is empty, so list plus live
        # _Admitting.cache never exceed the slots
        self._free_arenas: list[list[dict]] = []
        # slots whose next chunk is due and not yet dispatched: the
        # chunks of one tick share their programs (_run_pending)
        self._pending: list[int] = []
        self._tick_chunks = self._tick_chunk_programs = 0
        self._tick_wide = 0  # of ``_tick_chunks``, in the wide program
        # the chunks (of ``prompt_chunk`` rows) run so far, and those of
        # them that ran in the wide program: over any stretch of ticks
        # the share of prefill rows that ran wide is the quotient of
        # their differences
        self.prefill_chunks = self.wide_chunks = 0
        self.tick_count = 0
        # the ticks whose admissions were planned behind the tick
        # before them (``step``), and what that plan left for the tick
        # it was made for: the counts as that tick begins and the queue
        # behind its admissions (None: nothing planned ahead), and the
        # requests it admitted, whose ``admitted_tick`` that tick sets
        self.ticks_ahead = 0
        self._ahead: tuple[dict, int] | None = None
        self._admitted_ahead: list[Request] = []
        # the tick whose admission work is running: ``tick_count``, and
        # one more while the next tick's is planned ahead
        self._admit_tick = 0
        # a dispatched tick's tokens until they are fetched
        self._toks_dev = None
        # device-resident row state + batched ring cache arena
        self.temperature = float(temperature)
        self.top_k = top_k
        # a slot's last certain token (with a drafter: and its draft of
        # the next, ``(S, 2)``)
        self._tok = jnp.zeros((self.S,) + (2,) * (draft is not None),
                              jnp.int32)
        # the tick's own counts with a drafter: steps that verified a
        # draft of a live request, those that accepted it, and the
        # module's expert layer's ``experts_hit``
        self.drafted = self.accepted = 0
        self.mtp_experts_hit: float | None = None
        # admissions that wrote a slot's recurrent state over (each
        # one of a configuration with state layers does)
        self.state_resets = 0
        self._pos = jnp.zeros((self.S,), jnp.int32)
        self._done = jnp.ones((self.S,), bool)  # idle rows stay done
        self._keys = jax.random.split(jax.random.key(0), self.S)
        # page-pool arena: the capacity knob is cache_pages, not
        # slots x W. The default lets every slot hold a full window,
        # plus the null page. With several cache widths every kind has
        # a pool of its own, sized the same way; cache_pages is then
        # one count per kind, narrowest first.
        if cache_pages is None or isinstance(cache_pages, int):
            if cache_pages is not None and len(kinds) > 1:
                raise ValueError(
                    f"cache_pages needs one count per cache width "
                    f"({list(kinds)}), got {cache_pages}"
                )
            cache_pages = [cache_pages] * len(kinds)
        if len(cache_pages) != len(kinds):
            raise ValueError(
                f"cache_pages names {len(cache_pages)} pools, the "
                f"configuration has {len(kinds)} cache widths"
            )
        span_of = {w: span for w, span in zip(widths, cfg.windows)
                   if w is not None}
        n_windows = sum(span_of[w] is not None for w in kinds)
        self._kinds: list[_PageKind] = []
        for k, (Wk, n) in enumerate(zip(kinds, cache_pages)):
            n_pages = (int(n) if n is not None
                       else self.S * (Wk // self.P) + 1)
            if n_pages < Wk // self.P + 1:
                raise ValueError(
                    f"cache_pages {n_pages} cannot hold even one "
                    f"window-filling request ({Wk // self.P} pages "
                    "+ the null page)"
                )
            name = ("full" if span_of[Wk] is None
                    else "window" if n_windows == 1
                    else f"window{Wk}")
            self._kinds.append(_PageKind(
                name, Wk, self.P, n_pages, self.S,
                tuple(li for li, kk in enumerate(kind_of) if kk == k),
            ))
        self._caches = _fresh_pages(
            cfg, tuple(0 if k is None else self._kinds[k].pool.n_pages
                       for k in kind_of),
            self.P, self.quantize_kv, slots=self.S,
        )
        # the narrowest kind under the names the single-width code
        # (quotas, fleet cache, migration) reads: the pool, the
        # host-authoritative page table (the device copy refreshes
        # lazily whenever admission/COW/retirement dirties it) and
        # the per-slot wrap flags
        self.pool = self._kinds[0].pool
        self._pt_host = self._kinds[0].pt_host
        self._slot_wraps = self._kinds[0].slot_wraps
        self._pt_dev = None
        # per-slot global position mirror (the COW pass must know
        # which ring pages the NEXT tick will write, host-side)
        self._host_pos = [0] * self.S
        # int8 Pallas kernel routing, resolved at construction against
        # THIS scheduler's slot count (decode.py's _route_kernel: the tick
        # batches all S slots into one kernel call per layer, which is
        # what amortizes the scan boundary cost the B=1 path cannot).
        # The page-geometry conditions (_paged_kernel_possible) are all
        # cfg-static, so the resolution is a construction-time decision.
        self.use_kernel = (
            _paged_kernel_possible(cfg, self.quantize_kv, self.P)
            and _route_kernel(self.S)
            # a drafting step's two queries a slot: the latent form
            # takes them as two rows, the K/V form has one a slot
            and (draft is None or cfg.latent_layers)
        )
        self._scan = _serving_scan_paged(
            cfg, self.n_inner, eos_id, self.temperature, top_k,
            self.use_kernel, self.P,
        )
        self._seed = _seed_admit_paged(cfg, min(W, self.Lmax),
                                       self.P)
        self._place = _place_paged(cfg, self.P)
        self._copy = _copy_pages_paged(cfg, self.P)
        self._gather = _gather_ring_paged(cfg, self.P)
        self._extend = _extend_chunk_dense(cfg, self.C, self.Lmax)
        # the same chunk for up to ``_group`` requests in one program
        # (None: a chunk of this model gains nothing from width); its
        # padding writes into scratch arenas that are nobody's
        self._group = _chunk_group_cap(cfg, self.C, self.S)
        self._extend_group = (
            _extend_chunk_group(cfg, self.C, self.Lmax, self._group)
            if self._group > 1 else None
        )
        self._scratch_arenas: list[list[dict]] | None = None
        # and ``_group`` chunks of ONE prompt in a program of its own
        # (_wide_slot), where width is free (chunks wait for bytes) and
        # can engage (a prompt can need more ticks of prefill than there
        # are slots: the deployment serves documents); None elsewhere
        self._extend_wide = (
            _extend_chunk_wide(cfg, self.C, self.Lmax, self._group)
            if self._group > 1 and self.Lmax // self.C > self.S else None
        )
        # ``serving.prefill_chunk``'s ``expert_tile``, by the chunks
        # the program holds: the k x n tile its grouped gate and up
        # products take (``moe.group_tiling``: whole K says each
        # expert is read once); nothing without expert layers
        w = next((lp["we_gate"] for lp in params["layers"]
                  if "we_gate" in lp), None)
        self._expert_tile = {} if w is None else {
            n: {"expert_tile": "{1}x{2}".format(*group_tiling(
                n * self.C * cfg.experts_per_token, *w.shape[1:],
                w.dtype.itemsize))}
            for n in (1, self._group)
        }
        # and its ``gdn_rule`` / ``la_rule``: the form each kind of
        # recurrence takes over a chunk's rows
        # (``transformer.gdn_rule_route`` / ``la_rule_route``, which
        # the halves ask too); nothing without such a layer
        mixers = cfg.layer_mixers or ()
        rule_routes = lambda T: {
            **({"gdn_rule": gdn_rule_route(cfg, T)}
               if "gdn" in mixers else {}),
            **({"la_rule": la_rule_route(cfg, T)}
               if "la" in mixers else {}),
            **({"ssm_rule": ssm_rule_route(cfg, T)}
               if cfg.ssm_layers else {}),
        }
        self._rule_routes = rule_routes(self.C)
        # (the wide program's rows are one call of the recurrence)
        self._wide_routes = rule_routes(self._group * self.C)
        # ``serving.decode``'s ``gdn_rule``: the form ONE token takes
        # in a step of the tick, from the same function
        self._step_route = {
            **({"gdn_rule": gdn_rule_route(cfg, 1)}
               if "gdn" in mixers else {}),
            **({"ssm_rule": ssm_rule_route(cfg, 1)}
               if cfg.ssm_layers else {}),
        }
        # beside ``ssm_rule`` on both spans: how many of the model's
        # layers keep a state and how many keep rows (a layer with the
        # mixer beside its attention counts in both, one with the mixer
        # alone in the first)
        layers = range(cfg.n_layers)
        self._layer_kinds = {
            "state_layers": sum(map(cfg.state, layers)),
            "row_layers": sum(map(cfg.rows, layers)),
        } if cfg.ssm_layers else {}
        self._finish = _finish_admit_dense(
            cfg, self.Lmax, self.temperature, top_k
        )
        # ``serving.first_token``'s ``ring_gathers``: the row layers
        # whose ring an arena of ``max_prompt`` rows can wrap, which
        # ``decode._ring_from_cache`` gathers by position (the others it
        # takes as they lie)
        self._ring_gathers = sum(
            w is not None and self.Lmax > w for w in _row_widths(cfg))
        # instruments resolved once here; None = dark (no tick cost)
        self._obs = (
            _ServingObs(self, registry, spans)
            if registry is not None or spans is not None
            else None
        )
        # flight recorder (obs/flight.py, opt-in): per-tick spans land
        # in the bounded postmortem ring; dark schedulers never stamp
        self._flight = flight
        # perf_counter of the latest completed tick — the liveness
        # signal for /healthz tick-freshness checks and flight
        # watchdogs; stays None on a fully dark scheduler (the dark
        # tick reads no clocks, pinned by tests/test_obs.py). An
        # exporter-ONLY scheduler must stamp too — its registered
        # health check reads this, and a never-set stamp would report
        # an actively-ticking scheduler as stuck forever.
        self.last_tick_at: float | None = None
        self._stamp_ticks = (
            self._obs is not None or flight is not None
            or exporter is not None
        )
        # causal tracing (round 22, opt-in per GC004): request
        # lifecycle events on the wall clock; dark schedulers pay one
        # `is None` check per transition
        self._trace = None
        if trace is not None:
            self.attach_trace(trace)
        # fleet prefix cache (cache/ package, opt-in): admission
        # probes the fleet namespace for page-aligned prefixes it
        # cannot share locally, fetching from host DRAM or a peer
        # replica instead of prefilling; reclaimed cold pages spill
        # the other way. Requires the paged arena — the fleet unit is
        # the page.
        self.cache = cache
        self.cache_name: str | None = None
        if cache is not None:
            self.cache_name = cache.attach(self)
        if exporter is not None:
            # register the tick-freshness health check (+ the span
            # recorder as a /trace source) on the ObsServer
            exporter.register_scheduler(self)

    @staticmethod
    def _resolve_draft(params, cfg: TransformerConfig, draft, qos, cache):
        """The configuration the scheduler's programs are made for:
        with ``draft="mtp"`` the one given, whose multi-token-prediction
        module drafts; with the drafter off the same without the module
        (no cache layer, no row and no read of it anywhere). What a
        drafting step cannot serve is refused here, by mechanism."""
        if draft is None:
            return (dataclasses.replace(cfg, mtp_depth=0)
                    if cfg.mtp_depth else cfg)
        if draft != "mtp":
            raise ValueError(f"draft is None or 'mtp', got {draft!r}")
        if not cfg.mtp_depth or "mtp" not in params:
            raise ValueError(
                "draft='mtp' drafts with the configuration's own "
                "multi-token-prediction module: TransformerConfig("
                "mtp_depth=1) and its weights, params['mtp']")
        if any(w is not None for w in cfg.windows):
            raise ValueError(
                "draft='mtp': a drafting step writes the draft's row one "
                "past the certain one, which in a sliding-window layer's "
                "ring is the slot of the oldest row the certain token "
                "still attends; every layer must attend every earlier "
                "position (max_context)")
        if qos is not None or cache is not None:
            raise ValueError(
                "draft='mtp': page quotas (qos=) and the fleet prefix "
                "cache (cache=) count and move prefix pages, and with a "
                "drafter none is shared (the module's row at a page's "
                "last position is made from the token behind the page)")
        return cfg

    def attach_trace(self, book) -> None:
        """Arm causal tracing (constructor ``trace=`` routes here; a
        router propagates its book the same way). DRR admission
        transitions ride the scheduler's trace hook — qos/ itself
        stays clock-free."""
        self._trace = book
        if self._drr is not None:
            self._drr.set_trace(self._drr_trace_event)

    def _drr_trace_event(self, kind, tenant, item, cost) -> None:
        tid = item.trace
        if tid is not None:
            self._trace.event(
                tid, kind, time.perf_counter(), tenant=tenant,
                cost=cost,
            )

    # -- public API -----------------------------------------------------

    def enable_tick_stamping(self) -> None:
        """Turn on the per-tick ``last_tick_at`` liveness stamp (one
        ``perf_counter`` read per tick). Construction with any of
        ``registry=``/``spans=``/``flight=``/``exporter=`` enables it
        already; :meth:`ObsServer.register_scheduler` calls this so a
        scheduler registered AFTER dark construction becomes probeable
        — its tick-freshness health check reads the stamp."""
        self._stamp_ticks = True

    def submit(self, prompt, max_new: int, key=None,
               tenant: str | None = None, trace=None) -> Request:
        """Queue a request; returns the live :class:`Request` whose
        ``tokens``/``finished`` the caller watches. Admission happens
        inside subsequent ticks — requests may arrive while others are
        mid-decode (the "straggling request" case). ``key``: the
        request's PRNG key when the scheduler samples
        (``temperature > 0``); defaults to a request-id-derived key.
        A sampled stream equals ``generate_ring_dense(..., key=key)``
        for the same key (tests pin it). ``tenant``: the contract the
        request is billed to — REQUIRED on a ``qos=`` scheduler
        (unknown tenants refused by name); on a plain scheduler the
        tag merely rides the request."""
        if key is not None and self.temperature == 0.0:
            raise ValueError(
                "submit(key=...) on a greedy scheduler: the key would "
                "be silently unused — construct the scheduler with "
                "temperature > 0 (generate_* raises the same way)"
            )
        if self.max_queue is not None and self.pending >= self.max_queue:
            raise RuntimeError(
                f"queue ceiling: {self.pending} requests already "
                f"queued at max_queue={self.max_queue} — shed at the "
                "router (shed_depth=) instead of queueing unboundedly"
            )
        if self._qos is not None:
            if tenant is None:
                raise ValueError(
                    "qos scheduler needs tenant= at submit: admission "
                    "order and page quotas are per-contract (register "
                    "a catch-all TenantContract for untagged traffic)"
                )
            self._qos.get(tenant)  # unknown tenant: named KeyError
        req = Request(prompt, max_new, key=key, tenant=tenant)
        if req.prompt.size > self.Lmax:
            raise ValueError(
                f"prompt of {req.prompt.size} tokens exceeds max_prompt "
                f"{self.Lmax}; raise max_prompt (one-time recompile)"
            )
        if (self._context is not None and req.prompt.size + req.max_new
                + self._tick_rows > self._context):
            raise ValueError(
                f"prompt of {req.prompt.size} tokens plus max_new "
                f"{req.max_new} (and the retirement tick's "
                f"{self._tick_rows} rows) passes max_context "
                f"{self._context}: a "
                "full-attention layer's ring is that wide and must "
                "never wrap; raise TransformerConfig(max_context=)"
            )
        obs = self._obs
        if obs is not None:
            req._t_submit = time.perf_counter()
        if trace is not None:
            # router-minted id: the leg joins an existing record
            req.trace = trace
        elif self._trace is not None:
            # this scheduler IS the entry door: mint here and own the
            # terminal events
            req.trace = self._trace.mint()
            req._trace_owned = True
            self._trace.event(
                req.trace, "submitted", time.perf_counter(),
                tenant=tenant, prompt=int(req.prompt.size),
            )
        if self._drr is not None:
            # DRR cost is in tokens (prompt + budget — the same unit
            # as the contracts' rate budgets), so fairness is fair
            # chip work, not fair request counts
            self._drr.enqueue(
                tenant, req, float(req.prompt.size + req.max_new)
            )
        else:
            self._queue.append(req)
        if obs is not None and obs._r:
            obs.m_queue.set(self.pending)
        return req

    @property
    def active(self) -> int:
        """Slots currently decoding or admitting."""
        return sum(r is not None for r in self._slot_req)

    @property
    def pending(self) -> int:
        return (self._drr.total if self._drr is not None
                else len(self._queue))

    def _scan_args(self) -> tuple:
        return (self.params, self._tok, self._pos, self._done,
                self._caches, self._keys, self._device_pt())

    def _decode_dispatch(self) -> None:
        """Launch the jitted decode tick; its tokens stay a device
        value until :meth:`_decode_scan_fetch` brings them home."""
        with _annotate("serving.decode_dispatch"):
            (self._tok, self._pos, self._done, self._caches,
             self._toks_dev) = self._scan(*self._scan_args())

    def _decode_scan_fetch(self) -> np.ndarray:
        """Fence the dispatched tick's tokens to the host (the tick was
        launched by :meth:`_decode_dispatch`; between the two ``step``
        may plan the next tick's admissions). The name is the handle
        the benchmark's tests hold on the tokens a tick hands over."""
        toks, self._toks_dev = self._toks_dev, None
        with _annotate("serving.decode_wait"):
            if self.draft is not None:
                return self._fetch_drafted(toks)
            host = np.asarray(toks)  # (S, n_inner) one fetch per tick
        if self._expert_layers:
            # the tick's own counter, in the row under the tokens: the
            # mean, per expert layer and step, of experts with a row
            per = self.n_inner * self._expert_layers
            self.experts_hit = float(host[self.S].sum()) / per
            if self.cfg.experts_held is not None:
                self.pairs_local = float(host[self.S + 1].sum()) / per
            host = host[:self.S]
        return host

    def _fetch_drafted(self, toks) -> np.ndarray:
        """A drafting tick's fetch: ``(S, n_inner, 4)`` of ``[x1, x2,
        accepted, the draft verified]`` and, beside it, the steps'
        counters (``_scan_body_draft``), the model's layers' first and
        the module's behind them. ``pairs_local`` is a mean per row a
        slot runs, so that it stays a share of ``slots x k``."""
        host, counts = jax.device_get(toks)
        if counts.shape[1]:
            total = counts.sum(axis=0) / self.n_inner
            held = self.cfg.experts_held is not None
            if self._expert_layers:
                self.experts_hit = float(total[0]) / self._expert_layers
                if held:
                    self.pairs_local = float(total[1]) / (
                        self._expert_layers * self._rows)
            if self.cfg.dropless(self.cfg.n_layers):
                self.mtp_experts_hit = float(total[-2 if held else -1])
        return host

    def lower_tick(self):
        """The decode tick's program lowered against the live state
        (nothing runs, nothing is donated): its text says which
        attention path the tick traced."""
        return self._scan.lower(*self._scan_args())

    @property
    def pools(self) -> dict[str, PagePool]:
        """The paged arena's pools by cache width: ``{"window": ...}``
        for sliding-window layers alone, ``"window"`` and ``"full"``
        where full-attention layers stand beside them."""
        return {kd.name: kd.pool for kd in self._kinds}

    def state_of(self, req: Request) -> tuple[int, list[dict | None]]:
        """What the recurrent layers hold for a decoding request
        between two ticks: ``(rows, layers)``, the number of rows of
        (prompt + tokens) the state stands behind (every token but the
        last delivered, which is sampled and not yet fed; the prompt
        alone where the first token is still on the device) and, layer
        by layer, the slot's part of each state leaf
        (``decode.STATE_LEAVES``) as the cache keeps it (``S`` in
        ``ops.ssm_step.ssm_state_shape``'s layout), None for a layer
        that keeps none. A request that holds no slot, or whose prompt
        is still being admitted, is refused."""
        s = next((s for s, r in enumerate(self._slot_req) if r is req), None)
        if s is None or s in self._admitting:
            raise ValueError("state_of: the request is not decoding: it "
                             "holds no slot or is still being admitted")
        return self._host_pos[s], [
            {kk: a[s] for kk, a in cl.items() if kk in STATE_LEAVES} or None
            for cl in self._caches]

    def _device_pt(self):
        """The device page tables, one per cache width, refreshed from
        the host-authoritative copies when admission/COW/retirement
        dirtied them. Each is made from a COPY of the host's table:
        the CPU backend may take a numpy buffer as it lies, and the
        host writes the next tick's rows into its table while the tick
        that reads this one is still running (``_admit_ahead``)."""
        if self._pt_dev is None:
            self._pt_dev = tuple(
                jnp.asarray(kd.pt_host.copy()) for kd in self._kinds)
        return self._pt_dev

    @staticmethod
    def _pt_rows(rows):
        """One slot's page-table row per kind, as the admission
        programs take it."""
        return tuple(np.array(r, np.int32) for r in rows)

    def _ends_known(self) -> bool:
        """Can the host count a tick's ends before its tokens are back?
        Where a request ends by its length alone, a decoding slot gains
        exactly ``n_inner`` tokens a tick, so which slots a tick frees,
        which pages they give back and which queued requests take them
        follow from lengths: ``step`` then plans the next tick's
        admissions behind the running tick, and a first token stays on
        the device until its request's first tick is fetched. Not with
        ``eos_id`` (an end is a token's VALUE; run ahead, an EOS would
        be found a tick after the next plan was made and cost its slot
        ``n_inner`` steps more: a trade for a later change, with
        traffic that ends by EOS to measure it on), not with a drafter
        (a step delivers one token or two as the data decide), and not
        under ``qos=`` or ``cache=`` (a departure there moves cold
        pages, tenants' counts and, through the fleet cache, page
        bytes the host reads: they stay in order)."""
        return (self.eos_id is None and self.draft is None
                and self._qos is None and self.cache is None)

    def _counted_ends(self, live) -> list[int] | None:
        """The slots whose request the tick just dispatched ends, by
        count (``live``: its ``(slot, request)`` pairs), or None where
        the next tick's admissions wait for this tick's harvest as they
        always did: :meth:`_ends_known` is false, or a request with
        ``max_new == 1`` is in prefill or among those the freed slots
        could take (it retires where its first token is made, which is
        a read of that token: an at-once case, kept in order)."""
        if not self._ends_known():
            return None
        ending = [
            s for s, req in live
            if (len(req.tokens) + (req._first is not None)
                + self.n_inner >= req.max_new)
        ]
        n_free = self._slot_req.count(None) + len(ending)
        if (any(st.req.max_new == 1 for st in self._admitting.values())
                or any(r.max_new == 1 for r in
                       itertools.islice(self._queue, n_free))):
            return None
        return ending

    def _admit_ahead(self, live, ending: list[int],
                     retired: list[Request]) -> None:
        """The next tick's admit phase, behind the tick still running:
        the slot's half of every counted retirement (the row done, its
        pages back, its table row nulled: the running tick was
        dispatched with the old table, so its writes go where they
        went), then what the top of the next ``step`` would run, on the
        same plan, in the same programs, stamped as that tick's. Every
        program it dispatches takes the running tick's outputs
        (``_caches``, ``_done``, ``_tok``), so the device runs them
        behind it, and no device value is read here. The request's half
        of a retirement stays in this tick's harvest."""
        for s, _ in live:
            self._host_pos[s] += self.n_inner
        for s in ending:
            self._free_slot(s)
        self._admit_tick = self.tick_count + 1
        begin = self._tick_begin()
        self._advance_admissions(retired)
        self._admit_from_queue(retired)
        self._run_pending(retired, last=True)
        self._ahead = (begin, self.pending)

    def _tick_begin(self) -> dict:
        """``serving.tick``'s counts of the schedule as a tick begins
        (before its admissions): read at the top of ``step`` or, for a
        tick planned ahead, where its admit phase began."""
        n_admitting = len(self._admitting)
        n_free = self._slot_req.count(None)
        return dict(
            queue=self.pending,
            decoding=self.S - n_free - n_admitting,
            admitting=n_admitting, free=n_free,
            # the route the tick's attention takes: 1 the int8 Pallas
            # kernel, 0 the einsum over gathered views
            kernel=int(self.use_kernel),
            # pages in use when the tick begins, by cache width (where
            # the layers have more than one)
            **({f"pages_{kd.name}": kd.pool.used for kd in self._kinds}
               if len(self._kinds) > 1 else {}),
            # slots whose recurrent layers hold a request's state
            **({"state_slots": self.S - n_free}
               if self.cfg.state_layers else {}),
            # rows the decoding slots attend in a latent layer when the
            # tick begins (a row a position: their positions' sum)
            **({"latent_rows": sum(
                self._host_pos[s] for s, r in enumerate(self._slot_req)
                if r is not None and s not in self._admitting)}
               if self.cfg.latent_layers else {}),
            # a selection of key blocks: the decoding slots that see
            # more than ``sparse_dense_len`` rows as the tick begins,
            # and the blocks they attend and see, summed over those
            # slots and the K/V heads
            **self._sparse_tick_counts(),
        )

    def step(self) -> list[Request]:
        """One scheduler tick; returns the requests retired in it
        (including any that retire at admission — max_new == 1 or a
        first-token EOS), each with all its tokens.

        The order of a tick: (1) ``serving.admit``: what this tick's
        admissions still need; (2) ``serving.decode``: the
        copy-on-write page pass and the DISPATCH of the tick's program;
        (3) where the tick's ends can be counted before its tokens are
        back (:meth:`_ends_known`: no ``eos_id``, no drafter, no
        ``qos=`` / ``cache=``) a second ``serving.admit``: the slots of
        the requests this tick ends are freed and the NEXT tick's whole
        admit phase is planned and dispatched behind the running
        program (:meth:`_admit_ahead`), so the chip goes from the tick
        straight into the next tick's prefill programs; (4)
        ``serving.decode`` again: the fetch of the tick's tokens; (5)
        ``serving.harvest``: tokens to their requests (a first token in
        front of its request's first tick's), the request's half of
        each retirement. No read of a device value stands between two
        dispatches on that path. A tick whose admissions were planned
        ahead does in (1) only what has arrived since (a ``submit``
        between two steps); in a backlog that is nothing. With
        ``eos_id`` or a drafter an end is a token's value, and (3) is
        left out: admit, dispatch and fetch, harvest with both halves
        of a retirement, the first token read where it is made, the
        order this method always had (so also around a request with
        ``max_new == 1``: :meth:`_counted_ends`). The schedule is the
        same either way: the same requests in the same slots and
        pages, the same chunks in the same programs, the same
        ``admitted_tick`` / ``retired_tick``, the same tokens.

        Every phase is a profiler annotation
        (``serving.tick`` around ``serving.admit`` / ``.decode`` /
        ``.harvest``, siblings in that order, see ``_LitPhase``): a
        ``jax.profiler`` session
        sees them on the device trace's clock, and with none open they
        cost an atomic check each. ``serving.tick``'s counts are the
        schedule's as the tick begins, wherever its admit phase ran,
        and ``ahead`` says whether it ran behind the tick before
        (``ticks_ahead`` counts those ticks). When instrumented
        (``registry=`` /
        ``spans=`` / ``flight=`` / ``exporter=``) the same boundaries
        also read the clock for the admit/decode/retire spans and the
        queue/slot/token series; dark, the hot path reads no clock."""
        obs = self._obs
        flight = self._flight
        lit = self._stamp_ticks  # obs, flight, OR exporter attached
        phase = _LitPhase if lit else _annotate
        if self._extend_group is not None and self._scratch_arenas is None:
            self._warm_chunk_group()
        self.tick_count += 1
        self._admit_tick = self.tick_count
        retired: list[Request] = []
        phases = []  # (the recorder's name, the closed phase), in order
        # this tick's admit phase ran behind the last tick: its counts
        # are that moment's, its queue what stood there plus what has
        # been submitted since
        planned, self._ahead = self._ahead, None
        if planned is None:
            begin = self._tick_begin()
        else:
            begin, left = planned
            begin["queue"] += self.pending - left
            self.ticks_ahead += 1
            # a request is stamped by the tick that admits it, when
            # that tick begins: between two steps nothing carries a
            # stamp of a tick that has not run
            for req in self._admitted_ahead:
                req.admitted_tick = self.tick_count
            self._admitted_ahead = []
        with phase("serving.tick", tick=self.tick_count,
                   ahead=int(planned is not None), **begin) as tick:
            with phase("serving.admit") as admit:
                if planned is None:
                    self._advance_admissions(retired)
                self._admit_from_queue(retired)
                self._run_pending(retired, last=True)
            phases.append(("admit", admit))
            # the chunks this tick ran (the admitting slots' and the
            # first of each request it admitted; a wide program's count
            # as the chunks they are), in how many programs, and where
            # there is a wide program how many of them ran in it
            tick.set_metadata(chunks=self._tick_chunks,
                              chunk_programs=self._tick_chunk_programs,
                              **({} if self._extend_wide is None else
                                 {"wide_chunks": self._tick_wide}))
            self._tick_chunks = self._tick_chunk_programs = 0
            self._tick_wide = 0
            decoding = [
                s for s, r in enumerate(self._slot_req)
                if r is not None and s not in self._admitting
            ]
            if decoding:
                # the slots and their requests as the tick is
                # dispatched: planned ahead, a slot has its next
                # request before this one's tokens are back
                live = [(s, self._slot_req[s]) for s in decoding]
                ending = self._counted_ends(live)
                decode_args = dict(slots=len(decoding), **self._step_route,
                                   **self._layer_kinds)
                with phase("serving.decode", **decode_args) as decode:
                    # COW pass: every page the next n_inner writes
                    # touch must be exclusively owned BEFORE the
                    # jitted scan runs (the device program never
                    # sees shared pages)
                    self._prepare_tick_pages(decoding)
                    self._decode_dispatch()
                    if ending is None:
                        host = self._decode_scan_fetch()
                if ending is not None:
                    phases.append(("decode", decode))
                    with phase("serving.admit") as admit:
                        self._admit_ahead(live, ending, retired)
                    phases.append(("admit", admit))
                    with phase("serving.decode", **decode_args) as decode:
                        host = self._decode_scan_fetch()
                phases.append(("decode", decode))
                with phase("serving.harvest") as harvest:
                    n_tokens = n_retired = 0
                    self.drafted = self.accepted = 0
                    for s, req in live:
                        if req._first is not None:
                            self._deliver_first(
                                req, decode.t1 if lit else None)
                        n_before = len(req.tokens)
                        if self.draft is None:
                            req.tokens.extend(int(t) for t in host[s])
                        else:
                            self._deliver_drafted(req, host[s])
                        if ending is None:
                            self._host_pos[s] += (len(req.tokens)
                                                  - n_before)
                        due = self._retire_if_due(req)
                        if self.draft is not None:
                            self._count_drafts(req, n_before)
                        # count AFTER the retirement trim: the
                        # EOS-clamped tail the host strips was never
                        # delivered to anyone, and a tokens/s series
                        # inflated by it would overstate throughput by
                        # up to n_inner-1 per retiring request
                        n_new = len(req.tokens) - n_before
                        n_tokens += n_new
                        if obs is not None:
                            obs.tokens_emitted(req, n_new, decode.t1)
                        if due:
                            # (counted ahead, the slot's half is done
                            # and the slot may have its next request)
                            if ending is None:
                                self._free_slot(s)
                            retired.append(req)
                            n_retired += 1
                    harvest.set_metadata(tokens=n_tokens,
                                         retired=n_retired)
                    if self._expert_layers:
                        harvest.set_metadata(
                            experts_hit=self.experts_hit)
                    if self.pairs_local is not None:
                        harvest.set_metadata(
                            pairs_local=self.pairs_local)
                    if self.draft is not None:
                        # the drafts of live requests this tick verified
                        # and how many of them it accepted; the module's
                        # expert layer's own ``experts_hit``
                        tick.set_metadata(
                            drafted=self.drafted, accepted=self.accepted,
                            **({} if self.mtp_experts_hit is None else
                               {"mtp_experts_hit": self.mtp_experts_hit}))
                phases.append(("retire", harvest))
        if obs is not None:
            obs.tick_done(self, retired, tick, phases,
                          planned is not None)
        if lit:
            self.last_tick_at = tick.t1
            if flight is not None:
                flight.span(
                    f"tick {self.tick_count}", tick.t0,
                    tick.t1 - tick.t0,
                    src="scheduler", track="scheduler",
                    queue=self.pending, active=self.active,
                    retired=len(retired),
                )
                flight.counter(
                    "serving_ticks_total", self.tick_count,
                    t=tick.t1,
                )
        return retired

    def _sparse_tick_counts(self) -> dict:
        """``serving.tick``'s ``sparse_slots`` / ``blocks_attended`` /
        ``blocks_visible`` (nothing without a selection of blocks)."""
        cfg = self.cfg
        if not cfg.sparse_layers:
            return {}
        # (a decoding slot's position is one short of the rows its next
        # query sees: the prompt's and its tokens', the newest unwritten)
        n = np.array([
            self._host_pos[s] + 1
            for s, r in enumerate(self._slot_req)
            if r is not None and s not in self._admitting], np.int64)
        return {"sparse_slots": int((n > cfg.sparse_dense_len).sum()),
                **self._block_counts(n)}

    def _block_counts(self, n) -> dict:
        """``blocks_attended`` / ``blocks_visible`` of the queries that
        see ``n`` rows (an array), summed over them and the K/V heads."""
        attended, visible = sparse_counts(n, self.cfg)
        return {"blocks_attended": attended * self.cfg.kv_heads,
                "blocks_visible": visible * self.cfg.kv_heads}

    def _sparse_chunk_counts(self, sts, offs) -> dict:
        """``serving.prefill_chunk``'s ``blocks_attended`` /
        ``blocks_visible``: the same over the chunks' real rows."""
        if not self.cfg.sparse_layers:
            return {}
        return self._block_counts(np.concatenate([
            np.arange(off, min(off + self.C, st.req.prompt.size)) + 1
            for st, off in zip(sts, offs)]))

    @staticmethod
    def _deliver_drafted(req: Request, steps: np.ndarray) -> None:
        """A drafting tick's ``n_inner`` steps of one slot, ``[x1, x2,
        accepted, draft]`` each, into the request: one token or two a
        step, and the draft each step verified with the index in
        ``req.tokens`` of the token it was a draft OF."""
        for x1, x2, accepted, draft in steps.tolist():
            req.drafts.append((len(req.tokens), draft, bool(accepted)))
            req.tokens.append(x1)
            if accepted:
                req.tokens.append(x2)

    def _count_drafts(self, req: Request, n_before: int) -> None:
        """After the retirement trim (which drops the drafts behind a
        request's end): the tick's ``drafted`` and ``accepted``, over
        the drafts of tokens this tick delivered."""
        for at, _, accepted in reversed(req.drafts):
            if at < n_before:
                break
            self.drafted += 1
            self.accepted += accepted

    def cancel(self, req: Request) -> bool:
        """Withdraw ``req`` wherever it currently is — queued, mid-
        admission, or decoding — freeing its slot (and its
        pages) for the next request. Returns True when the request was
        live here and is now retired with ``reason == "cancelled"``;
        False when it already finished or was never this scheduler's
        (both leave it untouched). The replica hook the request ROUTER
        leans on: a hedged request's losing leg must stop consuming
        slot-ticks the moment the other replica's first token wins
        (models/router.py, first-token-wins)."""
        if req.finished:
            return False
        if self._drr is not None:
            removed = self._drr.remove(req)
        else:
            try:
                self._queue.remove(req)
                removed = True
            except ValueError:
                removed = False
        if removed:
            self._retire_cancelled(req)
            return True
        for s, r in enumerate(self._slot_req):
            if r is req:
                st = self._admitting.pop(s, None)
                if st is not None:
                    self._release_arena(st)
                    # mid-admission the slot's pages live in the
                    # plan (_pt_host[s] stays NULL until finish), so
                    # _free_slot's table walk would miss them —
                    # release the committed plan here
                    n_refs = 0
                    for kd, pids, wraps in zip(self._kinds, st.pids,
                                               st.wraps):
                        for pid in pids:
                            if pid != NULL_PAGE:
                                kd.pool.decref(int(pid),
                                               wrapper=wraps)
                                n_refs += 1
                    self._tenant_debit(req.tenant, n_refs)
                self._free_slot(s)
                self._retire_cancelled(req)
                return True
        return False

    def _retire_cancelled(self, req: Request) -> None:
        req.finished = True
        req.reason = "cancelled"
        req.retired_tick = self.tick_count
        # terminal events belong to the request's OWNER: only traces
        # minted at THIS door get their cancel stamped here (a router
        # leg's cancel is the router's reap, not the request's end)
        if self._trace is not None and req.trace is not None \
                and req._trace_owned:
            self._trace.event(
                req.trace, "cancelled", time.perf_counter(),
                tick=self.tick_count,
            )

    # -- KV-page migration (models/disagg.py's replica hooks) -----------
    #
    # The disaggregation subsystem moves a DECODING request between
    # paged schedulers: export gathers the slot's ring view out of the
    # page pool (fresh device buffers) plus the row's sampler/position
    # state and frees the slot; adopt re-plans pages in the destination
    # pool (sharing resident prefix digests exactly like admission,
    # reservations included), scatters the view back through the new
    # table, and re-registers the prefix-digest chain so COW sharing
    # survives the move. Between the two calls the request is resident
    # NOWHERE — the planner (MigrationPlanner) owns that window,
    # including its cancellation contract.

    def _migration_slot(self, req: Request) -> int | None:
        """The slot of a migratable request: resident, past admission
        (first token emitted), not finished. None otherwise."""
        if req.finished or not req.tokens:
            return None
        if self.draft is not None:
            raise ValueError(
                "KV-page migration moves a slot's last token and its "
                "rows; a drafting slot also holds a draft and the "
                "module's rows one position behind")
        _refuse_state_layers(
            self.cfg, "KV-page migration", "an exported image is ring "
            "views behind a page table, and the state block is in none")
        _refuse_latent_layers(
            self.cfg, "KV-page migration", "a migrated image is K/V ring views of "
            "kv_heads heads, which such a layer has not")
        _refuse_sparse_layers(
            self.cfg, "KV-page migration", "a migrated image is K/V "
            "rows, and the pooled cells are in none")
        if len(self._kinds) > 1:
            raise ValueError(
                "KV-page migration moves one ring view per layer "
                "through one page table; this configuration has layers "
                "of more than one cache width"
            )
        for s, r in enumerate(self._slot_req):
            if r is req and s not in self._admitting:
                return s
        return None

    def _page_row_bytes(self) -> int:
        """Bytes one page carries across every layer and leaf."""
        total = 0
        for cl in self._caches:
            for a in cl.values():
                total += a.nbytes // a.shape[0]
        return total

    def _page_payload(self, pid: int) -> np.ndarray:
        """One page's KV bytes as a flat uint8 array: per layer (list
        order), per leaf (SORTED key order — the frame-serialization
        convention of disagg.py), the page's row slice. This layout IS
        the fleet cache's wire/storage format: two schedulers with the
        same config produce byte-identical payloads for the same
        digest, which is what the spill/fetch parity tests pin."""
        parts = []
        for cl in self._caches:
            for kk in sorted(cl):
                a = np.asarray(cl[kk][pid])
                parts.append(
                    np.ascontiguousarray(a).reshape(-1).view(np.uint8)
                )
        return np.concatenate(parts)

    def _install_page_payload(self, pid: int, payload) -> None:
        """Scatter a :meth:`_page_payload`-format byte string into
        page ``pid`` of this arena (the fetch landing). The split
        walks the same layer/sorted-leaf order; a size mismatch is a
        geometry bug refused by name (the cache hub validates
        page-byte equality at attach, so this only fires on config
        drift between attach and fetch)."""
        buf = np.asarray(payload).reshape(-1).view(np.uint8)
        if buf.size != self._page_row_bytes():
            raise ValueError(
                f"page payload is {buf.size} bytes, this arena's "
                f"pages are {self._page_row_bytes()}"
            )
        off = 0
        for cl in self._caches:
            for kk in sorted(cl):
                a = cl[kk]
                nb = a.dtype.itemsize * int(np.prod(a.shape[1:]))
                vals = np.frombuffer(
                    buf[off:off + nb].tobytes(), dtype=a.dtype
                ).reshape(a.shape[1:])
                cl[kk] = a.at[pid].set(jnp.asarray(vals))
                off += nb

    def _spill_page(self, pid: int, *,
                    tenant: str | None = None) -> None:
        """Offer a still-registered, sole-held page to the fleet
        cache's DRAM tier before it is freed/evicted. Reads the bytes
        BEFORE the freeing decref — a registered page's content still
        matches its digest (note_write/COW drop registration first).
        No-ops when the fleet already holds the digest somewhere else
        (re-spilling wastes the eviction bandwidth)."""
        d = self.pool.digest_of(pid)
        if d is None:
            return
        if not self.cache.wants(d, exclude=self.cache_name):
            return
        self.cache.spill(d, self._page_payload(pid), tenant=tenant)

    def migration_nbytes(self, req: Request) -> int:
        """Payload bytes a migration of ``req`` would move —
        ``pages_held * page_bytes`` summed over layers and leaves (the
        PERF round-16 byte model). 0 when ``req`` is not migratable
        here (queued, mid-admission, finished, or not this
        scheduler's)."""
        s = self._migration_slot(req)
        if s is None:
            return 0
        n_pages = int(np.sum(self._pt_host[s] != NULL_PAGE))
        return n_pages * self._page_row_bytes()

    def export_page_state(self, req: Request) -> dict:
        """Capture ``req``'s decode state as a portable page-layout
        image and FREE its slot (pages decref'd — shared prefixes just
        drop a reference). The returned dict is everything
        :meth:`adopt_page_state` needs to continue the stream
        token-for-token on another scheduler with the same params and
        generation config: the gathered ``(1, W, ...)`` ring view per
        layer (fresh device buffers — independent of this pool's
        later reuse), the row's token/position/PRNG-key state, and the
        prefix-digest chain for re-registration. The request object
        itself is NOT finished or mutated — it is simply resident
        nowhere until adopted."""
        s = self._migration_slot(req)
        if s is None:
            raise ValueError(
                "export_page_state: request must be decoding on this "
                "paged scheduler (queued/mid-admission/finished "
                "requests have no page image to move)"
            )
        pos = self._host_pos[s]
        n_pages = int(np.sum(self._pt_host[s] != NULL_PAGE))
        # prefix pages still hold the content their digests describe
        # only while no ring write has wrapped past W (decode writes
        # land at positions >= Tp; position p >= W overwrites page
        # (p mod W) // P). The chain is a pure function of the prompt
        # (paging.py), so it is recomputed rather than carried.
        clean = pos <= self.W and req.prompt.size <= self.W
        if clean:
            digests = prefix_page_digests(req.prompt, self.P,
                                          self.max_pages)
            n_cover = min(req.prompt.size // self.P, self.max_pages)
        else:
            digests, n_cover = [], 0
        ring = self._gather(
            self._caches, jnp.asarray(self._pt_host[s], jnp.int32)
        )
        state = {
            "request": req,
            "prompt": req.prompt,
            "tokens": list(req.tokens),
            "max_new": req.max_new,
            "tok": int(np.asarray(self._tok)[s]),
            "pos": int(pos),
            "key_data": np.asarray(jax.random.key_data(self._keys[s])),
            "ring": ring,
            "digests": tuple(digests),
            "n_cover": int(n_cover),
            "n_pages": n_pages,
            "P": self.P,
            "W": self.W,
            "quantize_kv": self.quantize_kv,
            "temperature": self.temperature,
            "top_k": self.top_k,
            "eos_id": self.eos_id,
        }
        self._free_slot(s)
        return state

    def _check_adopt_compat(self, state: dict) -> None:
        _refuse_state_layers(
            self.cfg, "adopt_page_state", "a migrated image is ring "
            "views behind a page table, and the state block is in none")
        _refuse_latent_layers(
            self.cfg, "adopt_page_state", "a migrated image is K/V ring views of "
            "kv_heads heads, which such a layer has not")
        _refuse_sparse_layers(
            self.cfg, "adopt_page_state", "a migrated image is K/V "
            "rows, and the pooled cells are in none")
        if len(self._kinds) > 1:
            raise ValueError(
                "adopt_page_state: a migrated image is one ring view "
                "per layer behind one page table; this configuration "
                "has layers of more than one cache width"
            )
        for k, want in (
            ("P", self.P), ("W", self.W),
            ("quantize_kv", self.quantize_kv),
            ("temperature", self.temperature), ("top_k", self.top_k),
            ("eos_id", self.eos_id),
        ):
            if state[k] != want:
                raise ValueError(
                    f"adopt_page_state: {k} mismatch (source "
                    f"{state[k]!r}, this scheduler {want!r}) — tiers "
                    "must share page geometry and generation config "
                    "for the stream to continue token-for-token"
                )

    def _plan_adopt(self, state: dict, *, reclaim: bool = False):
        """(slot, shared pids, n_pages, wraps, reserve) for adopting
        ``state``, or None when no free slot / pool capacity covers
        it — the same whole-lifetime budget as admission planning, so
        PagePoolExhausted stays unreachable mid-decode. On a qos
        scheduler, cold pages count as reclaimable headroom (cache,
        not entitlement — the two-tier liveness contract: a stream is
        resident NOWHERE while its migration waits): ``reclaim=True``
        (the adopt path) actually evicts the shortfall; False (the
        ``can_adopt_state`` predicate) only counts it, so a
        feasibility probe never drains a replica's cold prefix cache
        as a side effect."""
        free_s = next(
            (s for s, r in enumerate(self._slot_req)
             if r is None and s not in self._admitting), None,
        )
        if free_s is None:
            return None
        Tp = int(state["prompt"].size)
        horizon = Tp + state["max_new"] + self._tick_rows
        wraps = horizon > self.W
        n_pages = -(-min(self.W, horizon) // self.P)
        shared: list[int] = []
        for d in state["digests"][: min(state["n_cover"], n_pages)]:
            pid = self.pool.lookup(d)
            if pid is None:
                break
            shared.append(pid)
        reserve = sum(
            1 for pid in shared
            if self.pool.share_needs_reserve(pid, wraps)
        )
        shortfall = (n_pages - len(shared) + reserve
                     + self.pool.reserved - self.pool.free)
        if shortfall > 0:
            if self._drr is None:
                return None
            sset = set(shared)
            if reclaim:
                for _ in range(shortfall):
                    if not self._evict_cold_page(protect=sset):
                        return None
            else:
                evictable = sum(
                    1 for pid in self._cold if pid not in sset
                )
                if evictable < shortfall:
                    return None
        return free_s, shared, n_pages, wraps, reserve

    def can_adopt_state(self, state: dict) -> bool:
        """Would :meth:`adopt_page_state` succeed right now? (A free
        slot plus pool capacity for the request's whole-lifetime page
        budget, shared resident prefixes counted.) Boolean under ALL
        refusals — a config-mismatched state is False, not a raise, so
        the router's adoption gate can scan a heterogeneous tier
        without crashing the step loop."""
        try:
            self._check_adopt_compat(state)
        except ValueError:
            return False
        return self._plan_adopt(state) is not None

    def could_adopt_state(self, state: dict) -> bool:
        """Would :meth:`adopt_page_state` EVER succeed here — i.e. does
        the whole-lifetime page budget fit this scheduler's pool even
        when every slot and page is free? False means parking a
        migration on this replica's capacity can never resolve (the
        pool is statically too small or the config mismatches); the
        two-tier router bounces such tickets back to the prefill tier
        instead of stranding the captured stream."""
        try:
            self._check_adopt_compat(state)
        except ValueError:
            return False
        Tp = int(state["prompt"].size)
        horizon = Tp + state["max_new"] + self._tick_rows
        n_pages = -(-min(self.W, horizon) // self.P)
        # an empty pool has n_pages-1 usable pages (page 0 is the null
        # page); prefix sharing could only lower the demand
        return n_pages <= self.pool.n_pages - 1

    def adopt_page_state(self, state: dict,
                         request: Request | None = None) -> Request:
        """Land a migrated request (:meth:`export_page_state` on the
        source) in this scheduler: allocate its page budget (sharing
        resident prefix-digest pages with COW reservations exactly
        like admission), scatter the carried ring view through the new
        page table, install the row's token/position/key state, and
        re-register the prefix-digest chain so future admissions and
        migrations keep sharing. Shared pages are scattered with bytes
        identical to what they already hold (same params, same prefix
        — the ``_place_paged`` admission argument), so sharers are
        never perturbed. ``request``: override the continued request
        object (cross-process adoption rebuilds one; in-process the
        captured object rides in ``state`` and keeps streaming)."""
        self._check_adopt_compat(state)
        plan = self._plan_adopt(state, reclaim=True)
        if plan is None:
            raise PagePoolExhausted(
                "adopt_page_state: no free slot or page capacity for "
                "the migrated request (gate on can_adopt_state)"
            )
        s, shared, n_pages, wraps, _ = plan
        req = request if request is not None else state["request"]
        if req is None:
            req = Request(state["prompt"], state["max_new"])
            req.tokens = list(state["tokens"])
            req._scanned = len(req.tokens)
        pids = [NULL_PAGE] * self.max_pages
        for j, pid in enumerate(shared):
            self.pool.share(
                pid, reserve=self.pool.share_needs_reserve(pid, wraps),
                wrapper=wraps,
            )
            pids[j] = pid
            if self._trace is not None and req is not None \
                    and req.trace is not None:
                self._trace.event(
                    req.trace, "share_hit", time.perf_counter(),
                    page=int(pid),
                )
            if self._drr is not None and pid in self._cold:
                self._warm_cold(pid)
        try:
            for j in range(len(shared), n_pages):
                pids[j] = self.pool.alloc()
        except PagePoolExhausted:
            # roll back: a planned adoption must never half-commit
            for pid in pids:
                if pid != NULL_PAGE:
                    self.pool.decref(int(pid), wrapper=wraps)
            raise
        if self._drr is not None and req is not None \
                and getattr(req, "tenant", None) is not None:
            # migrated streams carry their tenant: the destination's
            # quota ledger takes the pages over (enforcement stays an
            # admission-time decision — an in-flight stream is never
            # evicted mid-decode)
            self._tenant_pages[req.tenant] = (
                self._tenant_pages.get(req.tenant, 0) + n_pages
            )
        self._pt_host[s] = pids
        self._pt_dev = None
        self._host_pos[s] = state["pos"]
        self._slot_wraps[s] = wraps
        rkey = jax.random.wrap_key_data(jnp.asarray(state["key_data"]))
        ring = [
            {kk: jnp.asarray(a) for kk, a in cl.items()}
            for cl in state["ring"]
        ]
        (self._caches, self._tok, self._pos, self._done,
         self._keys) = self._place(
            self._caches, ring, self._tok, self._pos, self._done,
            self._keys, jnp.asarray(self._pt_host[s]),
            jnp.int32(s), jnp.int32(state["tok"]),
            jnp.int32(state["pos"]), rkey,
        )
        n_cover = min(state["n_cover"], n_pages)
        for j in range(n_cover):
            self.pool.register(state["digests"][j], pids[j],
                               volatile=wraps)
        self._slot_req[s] = req
        if req.admitted_tick is None:
            req.admitted_tick = self.tick_count
        if self._trace is not None \
                and getattr(req, "trace", None) is not None:
            self._trace.event(
                req.trace, "admitted", time.perf_counter(),
                tick=self.tick_count, adopted=True,
            )
        return req

    def run(self, max_ticks: int = 10_000) -> None:
        """Tick until every queued and in-flight request retires."""
        for _ in range(max_ticks):
            if self.pending == 0 and self.active == 0:
                return
            self.step()
        raise RuntimeError(
            f"not drained after {max_ticks} ticks: {self.pending} "
            f"queued, {self.active} active"
        )

    # -- admission ------------------------------------------------------

    def _admit_from_queue(self, retired: list[Request]) -> None:
        free = [s for s, r in enumerate(self._slot_req) if r is None]
        if self._drr is not None:
            self._admit_drr(free, retired)
            return
        while self._queue and free:
            plan = self._plan_pages(self._queue[0])
            if plan is None:
                # head-of-line request does not fit the page
                # budget: admission waits for retirements to
                # return pages (FIFO — no reordering, so a large
                # request cannot be starved by later small ones;
                # the qos= DRR hook above is the per-TENANT
                # alternative, where only that tenant's queue
                # defers and the rotation tries the next)
                break
            s = free.pop(0)
            req = self._queue.popleft()
            self._admit_into(s, req, plan, retired)

    def _admit_drr(self, free: list[int],
                   retired: list[Request]) -> None:
        """QoS admission: free slots are filled in deficit-round-robin
        order (:class:`~..qos.DeficitScheduler` — weighted,
        work-conserving, deficits carried). A tenant whose head cannot
        be PLANNED right now (page-pool pressure, or its page quota
        even after reclaiming its own cold pages) is restored
        unchanged and the rotation passes over that TENANT for the
        rest of this pass — one tenant's unplannable head never blocks
        another tenant's admission, which is the head-of-line
        decoupling FIFO cannot give."""
        deferred: set[str] = set()
        while free:
            pick = self._drr.pick(skip=deferred)
            if pick is None:
                return
            tenant, req, cost = pick
            plan = self._plan_pages_qos(req)
            if plan is None:
                self._drr.restore(tenant, req, cost)
                deferred.add(tenant)
                continue
            s = free.pop(0)
            self._admit_into(s, req, plan, retired)

    def _take_arena(self) -> tuple[list[dict], str]:
        """The zeroed ``(1, max_prompt)`` positional cache an admission
        prefills into, and where it came from: ``"reused"`` (a dead
        arena off the free list, zeroed in place by
        :func:`serving_reset_arena`) or ``"new"`` (the list was empty:
        more prompts are in prefill at once than ever before). Either
        way one dispatch, and the same zeros."""
        if self._free_arenas:
            return serving_reset_arena(self._free_arenas.pop()), "reused"
        return (_fresh_cache(self.cfg, 1, self.Lmax, self.quantize_kv),
                "new")

    def _release_arena(self, st: _Admitting) -> None:
        """An admission is over (first token, or cancelled between two
        chunks): its arena is dead and goes to the free list. ``st.cache``
        is the binding the last program RETURNED (every donating program
        rebinds it), so the list never holds a donated buffer."""
        self._free_arenas.append(st.cache)
        st.cache = None

    def _admit_into(self, s: int, req: Request, plan,
                    retired: list[Request]) -> None:
        """Install one dequeued request into free slot ``s`` (the
        admission body both the FIFO and DRR paths share); ``plan`` is
        the committed-page plan."""
        Tp = req.prompt.size
        with _annotate("serving.admit_new", req=req.id, slot=s,
                       prompt_tokens=Tp) as span:
            base, admit_kw = self._commit_pages(req, plan)
            rem = Tp - base
            n_chunks = -(-rem // self.C)
            padded = np.zeros((1, n_chunks * self.C), np.int32)
            padded[0, :rem] = req.prompt[base:]
            cache, arena = self._take_arena()
            span.set_metadata(
                chunks=n_chunks,
                shared_pages=base // self.P,
                arena=arena,
            )
            if base:
                # skip the shared prefix's prefill outright: its K/V
                # seed the transient cache from the resident pages
                # (identical bytes to what this prefill would compute)
                cache = self._seed(
                    cache, self._caches,
                    self._pt_rows(admit_kw["pids"]), np.int32(base),
                )
            self._slot_req[s] = req
            self._admitting[s] = _Admitting(
                req, cache, padded, n_chunks, base=base,
                **admit_kw,
            )
        if self._admit_tick == self.tick_count:
            req.admitted_tick = self.tick_count
        else:  # planned ahead: the tick it is planned for stamps it
            self._admitted_ahead.append(req)
        if self._obs is not None and req.tenant is not None:
            self._obs.qos_admitted(self, req.tenant)
        if self._trace is not None and req.trace is not None:
            self._trace.event(
                req.trace, "admitted", time.perf_counter(),
                tick=self._admit_tick,
            )
        # first chunk runs this very tick (short prompts admit in
        # one tick and decode from the next), in one program with the
        # chunks already due. A one-chunk prompt ends its admission
        # here and now where the next request's plan may count on what
        # it registers or frees
        self._pending.append(s)
        if self._due_at_once(self._admitting[s]):
            self._run_pending(retired)

    # -- paged admission planning --------------------------------------

    def _plan_pages(self, req: Request):
        """Page budget for ``req``, for every cache width: which
        resident prefix pages it can share, how many fresh pages it
        needs, and how many COW reservations the shares must attach
        (one per share that can ever end in a write — the sharer wraps
        its ring, or the page's owner does). Returns None when a pool
        cannot cover the plan — the caller leaves the request queued.

        The budget is the request's whole lifetime upper bound: ring
        slots ``[0, min(W, Tp + max_new + _tick_rows))`` of each width
        (``_tick_rows``: ``n_inner``, twice that with a drafter) —
        prefill plus every decode write including the bounded overshoot
        of the retirement tick — so :class:`PagePoolExhausted` is
        unreachable mid-decode (the capacity contract the fuzz tests
        pin)."""
        digests, fetch, needs = self._page_needs(req)
        for kd, (_, _, _, n_fresh, reserve) in zip(self._kinds, needs):
            if not kd.pool.can_alloc(n_fresh, reserve=reserve):
                return None
        return (digests, fetch, [n[:3] for n in needs])

    def _page_needs(self, req: Request):
        """The share walk + budget arithmetic both planners share:
        ``(digests, fetch, needs)`` with, per cache width in kind
        order, ``needs[k] = (shared, n_pages, wraps, n_fresh,
        reserve)``, computed WITHOUT consulting pool capacity —
        :meth:`_plan_pages` checks ``can_alloc`` and
        :meth:`_plan_pages_qos` turns the same numbers into a reclaim
        shortfall instead.

        A shared prefix skips its prefill in EVERY layer, so a page
        index is shared only where every kind holds that page: the
        share run is the shortest over the kinds, and it exists only
        for a prompt inside the narrowest ring (beyond it that ring's
        pages hold late positions). ``fetch`` is the fleet-cache
        extension (one cache width only): where the LOCAL share walk
        breaks, the walk continues against the fleet directory
        (host-DRAM store / peer replicas), and every
        contiguously-probeable digest becomes a planned fetch — a fresh
        allocation whose prefill is replaced by a page copy.
        Budget-wise fetched pages ARE fresh pages (they are inside
        ``n_fresh``), so the capacity/quota arithmetic is unchanged;
        only the prefill skip differs, and a fetch that fails at
        commit time degrades to exactly the prefill the plan budgeted
        for."""
        Tp = req.prompt.size
        W, P = self.W, self.P
        digests: list[bytes] = []
        fetch: list[bytes] = []
        m = 0
        if Tp <= W and self.shares_prefixes:
            # within-window prompts: ring slot s == position s, so the
            # page content is determined by the page-aligned prefix —
            # the shareable case. (A wrapped prompt's pages hold late
            # positions; they are neither shared nor registered.)
            digests = prefix_page_digests(req.prompt, P, self.max_pages)
            # cap: at least the prompt's last token must prefill (the
            # first sampled token needs its logits)
            shareable = digests[: (Tp - 1) // P]
            m = len(shareable)
            for kd in self._kinds:
                for j, d in enumerate(shareable[:m]):
                    if kd.pool.lookup(d) is None:
                        m = j
                        break
            if self.cache is not None:
                for d in shareable[m:]:
                    if self.cache.probe(
                            d, exclude=self.cache_name) is None:
                        break
                    fetch.append(d)
        horizon = Tp + req.max_new + self._tick_rows
        needs = []
        for kd in self._kinds:
            shared = [kd.pool.lookup(d) for d in digests[:m]]
            wraps = horizon > kd.W
            n_pages = -(-min(kd.W, horizon) // P)
            reserve = sum(
                1 for pid in shared
                if kd.pool.share_needs_reserve(pid, wraps)
            )
            needs.append((shared, n_pages, wraps, n_pages - m, reserve))
        return digests, fetch, needs

    def _commit_pages(self, req: Request, plan) -> tuple[int, dict]:
        """Execute an admission plan, cache width by cache width: take
        references on the shared pages (attaching their COW
        reservations), FETCH the planned fleet-cache pages (host DRAM
        or a peer replica — each fetched page is a fresh allocation
        filled with the transferred bytes and registered, extending
        the prefill skip past the local share run), and allocate the
        fresh tail. A fetch that comes back empty (eviction,
        partition, kill raced the plan) stops the fetch run and the
        remaining pages prefill as budgeted — the cache saves work or
        does nothing, never corrupts.
        Returns (base, _Admitting kwargs)."""
        digests, fetch, needs = plan
        m = len(needs[0][0])
        all_pids = []
        for kd, (shared, n_pages, wraps) in zip(self._kinds, needs):
            pids = [NULL_PAGE] * kd.max_pages
            for j, pid in enumerate(shared):
                kd.pool.share(
                    pid,
                    reserve=kd.pool.share_needs_reserve(pid, wraps),
                    wrapper=wraps,
                )
                pids[j] = pid
                if self._trace is not None and req.trace is not None:
                    self._trace.event(
                        req.trace, "share_hit", time.perf_counter(),
                        page=int(pid),
                    )
                if self._drr is not None and pid in self._cold:
                    # a cold page found its next sharer: the cache's
                    # hold transfers to the new slot (warm)
                    self._warm_cold(pid)
            all_pids.append(pids)
        # the fleet cache is a one-width feature (refused otherwise at
        # construction): its pages go to the one pool there is
        pids, wraps = all_pids[0], needs[0][2]
        n_fetched = 0
        for d in fetch:
            got = self.cache.fetch(d, exclude=self.cache_name)
            if got is None:
                break  # fall back to prefill for the rest of the run
            src, payload = got
            pid = self.pool.alloc()
            self._install_page_payload(pid, payload)
            # first-wins: if a concurrent admission registered the
            # digest since planning, this is a no-op and the page is
            # simply this slot's private copy — still correct bytes
            self.pool.register(d, pid, volatile=wraps)
            pids[m + n_fetched] = pid
            n_fetched += 1
            if self._obs is not None:
                self._obs.fleet_hit(src)
            if self._trace is not None and req.trace is not None:
                self._trace.event(
                    req.trace, "share_hit", time.perf_counter(),
                    page=int(pid), tier=src,
                )
        m += n_fetched
        for kd, pids, (_, n_pages, _) in zip(self._kinds, all_pids,
                                            needs):
            for j in range(m, n_pages):
                pids[j] = kd.pool.alloc()
        if self._drr is not None and req.tenant is not None:
            self._tenant_pages[req.tenant] = (
                self._tenant_pages.get(req.tenant, 0) + needs[0][1]
            )
        # pages fully covered by the prompt hold registerable prefix
        # content once prefill lands them (done at finish)
        n_cover = min(req.prompt.size // self.P, self.max_pages) \
            if digests else 0
        return m * self.P, {
            "pids": all_pids, "digests": tuple(digests),
            "n_cover": n_cover, "wraps": [n[2] for n in needs],
        }

    # -- QoS page quotas + cold-page reclaim (qos= only) ----------------
    #
    # A retiring request's still-registered refcount-1 prefix pages go
    # COLD instead of freeing: resident for future sharers (their
    # digests stay in the pool's table, their bytes untouched in the
    # arena — nothing writes a page no slot's table names), attributed
    # to the departing tenant, and evictable. Reclaim is COW-aware by
    # construction: cold pages have refcount 1 (a cold page that gains
    # a sharer is warmed out of the cold set first), so eviction can
    # never touch a page a live holder reads — a shared prefix page is
    # never yanked from under a compliant co-holder.

    def _tenant_usage(self, tenant: str) -> int:
        """Pages attributed to the tenant: hot refs held by its
        resident slots + its cold pages. The quota number."""
        return (self._tenant_pages.get(tenant, 0)
                + self._cold_count.get(tenant, 0))

    def _tenant_debit(self, tenant: str | None, n: int) -> None:
        if self._drr is None or tenant is None or n == 0:
            return
        left = self._tenant_pages.get(tenant, 0) - n
        if left:
            self._tenant_pages[tenant] = left
        else:
            self._tenant_pages.pop(tenant, None)

    def _over_quota(self, tenant: str) -> bool:
        if tenant not in self._qos:
            return False  # adopted stream from an unregistered tenant
        quota = self._qos.get(tenant).pages
        return quota is not None and self._tenant_usage(tenant) > quota

    def _drop_cold(self, pid: int) -> str:
        """Remove ``pid`` from the cold set — the ONE place the cold
        bookkeeping (set, per-tenant count, the cache's pool hold)
        comes apart, shared by warm and evict. Returns the tenant the
        page was attributed to."""
        t = self._cold.pop(pid)
        n = self._cold_count.get(t, 0) - 1
        if n:
            self._cold_count[t] = n
        else:
            self._cold_count.pop(t, None)
        self.pool.decref(pid)
        return t

    def _warm_cold(self, pid: int) -> None:
        """A cold page gained a holder: drop the cache's hold and the
        tenant attribution (the new holder's refs are the page's life
        now)."""
        self._drop_cold(pid)

    def _evict_cold_page(self, *, protect=frozenset(),
                         tenant: str | None = None) -> bool:
        """Evict ONE cold page — the reclaim primitive. ``tenant``
        narrows to that tenant's cold pages (quota enforcement);
        otherwise pool-pressure order: an OVER-QUOTA tenant's cold
        pages first, then any (cold residency is cache, not
        entitlement — deferring live work to preserve a cold page
        would break work conservation). Oldest-first within each
        class; ``protect`` pins pages the in-flight plan would share.
        Returns False when nothing evictable remains."""
        victim = None
        if tenant is not None:
            for pid, t in self._cold.items():
                if t == tenant and pid not in protect:
                    victim = pid
                    break
        else:
            for pid, t in self._cold.items():
                if pid not in protect and self._over_quota(t):
                    victim = pid
                    break
            if victim is None:
                for pid in self._cold:
                    if pid not in protect:
                        victim = pid
                        break
        if victim is None:
            return False
        if self.cache is not None:
            # the evicted cold page's last HBM incarnation dies here:
            # spill its bytes to the DRAM tier (tenant-attributed, so
            # spill_pages quotas bind) before the freeing decref
            self._spill_page(victim, tenant=self._cold.get(victim))
        t = self._drop_cold(victim)
        if self._flight is not None:
            self._flight.event(
                "qos reclaim", src="scheduler", tenant=t, page=victim,
            )
        return True

    def _plan_pages_qos(self, req: Request):
        """:meth:`_plan_pages` under the tenant's page quota, with
        cold-page reclaim on both pressure paths: pool exhaustion
        evicts exactly the shortfall in cold pages (over-quota
        tenants' first, oldest-first); quota exhaustion evicts the
        requesting tenant's OWN cold pages. Returns None when the
        request still cannot be planned — the DRR pass then defers
        this tenant, not the rotation."""
        contract = self._qos.get(req.tenant)
        # quotas run on one cache width (refused otherwise at
        # construction), so there is one kind's needs to read
        digests, fetch, needs = self._page_needs(req)
        shared, n_pages, wraps, n_fresh, reserve = needs[0]
        # the plan's own shares are never reclaim victims: evicting
        # one to make room would trade a prefill skip for a fresh
        # page — strictly worse on both bytes and time. (A resident
        # page the plan cannot share gives no skip and stays an
        # honest eviction candidate.)
        protect = set(shared)
        # pool pressure: can_alloc is `n_fresh + reserve + reserved
        # <= free`, and an evicted cold page (refcount 1, zero
        # reservations by construction) frees exactly one page — so
        # the shortfall is computed ONCE and reclaimed in one pass,
        # never replanned (the protect set keeps the share walk
        # valid across evictions)
        shortfall = (n_fresh + reserve + self.pool.reserved
                     - self.pool.free)
        for _ in range(max(shortfall, 0)):
            if not self._evict_cold_page(protect=protect):
                return None
        if contract.pages is not None:
            own_cold_shared = sum(
                1 for pid in shared
                if self._cold.get(pid) == req.tenant
            )
            # sharing one's own cold page moves it cold -> hot: no new
            # usage; everything else is net-new attribution
            need = (self._tenant_usage(req.tenant) + n_pages
                    - own_cold_shared)
            while need > contract.pages:
                if not self._evict_cold_page(protect=protect,
                                             tenant=req.tenant):
                    return None
                need -= 1
        return (digests, fetch, [(shared, n_pages, wraps)])

    def _prepare_tick_pages(self, decoding: list[int]) -> None:
        """Pre-tick COW pass: the next ``n_inner`` decode steps write
        ring slots ``[pos, pos + _tick_rows)`` (mod W) of every decoding
        row. Any touched page still shared (refcount > 1) is copied to
        a fresh page, consuming the reservation attached to the shared
        page at admission (``PagePool.cow_alloc``); a touched page
        this slot owns outright but once REGISTERED as a prefix drops
        out of the share table (its bytes are about to change). After
        this pass the device scan only ever writes exclusively-owned
        pages — COW is invisible to the compiled program."""
        for kd in self._kinds:
            self._prepare_kind_pages(kd, decoding)

    def _prepare_kind_pages(self, kd: _PageKind,
                            decoding: list[int]) -> None:
        """:meth:`_prepare_tick_pages` for the layers of one cache
        width: their ring, their pool, their page table."""
        pool, pt_host = kd.pool, kd.pt_host
        copies: list[tuple[int, int]] = []
        for s in decoding:
            pos = self._host_pos[s]
            touched = {
                ((pos + t) % kd.W) // self.P
                for t in range(self._tick_rows)
            }
            for j in sorted(touched):
                pid = int(pt_host[s, j])
                if pid == NULL_PAGE:
                    # defensive: the admission budget allocates every
                    # touchable page eagerly, so this is unreachable
                    # unless the budget math regressed
                    raise PagePoolExhausted(
                        f"slot {s} page {j} unallocated at write time "
                        "(admission budget bug)"
                    )
                if pool.refcount(pid) > 1:
                    new = pool.cow_alloc(pid)
                    copies.append((pid, new))
                    if self._trace is not None:
                        _r = self._slot_req[s]
                        if _r is not None and _r.trace is not None:
                            self._trace.event(
                                _r.trace, "cow_copy",
                                time.perf_counter(), page=int(pid),
                            )
                    # the writer leaves the shared page for its copy;
                    # only wrapping slots ever write shared pages, so
                    # the page's wrapper count drops with it
                    pool.decref(pid, wrapper=kd.slot_wraps[s])
                    pt_host[s, j] = new
                    self._pt_dev = None
                else:
                    pool.note_write(pid)
        if copies:
            # one device call for the whole tick's copies; pad to a
            # power of two with null-page self-copies so the jitted
            # program compiles O(log) distinct shapes, not one per
            # divergence count
            n = 1 << (len(copies) - 1).bit_length()
            copies += [(NULL_PAGE, NULL_PAGE)] * (n - len(copies))
            src, dst = (np.asarray(c, np.int32) for c in zip(*copies))
            # the copy runs over this kind's layers alone: all of them
            # where there is one width
            moved = self._copy(
                [self._caches[li] for li in kd.layers],
                jnp.asarray(src), jnp.asarray(dst),
            )
            for li, cl in zip(kd.layers, moved):
                self._caches[li] = cl

    def _warm_chunk_group(self) -> None:
        """Compile (or load) the grouped prefill program before the
        first tick returns, with one run on throw-away arenas: whether
        and when a tick has two chunks due is the traffic's, and the
        tick that is first to must not pay the compile. Nor the tick
        that is first to have ONE chunk due: a full backlog's first
        ticks fill every program of a group, and with a drafter which
        tick comes to a lone chunk follows the seed's acceptances; so
        the lone chunk's program has a run here too, and the wide one
        where the scheduler has it. Two arenas fewer than the grouped
        program takes stay as its scratch (a group is at least two)."""
        n = self._group
        valid = ((np.zeros((n,), np.int32),)
                 if self.cfg.counts_rows else ())
        nxt = ({"nxt": np.zeros((n, self.C), np.int32)}
               if self.draft is not None else {})
        _, arenas = self._extend_group(
            self.params, np.zeros((n, self.C), np.int32),
            tuple(_fresh_cache(self.cfg, 1, self.Lmax, self.quantize_kv)
                  for _ in range(n)),
            np.zeros((n,), np.int32), *valid, **nxt)
        self._extend(
            self.params, np.zeros((1, self.C), np.int32), arenas[0],
            np.int32(0), *(v[0] for v in valid),
            **{k: v[:1] for k, v in nxt.items()})
        if self._extend_wide is not None:
            # the tick that is first to go wide pays no compile either
            self._extend_wide(
                self.params, np.zeros((1, n * self.C), np.int32), arenas[1],
                np.int32(0), *(v[0] for v in valid),
                **{k: v.reshape(1, -1) for k, v in nxt.items()})
        self._scratch_arenas = list(arenas[2:])

    def _advance_admissions(self, retired: list[Request]) -> None:
        """Every admitting slot's next chunk is due. They wait for the
        chunks of the requests this tick admits (:meth:`_run_pending`)
        unless one of them cannot (:meth:`_due_at_once`): then all run
        now, and the requests whose last chunk that was get their first
        token before the queue is looked at, as when each chunk was a
        program of its own."""
        self._pending.extend(self._admitting)
        if any(map(self._due_at_once, self._admitting.values())):
            self._run_pending(retired)

    def _due_at_once(self, st: _Admitting) -> bool:
        """Can this request's next chunk not wait for the chunks the
        rest of the tick brings? Where chunks share no program there is
        nothing to wait for (and the device would wait for the host's
        planning). Else only a LAST chunk whose admission must end
        before the next request is planned, because its end changes
        what a plan reads: it registers prefix pages the next request
        may share, or it may retire at once (``max_new`` 1, or an EOS
        as first token) and give back a slot and pages."""
        if self._group == 1:
            return True
        return st.next_chunk + 1 == st.n_chunks and bool(
            (self.shares_prefixes and st.n_cover)
            or st.req.max_new == 1 or self.eos_id is not None
        )

    def _run_pending(self, retired: list[Request],
                     last: bool = False) -> None:
        """Dispatch the chunks that are due, in the order the slots
        came, as many a program as the grouped program takes (a lone
        one left over, or every one where there is no grouped program,
        in the program of one chunk); after each program the requests
        whose last chunk it held are finished, in the same order.
        ``last``: the tick's admit phase ends with this call, which is
        where one of the slots may go wide (:meth:`_wide_slot`): its
        program runs first, the others are grouped as ever."""
        slots, self._pending = self._pending, []
        if last and self._extend_wide is not None:
            wide = self._wide_slot(slots)
            if wide is not None:
                self._run_wide_chunk(wide)
                slots.remove(wide)
        for at in range(0, len(slots), self._group):
            members = slots[at:at + self._group]
            self._run_chunk_group(members)
            for s in members:
                st = self._admitting[s]
                if st.next_chunk == st.n_chunks:
                    self._finish_admission(s, retired)

    def _wide_slot(self, slots: list[int]) -> int | None:
        """Of the slots whose chunk is due, the one that advances
        ``_group`` chunks in the wide program in this tick, or None.
        The one admitted first (``slots`` is in admission's order) that
        has ``_group`` whole chunks left BEFORE its last, which stays a
        chunk of ``prompt_chunk`` rows (the first token is read off its
        hidden state, at its offset): the oldest document finishes
        first, and the documents one after another and not side by
        side. None where the tick has run a prefill program already or
        more than ``_group`` other chunks are due: a tick that goes
        wide runs ONE more prefill program at most, so never more than
        two, or than it would have run anyway; every decoding slot
        waits for all of them. Counts the host has, never a token's
        value: a plan made behind the running tick stays valid."""
        g = self._group
        if self._tick_chunk_programs or len(slots) > g + 1:
            return None
        for s in slots:
            st = self._admitting[s]
            if st.n_chunks - 1 - st.next_chunk >= g:
                return s
        return None

    def _run_wide_chunk(self, s: int) -> None:
        """The wide program for slot ``s``: its next ``_group`` chunks
        as one chunk of their rows, every one the prompt's."""
        C, g = self.C, self._group
        st = self._admitting[s]
        at, off = st.next_chunk * C, st.base + st.next_chunk * C
        offs = [off + i * C for i in range(g)]
        with _annotate(
            "serving.prefill_chunk", chunks=g, wide=1, req=st.req.id,
            slot=s, chunk=st.next_chunk, of=st.n_chunks,
            rows_seen=_chunk_rows_seen(off, g * C, self.Lmax,
                                       self.cfg.windows),
            **self._expert_tile.get(g, {}), **self._wide_routes,
            **self._layer_kinds,
            **self._sparse_chunk_counts([st] * g, offs),
        ):
            valid = ((np.int32(g * C),) if self.cfg.counts_rows else ())
            nxt = ({"nxt": st.padded[:, at + 1:at + g * C + 1]}
                   if self.draft is not None else {})
            # the hidden state goes unread: no row of a wide chunk is
            # a prompt's last
            _, st.cache = self._extend_wide(
                self.params, st.padded[:, at:at + g * C], st.cache,
                np.int32(off), *valid, **nxt)
        st.last_hidden = None
        self._tick_chunk_programs += 1
        self._chunks_advanced(st, g)

    def _chunks_advanced(self, st: _Admitting, n: int = 1) -> None:
        """A program has run the request's next ``n`` chunks (more than
        one: the wide program): the cursor and the counts."""
        st.next_chunk += n
        self._tick_chunks += n
        self.prefill_chunks += n
        if n > 1:
            self._tick_wide += n
            self.wide_chunks += n
        if self._obs is not None:
            self._obs.prefill_chunk(n, wide=n > 1)
        if self._trace is not None and st.req.trace is not None:
            self._trace.event(
                st.req.trace, "prefill_chunk", time.perf_counter(),
                tick=self._admit_tick, **({"chunks": n} if n > 1 else {}),
            )

    def _run_chunk_group(self, slots: list[int]) -> None:
        """One prefill program for the next chunk of each of ``slots``:
        the program of one chunk for one, the grouped program for more,
        padded to its size with chunks of no request (no valid row,
        scratch arenas): a chunk short of the ridge waits for the
        weights' bytes, which the padding does not add to."""
        C, n = self.C, len(slots)
        sts = [self._admitting[s] for s in slots]
        offs = [st.base + st.next_chunk * C for st in sts]
        each = lambda values: (values[0] if n == 1
                               else ",".join(map(str, values)))
        size = 1 if n == 1 else self._group
        with _annotate(
            "serving.prefill_chunk", chunks=n,
            req=each([st.req.id for st in sts]), slot=each(slots),
            chunk=each([st.next_chunk for st in sts]),
            of=each([st.n_chunks for st in sts]),
            rows_seen=sum(_chunk_rows_seen(off, C, self.Lmax,
                                           self.cfg.windows)
                          for off in offs),
            **self._expert_tile.get(size, {}), **self._rule_routes,
            **self._layer_kinds,
            **self._sparse_chunk_counts(sts, offs),
        ):
            # host arrays and numpy scalars go to the device with the
            # program's own dispatch; an eager slice or ``jnp.int32``
            # is a dispatch (and a transfer) of its own, each a stretch
            # in which the device waits for the host
            chunks = np.zeros((size, C), np.int32)
            for i, st in enumerate(sts):
                chunks[i] = st.padded[0, st.next_chunk * C:
                                      (st.next_chunk + 1) * C]
            # recurrent layers (and a selector's pooled cells) are told
            # where the prompt ends in the chunk
            valid = ()
            if self.cfg.counts_rows:
                valid = (np.zeros((size,), np.int32),)
                valid[0][:n] = [min(C, st.req.prompt.size - off)
                                for st, off in zip(sts, offs)]
            # a drafter's module is given the tokens that follow the
            # chunk's (behind the prompt's last: none yet, that row is
            # the first token's program's)
            nxt = {}
            if self.draft is not None:
                nxt["nxt"] = np.zeros((size, C), np.int32)
                for i, st in enumerate(sts):
                    after = st.padded[0, st.next_chunk * C + 1:
                                      (st.next_chunk + 1) * C + 1]
                    nxt["nxt"][i, :after.size] = after
            if n == 1:
                hidden, caches = self._extend(
                    self.params, chunks, sts[0].cache, np.int32(offs[0]),
                    *(v[0] for v in valid), **nxt,
                )
                hidden, caches = (hidden,), (caches,)
            else:
                pad = size - n
                hidden, caches = self._extend_group(
                    self.params, chunks,
                    (*(st.cache for st in sts),
                     *self._scratch_arenas[:pad]),
                    np.array(offs + [0] * pad, np.int32), *valid, **nxt,
                )
                self._scratch_arenas[:pad] = caches[n:]
        self._tick_chunk_programs += 1
        for st, h, cache in zip(sts, hidden, caches):
            st.last_hidden, st.cache = h, cache
            self._chunks_advanced(st)

    def _finish_admission(self, s: int, retired: list[Request]) -> None:
        """The request in slot ``s`` has had its last chunk: first
        token, its window placed into the slot, the arena released."""
        st = self._admitting[s]
        rid = st.req.id
        Tp = st.req.prompt.size
        with _annotate("serving.first_token", req=rid, slot=s,
                       # what the hand-over costs: the pages placement
                       # writes, over the cache widths (a prompt's own,
                       # ``_place_paged``), and the layers whose ring
                       # is still gathered row by row
                       pages_placed=sum(
                           min(-(-Tp // self.P), kd.max_pages)
                           for kd in self._kinds),
                       ring_gathers=self._ring_gathers,
                       # the slot's recurrent state is written over by
                       # this request's: a reset, counted with the
                       # admission that makes it
                       **({"state_reset": 1} if self.cfg.state_layers
                          else {})):
            self.state_resets += bool(self.cfg.state_layers)
            rkey = (st.req.key if st.req.key is not None
                    else jax.random.key(st.req.id + 1))
            tok0, ring = self._finish(
                self.params, st.cache, st.last_hidden, np.int32(Tp),
                np.int32(st.base + (st.n_chunks - 1) * self.C), rkey,
            )
            # _finish read the arena without donating it: recycle it
            self._release_arena(st)
            # install the page table NOW (stale row writes landed in
            # the null page until this point), then scatter the ring
            # window into the pages and flip the row live
            for kd, pids, wraps in zip(self._kinds, st.pids,
                                       st.wraps):
                kd.pt_host[s] = pids
                kd.slot_wraps[s] = wraps
            self._pt_dev = None
            self._host_pos[s] = Tp
            (self._caches, self._tok, self._pos, self._done,
             self._keys) = self._place(
                self._caches, ring, self._tok, self._pos, self._done,
                self._keys,
                self._pt_rows(kd.pt_host[s] for kd in self._kinds),
                np.int32(s), tok0, np.int32(Tp), rkey,
            )
            # the prompt-covered pages now hold exactly the content
            # their chained prefix digests describe — publish them for
            # future admissions to share (first-wins; the shared ones
            # are already registered)
            for kd, pids, wraps in zip(self._kinds, st.pids,
                                       st.wraps):
                for j in range(st.n_cover):
                    kd.pool.register(st.digests[j], pids[j],
                                     volatile=wraps)
        del self._admitting[s]
        st.req._first = tok0
        if self._ends_known() and st.req.max_new > 1:
            # the first token stays a device value (placement has put
            # it into the slot's row): the host reads it with the fetch
            # of the request's first tick, so that no read stands
            # between this dispatch and the next
            return
        # admission blocks on the device: the request's first token
        # comes back before the tick's decode is dispatched, because
        # its value (an EOS) or its being the last (``max_new`` 1) may
        # end the request here
        self._deliver_first(st.req)
        if self._retire_if_due(st.req):  # max_new == 1 or prompt EOS
            self._free_slot(s)
            retired.append(st.req)

    def _deliver_first(self, req: Request, t: float | None = None) -> None:
        """Read the request's first token and hand it over: where it
        was made (an end that waits for its value) or, kept on the
        device, in the harvest of the request's first tick, in front of
        that tick's tokens (``t``: the fetch's stamp, which is then the
        token's)."""
        tok0, req._first = req._first, None
        with _annotate("serving.first_token_wait", req=req.id):
            # with a drafter ``[first token, first draft]``: the draft
            # stays on the device, in the slot's row
            first = int(np.asarray(tok0).reshape(-1)[0])
        req.tokens.append(first)
        if self._obs is not None:
            self._obs.first_token(
                req, time.perf_counter() if t is None else t)
        if self._trace is not None and req.trace is not None:
            self._trace.event(
                req.trace, "first_token", time.perf_counter(),
                tick=self.tick_count,
            )

    # -- retirement -----------------------------------------------------

    def _retire_if_due(self, req: Request) -> bool:
        cut = None
        if self.eos_id is not None and req._eos_at is None:
            # scan only this tick's new tokens (a long-lived request
            # must not pay a full-history scan per tick)
            try:
                req._eos_at = req.tokens.index(
                    self.eos_id, req._scanned
                )
            except ValueError:
                pass
            req._scanned = len(req.tokens)
        if req._eos_at is not None:
            cut = req._eos_at + 1
            if cut <= req.max_new:
                req.reason = "eos"
            else:
                cut = None
        if cut is None and len(req.tokens) >= req.max_new:
            cut = req.max_new
            req.reason = "length"
        if cut is None:
            return False
        del req.tokens[cut:]
        # (a draft counts where the token it guessed was delivered: the
        # steps a retiring slot ran on behind the end verified nothing)
        while req.drafts and req.drafts[-1][0] >= cut:
            req.drafts.pop()
        req.finished = True
        req.retired_tick = self.tick_count
        # owner-only terminal stamp (see _retire_cancelled)
        if self._trace is not None and req.trace is not None \
                and req._trace_owned:
            self._trace.event(
                req.trace, "retired", time.perf_counter(),
                outcome=req.reason, tokens=len(req.tokens),
                **({"drafted": len(req.drafts),
                    "accepted": sum(d[2] for d in req.drafts)}
                   if req.drafts else {}),
            )
        return True

    def _free_slot(self, s: int) -> None:
        req = self._slot_req[s]
        self._slot_req[s] = None
        # the row keeps decoding garbage until reused — done=True makes
        # it emit EOS-clamped tokens nobody reads; admission resets it
        self._done = self._done.at[s].set(True)
        # return the slot's pages (shared prefixes just drop one
        # reference; a page frees — and leaves the prefix table —
        # only when its last reader retires) and null the row so
        # its zombie writes land in the null page. Under qos=, a
        # sole-held page whose prefix digest is still registered
        # goes COLD instead of freeing (the reclaim contract
        # above): resident for future sharers, attributed to the
        # departing tenant, evicted oldest-first under pressure.
        tenant = (req.tenant if self._drr is not None
                  and req is not None else None)
        keep_cold = tenant is not None and not self._slot_wraps[s]
        n_refs = 0
        for pid in self._pt_host[s]:
            if pid == NULL_PAGE:
                continue
            pid = int(pid)
            n_refs += 1
            if (keep_cold and self.pool.refcount(pid) == 1
                    and self.pool.registered(pid)):
                self._cold[pid] = tenant
                self._cold_count[tenant] = (
                    self._cold_count.get(tenant, 0) + 1
                )
            else:
                # fleet spill: a sole-held registered page is
                # about to free (and leave the share table) —
                # offer its bytes to the DRAM tier first, so a
                # sibling's future admission fetches instead of
                # re-prefilling. Cold retention above takes
                # precedence (HBM residency beats DRAM); eviction
                # of the cold set spills on its own path.
                if (self.cache is not None
                        and not self._slot_wraps[s]
                        and self.pool.refcount(pid) == 1
                        and self.pool.registered(pid)):
                    self._spill_page(pid, tenant=tenant)
                self.pool.decref(pid,
                                 wrapper=self._slot_wraps[s])
        self._tenant_debit(tenant, n_refs)
        self._pt_host[s] = NULL_PAGE
        self._slot_wraps[s] = False
        # the wider kinds' pages (quotas and the fleet cache are
        # one-width features, so nothing goes cold or spills here)
        for kd in self._kinds[1:]:
            for pid in kd.pt_host[s]:
                if pid != NULL_PAGE:
                    kd.pool.decref(int(pid),
                                   wrapper=kd.slot_wraps[s])
            kd.pt_host[s] = NULL_PAGE
            kd.slot_wraps[s] = False
        self._pt_dev = None
        self._host_pos[s] = 0
