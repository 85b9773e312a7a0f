"""Inference for the flagship transformer: KV cache, prefill, decode.

The reference has no inference code of any kind (it has no model code —
SURVEY §2); this is north-star flagship scope (VERDICT r3 missing #2):
a framework that trains long-context models must also serve them.

Design (TPU-first):

* **One incremental forward.** Prefill and decode are the same program
  at different chunk sizes: a chunk of ``T`` tokens at global offset
  ``off`` writes its per-layer K/V into the cache at ``[off, off+T)``
  and attends causally. Prefill (``off == 0``) needs no cache reads, so
  it runs the configured chunk kernel — the flash Pallas kernel for
  long prompts. A later chunk (``T > 1`` at ``off > 0``: chunked
  prefill) walks the key blocks of the cache
  its rows can see with an online softmax (:func:`_chunk_attention`),
  so it costs what ``off + T`` holds and not ``max_len``. Decode
  (``T == 1``) attends the single query against
  the whole cache through the grouped GQA einsums
  (:func:`~..parallel.ring_attention._group_scores`), so MQA/GQA
  configs read ``kv_heads`` cache heads, not ``n_heads`` — the KV
  bandwidth/memory win is structural, never faked by a repeat.
* **Static shapes.** The cache is ``(B, max_len, kv_heads, head_dim)``
  per layer; validity is positional masking (``kpos <= qpos``), never a
  dynamic slice length — one compiled program serves every step.
* **tp-sharded cache.** Cache heads shard over ``tp`` like the K/V
  projections. When ``kv_heads < tp`` (MQA/GQA serving with wide tp)
  the cache uses the *replicated-groups* layout: global head axis
  ``tp`` slots, slot ``t`` holding kv head ``t * kv_heads // tp`` —
  each device computes its own replica from the tp-replicated K/V
  projections, so the layout needs no extra collectives.
* **Sliding windows roll.** With ``attn_window=W`` the default path
  masks the (q-W, q] band over a ``max_len`` cache exactly like
  training; the *ring* path (``generate_ring_dense`` /
  ``make_ring_generate``) keeps an O(W) circular cache instead:
  position ``p`` writes slot ``p % W``, and slot ``s`` at decode
  position ``pos`` holds position ``kpos(s) = pos - ((pos - s) mod
  W)`` — valid iff ``kpos >= 0``, which makes the window+causal mask
  *and* the warmup masking of unwritten slots the same one predicate.
  RoPE is applied at write time with absolute positions, so rotation
  survives the permuted storage order (dot products are relative).
  Decode reads W cache positions per step regardless of how long the
  stream runs — the window's memory/bandwidth prize at W << max_len.
* **Greedy generation is one program.** ``make_generate`` runs prefill
  plus a ``lax.scan`` over decode steps *inside a single shard_map
  jit* — no host round trip per token: the device steps on its own
  and the host fetches the finished stream once.

Decode-time attention is exact; the teacher-forced logits equal the
training forward's (tests/test_decode.py pins both, sharded included).
One caveat: MoE expert capacity is a per-call shape, so MoE configs
tight enough to drop tokens route per chunk, not per full sequence —
see :func:`prefill_dense`.
"""

from __future__ import annotations

import functools
from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..parallel.ring_attention import (
    _band_mask,
    _flash_interpreted,
    _group_pv,
    _group_scores,
    resolve_attention_impl,
)
from .transformer import (
    TransformerConfig,
    _kv_tp_sharded,
    _rope,
    attn_qkv,
    attn_merge,
    embed,
    ffn_half,
    hc_fold,
    hc_pre,
    head_logits,
    make_kv_slice,
    mla_absorb,
    mla_merge,
    mla_project,
    mtp_input,
    param_specs,
    pool_cells,
    sparse_pick,
    ssm_half,
    state_half,
    zero_state,
)

__all__ = [
    "init_cache",
    "cache_specs",
    "decode_batch_axes",
    "prefill_dense",
    "decode_step_dense",
    "decode_step_ring_dense",
    "generate_dense",
    "generate_ring_dense",
    "init_ring_cache",
    "ring_from_cache",
    "ring_widths",
    "make_generate",
    "make_ring_generate",
    "make_prefill",
    "make_decode_step",
    "make_extend",
]

_NEG = -1e30  # matches parallel/ring_attention.py

# int8 decode-kernel routing (ops/decode_attention.py). Nothing sets
# it: every program factory resolves the route once, from what it sees
# (``_kernel_possible(cfg, quantize_kv) and _route_kernel(B)``; the
# paged tick ``_paged_kernel_possible``), and hands the inner functions
# a plain bool. Route and ``check_vma`` come from the same two
# predicates.
#
# The kernel routes only for BATCHED decode. Standalone it beat the
# bf16 einsum 1.22x at its DMA floor, but inside the generation scan
# each pallas_call pays a launch/carry-aliasing boundary cost of
# ~0.02-0.04 ms/layer. That cost is PER CALL, so batching divides it by
# the rows the call serves: at B=1 it swamped the byte win
# (0.70-0.91x), at B >= 4 the amortized boundary rode under the
# streaming win. The threshold (~0.03 ms/call of boundary over the
# kernel's standalone margin of ~0.012 ms at a 16k cache crosses under
# 4 rows per call) was measured on the earlier installation and has no
# reading on this one: every cell serves 16 slots (ROADMAP D5).
KERNEL_MIN_BATCH = 4


def _route_kernel(B: int) -> bool:
    """Does a call serving ``B`` local rows amortize the kernel's
    scan/custom_call boundary cost? (``KERNEL_MIN_BATCH``.)"""
    return B >= KERNEL_MIN_BATCH


def _kernel_viable(q, cache_l) -> bool:
    """Trace-time shape gate shared by EVERY int8-kernel call site
    (masked ``_cached_attention``, ring ``_ring_cached_attention``,
    and serving's per-row ``_ring_attention_rows``): quantized cache,
    single query, lane-aligned head_dim, query heads in whole GQA
    groups (any group: the kernel's q tile is the group rounded up to
    8 sublanes, ops/decode_attention._group_tile), and a 128-multiple
    block divisor for the cache length whose working set, at that
    tile, fits the kernel's VMEM budget. One predicate so the routing
    sites cannot drift from the kernel's actual constraints."""
    if not _is_quantized(cache_l) or "v" not in cache_l:
        return False
    if "kp" in cache_l:  # a selection of blocks: the paged tick's kernel
        return False
    Hq, Hkv = q.shape[2], cache_l["k"].shape[2]
    if q.shape[1] != 1 or q.shape[-1] % 128 != 0 or Hq % Hkv != 0:
        return False
    from ..ops.decode_attention import (
        DEFAULT_BLOCK_K,
        _group_tile,
        _pick_block_128,
    )

    return _pick_block_128(
        cache_l["k"].shape[1], DEFAULT_BLOCK_K, Hkv, q.shape[-1],
        _group_tile(Hq // Hkv),
    ) is not None


def _kernel_possible(cfg, quantize_kv: bool) -> bool:
    """Could a program for ``cfg`` route T=1 cached attention through
    the int8 kernel? The shard-invariant part of ``_cached_attention``'s
    guard (quantized cache, lane-aligned head_dim); the remaining
    conditions (GQA ratio, block divisor, ``_route_kernel``'s batch
    threshold) depend on per-shard shapes and stay trace-time. Also
    scopes the vma carve-out (``_decode_kernel_interpreted``). A latent
    layer's one row a position is no K/V head of these kernels: the
    positional and ring programs of a configuration with one take
    the ``jax.numpy`` route; its PAGED tick has a kernel of its own
    (``_paged_kernel_possible``)."""
    return bool(quantize_kv and cfg.head_dim % 128 == 0
                and not cfg.latent_layers)


def _paged_kernel_possible(cfg, quantize_kv: bool,
                           page_tokens: int) -> bool:
    """Could the PAGED serving tick route the int8 kernel's page-table
    mode? ``_kernel_possible``'s cfg-static guard plus the paged-only
    conditions the dense gather fallback does not have: query heads in
    whole GQA groups (trace-time in the dense path, cfg-static here —
    the serving tick fixes its routing at construction; any group
    routes, the kernel's q tile follows it), a page size that is a
    streamable k-block (``ops.decode_attention.paged_block_viable``)
    and one page of it inside the kernel's VMEM budget at this head
    count and group tile. The serving scheduler resolves this ONCE at
    construction against its slot count; there is no trace-time
    re-gate on the paged path.

    Latent layers take the kernel's latent form
    (``ops.decode_attention.latent_decode_attention``) where every layer
    is one (latent layers beside K/V layers keep the gather route), the
    cache is quantized, the latent is whole lane tiles (the row splits
    into its two parts at a tile's edge) and one page fits the budget
    under the absorbed query's ``n_heads`` rows."""
    if cfg.latent_layers:
        from ..ops.decode_attention import (
            latent_pages_per_step,
            paged_block_viable,
        )

        return bool(
            quantize_kv and all(cfg.mla(li) for li in range(cfg.n_layers))
            and cfg.mla_kv_rank % 128 == 0
            and paged_block_viable(page_tokens)
            and latent_pages_per_step(
                1, page_tokens, cfg.latent_width, cfg.n_heads) is not None)
    if not _kernel_possible(cfg, quantize_kv):
        return False
    if cfg.n_heads % cfg.kv_heads:
        return False
    from ..ops.decode_attention import (
        _group_tile,
        _pages_per_step,
        paged_block_viable,
    )

    return paged_block_viable(page_tokens) and _pages_per_step(
        1, page_tokens, cfg.kv_heads, cfg.head_dim,
        _group_tile(cfg.n_heads // cfg.kv_heads),
    ) is not None


def _decode_kernel_interpreted(cfg, quantize_kv: bool) -> bool:
    """True iff a quantized decode program for ``cfg`` could trace the
    int8 Pallas kernel via the Pallas *interpreter* (non-TPU mesh) —
    shard_map's varying-axes checking must be off for it, the same
    carve-out ``_flash_interpreted`` gives the flash kernels. A slight
    over-approximation is safe only in one direction: claiming "kernel"
    for a kernel-free program silently loses vma checking, so the
    cfg-static guard conditions are all applied here. The per-shard
    batch is not known at make time, so a small-batch program on an
    interpreted mesh runs without vma checking (the conservative
    direction is unreachable without the batch)."""
    if not _kernel_possible(cfg, quantize_kv):
        return False
    from ..ops.flash_attention import _use_interpret

    return _use_interpret()


# --------------------------------------------------------------------------
# int8 KV-cache quantization (serving-time choice, orthogonal to layout)
# --------------------------------------------------------------------------


def _kv_quantize(x):
    """Per-(batch, position, head) absmax int8 quantization over the
    head_dim axis: ``x ~= x_i8 * s[..., None]``. The scale axis choice
    matters: per-position scales ride the cache (tiny — no D axis) and
    dequantization folds into the attention einsums as a rank-1 scale
    on scores (K) and probabilities (V), so no dequantized copy is
    *required* at full size. Measured reality: in the einsum form XLA
    materializes one anyway before the dot, so there
    int8 only halves the cache's bytes; through the Pallas kernel
    (ops/decode_attention.py), which dequantizes in VMEM, it is a
    latency feature too: the paged serving tick that routes it reads
    pages in place, and StarCoder2-3B's tick fell from 156 ms to 73
    when it did (`serve_sc2_chat`; PERF.md section 6, PR 27)."""
    xf = x.astype(jnp.float32)
    s = jnp.max(jnp.abs(xf), axis=-1) / 127.0
    s = jnp.maximum(s, 1e-8)  # all-zero rows (unwritten slots)
    return jnp.round(xf / s[..., None]).astype(jnp.int8), s


def _is_quantized(cache_l: dict) -> bool:
    return "k_s" in cache_l


def _expand_kv_scale(s, Hq):
    """(B, L, Hkv) per-position scales -> (B, Hq, 1, L) broadcastable
    against (B, Hq, Lq, L) scores/probs, repeating each kv head's scale
    over its GQA group (same grouping as ``_group_scores``)."""
    g = Hq // s.shape[2]
    if g > 1:
        s = jnp.repeat(s, g, axis=2)
    return s.transpose(0, 2, 1)[:, :, None, :]


# A latent-attention layer's cache (``TransformerConfig(layer_mixers=
# "mla")``) is ONE row a position and no ``v``: ``k`` (B, L, 1, R +
# rope) holds the normalised latent beside the one rotated key all
# heads share, and an int8 cache one float32 scale for each of the two
# parts, ``k_s`` (B, L, 2): 512 + 64 + 8 = 584 bytes a position at the
# published sizes where the same 32 heads uncompressed keep 10,496. Keys
# and values are both read from it: the absorbed query's
# (``transformer.mla_absorb``) product with the whole row is the score,
# and the probabilities' product with the row's first R dims the
# result, which the value's up-projection then takes. The functions
# below take the width R of the latent as ``latent`` (None: a K/V
# cache); everything that moves rows (ring gather, pages, arenas) reads
# the leaves as they come.


def _latent_leaves(row, R: int, quantized: bool) -> dict:
    """The leaves a latent layer's cache writes for ``row`` (..., 1, R +
    rope): the row itself, or its int8 values with the scale of the
    latent and the scale of the rotated key (``_kv_quantize`` on each
    part)."""
    if not quantized:
        return {"k": row}
    cq, cs = _kv_quantize(row[..., :R])
    rq, rs = _kv_quantize(row[..., R:])
    return {"k": jnp.concatenate([cq, rq], axis=-1),
            "k_s": jnp.concatenate([cs, rs], axis=-1)}


def _latent_scores(q, cache_l: dict, scale, R: int):
    """The absorbed query (B, T, H, R + rope) against every row of a
    latent cache: (B, H, T, L) float32; int8 rows dequantize through
    the rank-1 correction of each part's scale."""
    rows = cache_l["k"]
    if not _is_quantized(cache_l):
        return _group_scores(q, rows, scale)
    rows, ks = rows.astype(q.dtype), cache_l["k_s"]
    part = lambda sl, j: _group_scores(
        q[..., sl], rows[..., sl], scale) * ks[:, None, None, :, j]
    return part(slice(None, R), 0) + part(slice(R, None), 1)


def _latent_pv(p, cache_l: dict, R: int):
    """Probabilities (B, H, T, L) x the rows' latent part: (B, T, H, R)
    float32, what the value's up-projection takes."""
    if _is_quantized(cache_l):
        p = p * cache_l["k_s"][:, None, None, :, 0]
    return _group_pv(p, cache_l["k"][..., :R])


def _cache_write(cache_l: dict, k, v, off, latent=None, *, cfg=None,
                 valid=None) -> dict:
    """Write a chunk's K/V at position-axis offset ``off``, quantizing
    when the cache is int8 (detected from the layout, so every caller
    — masked, ring, chunked — shares one write path). A latent layer
    (``latent``: the latent's width) writes its one row, ``k``. A
    cache that keeps pooled cells for a selection of blocks (``kp``;
    ``cfg`` says their sizes) adds the rows to their cells, those
    behind ``valid`` (a count, None = all) left out."""
    upd = partial(jax.lax.dynamic_update_slice_in_dim, start_index=off,
                  axis=1)
    if latent is not None:
        return {kk: upd(cache_l[kk], update=u) for kk, u in _latent_leaves(
            k, latent, _is_quantized(cache_l)).items()}
    pooled = {}
    if "kp" in cache_l:
        with jax.named_scope("sparse_pool"):
            pooled["kp"] = _pool_write(cache_l["kp"], k, off, cfg, valid)
    if not _is_quantized(cache_l):
        return {"k": upd(cache_l["k"], update=k),
                "v": upd(cache_l["v"], update=v), **pooled}
    kq, ks = _kv_quantize(k)
    vq, vs = _kv_quantize(v)
    return {
        "k": upd(cache_l["k"], update=kq),
        "v": upd(cache_l["v"], update=vq),
        "k_s": upd(cache_l["k_s"], update=ks),
        "v_s": upd(cache_l["v_s"], update=vs),
        **pooled,
    }


# A layer that attends a selection of its key blocks
# (``TransformerConfig(sparse_block=...)``) keeps one more leaf beside
# its rows: ``kp`` (B, cells, Hkv, D) float32, the mean of every
# ``sparse_stride`` keys as they were before the cache quantized them
# (``transformer.pool_cells``; a cell that is not full holds what it
# has so far, and no window that reaches into it counts yet). A
# positional cache of L rows has the cells of its whole blocks and one
# to spare, which a write that does not start on a cell's first row
# spills into.


def _pool_cells_for(L: int, cfg) -> int:
    return -(-L // cfg.sparse_block) * cfg.sparse_cells + 1


def _pool_write(kp, k, off, cfg, valid=None):
    """Add the rows k (B, T, Hkv, D) at positions ``[off, off + T)`` to
    their cells. The rows are laid into a buffer of whole cells at
    ``off mod stride``, so the cells' sums are one reshape whatever
    ``off`` is."""
    st = cfg.sparse_stride
    B, T = k.shape[:2]
    kf = k.astype(jnp.float32)
    if valid is not None:
        kf = jnp.where((jnp.arange(T) < valid)[None, :, None, None], kf, 0.0)
    n = -(-T // st) + 1
    buf = jnp.zeros((B, n * st) + k.shape[2:], jnp.float32)
    buf = jax.lax.dynamic_update_slice_in_dim(buf, kf, off % st, axis=1)
    c0 = off // st
    old = jax.lax.dynamic_slice_in_dim(kp, c0, n, axis=1)
    return jax.lax.dynamic_update_slice_in_dim(
        kp, old + pool_cells(buf, cfg), c0, axis=1)


def _select_blocks(q, cache_l, qpos, cfg):
    """Which key blocks each of the chunk's queries attends
    (``transformer.sparse_pick`` a request): q (B, T, H, D) at
    positions ``qpos`` (T,) over a positional cache -> (B, T, Hkv,
    blocks) bool."""
    nb = -(-cache_l["k"].shape[1] // cfg.sparse_block)
    with jax.named_scope("sparse_select"):
        return jax.vmap(lambda qb, cb: sparse_pick(
            qb, cb, qpos + 1, cfg, nb)[0])(q, cache_l["kp"])


def _select_rows(stands, kpos, cfg, g: int):
    """:func:`_select_blocks`'s answer for the key rows ``kpos``, a
    query head each: (B, H, T, len(kpos)) bool."""
    rows = jnp.take(stands, kpos // cfg.sparse_block, axis=-1)
    return jnp.repeat(rows.transpose(0, 2, 1, 3), g, axis=1)


def _cache_scores(q, cache_l: dict, scale, latent=None):
    """Grouped scores against the cache, dequantizing via the rank-1
    score correction when int8."""
    if latent is not None:
        return _latent_scores(q, cache_l, scale, latent)
    kc = cache_l["k"]
    if not _is_quantized(cache_l):
        return _group_scores(q, kc, scale)
    s = _group_scores(q, kc.astype(q.dtype), scale)
    return s * _expand_kv_scale(cache_l["k_s"], q.shape[2])


def _cache_pv(p, cache_l: dict, latent=None):
    """Grouped probs x V against the cache; int8 V dequantizes by
    folding the per-position scale into the probabilities."""
    if latent is not None:
        return _latent_pv(p, cache_l, latent)
    if _is_quantized(cache_l):
        p = p * _expand_kv_scale(cache_l["v_s"], p.shape[1])
    return _group_pv(p, cache_l["v"])


def _cache_heads_global(cfg: TransformerConfig, mesh: Mesh | None) -> int:
    """Global cache head count: ``kv_heads``, or ``tp`` replicated-group
    slots when kv_heads < tp (see module docstring)."""
    if mesh is None or "tp" not in mesh.axis_names:
        return cfg.kv_heads
    tp = mesh.shape["tp"]
    return cfg.kv_heads if _kv_tp_sharded(cfg, mesh) else tp


def _zero_cache_layer(B, L, H, Dh, dtype, quantize_kv):
    z = jnp.zeros((B, L, H, Dh), jnp.int8 if quantize_kv else dtype)
    layer = {"k": z, "v": z}
    if quantize_kv:
        zs = jnp.zeros((B, L, H), jnp.float32)
        layer["k_s"], layer["v_s"] = zs, zs
    return layer


def _zero_latent_layer(B, L, cfg, quantize_kv):
    """A latent layer's zeroed cache: one row a position."""
    layer = {"k": jnp.zeros((B, L, 1, cfg.latent_width),
                            jnp.int8 if quantize_kv else cfg.dtype)}
    if quantize_kv:
        layer["k_s"] = jnp.zeros((B, L, 2), jnp.float32)
    return layer


def init_cache(
    cfg: TransformerConfig, batch: int, max_len: int,
    mesh: Mesh | None = None, *, quantize_kv: bool = False,
) -> list[dict]:
    """Zeroed per-layer KV cache (host pytree; ``shard_cache`` places
    it). Layout: layers -> {"k","v"} of (B, max_len, cache_heads, Dh);
    ``quantize_kv=True`` stores int8 K/V plus per-(batch, position,
    head) f32 scales ``{"k_s","v_s"}`` — half the bytes of a bf16
    cache, dequantized inside the attention einsums (never at full
    size)."""
    H = _cache_heads_global(cfg, mesh)
    def rows(li):
        layer = _zero_cache_layer(batch, max_len, H, cfg.head_dim,
                                  cfg.dtype, quantize_kv)
        if cfg.sparse(li):
            layer["kp"] = jnp.zeros(
                (batch, _pool_cells_for(max_len, cfg), H, cfg.head_dim),
                jnp.float32)
        return layer

    return [
        # a recurrent layer keeps its fixed block of state; a layer that
        # holds a state-space mixer beside its attention, rows AND state
        zero_state(cfg, li, batch) if not cfg.rows(li)
        else _zero_latent_layer(batch, max_len, cfg, quantize_kv)
        if cfg.mla(li)
        else {**rows(li), **zero_state(cfg, li, batch)} if cfg.ssm(li)
        else rows(li)
        for li in range(cfg.n_layers)
    ]


def decode_batch_axes(cfg: TransformerConfig) -> tuple[str, ...]:
    """Mesh axes the batch shards over at decode: MoE configs add
    ``ep`` (every expert-parallel member routes distinct rows — the
    GShard layout, matching the training path's ``batch_axes``)."""
    return ("dp", "ep") if cfg.n_experts else ("dp",)


def cache_specs(cfg: TransformerConfig, *,
                quantize_kv: bool = False) -> list[dict]:
    """PartitionSpecs for the cache: batch over dp (and ep for MoE),
    heads over tp; int8 scales shard exactly like their K/V."""
    s = P(decode_batch_axes(cfg), None, "tp", None)
    layer = {"k": s, "v": s}
    if quantize_kv:
        ss = P(decode_batch_axes(cfg), None, "tp")
        layer["k_s"], layer["v_s"] = ss, ss
    return [dict(layer) for _ in range(cfg.n_layers)]


def shard_cache(cache, cfg: TransformerConfig, mesh: Mesh):
    return jax.tree.map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)),
        cache, cache_specs(cfg, quantize_kv=_is_quantized(cache[0])),
    )


# key rows one step of the chunk walk scores (``_chunk_attention``): the
# per-block (B, H, T, CHUNK_BLOCK_K) float32 scores are all a chunk
# ever holds, whatever the cache's length
CHUNK_BLOCK_K = 512


def _chunk_attention(q, cache_l, qpos, scale, window, latent=None,
                     select=None):
    """A chunk's (T > 1) grouped attention, walking the key blocks its
    queries can see: block ``j`` holds the cache rows ``[j*bk,
    (j+1)*bk)`` (``bk = min(CHUNK_BLOCK_K, Lmax)``; absolute positions,
    so a query row meets the same blocks in the same order whatever the
    chunk size or the cache's length), and the walk runs from the block
    of the first row the widest query sees (``off - window + 1``, or 0)
    to the block of the chunk's last row, both traced from ``off =
    qpos[0]``. Running maximum, sum and accumulator are the online
    softmax of ``parallel/ring_attention._block_update``; a block
    that is all masked for a row leaves that row as it was (``p`` is 0
    and the rescale ``exp(0)``). Scores, mask and p.v of a block are
    ``_cache_scores`` / ``_band_mask`` / ``_cache_pv`` on the block's
    rows, int8 rows dequantized with their per-position scales. No
    (H, T, Lmax) tensor exists and a block outside the walk costs
    nothing: the work follows the rows the chunk can see, not the
    cache's length. ``latent`` (the latent's width): ``q`` is the
    absorbed query, the rows a latent layer's, the result (B, T, H,
    latent). ``select`` (``(stands, cfg)``: :func:`_select_blocks`'s
    answer): each row attends the blocks it picked alone; a block of
    the walk that no row picked anything in is skipped, the rest
    masked."""
    T = q.shape[1]
    Lmax = cache_l["k"].shape[1]
    bk = min(CHUNK_BLOCK_K, Lmax)
    off = qpos[0]
    lo = 0 if window is None else jnp.maximum(off - window + 1, 0) // bk
    hi = jnp.minimum(-(-(off + T) // bk), -(-Lmax // bk))
    # accumulators derived from q: they inherit its varying mesh axes
    # (make_extend runs this under shard_map), as in ring_self_attention
    o0 = (q if latent is None else q[..., :latent]).astype(
        jnp.float32) * 0.0
    zeros = o0.sum(-1).transpose(0, 2, 1)  # (B, H, T)

    def block(j, carry):
        o, m, l = carry
        # the last block of a cache that is no multiple of bk slides
        # back inside it; its rows below j*bk are block j-1's
        start = jnp.minimum(j * bk, Lmax - bk)
        blk = {
            name: jax.lax.dynamic_slice_in_dim(a, start, bk, axis=1)
            for name, a in cache_l.items() if name != "kp"
        }
        kpos = start + jnp.arange(bk)
        # the one band predicate (parallel/ring_attention._band_mask):
        # the serving path cannot silently diverge from the training
        # oracle
        mask = _band_mask(qpos, kpos, True, window)
        if Lmax % bk:
            mask = jnp.logical_and(mask, (kpos >= j * bk)[None, :])
        mask = mask[None, None]
        if select is not None:
            mask = mask & _select_rows(select[0], kpos, select[1],
                                       q.shape[2] // blk["k"].shape[2])

        def attend():
            s = jnp.where(mask, _cache_scores(q, blk, scale, latent), _NEG)
            m_new = jnp.maximum(m, s.max(axis=-1))
            p = jnp.where(mask, jnp.exp(s - m_new[..., None]), 0.0)
            corr = jnp.exp(m - m_new)  # (B, H, T)
            l_new = l * corr + p.sum(axis=-1)
            o_new = o * corr.transpose(0, 2, 1)[..., None] + _cache_pv(
                p, blk, latent)
            return o_new, m_new, l_new

        if select is None:
            return attend()
        return jax.lax.cond(jnp.any(mask), attend, lambda: carry)

    o, _, l = jax.lax.fori_loop(lo, hi, block, (o0, zeros + _NEG, zeros))
    # every row sees at least itself while the caller keeps off + T <=
    # Lmax (its contract); past it nothing is promised, only no NaN
    l = jnp.maximum(l, 1e-20)
    return (o / l.transpose(0, 2, 1)[..., None]).astype(q.dtype)


def _chunk_rows_seen(off: int, T: int, Lmax: int, windows) -> int:
    """Key rows the walk of :func:`_chunk_attention` scores for a chunk
    of ``T`` queries at ``off`` in its widest layer (``windows``: every
    layer's, ``cfg.windows``): the same ``lo`` and ``hi`` in host
    integers, for a scheduler that counts what its chunks attend
    without a read from the device."""
    bk = min(CHUNK_BLOCK_K, Lmax)
    lo = 0
    if all(w is not None for w in windows):
        lo = max(off - max(windows) + 1, 0) // bk
    return min(off + T, Lmax) - lo * bk


def _cached_attention(q, cache_l, qpos, scale, window=None,
                      use_kernel: bool = False, latent=None, sparse=None):
    """Grouped attention of the chunk's queries against the rows of the
    cache they can see.

    q: (B, T, H, D); the cache holds (B, Lmax, Hkv, D) at positions
    ``arange(Lmax)``; validity is ``kpos <= qpos`` (cache entries past
    the chunk are zeros or an earlier request's leftovers AND masked;
    entries below the offset are real), intersected with the
    sliding-window band when ``window`` is set.

    A chunk (T > 1) walks the key blocks it can see
    (:func:`_chunk_attention`). A single query scores the whole cache
    at once: int8 caches take the Pallas decode kernel
    (ops/decode_attention.py), which dequantizes in VMEM, so HBM reads
    really are the int8 bytes — the einsum form's ``.astype`` is
    materialized by XLA and gives half the bytes back.
    ``use_kernel`` is the program's resolved route (the module note).
    ``sparse`` (the configuration, for a layer that attends a
    selection of its key blocks): every query's own blocks
    (:func:`_select_blocks`) and no other row.
    """
    select = None
    if sparse is not None:
        select = (_select_blocks(q, cache_l, qpos, sparse), sparse)
    if q.shape[1] > 1:
        with jax.named_scope("chunk_attn"):
            return _chunk_attention(
                q, cache_l, qpos, scale, window, latent,
                **({} if select is None else {"select": select}))
    if use_kernel and _kernel_viable(q, cache_l):
        from ..ops.decode_attention import quantized_decode_attention

        return quantized_decode_attention(
            q, cache_l, qpos[0], scale, window
        )
    Lmax = cache_l["k"].shape[1]
    s = _cache_scores(q, cache_l, scale, latent)  # (B, H, 1, Lmax) f32
    # the one band predicate (parallel/ring_attention._band_mask): the
    # serving path cannot silently diverge from the training oracle
    mask = _band_mask(qpos, jnp.arange(Lmax), True, window)[None, None]
    if select is not None:
        mask = mask & _select_rows(select[0], jnp.arange(Lmax), sparse,
                                   q.shape[2] // cache_l["k"].shape[2])
    s = jnp.where(mask, s, _NEG)
    p = jax.nn.softmax(s, axis=-1)
    o = _cache_pv(p, cache_l, latent)  # (B, 1, H, D) f32
    return o.astype(q.dtype)


def _ring_cached_attention(q, cache_l, pos, scale,
                           use_kernel: bool = False):
    """Single-query attention against an O(W) ring cache.

    q: (B, 1, H, D); the cache holds (B, W, Hkv, D) where slot ``s``
    holds the K/V of position ``kpos(s) = pos - ((pos - s) mod W)``
    (the module docstring's invariant, established by the prefill
    gather and maintained by the per-step slot write). ``kpos >= 0`` is
    the whole mask: it is simultaneously the causal bound (every stored
    position is <= pos by construction), the sliding-window bound
    (every stored position is > pos - W), and the warmup guard for
    slots no position has reached yet.

    int8 ring caches route the same Pallas kernel as the masked path
    when the routing gate says so (``ring=True`` mode evaluates the
    identical ``kpos >= 0`` predicate in VMEM) — the window serving
    scan gets the dequantize-in-registers win at batch."""
    W = cache_l["k"].shape[1]
    if use_kernel and _kernel_viable(q, cache_l):
        from ..ops.decode_attention import quantized_decode_attention

        return quantized_decode_attention(
            q, cache_l, pos, scale, ring=True
        )
    s = _cache_scores(q, cache_l, scale)  # (B, H, 1, W) f32
    kpos = pos - jnp.mod(pos - jnp.arange(W), W)
    s = jnp.where((kpos >= 0)[None, None, None, :], s, _NEG)
    p = jax.nn.softmax(s, axis=-1)
    o = _cache_pv(p, cache_l)  # (B, 1, H, D) f32
    return o.astype(q.dtype)


# the leaves of a layer's cache that are recurrent state, one fixed
# block a request (``transformer.*_zero_state``); every other leaf has a
# row a position. A layer that holds a state-space mixer beside its
# attention keeps both kinds in one dict.
STATE_LEAVES = ("S", "conv")


def _split_state(cache_l: dict) -> tuple[dict, dict]:
    """``(rows, state)``: a cache layer's leaves by kind."""
    return ({kk: a for kk, a in cache_l.items() if kk not in STATE_LEAVES},
            {kk: a for kk, a in cache_l.items() if kk in STATE_LEAVES})


def _incremental_layer(x, lp, cache_l, qpos, cfg, li, *, chunk_attn,
                       kv_slice, tp_psum, ring=False,
                       decode_kernel: bool = False, valid=None):
    """Layer ``li`` of the incremental forward: write the chunk's K/V
    into the cache at ``qpos`` positions, attend, feed-forward. Returns
    (x, cache_l). The block itself is models/transformer.py's
    (``attn_qkv`` / ``attn_merge`` / ``ffn_half``); this function owns
    only where K/V live. ``tp_psum=True`` combines the head-shard
    out-projection and the d_ff-shard down-projection over the ``tp``
    axis, exactly like the training path (``_forward_local``).
    ``ring=True`` treats the cache as the O(W) circular window buffer
    (single-token chunks only): the write lands at slot ``pos % W`` and
    attention runs through :func:`_ring_cached_attention`. A gated
    delta-rule layer's ``cache_l`` is its state (no rows), carried
    through the chunk; of ``valid`` see ``gdn_half``."""
    h, mix = hc_pre(x, lp, cfg, "hc1")
    rope = partial(_rope, pos=qpos, theta=cfg.rope_theta,
                   table=cfg.rope_table)
    beside, state = None, {}
    if cfg.ssm(li):
        # the state-space mixer beside the attention: its state carried
        # through the chunk, its result joined to the attention's below
        if ring or tp_psum:
            raise ValueError("a layer that holds a state-space mixer has "
                             "neither a ring-cache nor a tp-sharded form")
        cache_l, state = _split_state(cache_l)
        beside, state = ssm_half(h, lp, state, cfg, valid)
    elif cfg.state(li):
        x, cache_l = state_half(h, lp, cache_l, cfg, li, rope, valid,
                                mix=mix)
        x, _, _ = ffn_half(x, lp, cfg, li, tp_psum=tp_psum)
        return x, cache_l
    if cfg.mla(li):
        # a latent layer attends its cache whatever the chunk: the row
        # it has just written is all that exists of a position's keys
        # and values
        if ring or tp_psum:
            raise ValueError("a latent-attention layer has neither a "
                             "ring-cache nor a tp-sharded form")
        x, cache_l = _latent_attend(
            h, lp, cfg, rope, mix, lambda row: _cache_write(
                cache_l, row, None, qpos[0], cfg.mla_kv_rank),
            lambda q, cl: _cached_attention(
                q, cl, qpos, cfg.softmax_scale, latent=cfg.mla_kv_rank))
        x, _, _ = ffn_half(x, lp, cfg, li)
        return x, cache_l
    q, k, v, gate = attn_qkv(h, lp, cfg, li, rope, kv_slice)
    off = qpos[0]
    if ring:
        off = jnp.mod(off, cache_l["k"].shape[1])
    cache_l = _cache_write(cache_l, k, v, off, cfg=cfg, valid=valid)
    scale = cfg.softmax_scale
    if cfg.sparse(li):
        # a selection of blocks is made from the cache's pooled cells,
        # whatever the chunk: also a prefill at offset 0
        if ring or tp_psum:
            raise ValueError("a layer that attends a selection of its "
                             "key blocks has neither a ring-cache nor a "
                             "tp-sharded form")
        o = _cached_attention(q, cache_l, qpos, scale, sparse=cfg)
    elif chunk_attn is not None:
        # prefill at offset 0: attention lives entirely inside the chunk,
        # so the configured chunk kernel (flash on TPU) does the work on
        # the exact (unquantized) chunk K/V — only the cache quantizes
        o = chunk_attn(q, k, v, window=cfg.windows[li])
    elif ring:
        o = _ring_cached_attention(q, cache_l, qpos[0], scale,
                                   use_kernel=decode_kernel)
    else:
        o = _cached_attention(q, cache_l, qpos, scale, cfg.windows[li],
                              use_kernel=decode_kernel)
    x = attn_merge(h, o, gate, lp, cfg, tp_psum=tp_psum, mix=mix,
                   beside=beside)
    x, _, _ = ffn_half(x, lp, cfg, li, tp_psum=tp_psum)
    return x, {**cache_l, **state}


def _latent_attend(h, lp, cfg, rope, mix, write, attend):
    """A latent-attention half over a cache, the same for a chunk, a
    decode step and the serving tick, which differ in where the row
    goes (``write(row) -> cache_l``) and which rows the absorbed query
    sees (``attend(q, cache_l) -> (B, T, H, kv rank)``). Returns ``(x,
    cache_l)``."""
    qn, qr, row = mla_project(h, lp, cfg, rope)
    q = mla_absorb(qn, qr, lp, cfg)
    with jax.named_scope("mla_attn"):
        cache_l = write(row)
        o = attend(q, cache_l)
    return mla_merge(h, o, lp, cfg, mix, latent=True), cache_l


def _mtp_rows(params, x, nxt, cfg, layer):
    """A chunk's rows of the multi-token-prediction module's OWN cache
    layer (the last of ``cfg.cache_layers``): the module's block on
    ``mtp_input(x, nxt)``, ``x`` the model's last block output at the
    chunk's positions and ``nxt`` the tokens that follow them, run by
    ``layer(h, block weights, li)`` as the caller runs the model's last
    layer. Only the rows it writes are kept: nothing reads the block's
    output of a prompt's inner positions, and the compiler drops what
    feeds it alone."""
    with jax.named_scope("mtp"):
        return layer(mtp_input(params, x, nxt, cfg),
                     params["mtp"]["block"], cfg.n_layers - 1)[1]


def _incremental_hidden(params, tokens, cache, offset, cfg,
                        *, prefill, kv_slice=None, tp_psum=False,
                        ring=False, decode_kernel: bool = False,
                        valid=None, nxt=None):
    """Chunk forward at global ``offset`` up to the last layer's output;
    returns (hidden (B, T, d), cache): :func:`_incremental_forward`
    without the head, for callers that read few of the chunk's rows (the
    server's prefill chunk reads one a request) or none.

    ``prefill=True`` (static) means offset is known to be 0 and chunk
    attention uses the configured kernel; otherwise attention runs
    against the cache — the ``max_len`` positional cache by default,
    the O(W) ring buffer when ``ring=True``. ``decode_kernel`` is the
    program's resolved int8-kernel route (the module note). ``valid``
    (a traced count, None = all) is how many leading rows of the chunk
    are the prompt's and not padding: attention never reads the
    padding, a recurrent layer must be told to skip it. ``nxt`` (B, T;
    ``cfg.mtp_depth`` and one more layer in ``cache``): the tokens that
    follow the chunk's, for the multi-token-prediction module's rows
    (:func:`_mtp_rows`).
    """
    T = tokens.shape[1]
    if ring and (T != 1 or prefill):
        raise ValueError(
            "ring cache reads are decode-only (T == 1): prefill runs "
            "positionally, then _ring_from_cache gathers the window"
        )
    qpos = offset + jnp.arange(T)
    chunk_attn = None
    if prefill:
        chunk_attn = partial(
            resolve_attention_impl(cfg.attn_impl), causal=True,
            scale=cfg.softmax_scale,
        )
    x = embed(params, tokens, cfg)
    new_cache = []
    for li, (lp, cache_l) in enumerate(zip(params["layers"], cache)):
        x, cache_l = _incremental_layer(
            x, lp, cache_l, qpos, cfg, li,
            chunk_attn=chunk_attn, kv_slice=kv_slice, tp_psum=tp_psum,
            ring=ring, decode_kernel=decode_kernel, valid=valid,
        )
        new_cache.append(cache_l)
    x = hc_fold(x, cfg)
    if nxt is not None:
        new_cache.append(_mtp_rows(
            params, x, nxt, cfg, lambda h, lp, li: _incremental_layer(
                h, lp, cache[cfg.n_layers], qpos, cfg, li,
                chunk_attn=chunk_attn, kv_slice=kv_slice,
                tp_psum=tp_psum)))
    return x, new_cache


@functools.lru_cache(maxsize=64)
def _grouped_layer(cfg: TransformerConfig, li: int):
    """Layer ``li`` of :func:`_grouped_hidden` as a program of its own:
    (x (n, T, d), the layer's weights, the n requests' own stores for
    this layer, offsets (n,), valid) -> (x, the stores). What is per
    token runs once on all rows of ``x``; row ``i``'s K/V is written
    into store ``i`` at ``offsets[i]`` and its queries walk that store
    alone, with the operations a lone chunk runs (:func:`_cache_write`,
    :func:`_cached_attention`); a recurrent layer's state is batched on
    its leading axis as it is. Jitted on its own so that a program
    traces one layer of a kind (``cfg.layer_like``) and calls it for
    the others: thirty layers of four requests each traced one by one
    cost a process seconds before its first tick."""

    @jax.jit
    def grouped_layer(x, lp, rows, offsets, valid):
        n, T = x.shape[:2]
        h, mix = hc_pre(x, lp, cfg, "hc1")
        qpos = offsets[:, None] + jnp.arange(T)  # (n, T)

        def rope(t):  # each request's rows at its own positions
            return jax.vmap(lambda a, pos: _rope(
                a[None], pos, cfg.rope_theta, cfg.rope_table)[0])(
                    t, qpos)

        def batched(stores):  # the n requests' state, one batch
            return {kk: jnp.concatenate([r[kk] for r in stores])
                    for kk in stores[0]}

        def unbatched(state):
            return [{kk: a[i:i + 1] for kk, a in state.items()}
                    for i in range(n)]

        beside = None
        if cfg.ssm(li):
            rows, states = zip(*(_split_state(r) for r in rows))
            beside, state = ssm_half(h, lp, batched(states), cfg, valid)
        if not cfg.rows(li):
            x, state = state_half(h, lp, batched(rows), cfg, li, rope,
                                  valid, mix=mix)
            rows = unbatched(state)
        else:

            def attend(q, rows, **kw):  # each request's queries, its store
                return jnp.concatenate([
                    _cached_attention(q[i:i + 1], r, qpos[i],
                                      cfg.softmax_scale, **kw)
                    for i, r in enumerate(rows)])

            if cfg.mla(li):
                R = cfg.mla_kv_rank
                x, rows = _latent_attend(
                    h, lp, cfg, rope, mix,
                    lambda row: [
                        _cache_write(r, row[i:i + 1], None, offsets[i], R)
                        for i, r in enumerate(rows)],
                    functools.partial(attend, latent=R))
            else:
                q, k, v, gate = attn_qkv(h, lp, cfg, li, rope)
                rows = [_cache_write(
                    r, k[i:i + 1], v[i:i + 1], offsets[i], cfg=cfg,
                    valid=None if valid is None else valid[i])
                    for i, r in enumerate(rows)]
                o = attend(q, rows, window=cfg.windows[li],
                           sparse=cfg if cfg.sparse(li) else None)
                x = attn_merge(h, o, gate, lp, cfg, mix=mix, beside=beside)
        if beside is not None:
            rows = [{**r, **st} for r, st in zip(rows, unbatched(state))]
        x, _, _ = ffn_half(x, lp, cfg, li)
        return x, rows

    return grouped_layer


def _grouped_hidden(params, tokens, caches, offsets, cfg, valid=None,
                    nxt=None):
    """The chunks of ``n`` requests as ONE forward, so that every
    weight (every expert) is read once for all of them: ``tokens``
    (n, T), ``caches`` the n requests' own positional caches of one row
    each, ``offsets`` (n,) where each chunk starts in its cache,
    ``valid`` (n,) as in :func:`_incremental_hidden` (None = all).
    Returns (hidden (n, T, d), the n caches). The rows of different
    requests meet only in products that are row-wise
    (:func:`_grouped_layer`). ``nxt`` (n, T): as in
    :func:`_incremental_hidden`."""
    x = embed(params, tokens, cfg)
    caches = [list(c) for c in caches]

    def layer(x, lp, li, at):  # cache layer ``at``, run as layer ``li``
        x, rows = _grouped_layer(cfg, cfg.layer_like(li))(
            x, lp, [c[at] for c in caches], offsets, valid)
        for c, r in zip(caches, rows):
            c[at] = r
        return x, rows

    for li, lp in enumerate(params["layers"]):
        x, _ = layer(x, lp, li, li)
    x = hc_fold(x, cfg)
    if nxt is not None:
        _mtp_rows(params, x, nxt, cfg,
                  lambda h, lp, li: layer(h, lp, li, cfg.n_layers))
    return x, caches


def _incremental_forward(params, tokens, cache, offset, cfg, **kw):
    """Chunk forward at global ``offset``; returns (logits (B, T, V),
    cache): the head on every row of :func:`_incremental_hidden`, whose
    keywords these are."""
    x, cache = _incremental_hidden(params, tokens, cache, offset, cfg,
                                   **kw)
    return head_logits(params, x, cfg), cache


# --------------------------------------------------------------------------
# dense (single-device oracle) API
# --------------------------------------------------------------------------


def _check_prefill_fits(T: int, cache) -> None:
    """Trace-time guard: ``dynamic_update_slice`` CLAMPS out-of-range
    offsets, so an over-long chunk would silently wrap the tail of the
    cache instead of erroring."""
    rows = [cl["k"] for cl in cache if "k" in cl]
    if not rows:  # recurrent layers alone: no row a token, no bound
        return
    Lmax = rows[0].shape[1]
    if T > Lmax:
        raise ValueError(
            f"chunk of {T} tokens does not fit the cache (max_len "
            f"{Lmax}); build the cache at least prompt+decode long"
        )


def _aligned_quantized_prefill(params, prompt, cache, cfg, *,
                               decode_kernel, kv_slice=None,
                               tp_psum=False, chunk=512):
    """Quantized-ring ORACLE prefill, in C-token chunks: every chunk
    attends the ALREADY-QUANTIZED cache (``prefill=False``), which is
    the only math the serving scheduler's chunked admission can ever
    evaluate — raw K/V of earlier chunks are gone once written. Per-
    position absmax quantization makes the chunk size invisible (a
    position's scale never depends on its neighbours) and the chunk
    walks key blocks laid on absolute positions
    (:func:`_chunk_attention`), so any C yields the identical stream;
    the scores that exist at one time are a block's, O(C *
    CHUNK_BLOCK_K) per layer whatever Tp — the flagship 16k prompt
    stays servable through this path, not just test-scale oracles.

    The shape-identical full chunks run under ONE ``lax.scan`` body
    (no head runs on them; only the cache carries), so trace and
    compile cost stay flat in Tp — a python loop would retrace the
    whole per-layer forward Tp/C times. At most two chunks trace
    directly at the tail: the one whose logits the caller needs, plus
    the ragged remainder when Tp % C != 0."""
    B, Tp = prompt.shape
    _check_prefill_fits(Tp, cache)
    nfull, rem = divmod(Tp, chunk)
    # fold all full chunks whose logits nobody reads into the scan
    nscan = nfull - (1 if rem == 0 else 0)
    off0 = 0
    if nscan >= 2:
        chunks = (
            prompt[:, :nscan * chunk]
            .reshape(B, nscan, chunk)
            .swapaxes(0, 1)
        )
        offs = jnp.arange(nscan, dtype=jnp.int32) * chunk

        def body(cache, xs):
            ch, off = xs
            _, cache = _incremental_hidden(
                params, ch, cache, off, cfg, prefill=False,
                kv_slice=kv_slice, tp_psum=tp_psum,
                decode_kernel=decode_kernel,
            )
            return cache, None

        cache, _ = jax.lax.scan(body, cache, (chunks, offs))
        off0 = nscan * chunk
    logits = None
    for off in range(off0, Tp, chunk):
        logits, cache = _incremental_forward(
            params, prompt[:, off:off + chunk], cache, jnp.int32(off),
            cfg, prefill=False, kv_slice=kv_slice, tp_psum=tp_psum,
            decode_kernel=decode_kernel,
        )
    return logits, cache


def prefill_dense(params, tokens, cache, cfg: TransformerConfig):
    """Fill the cache from a prompt; returns (logits (B, T, V), cache).

    MoE caveat: expert *capacity* is a per-call shape (ceil of
    tokens-routed-per-expert x capacity_factor, models/moe.py), so a
    config tight enough to DROP tokens can drop differently here than
    in the full-sequence training forward — teacher-forced equality
    holds exactly whenever no drops occur (generous capacity_factor or
    single-step decode, where capacity >= 1 covers every token)."""
    _check_prefill_fits(tokens.shape[1], cache)
    return _incremental_forward(
        params, tokens, cache, jnp.int32(0), cfg, prefill=True
    )


def decode_step_dense(params, token, cache, pos, cfg: TransformerConfig):
    """One decode step: ``token`` (B,) at global position ``pos``
    (scalar; caller keeps pos < the cache's max_len — out-of-range
    writes clamp, they do not error). Returns (logits (B, V), cache)."""
    logits, cache = _incremental_forward(
        params, token[:, None], cache, pos, cfg, prefill=False,
        decode_kernel=_kernel_possible(cfg, _is_quantized(cache[0]))
        and _route_kernel(token.shape[0]),
    )
    return logits[:, 0], cache


# --------------------------------------------------------------------------
# O(W) ring cache for sliding-window serving
# --------------------------------------------------------------------------


def ring_widths(cfg: TransformerConfig) -> tuple[int, ...]:
    """Ring width of every layer's serving cache. A window layer's ring
    is its window. A full-attention layer's is the context budget
    ``cfg.max_context``: a ring that wide never wraps inside the
    budget, so slot ``s`` holds position ``s`` and the one invariant
    (``kpos = pos - ((pos - s) mod W)``, valid iff ``kpos >= 0``) is
    plain causal attention there. A gated delta-rule layer has no
    rows and so no width: a configuration with one is refused here
    (``ServingScheduler`` serves it, its state a fixed block a slot)."""
    if cfg.state_layers:
        raise ValueError(
            "the ring cache is rows of K/V, a position each; this "
            "configuration has gated delta-rule layers (or decayed "
            "linear attention, or a state-space mixer beside attention "
            "or alone), whose state is one fixed block a "
            "request and has no width. ServingScheduler serves it"
        )
    if cfg.sparse_layers:
        raise ValueError(
            "the ring cache is rows of K/V alone; this configuration "
            "attends a selection of key blocks, made from pooled keys "
            "that a ring does not keep. ServingScheduler and the "
            "max_len cache of init_cache serve it"
        )
    if cfg.latent_layers:
        raise ValueError(
            "the ring cache is rows of K and of V, a head each; this "
            "configuration has latent-attention layers, which keep one "
            "row a position and no V. ServingScheduler and the "
            "max_len cache of init_cache serve it"
        )
    return _row_widths(cfg)


def _row_widths(cfg: TransformerConfig) -> tuple:
    """:func:`ring_widths` with None for a layer that keeps recurrent
    state in place of rows, and behind the model's layers the width of
    a multi-token-prediction module's block (``cfg.cache_layers``: the
    last layer's, whose kind it is)."""
    out = []
    for li in range(cfg.cache_layers):
        w = cfg.windows[cfg._like(li)]
        if not cfg.rows(li):
            out.append(None)
            continue
        if w is None:
            if cfg.max_context is None:
                raise ValueError(
                    "the ring cache needs a width for every layer: a "
                    "sliding-window layer's is its window "
                    "(TransformerConfig(attn_window=W) or layer_windows)"
                    ", a full-attention layer's is the context budget "
                    "(TransformerConfig(max_context=N); the max_len "
                    "cache of init_cache needs neither)"
                )
            w = cfg.max_context
        out.append(int(w))
    return tuple(out)


def _check_ring_cfg(cfg: TransformerConfig) -> int:
    """The one ring width of a configuration whose layers all share it
    (the single-request ring generators and the sharded tick)."""
    widths = set(ring_widths(cfg))
    if len(widths) > 1:
        raise ValueError(
            "this ring-cache program keeps one width for all layers; "
            f"the configuration has layers of more than one cache width "
            f"({sorted(widths)}). ServingScheduler serves it"
        )
    return widths.pop()


def init_ring_cache(
    cfg: TransformerConfig, batch: int, mesh: Mesh | None = None, *,
    quantize_kv: bool = False,
) -> list[dict]:
    """Zeroed per-layer ring cache: layers -> {"k","v"} of
    (B, attn_window, cache_heads, Dh). Sharding specs are
    :func:`cache_specs` (the layouts coincide; only the length axis'
    meaning differs — slots, not positions)."""
    W = _check_ring_cfg(cfg)
    H = _cache_heads_global(cfg, mesh)
    return [
        _zero_cache_layer(batch, W, H, cfg.head_dim, cfg.dtype,
                          quantize_kv)
        for _ in range(cfg.n_layers)
    ]


def _ring_from_cache(cache_l: dict, Tp: int, W: int,
                     stride: int | None = None) -> dict:
    """A positional cache holding positions [0, Tp) in the ring layout:
    slot ``s`` <- the latest prompt position congruent to ``s``
    (mod W); slots no position has reached (Tp < W) are zero — the
    ``kpos >= 0`` read mask of :func:`_ring_cached_attention` already
    treats them as unwritten. Every cache leaf (int8 scales included)
    shares the position axis, so one rule covers the layout, and the
    two shapes choose its form: a leaf no longer than the ring cannot
    wrap it (``Tp <= a.shape[1] <= W``), so slot ``s`` IS position
    ``s`` and the ring is the leaf as it lies, zeroed from ``Tp`` on
    (the rows a padded last chunk wrote behind the prompt) and padded
    to ``W``; only a leaf longer than the ring is gathered by
    position. A layer's pooled cells (``kp``, one every ``stride``
    rows) are taken as they lie either way."""

    def zero_behind(a, n, live):
        # the first n of a's positions, those behind ``live`` zero
        a = a[:, :n] if a.shape[1] >= n else jnp.pad(
            a, [(0, 0), (0, n - a.shape[1])] + [(0, 0)] * (a.ndim - 2))
        keep = jnp.arange(n) < live
        return jnp.where(keep.reshape((1, n) + (1,) * (a.ndim - 2)), a, 0)

    def rows(a):
        if a.shape[1] <= W:
            return zero_behind(a, W, Tp)
        s = jnp.arange(W)
        p = (Tp - 1) - jnp.mod((Tp - 1) - s, W)
        g = jnp.take(a, jnp.maximum(p, 0), axis=1)
        return jnp.where((p >= 0).reshape((1, W) + (1,) * (a.ndim - 2)),
                         g, 0)

    def cells(a):
        # pooled cells lie on positions like the rows: a ring as wide
        # as the context budget never wraps, so cell c stays cell c,
        # and W rows have W / stride of them (those behind the prompt
        # zero)
        return zero_behind(a, W // stride, -(-Tp // stride))

    # (a state-space mixer's state beside the rows is handed on as it is)
    return {kk: a if kk in STATE_LEAVES else cells(a) if kk == "kp"
            else rows(a) for kk, a in cache_l.items()}


def ring_from_cache(cache, Tp: int, cfg: TransformerConfig) -> list[dict]:
    """Public positional-prefill -> ring handoff: convert a full cache
    holding prompt positions ``[0, Tp)`` (from :func:`prefill_dense`
    over an :func:`init_cache` arena) into the O(W) ring layout that
    :func:`decode_step_ring_dense` consumes. The source cache must
    actually hold every prompt position — prefilling directly into a
    W-slot ring arena would need wrapped writes the positional prefill
    does not do (:func:`_check_prefill_fits` rejects that at trace
    time); prefill long prompts into a Tp-length positional cache, then
    hand off here."""
    W = _check_ring_cfg(cfg)
    if not cache or jax.tree.leaves(cache[0])[0].shape[1] < Tp:
        have = jax.tree.leaves(cache[0])[0].shape[1] if cache else 0
        raise ValueError(
            f"source cache holds {have} positions < prompt {Tp}; the "
            "ring gather needs every prompt position present"
        )
    return [_ring_from_cache(cl, Tp, W) for cl in cache]


def decode_step_ring_dense(params, token, cache, pos,
                           cfg: TransformerConfig):
    """One decode step against the O(W) ring cache: ``token`` (B,) at
    global position ``pos``. Returns (logits (B, V), cache). Unlike
    :func:`decode_step_dense` there is no max_len to overflow — the
    stream may run indefinitely; the model simply never sees past the
    window."""
    _check_ring_cfg(cfg)
    logits, cache = _incremental_forward(
        params, token[:, None], cache, pos, cfg, prefill=False, ring=True,
        decode_kernel=_kernel_possible(cfg, _is_quantized(cache[0]))
        and _route_kernel(token.shape[0]),
    )
    return logits[:, 0], cache


def _pick_token(logits, pos, key, temperature, top_k, dtype, row0=0):
    """Next-token choice shared by the dense and sharded generators:
    greedy at ``temperature == 0`` (static), else softmax sampling at
    the given temperature, optionally truncated to the top-k logits.

    The per-draw key folds the global position AND the GLOBAL batch
    row (``row0`` = this shard's batch offset under shard_map, the
    mixed-radix index over ``decode_batch_axes`` times B_local): a
    fixed key then yields one stream per
    (row, position) regardless of how the batch is sharded — dense and
    dp-sharded programs sample identical tokens, and every tp member
    draws the same token from the identical post-psum logits."""
    if temperature == 0.0:
        return jnp.argmax(logits, axis=-1).astype(dtype)
    lg = logits.astype(jnp.float32) / temperature
    if top_k is not None:
        # lax.top_k's partial reduction, NOT a full-vocab sort: this
        # runs per token inside the latency-critical decode scan
        kth = jax.lax.top_k(lg, int(top_k))[0][..., -1:]
        lg = jnp.where(lg < kth, -jnp.inf, lg)
    kpos = jax.random.fold_in(key, pos)
    rows = row0 + jnp.arange(lg.shape[0])
    return jax.vmap(
        lambda r, ll: jax.random.categorical(
            jax.random.fold_in(kpos, r), ll
        )
    )(rows, lg).astype(dtype)


def _check_sampling_params(temperature, top_k) -> None:
    if temperature < 0.0:
        raise ValueError(f"temperature must be >= 0, got {temperature}")
    if top_k is not None and top_k < 1:
        raise ValueError(f"top_k must be >= 1, got {top_k}")


def _check_sampling(temperature, top_k, key) -> None:
    _check_sampling_params(temperature, top_k)
    if temperature == 0.0 and key is not None:
        raise ValueError("a PRNG key is only meaningful with "
                         "temperature > 0 (greedy decoding is "
                         "deterministic)")
    if temperature > 0.0 and key is None:
        raise ValueError("temperature > 0 needs a jax.random key")


def _eos_clamp(nxt, tok, done, eos_id):
    """Static-shape EOS handling: once a row has emitted ``eos_id``
    every later token is forced to it (the scan always runs n_new
    steps — shapes never depend on content; callers strip the EOS tail
    host-side). Returns (next_token, next_done)."""
    if eos_id is None:
        return nxt, done
    done = jnp.logical_or(done, tok == eos_id)
    return jnp.where(done, jnp.asarray(eos_id, nxt.dtype), nxt), done


@functools.lru_cache(maxsize=64)
def _dense_runner(cfg: TransformerConfig, B: int, Tp: int, n_new: int,
                  max_len: int, temperature: float, top_k: int | None,
                  eos_id: int | None, quantize_kv: bool,
                  ring: bool = False):
    """Shape-keyed jitted prefill+scan generation program (one compile
    per (cfg, shapes, sampling); the cache is built inside the jit, not
    baked in as a constant). ``ring=True`` is the O(W) sliding-window
    variant: prefill fills a Tp-length transient positional cache
    (freed after the gather), the last-W K/V gathers into ring slots,
    and the decode scan carries W positions per layer (``max_len`` is
    ignored — the ring has no horizon).

    Quantized RING prefill attends through the masked cached-attention
    path (``prefill=False`` at offset 0) instead of the exact chunk
    kernel: the serving scheduler's chunked admission can only ever
    attend the already-quantized cache (raw K/V of earlier chunks are
    gone once written), and per-position quantization makes one
    whole-prompt "chunk" here IDENTICAL to the scheduler's C-token
    chunks — so ``generate_ring_dense(quantize_kv=True)`` is the
    scheduler's stream as an IDENTITY, not a coincidence
    (tests/test_serving.py pins it). The masked (non-ring) generator
    keeps its exact prefill (the prompt attends its raw K/V); the
    aligned prefill runs CHUNKED (``_aligned_quantized_prefill``), so
    its score memory is O(C * Tp) and long prompts stay servable."""
    W = _check_ring_cfg(cfg) if ring else None
    # the ring kernel (ops/decode_attention ring=True) routes under the
    # same gate as the masked path
    use_kernel = _kernel_possible(cfg, quantize_kv) and _route_kernel(B)

    @jax.jit
    def run(params, prompt, key):
        c = init_cache(cfg, B, Tp if ring else max_len,
                       quantize_kv=quantize_kv)
        if ring and quantize_kv:
            logits, c = _aligned_quantized_prefill(
                params, prompt, c, cfg, decode_kernel=use_kernel,
            )
        else:
            logits, c = prefill_dense(params, prompt, c, cfg)
        if ring:
            c = [_ring_from_cache(cl, Tp, W) for cl in c]
        tok = _pick_token(
            logits[:, -1], Tp - 1, key, temperature, top_k, prompt.dtype
        )
        done = jnp.zeros((B,), bool)

        def step(carry, pos):
            tok, done, c = carry
            lg, c = _incremental_forward(
                params, tok[:, None], c, pos, cfg, prefill=False,
                ring=ring, decode_kernel=use_kernel,
            )
            nxt = _pick_token(
                lg[:, 0], pos, key, temperature, top_k, tok.dtype
            )
            nxt, done = _eos_clamp(nxt, tok, done, eos_id)
            return (nxt, done, c), tok

        # n_new - 1 decode forwards: the last emitted token is the final
        # carry, so no forward is spent computing a discarded successor
        (tok, _, _), toks = jax.lax.scan(
            step, (tok, done, c), Tp + jnp.arange(n_new - 1)
        )
        toks = jnp.concatenate([toks, tok[None]], axis=0)
        return toks.swapaxes(0, 1)  # (B, n_new)

    return run


def generate_dense(params, prompt, n_new: int, cfg: TransformerConfig,
                   max_len: int | None = None, *,
                   temperature: float = 0.0, top_k: int | None = None,
                   key=None, eos_id: int | None = None,
                   quantize_kv: bool = False):
    """Generation, dense single-program: prefill + lax.scan of decode
    steps under one jit (compiled once per shape, cached). Greedy by
    default; ``temperature > 0`` samples (optionally top-k-truncated)
    with the given ``key``. ``eos_id``: rows that emit it keep emitting
    it (static shapes; strip the tail host-side). Returns (B, n_new)
    tokens."""
    if n_new < 1:
        raise ValueError(f"n_new must be >= 1, got {n_new}")
    _check_sampling(temperature, top_k, key)
    B, Tp = prompt.shape
    if max_len is None:
        max_len = Tp + n_new
    if max_len < Tp + n_new:
        raise ValueError(
            f"max_len {max_len} < prompt {Tp} + n_new {n_new}: decode "
            "positions would clamp into the last cache slot"
        )
    if key is None:
        key = jax.random.key(0)  # unused at temperature 0
    return _dense_runner(
        cfg, B, Tp, n_new, max_len, float(temperature), top_k, eos_id,
        quantize_kv,
    )(params, prompt, key)


def generate_ring_dense(params, prompt, n_new: int,
                        cfg: TransformerConfig, *,
                        temperature: float = 0.0, top_k: int | None = None,
                        key=None, eos_id: int | None = None,
                        quantize_kv: bool = False):
    """Sliding-window generation over the O(W) ring cache, dense
    single-program. Token-for-token equal to :func:`generate_dense` on
    a window config (both attend exactly the (pos-W, pos] band; only
    storage differs) while the decode scan carries W cache positions
    per layer instead of ``Tp + n_new`` — memory AND per-step cache
    bandwidth are O(W). Returns (B, n_new) tokens.

    With ``quantize_kv=True`` this is THE serving oracle: prefill
    attends the already-quantized cache exactly like the scheduler's
    chunked admission (see :func:`_dense_runner`), so a scheduler slot
    reproduces this stream as an identity; the masked generator keeps
    exact prefill, so the two quantized generators may differ at
    prefill-adjacent tokens (tests pin each contract separately)."""
    if n_new < 1:
        raise ValueError(f"n_new must be >= 1, got {n_new}")
    _check_ring_cfg(cfg)
    _check_sampling(temperature, top_k, key)
    B, Tp = prompt.shape
    if key is None:
        key = jax.random.key(0)  # unused at temperature 0
    return _dense_runner(
        cfg, B, Tp, n_new, 0, float(temperature), top_k, eos_id,
        quantize_kv, ring=True,
    )(params, prompt, key)


# --------------------------------------------------------------------------
# sharded (dp [x ep] x tp mesh) API
# --------------------------------------------------------------------------


def _check_decode_mesh(cfg: TransformerConfig, mesh: Mesh):
    """MoE decode composes expert parallelism: the mesh must carry an
    ``ep`` axis (size 1 folds experts onto each member) alongside dp
    and tp — same layout as the training path."""
    need = {"dp", "tp"} | ({"ep"} if cfg.n_experts else set())
    missing = need - set(mesh.axis_names)
    if missing:
        raise ValueError(
            f"decode mesh is missing axes {sorted(missing)}; MoE "
            "configs shard over (dp, ep, tp), dense over (dp, tp)"
        )


def make_prefill(cfg: TransformerConfig, mesh: Mesh, *,
                 quantize_kv: bool = False):
    """Jitted sharded prefill: (params, tokens (B, Tp), cache) ->
    (last-position logits (B, V), cache). Batch over dp (and ep for
    MoE — expert routing runs sharded, all_to_all over ep, exactly as
    in training), heads over tp. ``quantize_kv`` must match the cache
    layout (init_cache's flag)."""
    _check_decode_mesh(cfg, mesh)
    bax = decode_batch_axes(cfg)
    cspecs = cache_specs(cfg, quantize_kv=quantize_kv)

    def local(params, tokens, cache):
        _check_prefill_fits(tokens.shape[1], cache)
        logits, cache = _incremental_forward(
            params, tokens, cache, jnp.int32(0), cfg, prefill=True,
            kv_slice=make_kv_slice(cfg), tp_psum=True,
        )
        return logits[:, -1], cache

    f = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(param_specs(cfg, mesh), P(bax, None), cspecs),
        out_specs=(P(bax, None), cspecs),
        check_vma=not _flash_interpreted(cfg.attn_impl),
    )
    return jax.jit(f)


def make_decode_step(cfg: TransformerConfig, mesh: Mesh, *,
                     quantize_kv: bool = False):
    """Jitted sharded decode step: (params, token (B,), cache, pos) ->
    (logits (B, V), cache). Donates the cache for in-place HBM update.
    """

    _check_decode_mesh(cfg, mesh)
    bax = decode_batch_axes(cfg)
    cspecs = cache_specs(cfg, quantize_kv=quantize_kv)

    def local(params, token, cache, pos):
        logits, cache = _incremental_forward(
            params, token[:, None], cache, pos, cfg, prefill=False,
            kv_slice=make_kv_slice(cfg), tp_psum=True,
            decode_kernel=_kernel_possible(cfg, quantize_kv)
            and _route_kernel(token.shape[0]),
        )
        return logits[:, 0], cache

    f = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(
            param_specs(cfg, mesh), P(bax), cspecs, P(),
        ),
        out_specs=(P(bax, None), cspecs),
        # decode traces no FLASH kernel, but with quantize_kv it can
        # trace the int8 decode kernel — which needs the same
        # interpreted-Pallas vma carve-out
        check_vma=not _decode_kernel_interpreted(cfg, quantize_kv),
    )
    return jax.jit(f, donate_argnums=(2,))


def make_extend(cfg: TransformerConfig, mesh: Mesh, *,
                quantize_kv: bool = False):
    """Jitted CHUNKED prefill step: (params, tokens (B, T), cache,
    offset) -> (logits (B, T, V), cache) — processes a T-token chunk at
    any global ``offset``, attending causally within the chunk and
    fully to everything already cached below it. One compiled program
    per chunk length serves a whole streaming prefill:

    >>> extend = make_extend(cfg, mesh)
    >>> for i in range(0, Tp, C):
    ...     lg, cache = extend(params, prompt[:, i:i+C], cache, i)

    The caller keeps ``offset + T <= max_len`` (dynamic offsets cannot
    be trace-checked; out-of-range writes would clamp — see
    :func:`decode_step_dense`); a chunk longer than the cache errors at
    trace time. Equivalent position-for-position to one-shot
    ``make_prefill`` (the
    incremental forward is the training forward evaluated causally —
    tests/test_decode.py pins the chunked == one-shot == dense-oracle
    chain). The chunk attends the cache through the walk of
    :func:`_chunk_attention`: key blocks from the first its queries can
    see to its own last row, so a chunk's attention costs what ``offset
    + T`` (or the window) holds, not ``max_len`` (offset 0 one-shot
    prefill keeps the flash chunk kernel); the MoE capacity caveat of
    :func:`prefill_dense` applies per chunk.

    The cache is NOT donated here (the T=1 decode step donates its
    own): each chunk's program writes a fresh cache pytree. Chunked
    prefill runs once per prompt, so the extra cache copy is small
    next to the chunk compute."""

    _check_decode_mesh(cfg, mesh)
    bax = decode_batch_axes(cfg)
    cspecs = cache_specs(cfg, quantize_kv=quantize_kv)

    def local(params, tokens, cache, offset):
        # the T-vs-cache half of the clamp guard is trace-time checkable
        # (offset is dynamic: the caller owns offset + T <= max_len,
        # as documented for decode_step_dense)
        _check_prefill_fits(tokens.shape[1], cache)
        logits, cache = _incremental_forward(
            params, tokens, cache, offset, cfg, prefill=False,
            kv_slice=make_kv_slice(cfg), tp_psum=True,
            decode_kernel=_kernel_possible(cfg, quantize_kv)
            and _route_kernel(tokens.shape[0]),
        )
        return logits, cache

    f = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(
            param_specs(cfg, mesh), P(bax, None), cspecs, P(),
        ),
        out_specs=(P(bax, None, None), cspecs),
        # extend is chunked (T > 1) on every real path, but a T == 1
        # chunk with quantize_kv can trace the int8 decode kernel like
        # a decode step — same vma carve-out
        check_vma=not _decode_kernel_interpreted(cfg, quantize_kv),
    )
    return jax.jit(f)


def make_generate(cfg: TransformerConfig, mesh: Mesh, n_new: int,
                  max_len: int | None = None, *,
                  temperature: float = 0.0, top_k: int | None = None,
                  eos_id: int | None = None, quantize_kv: bool = False,
                  ring: bool = False):
    """Jitted sharded generation: ``gen(params, prompt (B, Tp)[, key])``
    -> (B, n_new) tokens. Prefill + a lax.scan of decode steps inside
    ONE shard_map program — zero host round trips between tokens.
    Greedy by default; ``temperature > 0`` samples (optionally top-k)
    and ``eos_id`` rows that finish keep emitting the EOS token
    (static shapes; strip host-side). The returned callable takes the
    PRNG key as its third argument
    (replicated across the mesh — every tp member draws the same token
    from the identical post-psum logits; the dense and sharded
    programs produce the same stream for the same key).

    The attention inside every layer of the training forward is
    replaced by cache reads; the tp psum of the training path is
    implicit here because each device holds its q-head slice and the
    out-projection partial-sums are psummed per layer exactly like
    ``_forward_local`` — see ``_incremental_layer`` (attention output
    enters the residual after the wo einsum, whose head-shard partial
    sums cross tp via the psum below).

    ``ring=True`` (see :func:`make_ring_generate`) swaps the decode
    scan's cache carry for the O(W) sliding-window ring; ``max_len``
    is then ignored (the ring has no horizon).
    """

    _check_decode_mesh(cfg, mesh)
    W = _check_ring_cfg(cfg) if ring else None
    bax = decode_batch_axes(cfg)
    if n_new < 1:
        raise ValueError(f"n_new must be >= 1, got {n_new}")
    _check_sampling_params(temperature, top_k)

    def local(params, prompt, key):
        B, Tp = prompt.shape
        # resolved at THIS shard's batch (the kernel routes only when
        # the call serves enough rows to amortize the scan boundary
        # cost — see _route_kernel)
        routed = _kernel_possible(cfg, quantize_kv) and _route_kernel(B)
        if ring:
            L = Tp  # transient positional prefill cache, gathered below
        else:
            L = max_len if max_len is not None else Tp + n_new
            if L < Tp + n_new:
                raise ValueError(
                    f"max_len {L} < prompt {Tp} + n_new {n_new}: decode "
                    "positions would clamp into the last cache slot"
                )
            if quantize_kv and routed and L > 2048:
                # round up so the int8 decode KERNEL always has a big
                # lane-aligned block divisor (extra slots are masked).
                # Gated on the resolved routing: the einsum path needs
                # no alignment, and the extra masked positions would
                # skew its memory/time against the bf16 baseline
                L = -(-L // 2048) * 2048
        Hc = _cache_heads_global(cfg, mesh)
        tp = mesh.shape["tp"]
        cache = [
            _zero_cache_layer(B, L, Hc // tp, cfg.head_dim, cfg.dtype,
                              quantize_kv)
            for _ in range(cfg.n_layers)
        ]
        kv_slice = make_kv_slice(cfg)
        if ring and quantize_kv:
            # oracle alignment, same as _dense_runner: quantized ring
            # prefill attends the already-quantized cache — the only
            # math the scheduler's chunked admission can evaluate
            logits, cache = _aligned_quantized_prefill(
                params, prompt, cache, cfg, decode_kernel=routed,
                kv_slice=kv_slice, tp_psum=True,
            )
        else:
            logits, cache = _incremental_forward(
                params, prompt, cache, jnp.int32(0), cfg, prefill=True,
                kv_slice=kv_slice, tp_psum=True,
            )
        if ring:
            cache = [_ring_from_cache(cl, Tp, W) for cl in cache]
        # global batch-row offset of this shard, derived from the one
        # source of truth for the batch layout (dp-major, then ep)
        row0 = jnp.int32(0)
        for ax in decode_batch_axes(cfg):
            row0 = row0 * jax.lax.axis_size(ax) + jax.lax.axis_index(ax)
        row0 = row0 * B
        tok = _pick_token(
            logits[:, -1], Tp - 1, key, temperature, top_k,
            prompt.dtype, row0,
        )
        # all-False, derived from tok so it inherits tok's varying mesh
        # axes (a plain zeros carry trips the scan's vma type check)
        done = tok < jnp.asarray(0, tok.dtype)

        def step(carry, pos):
            tok, done, cache = carry
            lg, cache = _incremental_forward(
                params, tok[:, None], cache, pos, cfg, prefill=False,
                kv_slice=kv_slice, tp_psum=True, ring=ring,
                decode_kernel=routed,
            )
            nxt = _pick_token(
                lg[:, 0], pos, key, temperature, top_k, tok.dtype, row0
            )
            nxt, done = _eos_clamp(nxt, tok, done, eos_id)
            return (nxt, done, cache), tok

        # n_new - 1 decode forwards, as in the dense runner: the final
        # token comes out of the carry, not a discarded extra forward
        (tok, _, _), toks = jax.lax.scan(
            step, (tok, done, cache), Tp + jnp.arange(n_new - 1)
        )
        toks = jnp.concatenate([toks, tok[None]], axis=0)
        return toks.swapaxes(0, 1)

    f = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(param_specs(cfg, mesh), P(bax, None), P()),
        out_specs=P(bax, None),
        # the generate program can trace BOTH interpreted Pallas
        # kernels: flash in the prefill chunk, the int8 decode kernel
        # in the scan steps — either needs the vma carve-out
        check_vma=not (
            _flash_interpreted(cfg.attn_impl)
            or _decode_kernel_interpreted(cfg, quantize_kv)
        ),
    )
    jitted = jax.jit(f)

    def gen(params, prompt, key=None):
        _check_sampling(temperature, top_k, key)
        if key is None:
            key = jax.random.key(0)  # unused at temperature 0
        return jitted(params, prompt, key)

    return gen


def make_ring_generate(cfg: TransformerConfig, mesh: Mesh, n_new: int, *,
                       temperature: float = 0.0, top_k: int | None = None,
                       eos_id: int | None = None,
                       quantize_kv: bool = False):
    """Sharded sliding-window generation over the O(W) ring cache:
    ``gen(params, prompt (B, Tp)[, key])`` -> (B, n_new) tokens.

    The :func:`make_generate` program with the decode scan's cache carry
    replaced by the ring (see the module docstring): prefill runs
    positionally into a Tp-length transient (the chunk flash kernel
    applies the window band), each layer's last-W K/V gathers into ring
    slots, and every decode step writes slot ``pos % W`` and reads W
    positions — per-token cache traffic and carried HBM are O(W)
    however long the prompt or the stream. Sharding is unchanged:
    batch over dp (and ep for MoE), cache heads over tp."""
    return make_generate(
        cfg, mesh, n_new, temperature=temperature, top_k=top_k,
        eos_id=eos_id, quantize_kv=quantize_kv, ring=True,
    )
