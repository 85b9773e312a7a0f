"""Pallas TPU decode attention over the int8 KV cache.

Why this kernel exists (measured; earlier installation, not repeated
on this one): the einsum-form dequantization — int8 cache
``.astype(bf16)`` feeding the attention dots — is *expressed* as a
fused rank-1 correction, but XLA
materializes the converted operand in HBM, so the int8 cache read half
the bytes and then paid them back with interest (0.70x vs the bf16
cache). The fix is the standard Pallas move: stream the int8 blocks
through VMEM and dequantize in registers, so HBM traffic really is the
int8 bytes plus scales.

Layout lesson (both dead ends measured on the chip; earlier
installation, not repeated on this one):
a head-major kernel layout needs a transpose of the whole cache —
XLA materializes it per layer per step and the win drowns (0.82x);
slicing one head's D-chunk per grid row from the native layout makes
every DMA a strided 128-lane gather (0.53x). The kernel therefore
reads the cache EXACTLY as it is laid out — contiguous
``(bk, Hkv*D)`` blocks of the native ``(B, L, Hkv, D)`` cache — and
handles the GQA grouping *inside* the kernel with a static loop over
kv heads (static row/lane slices, one MXU dot per head group):

* grid ``(B, k_blocks)``, k innermost-sequential — batch rows are
  independent ("parallel"), and within a row Mosaic double-buffers the
  sequential k-blocks: block j+1's int8 K/V DMA overlaps block j's
  dots, so the stream never stalls on HBM;
* the q heads ride the sublane axis, each GQA group zero-padded to
  whole 8-row sublane tiles (``_group_tile``: a group of 12 takes 16
  rows, 8 takes 8, 3 takes 8; ``(Hkv * tile, D)`` total), so any group
  is served; padding rows compute garbage that is sliced off at the
  end, never normalized;
* per-(position, head) f32 scales arrive in their native
  ``(B, L, Hkv)`` layout too (whole-trailing-dim blocks are
  tile-legal) — NOTHING is transposed or copied outside the kernel;
* positions are PER ROW: ``pos`` may be a scalar (every row at the
  same step — the ``generate_*`` scan) or a ``(B,)`` vector (every
  serving slot at its own global position — the continuous-batching
  scheduler). Either way it rides SMEM and one compiled kernel serves
  every decode step; blocks entirely outside a row's visible range are
  predicated off grid-level.
* two cache layouts share the kernel: the POSITIONAL cache (slot s
  holds position s; validity ``kpos <= pos`` plus the sliding band
  when ``window`` is set) and the O(W) RING cache (``ring=True``:
  slot s holds ``kpos(s) = pos - ((pos - s) mod W)``, valid iff
  ``kpos >= 0`` — which reduces to ``s <= pos or pos >= W``, the same
  one-predicate mask models/decode.py's ring reads use).
* the ring cache additionally supports a PAGED layout
  (``page_table=``): K/V live in a pool of ``(page_tokens,
  Hkv*D)``-row pages shared by every serving slot, and each row's
  ``(max_pages,)`` int32 page-index vector rides scalar-prefetch SMEM
  and the pools stay in HBM: the grid is ``(B,)`` and the kernel
  itself dereferences the table, copying in a row's LIVE pages (those
  up to its position; all of them once the ring has wrapped) ``n`` to
  a block (``_pages_per_step``: the largest of 8, 4, 2, 1 that divides
  ``max_pages`` and fits the VMEM budget), double-buffered, the pages
  joined in VMEM into one ``n * page_tokens``-row k-block. Measured on
  the v5e (PERF.md, PR 27): handing every table entry to a block spec
  costs 0.07 us an entry and operand whether the page is live or not
  (0.29 ms a call at 16 rows x 64 entries, however many entries share
  a grid step), and a short request leaves most of its table
  unfilled; visiting live pages only is what makes a 64-entry table
  cheap. The math is otherwise the identical ring-mode
  online softmax (``W = max_pages * page_tokens``), so the paged
  serving tick and the dense gather fallback (models/serving.py
  ``_paged_gather`` + the einsum rows) stay numerically
  interchangeable.

* a latent-attention layer's pages take the paged walk in a form of
  their own (``latent_decode_attention``, below its siblings): the
  pool's ONE int8 row a position is key and value both, under the
  absorbed query's heads as one q tile.

Inference-only: no VJP (the cache is never differentiated through).
Interpret mode on non-TPU backends keeps the path testable on the CI
mesh, same as the flash kernels.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import _sds, _use_interpret

_NEG = -1e30
_LANE = 128
_SUB = 8  # TPU sublane tile: each GQA group pads to whole tiles of it

__all__ = ["quantized_decode_attention", "paged_block_viable",
           "paged_scale_lanes", "paged_select_attention",
           "latent_decode_attention", "latent_pages_per_step",
           "paged_row_lanes"]


# Scoped-VMEM budget per (block row x kv head), CALIBRATED on the
# bench chip: Mosaic's stack allocation for this kernel measured
# ~1435 B/(row*head) at D=128 (bk=5632, Hkv=2 hit 16.16 MiB against
# the 16 MiB scoped limit) — double-buffered int8 K/V plus the f32
# score/probability intermediates and allocator slack. That reading
# was taken at an 8-row group tile; the three (tile, bk) f32
# intermediates (scores, probabilities, scaled probabilities) grow
# with the tile, 12 bytes a (row, head) for each row beyond 8.
_VMEM_PER_ROW_HEAD = 11.3  # bytes per (row, head, D/128 lane group)
_VMEM_PER_TILE_ROW = 12  # f32 s, p, pv: bytes per (row, head, tile row)
_VMEM_CAP = 12 * 2 ** 20
# default k-block budget; the models/decode.py routing gate imports
# THIS constant so the two call sites cannot drift
DEFAULT_BLOCK_K = 8192


def _group_tile(g: int) -> int:
    """Rows of the q tile one K/V head's GQA group takes: the group
    rounded up to whole 8-row sublane tiles (12 -> 16, 8 -> 8, 3 -> 8)."""
    return -(-int(g) // _SUB) * _SUB


def _row_head_bytes(D: int, G: int) -> float:
    """Calibrated scoped-VMEM bytes per (k-block row, K/V head) at head
    size ``D`` and group tile ``G``."""
    return D * _VMEM_PER_ROW_HEAD + (G - _SUB) * _VMEM_PER_TILE_ROW


def paged_block_viable(page_tokens: int) -> bool:
    """Could the kernel stream ``page_tokens``-row k-blocks? Pages ride
    the sublane axis of the ``(page_tokens, Hkv*D)`` copy, so a
    compiled TPU kernel needs the int8 sublane tile (32 rows); the
    interpreter has no tiling and accepts any 8-row multiple (the CI
    parity surface — PAGE_TOKENS=16 tests run interpreted). The
    routing gates in models/serving.py consult THIS predicate so the
    call sites cannot drift from the kernel's real constraint."""
    P = int(page_tokens)
    if P < 8 or P % 8 != 0:
        return False
    return _use_interpret() or P % 32 == 0


def paged_scale_lanes(page_tokens: int) -> int:
    """Minor axis of a page pool's scale leaves ``(n_pages, Hkv,
    lanes)``: a page's positions rounded up to whole 128-lane rows.
    At that width the device stores the leaf row-major, as the kernel's
    ``(1, Hkv, lanes)`` blocks read it; a narrower minor axis it stores
    transposed, and every program re-lays the leaf out on entry."""
    return -(-int(page_tokens) // _LANE) * _LANE


def paged_row_lanes(width: int) -> int:
    """Minor axis of a latent layer's page pool ``(n_pages, P, lanes)``:
    its row of ``width`` values rounded up to whole 128-lane tiles (576
    -> 640; the padding is zeros that nothing reads). That is what the
    device stores either way, and a copy
    out of HBM moves whole tiles of the minor axis alone: Mosaic refuses
    the page's 576 lanes of 640 ("slice shape must be aligned to
    tiling"), and takes the row it is declared whole."""
    return -(-int(width) // _LANE) * _LANE


def _pages_per_step(max_pages: int, P: int, Hkv: int, D: int,
                    G: int) -> int | None:
    """Pages to a block of the paged form's loop: the largest of 8, 4,
    2, 1 that divides ``max_pages`` and whose joined ``n * P``-row
    k-block fits the calibrated VMEM budget at this head count and
    group tile (64 -> 8, 32 -> 8, 68 -> 4). None: not even one page
    fits — the caller keeps the gather route. On the v5e, 16 rows of
    100 to 800 positions: 0.068 ms a call at 1, 0.037 at 8; rings that
    have wrapped, 0.50 against 0.16 (PERF.md, PR 27)."""
    for n in (8, 4, 2, 1):
        if (max_pages % n == 0
                and n * P * Hkv * _row_head_bytes(D, G) <= _VMEM_CAP):
            return n
    return None


def _paged_kernel(pos_ref, pt_ref, q_ref, k_hbm, ks_hbm, v_hbm, vs_hbm,
                  o_ref, kbuf, ksbuf, vbuf, vsbuf, sem, acc, m_sc, l_sc,
                  *, n, P, max_pages, scale, Hkv, D, G):
    """One row of the batch a grid step: the pools stay in HBM and the
    kernel copies in only the row's LIVE pages (those up to its
    position; every page once the ring has wrapped), ``n`` a block,
    the next block's copies in flight while this one's dots run
    (:func:`_walk_live_pages`). A table entry that is never visited
    costs nothing, which is what makes a 64-entry table cheap for a
    request that fills five.

    ``kbuf``/``vbuf``: ``(2, n, P, Hkv*D)`` int8, ``ksbuf``/``vsbuf``:
    ``(2, n, Hkv, lanes)`` float32, two buffers each; ``sem``: one DMA
    semaphore a buffer. A page's scales arrive as ``(Hkv, lanes)``,
    its P positions on the first lanes of head h's row: a slice is the
    row the scores want."""

    def update(buf, mask):
        def rows(sbuf):  # head h's (1, n * P) row of a block's scales
            pages = [sbuf[buf, i] for i in range(n)]  # n x (Hkv, lanes)
            return lambda h: _join([x[h:h + 1, :P] for x in pages], 1)

        _update(
            q_ref,
            (_join([kbuf[buf, i] for i in range(n)], 0),
             _join([vbuf[buf, i] for i in range(n)], 0),
             rows(ksbuf), rows(vsbuf)),
            mask, acc, m_sc, l_sc, scale=scale, Hkv=Hkv, D=D, G=G,
        )

    _walk_live_pages(
        pos_ref, pt_ref, ((k_hbm, kbuf), (ks_hbm, ksbuf), (v_hbm, vbuf),
                          (vs_hbm, vsbuf)), sem, update, o_ref, acc, m_sc,
        l_sc, n=n, P=P, max_pages=max_pages)


def _join(parts, axis):
    """A block's pages side by side (one page: itself)."""
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=axis)


def _walk_live_pages(pos_ref, pt_ref, pools, sem, update, o_ref, acc, m_sc,
                     l_sc, *, n, P, max_pages, wraps=True):
    """The page walk of one row of the batch (grid step ``b``), shared
    by the paged forms: zero the softmax's carry, copy in the row's
    live pages ``n`` a block out of every ``(pool in HBM, (2, n, ...)
    buffer)`` of ``pools``, double-buffered, call ``update(buf, mask)``
    on each block as it lands (``mask`` ``(1, n * P)``: the block's
    ring-valid positions), and write the normalised result. ``wraps``
    False: the ring is as wide as the context budget and no position
    reaches its end, so a block's rows are valid up to the position
    alone, and ``n`` need not divide the table (the last block repeats
    the last live page behind it, masked)."""
    b = pl.program_id(0)
    pos = pos_ref[b]
    live = jnp.minimum(pos // P + 1, max_pages)  # pages with a live row
    bk = n * P

    def copies(blk, buf):
        # the block's n pages; past the row's last live page repeat it
        # (masked rows either way), so every block is n whole copies
        out = []
        for i in range(n):
            page = pt_ref[b, jnp.minimum(blk * n + i, live - 1)]
            for pool, dst in pools:
                out.append(pltpu.make_async_copy(
                    pool.at[page], dst.at[buf, i], sem.at[buf]))
        return out

    acc[:] = jnp.zeros_like(acc)
    m_sc[:] = jnp.full_like(m_sc, _NEG)
    l_sc[:] = jnp.zeros_like(l_sc)
    for c in copies(0, 0):
        c.start()

    def block(blk, carry):
        buf = blk % 2

        @pl.when((blk + 1) * n < live)
        def _prefetch():
            for c in copies(blk + 1, 1 - buf):
                c.start()

        for c in copies(blk, buf):
            c.wait()
        kpos = blk * bk + jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)
        # ring validity: slot s <= pos, or the ring has wrapped
        update(buf, jnp.logical_or(kpos <= pos, pos >= max_pages * P)
               if wraps else kpos <= pos)
        return carry

    jax.lax.fori_loop(0, (live + n - 1) // n, block, 0)
    l = jnp.maximum(l_sc[:, :1], 1e-20)
    o_ref[0] = (acc[:] / l).astype(o_ref.dtype)


def _pick_block_128(L: int, block: int, Hkv: int = 2,
                    D: int = 128, G: int = _SUB) -> int | None:
    """Largest lane-aligned block (multiple of 128) <= ``block``
    dividing L whose calibrated working set (at group tile ``G``) fits
    scoped VMEM. Lengths with no such divisor fall back to the whole
    dimension in one block (block == dim is always tile-legal) when IT
    fits; otherwise None — the caller keeps the einsum path."""
    cap = int(_VMEM_CAP / (Hkv * _row_head_bytes(D, G)))
    b = min(block, L, max(cap, 128))
    b -= b % 128
    while b >= 128:
        if L % b == 0:
            return b
        b -= 128
    if L <= max(cap, 128):  # whole-dim fallback
        return L
    return None


def _kernel(pos_ref, q_ref, k_ref, ks_ref, v_ref, vs_ref, o_ref,
            acc, m_sc, l_sc, **kw):
    def load():
        ksb = ks_ref[0].astype(jnp.float32)  # (bk, Hkv)
        vsb = vs_ref[0].astype(jnp.float32)
        return (k_ref[0], v_ref[0],
                lambda h: ksb[:, h][None, :], lambda h: vsb[:, h][None, :])

    _attend(pos_ref, q_ref, load, o_ref, acc, m_sc, l_sc, **kw)


def _attend(pos_ref, q_ref, load, o_ref, acc, m_sc, l_sc, *, scale,
            window, bk, nk, Hkv, D, G, ring):
    """Grid step ``(b, j)`` of the dense forms: k-block j of row b.
    ``load()``, called only when the step runs, reads the block
    ``_update`` takes."""
    b = pl.program_id(0)
    j = pl.program_id(1)
    pos = pos_ref[b]  # this row's global decode position

    @pl.when(j == 0)
    def _init():
        acc[:] = jnp.zeros_like(acc)
        m_sc[:] = jnp.full_like(m_sc, _NEG)
        l_sc[:] = jnp.zeros_like(l_sc)

    # any slot of this block visible? Positional: the causal frontier
    # (plus the band's lower edge). Ring: slots [0, min(pos, W-1)] are
    # valid, so the same frontier predicate covers warmup, and once
    # pos >= W every block runs (j*bk <= W - bk < W <= pos).
    run = j * bk <= pos
    if window is not None and not ring:
        run = jnp.logical_and(run, pos - (j * bk + bk - 1) < window)

    @pl.when(run)
    def _run():
        kpos = j * bk + jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)
        if ring:
            # slot s holds position pos - ((pos - s) mod W); kpos >= 0
            # iff s <= pos or pos >= W (W == bk * nk, the whole cache)
            mask = jnp.logical_or(kpos <= pos, pos >= bk * nk)
        else:
            mask = kpos <= pos
            if window is not None:
                mask = jnp.logical_and(mask, pos - kpos < window)
        _update(q_ref, load(), mask, acc, m_sc, l_sc, scale=scale,
                Hkv=Hkv, D=D, G=G)

    @pl.when(j == nk - 1)
    def _finish():
        l = jnp.maximum(l_sc[:, :1], 1e-20)
        o_ref[0] = (acc[:] / l).astype(o_ref.dtype)


def _update(q_ref, block, mask, acc, m_sc, l_sc, *, scale, Hkv, D, G):
    """One k-block's online-softmax update of ``acc``/``m_sc``/``l_sc``.
    ``block``: int8 K and V ``(bk, Hkv*D)`` as the cache lays them out,
    and for each of the two a function from a K/V head to its ``(1,
    bk)`` row of float32 scales. ``mask``: ``(1, bk)``, the block's
    valid positions. ``G`` is the q tile's rows per K/V head
    (``_group_tile``)."""
    kblk, vblk, k_scale, v_scale = block
    # static loop over kv heads: static row/lane slices, one MXU dot
    # per GQA group — the grouping costs index math, not DMA
    for h in range(Hkv):
        _update_head(q_ref, h, kblk, vblk, slice(h * D, (h + 1) * D),
                     k_scale(h), v_scale(h), mask, acc, m_sc, l_sc,
                     scale=scale, G=G)


def _update_head(q_ref, h, kblk, vblk, lanes, k_scale, v_scale, mask, acc,
                 m_sc, l_sc, *, scale, G):
    """:func:`_update` for K/V head ``h`` alone: its keys and values
    are the ``lanes`` of ``kblk`` / ``vblk`` (int8, ``bk`` rows), their
    scales the ``(1, bk)`` rows ``k_scale`` / ``v_scale``, its queries
    the head's rows of the q tile."""
    rows = slice(h * G, (h + 1) * G)
    q = q_ref[0][rows]  # (G, D): g live rows + padding
    kb = kblk[:, lanes].astype(q.dtype)
    s = jax.lax.dot_general(
        q, kb, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) * scale  # (G, bk)
    s = s * k_scale
    _softmax_update(s, rows, vblk, lanes, v_scale, mask, acc, m_sc, l_sc)


def _softmax_update(s, rows, vblk, lanes, v_scale, mask, acc, m_sc, l_sc):
    """The online softmax's step for the q tile's ``rows``: their
    scores ``s`` ``(G, bk)`` of a k-block, masked here, against the
    block's values, the ``lanes`` of ``vblk`` (int8) with their ``(1,
    bk)`` row of scales."""
    G = s.shape[0]
    s = jnp.where(mask, s, _NEG)
    m_prev = m_sc[rows, :1]
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    p = jnp.where(mask, p, 0.0)
    corr = jnp.exp(m_prev - m_new)
    l_sc[rows] = jnp.broadcast_to(
        l_sc[rows, :1] * corr + p.sum(axis=-1, keepdims=True),
        (G, _LANE),
    )
    vb = vblk[:, lanes].astype(jnp.float32)
    pv = p * v_scale
    acc[rows] = acc[rows] * corr + jax.lax.dot_general(
        pv, vb, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    m_sc[rows] = jnp.broadcast_to(m_new, (G, _LANE))


def _tile_q(q, Hkv: int):
    """(B, 1, H, D) -> ``(B, Hkv * G, D)`` and ``G``: each K/V head's g
    q rows padded to the group tile (tiny — no cache-sized copies)."""
    B, T, H, D = q.shape
    if T != 1:
        raise ValueError(f"decode kernel is single-query, got T={T}")
    g = H // Hkv
    G = _group_tile(g)
    q3 = q.reshape(B, Hkv, g, D)
    if g < G:
        q3 = jnp.pad(q3, ((0, 0), (0, 0), (0, G - g), (0, 0)))
    return q3.reshape(B, Hkv * G, D), G


def _untile_o(o3, q, Hkv: int, G: int):
    """Drop each group's padding rows: (B, Hkv*G, D) -> (B, 1, H, D)."""
    B, _, H, D = q.shape
    return o3.reshape(B, Hkv, G, D)[:, :, :H // Hkv].reshape(B, 1, H, D)


def _row(b, *prefetched):
    """Index map of the paged forms' q and result blocks: grid step
    ``b``'s own row, whatever the scalar-prefetched tables say."""
    del prefetched
    return (b, 0, 0)


def _scratch(rows: int, D: int) -> list:
    return [
        pltpu.VMEM((rows, D), jnp.float32),
        pltpu.VMEM((rows, _LANE), jnp.float32),
        pltpu.VMEM((rows, _LANE), jnp.float32),
    ]


def quantized_decode_attention(
    q, cache_l: dict, pos, scale, window=None, *, ring: bool = False,
    block_k: int = DEFAULT_BLOCK_K, interpret: bool | None = None,
    page_table=None, page_tokens: int | None = None,
):
    """Single-query grouped attention against an int8 cache layer.

    q: (B, 1, H, D); ``cache_l``: {"k","v"} int8 (B, L, Hkv, D) +
    {"k_s","v_s"} f32 (B, L, Hkv); ``pos``: scalar current position,
    or a ``(B,)`` vector of PER-ROW positions (the serving scheduler's
    slots each decode at their own step). Returns (B, 1, H, D) in q's
    dtype — numerically the online-softmax evaluation of the same
    masked attention ``models/decode.py::_cached_attention`` computes
    in einsum form (pinned by tests/test_decode_attention.py).

    ``ring=True`` reads the O(W) ring layout instead (L == W; slot s
    holds ``kpos(s) = pos - ((pos - s) mod W)``): validity is the one
    ``kpos >= 0`` predicate of ``_ring_cached_attention`` /
    ``_ring_attention_rows``, so the batched serving tick and the ring
    generate scan route the exact same kernel. ``window`` must be None
    in ring mode — the ring IS the window.

    ``page_table=`` (ring mode only) reads the PAGED ring layout:
    ``cache_l`` leaves are page pools in the layout the kernel copies
    pages in, so nothing is re-laid out on the way — {"k","v"} int8
    ``(n_pages, page_tokens, Hkv * D)`` + scales ``(n_pages, Hkv,
    paged_scale_lanes(page_tokens))``, shared by all rows — and
    ``page_table`` is the ``(B, max_pages)`` int32 table mapping row
    b's ring page j to its pool page. The table rides scalar-prefetch
    SMEM and the kernel dereferences it, copying in exactly the pages a
    row has filled — the HBM traffic of a decode step is the live rows,
    never the pool (see module docstring). ``W = max_pages *
    page_tokens`` and the validity mask is ring mode's unchanged.
    """
    if interpret is None:
        interpret = _use_interpret()
    if ring and window is not None:
        raise ValueError(
            "ring mode encodes the window in the cache layout; pass "
            "window=None (the ring length IS the window)"
        )
    if page_table is not None:
        if not ring:
            raise ValueError("page_table is a ring-layout feature; "
                             "pass ring=True")
        if page_tokens is None:
            raise ValueError("page_table needs page_tokens")
        return _paged_call(q, cache_l, pos, scale, page_table,
                           int(page_tokens), interpret)
    B, _, _, D = q.shape
    kc, vc = cache_l["k"], cache_l["v"]
    ks, vs = cache_l["k_s"], cache_l["v_s"]
    L, Hkv = kc.shape[1], kc.shape[2]
    q3, G = _tile_q(q, Hkv)
    bk = _pick_block_128(L, block_k, Hkv, D, G)
    if bk is None:
        raise ValueError(
            f"cache length {L} has no multiple-of-128 divisor <= "
            f"{block_k} and is too long for a whole-dimension block; "
            "size the cache (prompt + n_new) to a multiple of 128, or "
            "use the einsum path"
        )
    nk = L // bk
    rows = Hkv * G
    kf = kc.reshape(B, L, Hkv * D)  # free: (Hkv, D) tail is contiguous
    vf = vc.reshape(B, L, Hkv * D)
    # scalar pos broadcasts to every row; a (B,) vector rides as-is
    posv = jnp.broadcast_to(
        jnp.asarray(pos, jnp.int32).reshape(-1), (B,)
    )

    kern = functools.partial(
        _kernel, scale=scale, window=window, bk=bk, nk=nk, Hkv=Hkv,
        D=D, G=G, ring=ring,
    )
    o3 = pl.pallas_call(
        kern,
        grid=(B, nk),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, rows, D), lambda b, j: (b, 0, 0)),
            pl.BlockSpec((1, bk, Hkv * D), lambda b, j: (b, j, 0)),
            # whole-trailing-dim blocks are tile-legal at any Hkv
            pl.BlockSpec((1, bk, Hkv), lambda b, j: (b, j, 0)),
            pl.BlockSpec((1, bk, Hkv * D), lambda b, j: (b, j, 0)),
            pl.BlockSpec((1, bk, Hkv), lambda b, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, rows, D), lambda b, j: (b, 0, 0)),
        out_shape=_sds((B, rows, D), q.dtype, q),
        scratch_shapes=_scratch(rows, D),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
        interpret=interpret,
    )(posv, q3, kf, ks, vf, vs)
    return _untile_o(o3, q, Hkv, G)


def _paged_call(q, cache_l: dict, pos, scale, page_table, P: int,
                interpret: bool):
    """The paged form: the pools checked against the layout the kernel
    reads, the pages a block worked out from the shapes
    (``_pages_per_step``), then the jitted call
    (``paged_decode_attention``, the name a device trace shows)."""
    D = q.shape[-1]
    kc, ks = cache_l["k"], cache_l["k_s"]
    Hkv, lanes = ks.shape[1], paged_scale_lanes(P)
    if kc.shape[1:] != (P, Hkv * D) or ks.shape[2] != lanes:
        raise ValueError(
            f"page pool leaves {kc.shape} / {ks.shape} are not "
            f"(pages, {P}, {Hkv}*{D}) / (pages, {Hkv}, {lanes})"
        )
    n = _pages_per_step(page_table.shape[1], P, Hkv, D,
                        _group_tile(q.shape[2] // Hkv))
    if n is None:
        raise ValueError(
            f"a page of {P} rows x {Hkv} heads of {D} does not fit the "
            "kernel's VMEM budget; use the gather route"
        )
    return paged_decode_attention(q, cache_l, pos, page_table,
                                  scale=scale, P=P, n=n,
                                  interpret=interpret)


@functools.partial(
    jax.jit, static_argnames=("scale", "P", "n", "interpret"))
def paged_decode_attention(q, cache_l: dict, pos, page_table, *, scale,
                           P: int, n: int, interpret: bool):
    """Paged-ring pallas_call: grid ``(B,)``, the pools left in HBM and
    the scalar-prefetched page table dereferenced inside the kernel,
    which copies in a row's live pages ``n`` at a time and nothing else
    (``_paged_kernel``).

    Jitted so that the layers of a tick, which call it at one set of
    shapes, share ONE traced and lowered kernel: traced per call, 30
    layers' kernels were 5 of the 6 seconds a StarCoder2 tick took to
    lower, in every process, before the compile cache is even asked."""
    B, _, _, D = q.shape
    kc, vc = cache_l["k"], cache_l["v"]
    ks, vs = cache_l["k_s"], cache_l["v_s"]
    Hkv, lanes = ks.shape[1], ks.shape[2]
    max_pages = page_table.shape[1]
    q3, G = _tile_q(q, Hkv)
    rows = Hkv * G
    posv = jnp.broadcast_to(
        jnp.asarray(pos, jnp.int32).reshape(-1), (B,)
    )
    ptv = page_table.astype(jnp.int32)

    kern = functools.partial(
        _paged_kernel, n=n, P=P, max_pages=max_pages, scale=scale,
        Hkv=Hkv, D=D, G=G,
    )

    hbm = pl.BlockSpec(memory_space=pl.ANY)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B,),
        in_specs=[pl.BlockSpec((1, rows, D), _row), hbm, hbm, hbm, hbm],
        out_specs=pl.BlockSpec((1, rows, D), _row),
        scratch_shapes=[
            pltpu.VMEM((2, n, P, Hkv * D), kc.dtype),
            pltpu.VMEM((2, n, Hkv, lanes), ks.dtype),
            pltpu.VMEM((2, n, P, Hkv * D), vc.dtype),
            pltpu.VMEM((2, n, Hkv, lanes), vs.dtype),
            pltpu.SemaphoreType.DMA((2,)),
        ] + _scratch(rows, D),
    )
    o3 = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=_sds((B, rows, D), q.dtype, q),
        # a row starts and waits all of its own copies: rows are
        # independent grid steps
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)
        ),
        interpret=interpret,
    )(posv, ptv, q3, *(_in_hbm(a, interpret) for a in (kc, ks, vc, vs)))
    return _untile_o(o3, q, Hkv, G)


def _in_hbm(pool, interpret: bool):
    """A page pool as the kernel's operand, declared to live in HBM.
    The kernel copies in the live pages itself; left to choose
    (``pl.ANY`` says nothing to it), the TPU compiler carries a pool
    that fits its fast memory space through that space every step of
    the tick's scan: the whole pool in before the step's row is
    scattered into it, the whole pool out again for the scan's carry
    (Qwen3-Next's two 35.7 MB pools: 143 MB a step to write 16 rows of
    512 bytes; Trinity-Mini's tick 225 MB a step; PERF.md section 6,
    PR 43). With the operand pinned the scatter updates the carry
    where it lies. Nothing to pin in the interpreter."""
    return pool if interpret else pltpu.with_memory_space_constraint(
        pool, pltpu.HBM)


# A latent-attention layer's pages (models/decode.py ``_latent_leaves``):
# ONE int8 row a position for all heads, ``[latent R | rotated key]``,
# with a float32 scale for each of the two parts and no ``v``. The
# kernel below is the paged form with that row as key AND value: one
# K/V "head", the q tile the absorbed query's H rows, the scores the
# sum of the two parts' products each under its own scale, the values
# the row's first R lanes. R is whole lane tiles and so is the pool's
# row (``paged_row_lanes``: zeros behind the rotated key, never read),
# so a page copies whole and the row splits at a tile's edge of a block
# already in VMEM: nothing is sliced in HBM, and the query comes as it
# is. Walk, copies and softmax are the paged form's own.


def _paged_latent_kernel(pos_ref, pt_ref, q_ref, k_hbm, ks_hbm, o_ref, kbuf,
                         ksbuf, sem, acc, m_sc, l_sc, *, n, P, max_pages,
                         scale, R):
    """One row of the batch a grid step. ``kbuf``: ``(2, n, P, lanes)``
    int8; ``ksbuf``: ``(2, n, 2, scale lanes)`` float32, a page's
    latent scales on row 0 and its rotated key's on row 1."""

    def update(buf, mask):
        kblk = _join([kbuf[buf, i] for i in range(n)], 0)
        pages = [ksbuf[buf, i] for i in range(n)]  # n x (2, lanes)
        latent_s, rope_s = (  # each part's (1, n * P) row of scales
            _join([x[j:j + 1, :P] for x in pages], 1) for j in range(2))
        q = q_ref[0]  # (H, R + rope)

        def part(lanes, part_s):  # one part's scores under its scale
            return jax.lax.dot_general(
                q[:, lanes], kblk[:, lanes].astype(q.dtype),
                (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * scale * part_s

        s = (part(slice(None, R), latent_s)
             + part(slice(R, q.shape[1]), rope_s))
        _softmax_update(s, slice(None), kblk, slice(None, R), latent_s,
                        mask, acc, m_sc, l_sc)

    _walk_live_pages(
        pos_ref, pt_ref, ((k_hbm, kbuf), (ks_hbm, ksbuf)), sem, update,
        o_ref, acc, m_sc, l_sc, n=n, P=P, max_pages=max_pages, wraps=False)


def latent_pages_per_step(max_pages: int, P: int, width: int,
                          H: int) -> int | None:
    """Pages to a block of the latent form's loop: the largest of 8, 4,
    2, 1 whose joined k-block fits the calibrated VMEM budget under a q
    tile of ``H`` rows (the row of ``width`` values is key and value
    both, so the K/V budget a lane bounds it from above) and that a
    table of ``max_pages`` entries can fill at least half. It need not
    divide the table (68 -> 8, 13 -> 8, 3 -> 4): a latent layer's ring
    never wraps. None: not even one page fits, and the layer keeps the
    gather route."""
    lanes, G = paged_row_lanes(width), _group_tile(H)
    for n in (8, 4, 2, 1):
        if (n < 2 * max_pages
                and n * P * _row_head_bytes(lanes, G) <= _VMEM_CAP):
            return n
    return None


def latent_decode_attention(q, cache_l: dict, pos, page_table, *, scale,
                            P: int, R: int, interpret: bool | None = None):
    """Single-query attention of the absorbed query over a latent
    layer's page pool: q ``(B, 1, H, R + rope)``; ``cache_l`` {"k"}
    int8 ``(n_pages, P, paged_row_lanes(R + rope))`` + {"k_s"} float32
    ``(n_pages, 2, paged_scale_lanes(P))``, shared by all rows; ``pos``
    ``(B,)`` each row's position; ``page_table`` ``(B, max_pages)``
    int32. Returns ``(B, 1, H, R)`` in q's dtype: the online-softmax
    evaluation of ``_ring_attention_rows(latent=R)`` on the rows'
    gathered rings (models/serving.py), reading the pages a row has
    filled where they lie. Every position lies inside the table's
    ``max_pages * P`` rows (a latent layer's ring is as wide as the
    context budget and never wraps). Two rows of the batch may name one
    table row at two positions (a drafting step's two queries a slot).

    The pool checked against the layout the kernel reads and the pages
    a block worked out from the shapes, then the jitted call
    (:func:`paged_latent_attention`, the name a device trace shows)."""
    if interpret is None:
        interpret = _use_interpret()
    T, H, width = q.shape[1:]
    kc, ks = cache_l["k"], cache_l["k_s"]
    lanes = paged_row_lanes(width)
    if T != 1:
        raise ValueError(f"decode kernel is single-query, got T={T}")
    if (kc.shape[1:] != (P, lanes) or not 0 < R < width or R % _LANE
            or ks.shape[1:] != (2, paged_scale_lanes(P))):
        raise ValueError(
            f"latent page pool leaves {kc.shape} / {ks.shape} are not "
            f"(pages, {P}, {lanes}) / (pages, 2, {paged_scale_lanes(P)}) "
            f"with the latent's {R} of the row's {width} whole lane tiles"
        )
    n = latent_pages_per_step(page_table.shape[1], P, width, H)
    if n is None:
        raise ValueError(
            f"a page of {P} rows of {lanes} under {H} query rows does "
            "not fit the kernel's VMEM budget; use the gather route")
    return paged_latent_attention(q, cache_l, pos, page_table, scale=scale,
                                  P=P, R=R, n=n, interpret=interpret)


@functools.partial(
    jax.jit, static_argnames=("scale", "P", "R", "n", "interpret"))
def paged_latent_attention(q, cache_l: dict, pos, page_table, *, scale,
                           P: int, R: int, n: int, interpret: bool):
    """The latent form's pallas_call (:func:`latent_decode_attention`
    checks and sizes it): grid ``(B,)``, the pool left in HBM, ``n``
    pages a block. Jitted for the reason :func:`paged_decode_attention`
    is."""
    B, _, H, width = q.shape
    kc, ks = cache_l["k"], cache_l["k_s"]
    lanes, G = kc.shape[2], _group_tile(H)
    q3 = q[:, 0]
    if H < G:  # rows to the sublane tile (tiny: the query)
        q3 = jnp.pad(q3, ((0, 0), (0, G - H), (0, 0)))
    kern = functools.partial(
        _paged_latent_kernel, n=n, P=P, max_pages=page_table.shape[1],
        scale=scale, R=R)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B,),
        in_specs=[pl.BlockSpec((1, G, width), _row), hbm, hbm],
        out_specs=pl.BlockSpec((1, G, R), _row),
        scratch_shapes=[
            pltpu.VMEM((2, n, P, lanes), kc.dtype),
            pltpu.VMEM((2, n, 2, ks.shape[2]), ks.dtype),
            pltpu.SemaphoreType.DMA((2,)),
        ] + _scratch(G, R),
    )
    o3 = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=_sds((B, G, R), q.dtype, q),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)
        ),
        interpret=interpret,
    )(jnp.asarray(pos, jnp.int32).reshape(B), page_table.astype(jnp.int32),
      q3, _in_hbm(kc, interpret), _in_hbm(ks, interpret))
    return o3[:, None, :H]


# A selection of key blocks (models/transformer.py ``sparse_pick``): each
# K/V head of each row attends its OWN short list of pages. The kernel
# below is the paged form with the list a head in place of the table a
# row: a static loop over the K/V heads, each walking its list and
# copying in its own 128 lanes of a page alone (a page's tiles are
# whole lane groups, so a head's half of a page is two contiguous
# tiles, not a strided gather), the same online softmax a head.


def _paged_select_kernel(at_ref, pt_ref, q_ref, k_hbm, ks_hbm, v_hbm, vs_hbm,
                         o_ref, kbuf, ksbuf, vbuf, vsbuf, sem, acc, m_sc,
                         l_sc, *, n, P, width, scale, Hkv, D, G):
    """One row of the batch a grid step. ``pt_ref[b, h * width + j]``:
    the pool page of the j-th block that stands for head h, in position
    order; ``at_ref[b, h]``: the query's position among those rows, so
    the rows ``<= at`` are live and ``at // P + 1`` entries are walked.
    ``kbuf``/``vbuf``: ``(2, n, P, D)`` int8, one head's lanes."""
    b = pl.program_id(0)
    bk = n * P
    acc[:] = jnp.zeros_like(acc)
    m_sc[:] = jnp.full_like(m_sc, _NEG)
    l_sc[:] = jnp.zeros_like(l_sc)

    for h in range(Hkv):
        at = at_ref[b, h]
        live = jnp.minimum(at // P + 1, width)
        lanes = pl.ds(h * D, D)

        def copies(blk, buf, h=h, live=live, lanes=lanes):
            out = []
            for i in range(n):
                page = pt_ref[b, h * width + jnp.minimum(blk * n + i,
                                                         live - 1)]
                for src, dst in (
                        (k_hbm.at[page, :, lanes], kbuf),
                        (ks_hbm.at[page], ksbuf),
                        (v_hbm.at[page, :, lanes], vbuf),
                        (vs_hbm.at[page], vsbuf)):
                    out.append(pltpu.make_async_copy(
                        src, dst.at[buf, i], sem.at[buf]))
            return out

        for c in copies(0, 0):
            c.start()

        def block(blk, carry, h=h, at=at, live=live, copies=copies):
            buf = blk % 2

            @pl.when((blk + 1) * n < live)
            def _prefetch():
                for c in copies(blk + 1, 1 - buf):
                    c.start()

            for c in copies(blk, buf):
                c.wait()
            kpos = blk * bk + jax.lax.broadcasted_iota(
                jnp.int32, (1, bk), 1)
            row = lambda sbuf: _join(
                [sbuf[buf, i][h:h + 1, :P] for i in range(n)], 1)
            _update_head(
                q_ref, h, _join([kbuf[buf, i] for i in range(n)], 0),
                _join([vbuf[buf, i] for i in range(n)], 0), slice(None),
                row(ksbuf), row(vsbuf), kpos <= at, acc, m_sc, l_sc,
                scale=scale, G=G)
            return carry

        jax.lax.fori_loop(0, (live + n - 1) // n, block, 0)
    l = jnp.maximum(l_sc[:, :1], 1e-20)
    o_ref[0] = (acc[:] / l).astype(o_ref.dtype)


def select_pages_per_step(width: int, P: int, D: int, G: int) -> int | None:
    """:func:`_pages_per_step` for a head's list of ``width`` pages."""
    return _pages_per_step(width, P, 1, D, G)


@functools.partial(
    jax.jit, static_argnames=("scale", "P", "interpret"))
def paged_select_attention(q, cache_l: dict, at, pages, *, scale, P: int,
                           interpret: bool | None = None):
    """Single-query grouped attention over a selection of pages a K/V
    head: q (B, 1, H, D); ``cache_l`` the page pools of
    ``quantized_decode_attention(page_table=...)``; ``pages`` (B, Hkv,
    width) int32, for each row and K/V head the pool pages it attends
    in position order; ``at`` (B, Hkv) int32, the query's position among
    those pages' rows (``(entries - 1) * P + its row in the last``).
    Returns (B, 1, H, D). Jitted for the reason
    :func:`paged_decode_attention` is."""
    if interpret is None:
        interpret = _use_interpret()
    B, _, _, D = q.shape
    kc, vc = cache_l["k"], cache_l["v"]
    ks, vs = cache_l["k_s"], cache_l["v_s"]
    Hkv, lanes = ks.shape[1], ks.shape[2]
    width = pages.shape[2]
    q3, G = _tile_q(q, Hkv)
    rows = Hkv * G
    n = select_pages_per_step(width, P, D, G)
    if n is None:
        raise ValueError(
            f"a page of {P} rows of {D} does not fit the kernel's VMEM "
            "budget; use the gather route")
    kern = functools.partial(
        _paged_select_kernel, n=n, P=P, width=width, scale=scale, Hkv=Hkv,
        D=D, G=G)

    hbm = pl.BlockSpec(memory_space=pl.ANY)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B,),
        in_specs=[pl.BlockSpec((1, rows, D), _row), hbm, hbm, hbm, hbm],
        out_specs=pl.BlockSpec((1, rows, D), _row),
        scratch_shapes=[
            pltpu.VMEM((2, n, P, D), kc.dtype),
            pltpu.VMEM((2, n, Hkv, lanes), ks.dtype),
            pltpu.VMEM((2, n, P, D), vc.dtype),
            pltpu.VMEM((2, n, Hkv, lanes), vs.dtype),
            pltpu.SemaphoreType.DMA((2,)),
        ] + _scratch(rows, D),
    )
    o3 = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=_sds((B, rows, D), q.dtype, q),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)
        ),
        interpret=interpret,
    )(at.astype(jnp.int32), pages.astype(jnp.int32).reshape(B, Hkv * width),
      q3, kc, ks, vc, vs)
    return _untile_o(o3, q, Hkv, G)
