"""Pallas TPU decode attention over the int8 KV cache.

Why this kernel exists (measured, docs/PERF.md "int8 KV cache"): the
einsum-form dequantization — int8 cache ``.astype(bf16)`` feeding the
attention dots — is *expressed* as a fused rank-1 correction, but XLA
materializes the converted operand in HBM, so the int8 cache read half
the bytes and then paid them back with interest (0.70x vs the bf16
cache). The fix is the standard Pallas move: stream the int8 blocks
through VMEM and dequantize in registers, so HBM traffic really is the
int8 bytes plus scales.

Layout lesson (both dead ends measured on the chip, docs/PERF.md):
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
  the 8-row tile (``(Hkv * 8, D)`` total); padding rows compute
  garbage that is sliced off at the end, never normalized;
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
  so the BLOCK INDEX MAP itself dereferences the page table — block
  ``(b, j)`` DMAs page ``page_table[b, j]`` straight out of the pool.
  The k-block size becomes ``page_tokens`` and the math is otherwise
  the identical ring-mode online softmax (``W = max_pages *
  page_tokens``), so the paged serving tick and the dense gather
  fallback (models/serving.py ``_paged_gather`` + the einsum rows)
  stay numerically interchangeable.

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
_SUB = 8  # TPU sublane tile: each GQA group pads to this many q rows

__all__ = ["quantized_decode_attention", "paged_block_viable"]


# Scoped-VMEM budget per (block row x kv head), CALIBRATED on the
# bench chip: Mosaic's stack allocation for this kernel measured
# ~1435 B/(row*head) at D=128 (bk=5632, Hkv=2 hit 16.16 MiB against
# the 16 MiB scoped limit) — double-buffered int8 K/V plus the f32
# score/probability intermediates and allocator slack.
_VMEM_PER_ROW_HEAD = 11.3  # bytes per (row, head, D/128 lane group)
_VMEM_CAP = 12 * 2 ** 20
# default k-block budget; the models/decode.py routing gate imports
# THIS constant so the two call sites cannot drift
DEFAULT_BLOCK_K = 8192


def paged_block_viable(page_tokens: int) -> bool:
    """Could the kernel stream ``page_tokens``-row k-blocks? Pages ride
    the sublane axis of the ``(1, page_tokens, Hkv*D)`` block, so a
    compiled TPU kernel needs the int8 sublane tile (32 rows); the
    interpreter has no tiling and accepts any 8-row multiple (the CI
    parity surface — PAGE_TOKENS=16 tests run interpreted). The
    routing gates in models/serving.py consult THIS predicate so the
    call sites cannot drift from the kernel's real constraint."""
    P = int(page_tokens)
    if P < 8 or P % 8 != 0:
        return False
    return _use_interpret() or P % 32 == 0


def _paged_kernel(pos_ref, pt_ref, q_ref, k_ref, ks_ref, v_ref, vs_ref,
                  o_ref, acc, m_sc, l_sc, **kw):
    """Scalar-prefetch entry: the page table is consumed ENTIRELY by
    the block index maps (it decides which page each (b, j) step DMAs);
    the online-softmax body is the ring-mode ``_kernel`` unchanged."""
    del pt_ref
    _kernel(pos_ref, q_ref, k_ref, ks_ref, v_ref, vs_ref, o_ref,
            acc, m_sc, l_sc, **kw)


def _pick_block_128(L: int, block: int, Hkv: int = 2,
                    D: int = 128) -> int | None:
    """Largest lane-aligned block (multiple of 128) <= ``block``
    dividing L whose calibrated working set fits scoped VMEM. Lengths
    with no such divisor fall back to the whole dimension in one block
    (block == dim is always tile-legal) when IT fits; otherwise None —
    the caller keeps the einsum path."""
    cap = int(_VMEM_CAP / (Hkv * D * _VMEM_PER_ROW_HEAD))
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
            acc, m_sc, l_sc, *, scale, window, bk, nk, Hkv, D, ring):
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
    def _update():
        kpos = j * bk + jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)
        if ring:
            # slot s holds position pos - ((pos - s) mod W); kpos >= 0
            # iff s <= pos or pos >= W (W == bk * nk, the whole cache)
            mask = jnp.logical_or(kpos <= pos, pos >= bk * nk)
        else:
            mask = kpos <= pos
            if window is not None:
                mask = jnp.logical_and(mask, pos - kpos < window)
        kblk = k_ref[0]  # (bk, Hkv*D) int8, one contiguous DMA
        vblk = v_ref[0]
        ksb = ks_ref[0].astype(jnp.float32)  # (bk, Hkv)
        vsb = vs_ref[0].astype(jnp.float32)
        # static loop over kv heads: static row/lane slices, one MXU
        # dot per GQA group — the grouping costs index math, not DMA
        for h in range(Hkv):
            rows = slice(h * _SUB, (h + 1) * _SUB)
            q = q_ref[0][rows]  # (SUB, D): g live rows + padding
            kb = kblk[:, h * D:(h + 1) * D].astype(q.dtype)
            s = jax.lax.dot_general(
                q, kb, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * scale  # (SUB, bk)
            s = s * ksb[:, h][None, :]
            s = jnp.where(mask, s, _NEG)
            m_prev = m_sc[rows, :1]
            m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            p = jnp.where(mask, p, 0.0)
            corr = jnp.exp(m_prev - m_new)
            l_sc[rows] = jnp.broadcast_to(
                l_sc[rows, :1] * corr + p.sum(axis=-1, keepdims=True),
                (_SUB, _LANE),
            )
            vb = vblk[:, h * D:(h + 1) * D].astype(jnp.float32)
            pv = p * vsb[:, h][None, :]
            acc[rows] = acc[rows] * corr + jax.lax.dot_general(
                pv, vb, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            m_sc[rows] = jnp.broadcast_to(m_new, (_SUB, _LANE))

    @pl.when(j == nk - 1)
    def _finish():
        l = jnp.maximum(l_sc[:, :1], 1e-20)
        o_ref[0] = (acc[:] / l).astype(o_ref.dtype)


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
    ``cache_l`` leaves are page pools — {"k","v"} int8 ``(n_pages *
    page_tokens, Hkv, D)`` + scales ``(n_pages * page_tokens, Hkv)``
    shared by all rows — and ``page_table`` is the ``(B, max_pages)``
    int32 table mapping row b's ring page j to its pool page. The
    table rides scalar-prefetch SMEM and is dereferenced by the block
    index maps, so each (b, j) grid step DMAs exactly the page the
    table names — the HBM traffic of a decode step is the W live rows,
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
    B, T, H, D = q.shape
    if T != 1:
        raise ValueError(f"decode kernel is single-query, got T={T}")
    kc, vc = cache_l["k"], cache_l["v"]
    ks, vs = cache_l["k_s"], cache_l["v_s"]
    L, Hkv = kc.shape[1], kc.shape[2]
    g = H // Hkv
    bk = _pick_block_128(L, block_k, Hkv, D)
    if bk is None:
        raise ValueError(
            f"cache length {L} has no multiple-of-128 divisor <= "
            f"{block_k} and is too long for a whole-dimension block; "
            "size the cache (prompt + n_new) to a multiple of 128, or "
            "use the einsum path"
        )
    nk = L // bk
    if g > _SUB:
        raise ValueError(
            f"GQA group {g} exceeds the kernel's {_SUB}-row group tile"
        )

    # (B, 1, H, D) -> (B, Hkv*SUB, D): each kv head's g q-rows padded
    # to the 8-row tile (tiny — no cache-sized copies anywhere here)
    q3 = q.reshape(B, Hkv, g, D)
    if g < _SUB:
        q3 = jnp.pad(q3, ((0, 0), (0, 0), (0, _SUB - g), (0, 0)))
    q3 = q3.reshape(B, Hkv * _SUB, D)
    rows = Hkv * _SUB
    kf = kc.reshape(B, L, Hkv * D)  # free: (Hkv, D) tail is contiguous
    vf = vc.reshape(B, L, Hkv * D)
    # scalar pos broadcasts to every row; a (B,) vector rides as-is
    posv = jnp.broadcast_to(
        jnp.asarray(pos, jnp.int32).reshape(-1), (B,)
    )

    kern = functools.partial(
        _kernel, scale=scale, window=window, bk=bk, nk=nk, Hkv=Hkv,
        D=D, ring=ring,
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
        scratch_shapes=[
            pltpu.VMEM((rows, D), jnp.float32),
            pltpu.VMEM((rows, _LANE), jnp.float32),
            pltpu.VMEM((rows, _LANE), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
        interpret=interpret,
    )(posv, q3, kf, ks, vf, vs)
    # (B, Hkv*SUB, D) -> drop each group's padding rows -> (B, 1, H, D)
    return o3.reshape(B, Hkv, _SUB, D)[:, :, :g].reshape(B, 1, H, D)


def _paged_call(q, cache_l: dict, pos, scale, page_table, P: int,
                interpret: bool):
    """Paged-ring pallas_call: grid (B, max_pages), k-block = one page,
    block index maps dereference the scalar-prefetched page table."""
    B, T, H, D = q.shape
    if T != 1:
        raise ValueError(f"decode kernel is single-query, got T={T}")
    kc, vc = cache_l["k"], cache_l["v"]
    ks, vs = cache_l["k_s"], cache_l["v_s"]
    Nphys, Hkv = kc.shape[0], kc.shape[1]
    g = H // Hkv
    if g > _SUB:
        raise ValueError(
            f"GQA group {g} exceeds the kernel's {_SUB}-row group tile"
        )
    if Nphys % P != 0:
        raise ValueError(
            f"page pool of {Nphys} rows is not a multiple of "
            f"page_tokens {P}"
        )
    npages = Nphys // P
    max_pages = page_table.shape[1]

    q3 = q.reshape(B, Hkv, g, D)
    if g < _SUB:
        q3 = jnp.pad(q3, ((0, 0), (0, 0), (0, _SUB - g), (0, 0)))
    q3 = q3.reshape(B, Hkv * _SUB, D)
    rows = Hkv * _SUB
    # pool leaves reshaped page-major — free (the trailing dims are
    # contiguous), and each block below is one page's rows
    kf = kc.reshape(npages, P, Hkv * D)
    vf = vc.reshape(npages, P, Hkv * D)
    ksr = ks.reshape(npages, P, Hkv)
    vsr = vs.reshape(npages, P, Hkv)
    posv = jnp.broadcast_to(
        jnp.asarray(pos, jnp.int32).reshape(-1), (B,)
    )
    ptv = page_table.astype(jnp.int32)

    kern = functools.partial(
        _paged_kernel, scale=scale, window=None, bk=P, nk=max_pages,
        Hkv=Hkv, D=D, ring=True,
    )

    def _page(b, j, pos_ref, pt_ref):
        del pos_ref
        return (pt_ref[b, j], 0, 0)

    def _row(b, j, pos_ref, pt_ref):
        del pos_ref, pt_ref
        return (b, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, max_pages),
        in_specs=[
            pl.BlockSpec((1, rows, D), _row),
            pl.BlockSpec((1, P, Hkv * D), _page),
            pl.BlockSpec((1, P, Hkv), _page),
            pl.BlockSpec((1, P, Hkv * D), _page),
            pl.BlockSpec((1, P, Hkv), _page),
        ],
        out_specs=pl.BlockSpec((1, rows, D), _row),
        scratch_shapes=[
            pltpu.VMEM((rows, D), jnp.float32),
            pltpu.VMEM((rows, _LANE), jnp.float32),
            pltpu.VMEM((rows, _LANE), jnp.float32),
        ],
    )
    o3 = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=_sds((B, rows, D), q.dtype, q),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
        interpret=interpret,
    )(posv, ptv, q3, kf, ksr, vf, vsr)
    return o3.reshape(B, Hkv, _SUB, D)[:, :, :g].reshape(B, 1, H, D)
