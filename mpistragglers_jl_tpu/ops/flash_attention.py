"""Pallas TPU flash attention: fused online-softmax attention kernels.

The reference has no attention code at all (SURVEY §5 'Long-context');
this op is part of the framework's long-context story. The per-device
attention inside Ulysses sequence parallelism and the dense transformer
forward materialize an (L, L) score matrix per head
(parallel/ring_attention.py ``reference_attention``) — O(L^2) HBM
traffic and memory. This module replaces that hot op with a Pallas
kernel that streams K/V blocks through VMEM and keeps the softmax
normalizer in on-chip scratch, the standard flash-attention scheme
mapped to the TPU memory hierarchy (HBM -> VMEM -> MXU):

* forward: grid (batch*heads, q-blocks, k-blocks), k innermost; online
  softmax accumulators (o_acc, m, l) live in VMEM scratch across the
  k sweep; saves per-row logsumexp for the backward;
* backward: two kernels (dq over the k sweep; dk/dv over the q sweep,
  its tiles computed transposed) recompute probabilities from the saved
  logsumexp, the recomputation-based flash backward — no (L, L)
  residual is ever stored;
* a fetched block is computed in sub-tiles, each by its kind: a tile
  outside the band (above the causal diagonal, left of the sliding
  window) is skipped, a tile visible in full takes a body with no mask,
  only a tile the band's edge crosses builds one; a grid step whose
  block lies outside the band fetches nothing;
* wrapped in ``jax.custom_vjp`` so it differentiates inside the model
  train steps.

On non-TPU backends (the CI mesh is 8 virtual CPU devices) the kernels
run in Pallas interpret mode automatically, so the same code path is
testable everywhere.

Layout matches the rest of the framework: (batch, seq, heads, head_dim).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG = -1e30  # matches parallel/ring_attention.py: large-negative mask
_LANE = 128  # TPU lane width; m/l scratch is broadcast across lanes
# Blocks and tiles, read on one v5e chip (my chip runs, PR 38; a scratch
# micro-benchmark of the three kernels alone at the training cell's
# shape: q (48, 8192, 128), k / v (4, 8192, 128) bfloat16, causal,
# window 4096, group of 12; one layer; the forward kernel / the whole
# backward (delta, dq, dk/dv, the group sums), ms, as the slope between
# 4 and 34 calls back to back):
#   the kernels until PR 38 (1024 x 1024 computed whole, the mask built
#   in every block that runs, dk/dv contracting over the tiles' first
#   axis)                                                 8.60 / 21.17
#   interior blocks without a mask, dk/dv transposed, a skipped step
#   fetching nothing, m and l lane-wide (_lanes)          7.30 / 17.82
#   ... computed in tiles of 512 x 512 (_TILE)            6.43 / 17.63
#     512 x 1024: 7.35 / 18.26; 1024 x 512: 7.26 / 18.30; 256 x 512:
#     6.91 / 20.99; 512 x 256: 8.49 / 20.06
#   ... the tiles a fori_loop (compiles in 4.5 s, not 11)  6.55 / 17.88
#   ... in blocks of 2048 x 2048 (_BLOCK)                 5.98 / 16.80
#     (16 grid steps a sweep where 1024-blocks take 64: a step that
#     runs nothing still costs its launch; 2048 x 1024: 6.03 / 17.23)
# Not taken: q carrying the scale (reads nothing, rounds q anew), p and
# ds cast to bfloat16 (+0.1 / +0.2 and the same bits: Mosaic's default
# precision already feeds a float32 operand in one bfloat16 pass).
# No fused backward: one kernel sharing s and dp (5 tile products, not 7)
# for float32 partials of dk and dv a q block read 21.82 beside the split
# kernels' 17.84. Other shapes and each candidate alone: PERF.md section
# 6, PR 38.
_BLOCK = 2048  # default side of the blocks the grid fetches
_TILE = 512  # side of the sub-tile a fetched block is computed in
_WHOLE = 1024  # most that a block computed whole spans (no tile divides it)


def _grid_params():
    """Mosaic grid semantics: batch*heads and the outer block axis are
    embarrassingly parallel; only the innermost sweep (k blocks in the
    forward/dq, q blocks in dk/dv) carries loop state through scratch
    and must run in order. On a v5e, one core a chip, the annotation
    reads nothing (table above ``_BLOCK``); it is what lets a chip with
    two cores split the grid."""
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary")
    )


def _use_interpret() -> bool:
    """Pallas kernels compile through Mosaic on ``tpu`` and run in the
    Pallas interpreter on ``cpu`` (the test mesh). Any other platform
    is an error: silently interpreting there would report a kernel
    result the kernel never produced."""
    platform = jax.default_backend()
    if platform == "tpu":
        return False
    if platform == "cpu":
        return True
    raise RuntimeError(
        f"Pallas kernels run compiled on 'tpu' or interpreted on 'cpu'; "
        f"the default JAX backend is {platform!r}"
    )


def _pick_block(L: int, block: int) -> int:
    """The block of an axis of length L, at most ``block``: the largest
    divisor of L that is a multiple of ``_TILE``, which the kernels
    compute tile by tile. Where L has none, the block is computed whole
    (:func:`_compute_tile`) and is what it was until PR 38: the largest
    TPU-legal divisor of L (sublane-aligned, a multiple of 8, or the
    whole dimension; anything else compiles only in interpret mode) of
    at most ``_WHOLE``. When L has no such divisor either (odd/prime
    lengths), the fallback is the whole dimension in one block: legal
    but VMEM-bounded; :func:`_check_vmem` rejects fallback blocks whose
    working set cannot fit the 16 MiB scoped budget instead of letting
    Mosaic OOM mid-compile."""
    for b in range(min(block, L) // _TILE * _TILE, 0, -_TILE):
        if L % b == 0:
            return b
    for b in range(min(block, L, _WHOLE), 0, -1):
        if L % b == 0 and (b % 8 == 0 or b == L):
            return b
    return L


def _compute_tile(bq: int, bk: int) -> tuple[int, int]:
    """The (tq, tk) sub-tile of a fetched (bq, bk) block that the
    kernels compute at a time. The grid fetches blocks as large as VMEM
    allows (fewer steps); inside one, the run test and the interior /
    edge test apply per sub-tile, so a finer tile runs fewer masked
    pairs. A block no multiple of the tile is computed whole
    (:func:`_pick_block` holds it to ``_WHOLE``)."""
    tq = _TILE if bq % _TILE == 0 else bq
    tk = _TILE if bk % _TILE == 0 else bk
    return tq, tk


_VMEM_BUDGET = 16 * 2 ** 20  # Mosaic's scoped VMEM allocation (bytes)


def _vmem_estimate(bq: int, bk: int, D: int, itemsize: int) -> int:
    """Bytes of VMEM a grid step of the heaviest kernel works in: the
    pipeline's two buffers of every fetched block (q, do and the dq
    output; k and v; the dq kernel's lse and delta columns, which a
    (bq, 1) float32 block pads to a lane tile each), the float32
    scratch (the forward's o, m and l, or the dk/dv kernel's two
    accumulators) and two float32 intermediates of one COMPUTE tile
    (score / probability, dp / ds): since the kernels compute a block
    in sub-tiles (:func:`_compute_tile`), a block's footprint grows
    with its rows and not with its area.

    It errs high. The scoped allocation that Mosaic holds to the budget
    is the last two terms alone (the described v5e's compiler, the
    smallest ``vmem_limit_bytes`` it takes, head_dim 128, either dtype:
    5.0 MiB for 2048-blocks in 512-tiles, estimated 14 in bfloat16 and
    19 in float32; 3.5 for 1024-blocks in tiles, estimated 8 and 10.5;
    10.25 for a 1000-block computed whole, estimated 13.5 and 15.9;
    6.5 at head_dim 256 in 2048-blocks, estimated 20 and 30). Counting
    the windows as well keeps float32 at head_dim 128 and every wider
    head in the 1024-blocks they had before: the 2048-blocks were read
    on the chip in bfloat16 at head_dim 128 alone."""
    tq, tk = _compute_tile(bq, bk)
    return (
        2 * itemsize * (3 * bq * D + 2 * bk * D)
        + 2 * 2 * 4 * bq * _LANE
        + 4 * max(bq * D + 2 * bq * _LANE, 2 * bk * D)
        + 2 * 4 * tq * tk
    )


def _blocks(Lq: int, Lk: int, D: int, itemsize: int, block_q: int,
            block_k: int):
    """(bq, bk, tile): the blocks the grid fetches and the tile the
    kernels compute, chosen in this one place for the kernels and for
    :func:`block_plan`. ``block_q`` / ``block_k`` are upper bounds:
    :func:`_pick_block` shrinks them to divide the lengths, and where
    :func:`_vmem_estimate` says the working set does not fit the budget
    the larger side is halved until it does (the default 2048-blocks
    were read on the chip in bfloat16 at head_dim 128 alone; float32 or
    a wider head takes the 1024 the kernels had until PR 38)."""
    bq, bk = _pick_block(Lq, block_q), _pick_block(Lk, block_k)
    while _vmem_estimate(bq, bk, D, itemsize) > _VMEM_BUDGET:
        half = max(bq, bk) // 2
        smaller = (_pick_block(Lq, min(bq, half)),
                   _pick_block(Lk, min(bk, half)))
        if smaller == (bq, bk):  # a whole odd dimension: nothing divides
            break
        bq, bk = smaller
    return bq, bk, _compute_tile(bq, bk)


def _check_vmem(bq: int, bk: int, D: int, itemsize: int) -> None:
    """Reject blocks that cannot fit VMEM, with a clear error instead
    of an opaque Mosaic mid-compile allocation failure: the odd-length
    whole-dimension fallback (see :func:`_pick_block`), which
    :func:`_blocks` cannot shrink, and a head too wide for the blocks
    asked for."""
    est = _vmem_estimate(bq, bk, D, itemsize)
    if est > _VMEM_BUDGET:
        aligned = bq % 8 == 0 and bk % 8 == 0
        why = (
            "lower block_q/block_k"
            if aligned
            else "the sequence length has no 8-aligned divisor, so the "
            "kernel would take it in one block; pad the sequence to a "
            "multiple of 8 (ideally 1024) upstream"
        )
        raise ValueError(
            f"flash attention block ({bq}x{bk}, head_dim {D}) needs "
            f"~{est / 2**20:.0f} MiB of VMEM, over the "
            f"{_VMEM_BUDGET // 2**20} MiB scoped budget: {why}."
        )


def _block_run(i, j, bq, bk, causal, window):
    """Does block (or compute tile) (i, j) of a (bq, bk) tiling
    intersect the visible band? Causal skips blocks entirely above the
    diagonal; a sliding window additionally skips blocks entirely LEFT
    of the band (min possible qpos - max possible kpos >= window).
    Returns a traced bool (or True when nothing is masked); on plain
    integers (:func:`block_plan`) a plain one."""
    run = True
    if causal:
        run = j * bk <= i * bq + bq - 1
    if window is not None:
        in_band = i * bq - (j * bk + bk - 1) < window
        run = in_band if run is True else run & in_band
    return run


def _block_interior(i, j, bq, bk, causal, window):
    """Is every pair of block (i, j) visible, so that its body needs no
    mask? Its four corners lie inside the band: the last key at or
    below the first query, the last query within the window of the
    first key. Implies :func:`_block_run`; True (a plain bool) where
    there is neither ``causal`` nor ``window``."""
    inside = True
    if causal:
        inside = j * bk + bk - 1 <= i * bq
    if window is not None:
        near = i * bq + bq - 1 - j * bk < window
        inside = near if inside is True else inside & near
    return inside


def _k_run_range(i, bq, bk, nk, causal, window):
    """First and last k block that :func:`_block_run` lets run for q
    block ``i``, in closed form (the run blocks of a sweep are one
    stretch). The index maps clamp the swept index into this range
    (:func:`_clamp`), so a step that does not run names the block its
    neighbour fetched and the pipeline copies nothing for it: nothing
    computes beside a skipped step, so its copies would all be waited
    for."""
    lo, hi = 0, nk - 1
    if window is not None:
        lo = jnp.maximum((i * bq - window + 1) // bk, lo)
    if causal:
        hi = jnp.minimum((i * bq + bq - 1) // bk, hi)
    return lo, hi


def _q_run_range(j, bq, bk, nq, causal, window):
    """First and last q block that runs for k block ``j`` (the dk/dv
    kernel's sweep); see :func:`_k_run_range`."""
    lo, hi = 0, nq - 1
    if window is not None:
        hi = jnp.minimum((j * bk + bk - 2 + window) // bq, hi)
    if causal:
        lo = (j * bk) // bq
    return lo, hi


def _clamp(x, lo_hi):
    """``x`` held into [lo, hi]; where ``lo`` lies past the sweep's
    end (no block of it runs) that is ``hi``, a block that exists."""
    lo, hi = lo_hi
    if isinstance(lo, int) and isinstance(hi, int):
        return x  # nothing is masked: every step of the sweep runs
    return jnp.minimum(jnp.maximum(x, lo), hi)


def _block_mask(i, j, bq, bk, causal, window, transposed=False):
    """In-block (bq, bk) visibility mask for block (i, j), or None when
    nothing is masked (mirrors parallel/ring_attention._band_mask).
    ``transposed`` gives the (bk, bq) mask of the dk/dv kernel, whose
    tiles have keys on rows. The positions are two iotas with a scalar
    added to each: one ``row - col`` difference against two scalars
    read the same in the cell's 512-tiles and 5 to 7% SLOWER in the
    forward of causal 1000-blocks computed whole (L 2000, 3000; PERF.md
    section 6, PR 38)."""
    if not causal and window is None:
        return None
    shape, qa, ka = ((bk, bq), 1, 0) if transposed else ((bq, bk), 0, 1)
    qpos = i * bq + jax.lax.broadcasted_iota(jnp.int32, shape, qa)
    kpos = j * bk + jax.lax.broadcasted_iota(jnp.int32, shape, ka)
    mask = None
    if causal:
        mask = kpos <= qpos
    if window is not None:
        band = qpos - kpos < window
        mask = band if mask is None else jnp.logical_and(mask, band)
    return mask


def _tile_slice(n, size):
    """The n-th stretch of ``size`` along a block's axis; a traced
    start says that it is aligned."""
    if isinstance(n, int):
        return pl.ds(n * size, size)
    return pl.ds(pl.multiple_of(n * size, size), size)


def _for_tiles(i, j, bq, bk, tile, causal, window, body,
               transposed=False):
    """Run ``body(a, rows, cols, mask)`` for every compute tile
    (``tile``, :func:`_compute_tile`'s) of the fetched block (i, j), by
    its kind, read from its place and the static sizes: a tile outside the
    band does nothing, an interior tile (:func:`_block_interior`) takes
    the body with ``mask=None`` (no iota, no compare, no select), an
    edge tile (causal diagonal, window's left edge) the body with
    :func:`_block_mask`'s mask. ``rows`` / ``cols`` slice the tile out
    of the block's query / key axis, ``a`` is the tile's place on the
    query axis. More tiles than one are a ``fori_loop`` over ONE pair
    of bodies: unrolled, the 16 tiles of a 2048 x 2048 block are 32
    bodies a kernel, which Mosaic compiles in 40 s where the loop takes
    4.5 (three kernels; table above ``_BLOCK``)."""
    tq, tk = tile
    na, nb = bq // tq, bk // tk

    def one(a, b):
        ti, tj = i * na + a, j * nb + b
        rows, cols = _tile_slice(a, tq), _tile_slice(b, tk)
        inside = _block_interior(ti, tj, tq, tk, causal, window)
        if inside is True:  # nothing is ever masked
            body(a, rows, cols, None)
            return
        edge = jnp.logical_and(
            _block_run(ti, tj, tq, tk, causal, window),
            jnp.logical_not(inside),
        )
        pl.when(inside)(lambda: body(a, rows, cols, None))
        pl.when(edge)(lambda: body(a, rows, cols, _block_mask(
            ti, tj, tq, tk, causal, window, transposed)))

    if na * nb == 1:
        one(0, 0)
        return

    def step(t, carry):
        one(t // nb, t % nb)
        return carry

    jax.lax.fori_loop(0, na * nb, step, None)


def block_plan(Lq: int, Lk: int, *, causal: bool, window: int | None,
               head_dim: int = 128, itemsize: int = 2,
               block_q: int = _BLOCK, block_k: int = _BLOCK) -> dict:
    """What ONE (batch x head) forward sweep of the kernels' grid does
    at these lengths, counted on the host in plain integers from the
    kernels' own choices and rules: the blocks :func:`_blocks` chooses
    for this head size and dtype (``block``, "<bq>x<bk>") and the
    grid's steps (``grid_steps`` = nq * nk; a step a grid skips still
    costs its launch); then, at the granularity the kernels COMPUTE
    (:func:`_compute_tile`, ``tile``; ``run_steps`` stands beside
    ``tile_steps``, never beside ``grid_steps``): the tiles of a sweep
    (``tile_steps``), those :func:`_block_run` lets run
    (``run_steps``), those among them whose body carries no
    mask (``interior_steps``, :func:`_block_interior`), the (query,
    key) pairs the run tiles hold (``pairs_run``) and the pairs among
    them that :func:`_block_mask`'s rule lets through (``pairs_band``:
    ``kpos <= qpos`` when causal, ``qpos - kpos < window`` under a
    window). ``pairs_band / pairs_run`` is the share of the kernels'
    score work that is not masked away; the two backward kernels sweep
    the same tiles."""
    bq, bk, (tq, tk) = _blocks(Lq, Lk, head_dim, itemsize, block_q, block_k)
    run_steps = interior_steps = pairs_band = 0
    for i in range(Lq // tq):
        for j in range(Lk // tk):
            if not _block_run(i, j, tq, tk, causal, window):
                continue
            run_steps += 1
            interior_steps += bool(
                _block_interior(i, j, tq, tk, causal, window))
            k0, k1 = j * tk, j * tk + tk - 1
            for q in range(i * tq, i * tq + tq):
                lo = k0 if window is None else max(k0, q - window + 1)
                hi = min(k1, q) if causal else k1
                pairs_band += max(0, hi - lo + 1)
    return {
        "block": f"{bq}x{bk}", "tile": f"{tq}x{tk}",
        "grid_steps": (Lq // bq) * (Lk // bk),
        "tile_steps": (Lq // tq) * (Lk // tk), "run_steps": run_steps,
        "interior_steps": interior_steps,
        "pairs_run": run_steps * tq * tk, "pairs_band": pairs_band,
    }


def _sds(shape, dtype, like):
    """ShapeDtypeStruct carrying ``like``'s varying-mesh-axes, so the
    kernels are callable inside ``shard_map`` (e.g. as the per-device
    attention of Ulysses) where outputs must declare their vma.
    Toolchains without ``jax.typeof`` have no vma tracking either, so
    the plain struct is the correct degradation there."""
    typeof = getattr(jax, "typeof", None)
    vma = getattr(typeof(like), "vma", None) if typeof else None
    if vma:
        return jax.ShapeDtypeStruct(shape, dtype, vma=vma)
    return jax.ShapeDtypeStruct(shape, dtype)


# --------------------------------------------------------------------------
# forward kernel
# --------------------------------------------------------------------------


def _dot(a, b, contract):
    """One tile product on the MXU, summed in float32. ``q k^T`` takes
    the inputs' dtype; every product with a float32 tile (``p``,
    ``ds``) takes its other operand upcast. On the chip that costs
    nothing and rounds nothing anew: Mosaic's default precision feeds
    the MXU a float32 operand in one bfloat16 pass, so ``p`` cast to
    bfloat16 first gave the same bits and the same time (table above
    ``_BLOCK``)."""
    return jax.lax.dot_general(
        a, b, (contract, ((), ())), preferred_element_type=jnp.float32
    )


def _lanes(x, n: int):
    """``x`` (rows, 128) with every lane equal -> as good as (rows, n):
    whole lane tiles side by side where n is a multiple of 128, else
    one column, which broadcasts."""
    if n % _LANE == 0:
        return x if n == _LANE else jnp.tile(x, (1, n // _LANE))
    return x[:, :1]


_NT = ((1,), (1,))  # a @ b.T
_NN = ((1,), (0,))  # a @ b


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc, m_sc, l_sc,
                *, scale, causal, window, bq, bk, tile, nk):
    i, j = pl.program_id(1), pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        acc[:] = jnp.zeros_like(acc)
        m_sc[:] = jnp.full_like(m_sc, _NEG)
        l_sc[:] = jnp.zeros_like(l_sc)

    def _update(a, rows, cols, mask):
        vb = v_ref[0, cols, :]  # (tk, D)
        s = _dot(q_ref[0, rows, :], k_ref[0, cols, :], _NT) * scale
        if mask is not None:
            s = jnp.where(mask, s, _NEG)
        # m, l and corr stay (tq, 128) with every lane equal, as the
        # scratch holds them: widened by whole lane tiles (_lanes) they
        # meet the (tq, tk) tile without a broadcast a use
        m_prev = m_sc[rows, :]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - _lanes(m_new, s.shape[1]))
        if mask is not None:
            # a row with no visible key in this tile and none before it
            # has m_new == _NEG and would count exp(0) for every key
            p = jnp.where(mask, p, 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_sc[rows, :] = l_sc[rows, :] * corr + p.sum(axis=-1,
                                                     keepdims=True)
        acc[rows, :] = acc[rows, :] * _lanes(corr, acc.shape[1]) + _dot(
            p, vb.astype(jnp.float32), _NN)
        m_sc[rows, :] = m_new

    # tiles outside the visible band (above the causal diagonal, or
    # left of the sliding window) emit nothing
    _for_tiles(i, j, bq, bk, tile, causal, window, _update)

    @pl.when(j == nk - 1)
    def _finish():
        l = jnp.maximum(l_sc[:, :1], 1e-20)
        o_ref[0] = (acc[:] / l).astype(o_ref.dtype)
        lse_ref[0] = (m_sc[:, :1] + jnp.log(l)).astype(jnp.float32)


# ``jax.jit(inline=True)`` on _fwd and _bwd: a model calls them once a
# layer with the same shapes, and the jit's cache hands every layer the
# kernels the first one traced (a pallas_call traces its kernel at each
# call, and JAX lowers equal equations once: eight layers' 24 kernels
# cost every process's set-up 12 s on the chip's machine, three cost
# less than the whole-block kernels did). Inlined, no ``jit(...)`` joins
# the name stack: the compiled kernels keep the names ``jvp*`` /
# ``transpose_jvp*`` by which the benchmark finds them (ROADMAP D12).
@functools.partial(
    jax.jit, static_argnums=(3, 4, 5, 6, 7, 8, 9, 10), inline=True)
def _fwd(q3, k3, v3, scale, causal, window, bq, bk, tile, g, interpret):
    """q3: (B*H, L, D); k3/v3: (B*Hkv, L, D) -> (o (B*H, L, D),
    lse (B*H, L, 1)). GQA costs nothing here: the grid runs over q
    heads and the K/V BlockSpec index maps divide the flattened
    batch*head index by the group size ``g`` — flattened q index
    b = batch*H + h reads k3[b // g] = batch*Hkv + h // g, so grouped
    K/V blocks are simply fetched g times from the same HBM pages, no
    repeated/materialized K ever exists."""
    BH, Lq, D = q3.shape
    Lk = k3.shape[1]
    nq, nk = Lq // bq, Lk // bk
    kern = functools.partial(
        _fwd_kernel, scale=scale, causal=causal, window=window, bq=bq,
        bk=bk, tile=tile, nk=nk,
    )

    def kv_map(b, i, j):
        j = _clamp(j, _k_run_range(i, bq, bk, nk, causal, window))
        return (b // g, j, 0)

    return pl.pallas_call(
        kern,
        grid=(BH, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, D), kv_map),
            pl.BlockSpec((1, bk, D), kv_map),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
            # lse is (BH, L, 1): a trailing singleton keeps the TPU block
            # tiling legal ((1, bq, 1): bq sublane-divisible, 1 == whole
            # trailing dim) and broadcasts cleanly in the backward
            pl.BlockSpec((1, bq, 1), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            _sds((BH, Lq, D), q3.dtype, q3),
            _sds((BH, Lq, 1), jnp.float32, q3),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, D), jnp.float32),
            pltpu.VMEM((bq, _LANE), jnp.float32),
            pltpu.VMEM((bq, _LANE), jnp.float32),
        ],
        compiler_params=_grid_params(),
        interpret=interpret,
    )(q3, k3, v3)


# --------------------------------------------------------------------------
# backward kernels (recompute from lse)
# --------------------------------------------------------------------------


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                   acc, *, scale, causal, window, bq, bk, tile, nk):
    i, j = pl.program_id(1), pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        acc[:] = jnp.zeros_like(acc)

    def _update(a, rows, cols, mask):
        kb = k_ref[0, cols, :]
        s = _dot(q_ref[0, rows, :], kb, _NT) * scale
        if mask is not None:
            s = jnp.where(mask, s, _NEG)
        p = jnp.exp(s - lse_ref[0, rows, :])  # (tq, tk); masked -> 0
        dp = _dot(do_ref[0, rows, :].astype(jnp.float32),
                  v_ref[0, cols, :].astype(jnp.float32), _NT)
        ds = p * (dp - delta_ref[0, rows, :])
        acc[rows, :] = acc[rows, :] + _dot(
            ds, kb.astype(jnp.float32), _NN)

    _for_tiles(i, j, bq, bk, tile, causal, window, _update)

    @pl.when(j == nk - 1)
    def _finish():
        dq_ref[0] = (acc[:] * scale).astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_acc, dv_acc,
                    *, scale, causal, window, bq, bk, tile, nq):
    """dk/dv in TRANSPOSED form: the tile is computed with keys on rows
    from the start (``s^T = k q^T``, ``dp^T = v do^T``; ``lse`` and
    ``delta`` arrive as rows, (1, tq) a tile), so ``dv += p^T do`` and
    ``dk += ds^T q`` are plain products and no (bq, bk) tile is ever
    transposed on its way to the MXU."""
    j, i = pl.program_id(1), pl.program_id(2)  # k block major, q innermost

    @pl.when(i == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    def _update(a, rows, cols, mask):
        q = q_ref[0, rows, :]
        do = do_ref[0, rows, :].astype(jnp.float32)
        st = _dot(k_ref[0, cols, :], q, _NT) * scale  # (tk, tq)
        if mask is not None:
            st = jnp.where(mask, st, _NEG)
        pt = jnp.exp(st - lse_ref[0, a])  # (1, tq) row; masked -> 0
        dv_acc[cols, :] = dv_acc[cols, :] + _dot(pt, do, _NN)
        dpt = _dot(v_ref[0, cols, :].astype(jnp.float32), do, _NT)
        dst = pt * (dpt - delta_ref[0, a])
        dk_acc[cols, :] = dk_acc[cols, :] + _dot(
            dst, q.astype(jnp.float32), _NN)

    _for_tiles(i, j, bq, bk, tile, causal, window, _update,
               transposed=True)

    @pl.when(i == nq - 1)
    def _finish():
        dk_ref[0] = (dk_acc[:] * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


@functools.partial(
    jax.jit, static_argnums=(6, 7, 8, 9, 10, 11, 12, 13), inline=True)
def _bwd(q3, k3, v3, o3, lse, do3, scale, causal, window, bq, bk, tile,
         g, interpret):
    BH, Lq, D = q3.shape
    Lk = k3.shape[1]
    nq, nk = Lq // bq, Lk // bk
    delta = jnp.sum(
        do3.astype(jnp.float32) * o3.astype(jnp.float32),
        axis=-1, keepdims=True,
    )  # (BH, Lq, 1), same trailing-singleton layout as lse

    # a step that does not run fetches nothing (_k_run_range)
    def kv_map(b, i, j):
        j = _clamp(j, _k_run_range(i, bq, bk, nk, causal, window))
        return (b // g, j, 0)

    def q_of(j, i):
        return _clamp(i, _q_run_range(j, bq, bk, nq, causal, window))

    dq = pl.pallas_call(
        functools.partial(
            _bwd_dq_kernel, scale=scale, causal=causal, window=window,
            bq=bq, bk=bk, tile=tile, nk=nk,
        ),
        grid=(BH, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, D), kv_map),
            pl.BlockSpec((1, bk, D), kv_map),
            pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bq, 1), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bq, 1), lambda b, i, j: (b, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
        out_shape=_sds((BH, Lq, D), q3.dtype, q3),
        scratch_shapes=[pltpu.VMEM((bq, D), jnp.float32)],
        compiler_params=_grid_params(),
        interpret=interpret,
    )(q3, k3, v3, do3, lse, delta)

    # the dk/dv kernel computes its tiles transposed and reads lse and
    # delta as rows: (BH, Lq // tq, 1, tq), one (1, tq) row a compute
    # tile of the query axis, picked by a leading index; the trailing
    # two dims are whole, so any tq is a legal block. Made here, once a
    # call, from the columns the dq kernel reads.
    tq, _ = tile

    def rows(col):
        return col.reshape(BH, Lq // tq, 1, tq)

    # dk/dv: each grid-b is ONE q head, writing its own (B*H)-indexed
    # output block — per-q-head partials, no cross-head write conflicts
    # under the parallel grid axis. The group-sum down to the B*Hkv kv
    # heads happens outside the kernel: flattened q index b = batch*H +
    # hkv*g + g_idx = (batch*Hkv + hkv)*g + g_idx, so a (B*Hkv, g, Lk,
    # D) reshape puts the group on axis 1 and one XLA reduction
    # finishes the job.
    dkq, dvq = pl.pallas_call(
        functools.partial(
            _bwd_dkv_kernel, scale=scale, causal=causal, window=window,
            bq=bq, bk=bk, tile=tile, nq=nq,
        ),
        grid=(BH, nk, nq),
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda b, j, i: (b, q_of(j, i), 0)),
            pl.BlockSpec((1, bk, D), lambda b, j, i: (b // g, j, 0)),
            pl.BlockSpec((1, bk, D), lambda b, j, i: (b // g, j, 0)),
            pl.BlockSpec((1, bq, D), lambda b, j, i: (b, q_of(j, i), 0)),
            pl.BlockSpec((1, bq // tq, 1, tq),
                         lambda b, j, i: (b, q_of(j, i), 0, 0)),
            pl.BlockSpec((1, bq // tq, 1, tq),
                         lambda b, j, i: (b, q_of(j, i), 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bk, D), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, bk, D), lambda b, j, i: (b, j, 0)),
        ],
        out_shape=[
            _sds((BH, Lk, D), k3.dtype, k3),
            _sds((BH, Lk, D), v3.dtype, v3),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, D), jnp.float32),
            pltpu.VMEM((bk, D), jnp.float32),
        ],
        compiler_params=_grid_params(),
        interpret=interpret,
    )(q3, k3, v3, do3, rows(lse), rows(delta))
    BHkv = BH // g  # a group of one sums over an axis of one
    dk = dkq.reshape(BHkv, g, Lk, D).sum(axis=1).astype(k3.dtype)
    dv = dvq.reshape(BHkv, g, Lk, D).sum(axis=1).astype(v3.dtype)
    return dq, dk, dv


# --------------------------------------------------------------------------
# custom-vjp wrapper over (BH, L, D) tensors
# --------------------------------------------------------------------------


@functools.partial(
    jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9, 10)
)
def _flash3(q3, k3, v3, scale, causal, window, bq, bk, tile, g, interpret):
    o, _ = _fwd(q3, k3, v3, scale, causal, window, bq, bk, tile, g,
                interpret)
    return o


def _flash3_fwd(q3, k3, v3, scale, causal, window, bq, bk, tile, g,
                interpret):
    o, lse = _fwd(q3, k3, v3, scale, causal, window, bq, bk, tile, g,
                  interpret)
    return o, (q3, k3, v3, o, lse)


def _flash3_bwd(scale, causal, window, bq, bk, tile, g, interpret, res,
                do3):
    q3, k3, v3, o3, lse = res
    return _bwd(q3, k3, v3, o3, lse, do3, scale, causal, window, bq, bk,
                tile, g, interpret)


_flash3.defvjp(_flash3_fwd, _flash3_bwd)


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = False,
    scale: float | None = None,
    window: int | None = None,
    block_q: int = _BLOCK,
    block_k: int = _BLOCK,
    interpret: bool | None = None,
) -> jax.Array:
    """Fused flash attention on (B, L, H, D) tensors; differentiable.

    Drop-in for :func:`~..parallel.ring_attention.reference_attention`
    (same layout, same causal semantics) without materializing (L, L)
    scores. Block sizes shrink automatically to divide the sequence
    lengths; ``interpret`` defaults to compiled on TPU and interpret
    mode elsewhere.

    ``block_q`` / ``block_k`` bound the blocks the grid FETCHES, 2048
    by default (fewer grid steps a sweep: a step that runs nothing still
    costs its launch); :func:`_blocks` shrinks them to divide the
    lengths and to fit VMEM by :func:`_vmem_estimate` (2048 in bfloat16
    at head_dim 128, else 1024). What the kernels COMPUTE at a time is
    a 512 x 512 sub-tile of a block (:func:`_compute_tile`), each tile
    by its kind (:func:`_for_tiles`); a length that no multiple of 512
    divides is taken in blocks of at most 1024 computed whole, as until
    PR 38. Readings: the table above ``_BLOCK``.
    """
    B, Lq, H, D = q.shape
    Lk = k.shape[1]
    Hkv = k.shape[2]
    if H % Hkv != 0:
        raise ValueError(
            f"q heads ({H}) must be a multiple of kv heads ({Hkv})"
        )
    g = H // Hkv
    if scale is None:
        scale = D ** -0.5
    if interpret is None:
        interpret = _use_interpret()
    bq, bk, tile = _blocks(Lq, Lk, D, q.dtype.itemsize, block_q, block_k)
    if not interpret:  # the interpreter has no VMEM to blow
        _check_vmem(bq, bk, D, q.dtype.itemsize)

    def to3(x, L, h):
        return x.transpose(0, 2, 1, 3).reshape(B * h, L, D)

    if window is not None and window < 1:
        raise ValueError(f"window must be >= 1, got {window}")
    o3 = _flash3(
        to3(q, Lq, H), to3(k, Lk, Hkv), to3(v, Lk, Hkv),
        float(scale), bool(causal),
        None if window is None else int(window), bq, bk, tile, g,
        bool(interpret),
    )
    return o3.reshape(B, H, Lq, D).transpose(0, 2, 1, 3)
