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
  k sweep; causal blocks entirely above the diagonal are skipped via
  predication; saves per-row logsumexp for the backward;
* backward: two kernels (dq over the k sweep; dk/dv over the q sweep)
  recompute probabilities from the saved logsumexp, the
  recomputation-based flash backward — no (L, L) residual is ever
  stored;
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


def _grid_params():
    """Mosaic grid semantics: batch*heads and the outer block axis are
    embarrassingly parallel; only the innermost sweep (k blocks in the
    forward/dq, q blocks in dk/dv) carries loop state through scratch
    and must run in order. Without this annotation Mosaic assumes every
    grid axis is sequential — measured 20% slower on the round-3 chip
    (earlier installation, not repeated on this one)."""
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
    """Largest TPU-legal block <= ``block`` dividing L: sublane-aligned
    (multiple of 8) or spanning the whole dimension (both are legal
    Mosaic tilings; anything else compiles only in interpret mode).
    When L has no 8-aligned divisor <= ``block`` (odd/prime lengths),
    the fallback is the whole dimension in one block — legal but VMEM-
    bounded; :func:`_check_vmem` rejects fallback blocks whose working
    set cannot fit the 16 MiB scoped budget instead of letting Mosaic
    OOM mid-compile."""
    b = min(block, L)
    while b > 0:
        if L % b == 0 and (b % 8 == 0 or b == L):
            return b
        b -= 1
    return L


_VMEM_BUDGET = 16 * 2 ** 20  # Mosaic's scoped VMEM allocation (bytes)


def _check_vmem(bq: int, bk: int, D: int, itemsize: int) -> None:
    """Reject block choices that cannot fit VMEM, with a clear error
    instead of an opaque Mosaic mid-compile allocation failure.

    Covers both the odd-length whole-dimension fallback (see
    :func:`_pick_block`) and explicitly tuned oversize blocks (e.g.
    ``block_q=2048`` at head_dim 128 — the PERF round-4 block sweep hit
    exactly that OOM). The estimate is the per-grid-step working set of
    the heaviest kernel (dk/dv backward): f32 scratch accumulators +
    m/l lanes + the (bq, bk) score/probability intermediates + resident
    q/k/v/do blocks. The tuned 1024x1024 default at head_dim 128
    estimates ~11.5 MiB — inside the 16 MiB budget with the same
    headroom Mosaic's double-buffering eats in practice."""
    est = 4 * (2 * bk * D + 2 * bq * _LANE + 2 * bq * bk) + itemsize * (
        2 * bq * D + 2 * bk * D
    )
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
    """Grid-level predication: does block (i, j) intersect the visible
    band? Causal skips blocks entirely above the diagonal; a sliding
    window additionally skips blocks entirely LEFT of the band
    (min possible qpos - max possible kpos >= window). Returns a traced
    bool (or True when nothing is masked); on plain integers
    (:func:`block_plan`) a plain one."""
    run = True
    if causal:
        run = j * bk <= i * bq + bq - 1
    if window is not None:
        in_band = i * bq - (j * bk + bk - 1) < window
        run = in_band if run is True else run & in_band
    return run


def _block_mask(i, j, bq, bk, causal, window):
    """In-block (bq, bk) visibility mask for block (i, j), or None when
    nothing is masked (mirrors parallel/ring_attention._band_mask)."""
    if not causal and window is None:
        return None
    qpos = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    kpos = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = None
    if causal:
        mask = kpos <= qpos
    if window is not None:
        band = qpos - kpos < window
        mask = band if mask is None else jnp.logical_and(mask, band)
    return mask


def block_plan(Lq: int, Lk: int, *, causal: bool, window: int | None,
               block_q: int = 1024, block_k: int = 1024) -> dict:
    """What ONE (batch x head) forward sweep of the kernels' grid does
    at these lengths, counted on the host in plain integers: the blocks
    :func:`_pick_block` chooses (``block``, "<bq>x<bk>"), the grid's
    steps (``grid_steps`` = nq * nk), those :func:`_block_run` lets run
    (``run_steps``), the (query, key) pairs those blocks hold
    (``pairs_run`` = run_steps * bq * bk) and the pairs among them that
    :func:`_block_mask`'s rule lets through (``pairs_band``: ``kpos <=
    qpos`` when causal, ``qpos - kpos < window`` under a window).
    ``pairs_band / pairs_run`` is the share of the kernels' score work
    that is not masked away; the two backward kernels sweep the same
    blocks. A step a grid skips still costs its launch, so
    ``run_steps / grid_steps`` is there too."""
    bq, bk = _pick_block(Lq, block_q), _pick_block(Lk, block_k)
    nq, nk = Lq // bq, Lk // bk
    run_steps = pairs_band = 0
    for i in range(nq):
        for j in range(nk):
            if not _block_run(i, j, bq, bk, causal, window):
                continue
            run_steps += 1
            k0, k1 = j * bk, j * bk + bk - 1
            for q in range(i * bq, i * bq + bq):
                lo = k0 if window is None else max(k0, q - window + 1)
                hi = min(k1, q) if causal else k1
                pairs_band += max(0, hi - lo + 1)
    return {
        "block": f"{bq}x{bk}", "grid_steps": nq * nk,
        "run_steps": run_steps, "pairs_run": run_steps * bq * bk,
        "pairs_band": pairs_band,
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


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc, m_sc, l_sc,
                *, scale, causal, window, bq, bk, nk):
    i, j = pl.program_id(1), pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        acc[:] = jnp.zeros_like(acc)
        m_sc[:] = jnp.full_like(m_sc, _NEG)
        l_sc[:] = jnp.zeros_like(l_sc)

    # skip blocks outside the visible band (above the causal diagonal,
    # or left of the sliding window)
    run = _block_run(i, j, bq, bk, causal, window)

    @pl.when(run)
    def _update():
        q = q_ref[0]  # (bq, D)
        kb = k_ref[0]  # (bk, D)
        s = jax.lax.dot_general(
            q, kb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # (bq, bk)
        mask = _block_mask(i, j, bq, bk, causal, window)
        if mask is not None:
            s = jnp.where(mask, s, _NEG)
        m_prev = m_sc[:, :1]  # (bq, 1)
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        if mask is not None:
            p = jnp.where(mask, p, 0.0)
        corr = jnp.exp(m_prev - m_new)  # (bq, 1)
        l_sc[:] = jnp.broadcast_to(
            l_sc[:, :1] * corr + p.sum(axis=-1, keepdims=True),
            l_sc.shape,
        )
        acc[:] = acc[:] * corr + jax.lax.dot_general(
            p, v_ref[0].astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_sc[:] = jnp.broadcast_to(m_new, m_sc.shape)

    @pl.when(j == nk - 1)
    def _finish():
        l = jnp.maximum(l_sc[:, :1], 1e-20)
        o_ref[0] = (acc[:] / l).astype(o_ref.dtype)
        lse_ref[0] = (m_sc[:, :1] + jnp.log(l)).astype(jnp.float32)


def _fwd(q3, k3, v3, scale, causal, window, bq, bk, g, interpret):
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
        bk=bk, nk=nk,
    )
    return pl.pallas_call(
        kern,
        grid=(BH, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, D), lambda b, i, j: (b // g, j, 0)),
            pl.BlockSpec((1, bk, D), lambda b, i, j: (b // g, j, 0)),
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
                   acc, *, scale, causal, window, bq, bk, nk):
    i, j = pl.program_id(1), pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        acc[:] = jnp.zeros_like(acc)

    run = _block_run(i, j, bq, bk, causal, window)

    @pl.when(run)
    def _update():
        q = q_ref[0]
        kb = k_ref[0]
        s = jax.lax.dot_general(
            q, kb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale
        mask = _block_mask(i, j, bq, bk, causal, window)
        if mask is not None:
            s = jnp.where(mask, s, _NEG)
        p = jnp.exp(s - lse_ref[0])  # (bq, bk); masked rows -> 0
        dp = jax.lax.dot_general(
            do_ref[0].astype(jnp.float32), v_ref[0].astype(jnp.float32),
            (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
        )
        ds = p * (dp - delta_ref[0])
        acc[:] = acc[:] + jax.lax.dot_general(
            ds, kb.astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(j == nk - 1)
    def _finish():
        dq_ref[0] = (acc[:] * scale).astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_acc, dv_acc,
                    *, scale, causal, window, bq, bk, nq):
    j, i = pl.program_id(1), pl.program_id(2)  # k block major, q innermost

    @pl.when(i == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    run = _block_run(i, j, bq, bk, causal, window)

    @pl.when(run)
    def _update():
        q = q_ref[0]
        kb = k_ref[0]
        s = jax.lax.dot_general(
            q, kb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale
        mask = _block_mask(i, j, bq, bk, causal, window)
        if mask is not None:
            s = jnp.where(mask, s, _NEG)
        p = jnp.exp(s - lse_ref[0])  # (bq, bk)
        do = do_ref[0].astype(jnp.float32)
        dv_acc[:] = dv_acc[:] + jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # (bk, D)
        dp = jax.lax.dot_general(
            do, v_ref[0].astype(jnp.float32), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dp - delta_ref[0])  # (bq, bk)
        dk_acc[:] = dk_acc[:] + jax.lax.dot_general(
            ds, q.astype(jnp.float32), (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(i == nq - 1)
    def _finish():
        dk_ref[0] = (dk_acc[:] * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _bwd_fused_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                      dq_ref, dkp_ref, dvp_ref, dq_acc,
                      *, scale, causal, window, bq, bk, nk):
    """Single-pass backward: one (i, j) sweep computes dq (accumulated
    over the inner j sweep in scratch) AND per-q-block dk/dv partials
    (reduced outside). The split kernels recompute s and dp twice —
    7 block-dots + 2 exps per (i, j); this shares them: 5 dots + 1 exp,
    a ~25% executed-FLOP cut exactly where the short-sequence
    attention tax lives."""
    i, j = pl.program_id(1), pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    run = _block_run(i, j, bq, bk, causal, window)

    @pl.when(run)
    def _update():
        q = q_ref[0]
        kb = k_ref[0]
        s = jax.lax.dot_general(
            q, kb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale
        mask = _block_mask(i, j, bq, bk, causal, window)
        if mask is not None:
            s = jnp.where(mask, s, _NEG)
        p = jnp.exp(s - lse_ref[0])  # (bq, bk)
        do = do_ref[0].astype(jnp.float32)
        dp = jax.lax.dot_general(
            do, v_ref[0].astype(jnp.float32), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dp - delta_ref[0])
        dq_acc[:] = dq_acc[:] + jax.lax.dot_general(
            ds, kb.astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dvp_ref[0, 0] = jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        ).astype(dvp_ref.dtype)
        dkp_ref[0, 0] = (
            jax.lax.dot_general(
                ds, q.astype(jnp.float32), (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * scale
        ).astype(dkp_ref.dtype)

    if causal or window is not None:
        @pl.when(jnp.logical_not(run))
        def _zero():
            # skipped band-exterior blocks still own their partial block
            dkp_ref[0, 0] = jnp.zeros_like(dkp_ref[0, 0])
            dvp_ref[0, 0] = jnp.zeros_like(dvp_ref[0, 0])

    @pl.when(j == nk - 1)
    def _finish():
        dq_ref[0] = (dq_acc[:] * scale).astype(dq_ref.dtype)


def _bwd_fused(q3, k3, v3, o3, lse, do3, scale, causal, window, bq, bk,
               g, interpret):
    """Fused backward dispatch: dq + f32 dk/dv partials per q block,
    reduced by one XLA sum (and group-summed for GQA). Partial HBM is
    (BH, nq, Lk, D) f32 — the traffic that made this variant measure
    SLOWER than the split kernels on the chip (``_use_fused_bwd``);
    it runs only under an explicit ``bwd_impl="fused"``."""
    BH, Lq, D = q3.shape
    Lk = k3.shape[1]
    nq, nk = Lq // bq, Lk // bk
    delta = jnp.sum(
        do3.astype(jnp.float32) * o3.astype(jnp.float32),
        axis=-1, keepdims=True,
    )
    dq, dkp, dvp = pl.pallas_call(
        functools.partial(
            _bwd_fused_kernel, scale=scale, causal=causal, window=window,
            bq=bq, bk=bk, nk=nk,
        ),
        grid=(BH, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, D), lambda b, i, j: (b // g, j, 0)),
            pl.BlockSpec((1, bk, D), lambda b, i, j: (b // g, j, 0)),
            pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bq, 1), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bq, 1), lambda b, i, j: (b, i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, i, j: (b, i, j, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, i, j: (b, i, j, 0)),
        ],
        out_shape=[
            _sds((BH, Lq, D), q3.dtype, q3),
            _sds((BH, nq, Lk, D), jnp.float32, k3),
            _sds((BH, nq, Lk, D), jnp.float32, v3),
        ],
        scratch_shapes=[pltpu.VMEM((bq, D), jnp.float32)],
        compiler_params=_grid_params(),
        interpret=interpret,
    )(q3, k3, v3, do3, lse, delta)
    BHkv = BH // g
    dk = (
        dkp.reshape(BHkv, g * nq, Lk, D).sum(axis=1).astype(k3.dtype)
    )
    dv = (
        dvp.reshape(BHkv, g * nq, Lk, D).sum(axis=1).astype(v3.dtype)
    )
    return dq, dk, dv


def _use_fused_bwd() -> bool:
    """auto -> split, always. MEASURED NEGATIVE RESULT (round 4, real
    chip, flagship shape B=8 L=2048 H=8 Dh=128): the fused kernel's
    5-vs-7 block-dot saving is outweighed by its (BH, nq, Lk, D) f32
    partial writes + reduction — 27.5 ms vs the split kernels' 16.6 ms
    for the 8-layer attention phase. The kernel is VPU/HBM-co-bound at
    these shapes, so cutting MXU dots does not pay while the extra
    ~nq x f32 dk/dv traffic does. Kept selectable (bwd_impl="fused")
    so the measurement stays reproducible (round 4: earlier
    installation, not repeated on this one)."""
    return False


def _bwd(q3, k3, v3, o3, lse, do3, scale, causal, window, bq, bk, g,
         interpret):
    BH, Lq, D = q3.shape
    Lk = k3.shape[1]
    nq, nk = Lq // bq, Lk // bk
    delta = jnp.sum(
        do3.astype(jnp.float32) * o3.astype(jnp.float32),
        axis=-1, keepdims=True,
    )  # (BH, Lq, 1), same trailing-singleton layout as lse

    dq = pl.pallas_call(
        functools.partial(
            _bwd_dq_kernel, scale=scale, causal=causal, window=window,
            bq=bq, bk=bk, nk=nk,
        ),
        grid=(BH, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, D), lambda b, i, j: (b // g, j, 0)),
            pl.BlockSpec((1, bk, D), lambda b, i, j: (b // g, j, 0)),
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
            bq=bq, bk=bk, nq=nq,
        ),
        grid=(BH, nk, nq),
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((1, bk, D), lambda b, j, i: (b // g, j, 0)),
            pl.BlockSpec((1, bk, D), lambda b, j, i: (b // g, j, 0)),
            pl.BlockSpec((1, bq, D), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((1, bq, 1), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((1, bq, 1), lambda b, j, i: (b, i, 0)),
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
    )(q3, k3, v3, do3, lse, delta)
    if g == 1:
        return dq, dkq, dvq
    BHkv = BH // g
    dk = dkq.reshape(BHkv, g, Lk, D).sum(axis=1).astype(k3.dtype)
    dv = dvq.reshape(BHkv, g, Lk, D).sum(axis=1).astype(v3.dtype)
    return dq, dk, dv


# --------------------------------------------------------------------------
# custom-vjp wrapper over (BH, L, D) tensors
# --------------------------------------------------------------------------


@functools.partial(
    jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9, 10)
)
def _flash3(q3, k3, v3, scale, causal, window, bq, bk, g, fused_bwd,
            interpret):
    o, _ = _fwd(q3, k3, v3, scale, causal, window, bq, bk, g, interpret)
    return o


def _flash3_fwd(q3, k3, v3, scale, causal, window, bq, bk, g, fused_bwd,
                interpret):
    o, lse = _fwd(q3, k3, v3, scale, causal, window, bq, bk, g, interpret)
    return o, (q3, k3, v3, o, lse)


def _flash3_bwd(scale, causal, window, bq, bk, g, fused_bwd, interpret,
                res, do3):
    q3, k3, v3, o3, lse = res
    impl = _bwd_fused if fused_bwd else _bwd
    return impl(
        q3, k3, v3, o3, lse, do3, scale, causal, window, bq, bk, g,
        interpret,
    )


_flash3.defvjp(_flash3_fwd, _flash3_bwd)


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = False,
    scale: float | None = None,
    window: int | None = None,
    block_q: int = 1024,
    block_k: int = 1024,
    bwd_impl: str = "auto",
    interpret: bool | None = None,
) -> jax.Array:
    """Fused flash attention on (B, L, H, D) tensors; differentiable.

    Drop-in for :func:`~..parallel.ring_attention.reference_attention`
    (same layout, same causal semantics) without materializing (L, L)
    scores. Block sizes shrink automatically to divide the sequence
    lengths; ``interpret`` defaults to compiled on TPU and interpret
    mode elsewhere.

    Block defaults were tuned on the chip (round 3: earlier
    installation, not repeated on this one):
    1024x1024 is ~5x the forward throughput of 128x128 (small blocks
    drown in grid overhead — 16k grid steps at L=2048) and the largest
    size whose backward kernels stay inside the 16 MiB VMEM scoped
    allocation (2048-blocks compile for the forward but OOM the dk/dv
    kernel's scratch).

    ``bwd_impl``: ``"split"`` runs the classic two backward kernels
    (dq over the k sweep; dk/dv over the q sweep — each recomputes
    s/dp, 7 block-dots total); ``"fused"`` runs one kernel sharing the
    recompute (5 block-dots) at the cost of an (BH, nq, Lk, D) f32
    dk/dv-partial buffer reduced outside. ``"auto"`` (default)
    resolves to split: the fused variant measured SLOWER on the chip
    at the flagship shape (27.5 vs 16.6 ms for the 8-layer phase) —
    the partial-buffer HBM traffic outweighs the dot saving on this
    VPU/HBM-co-bound kernel (see ``_use_fused_bwd``).
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
    bq = _pick_block(Lq, block_q)
    bk = _pick_block(Lk, block_k)
    if not interpret:  # the interpreter has no VMEM to blow
        _check_vmem(bq, bk, D, q.dtype.itemsize)
    if bwd_impl == "auto":
        fused_bwd = _use_fused_bwd()
    elif bwd_impl in ("split", "fused"):
        fused_bwd = bwd_impl == "fused"
    else:
        raise ValueError(
            f"bwd_impl must be 'auto'|'split'|'fused', got {bwd_impl!r}"
        )

    def to3(x, L, h):
        return x.transpose(0, 2, 1, 3).reshape(B * h, L, D)

    if window is not None and window < 1:
        raise ValueError(f"window must be >= 1, got {window}")
    o3 = _flash3(
        to3(q, Lq, H), to3(k, Lk, Hkv), to3(v, Lk, Hkv),
        float(scale), bool(causal),
        None if window is None else int(window), bq, bk, g, fused_bwd,
        bool(interpret),
    )
    return o3.reshape(B, H, Lq, D).transpose(0, 2, 1, 3)
