"""Sequence/context parallelism: ring attention and Ulysses all-to-all.

Long-context support is first-class in this framework even though the
reference has none of it ("no ring attention, no context/sequence
parallel, no attention or model code of any kind" — SURVEY §5
'Long-context'): a framework at this scale must handle sequences longer
than one chip's HBM, and the mechanisms below are the TPU-native way.

Two complementary strategies over an ``"sp"`` mesh axis of size n:

* **Ring attention** (:func:`ring_self_attention`): Q stays put; K/V
  blocks rotate around the ring via ``jax.lax.ppermute`` (one ICI hop
  per step), with numerically-stable *online softmax* accumulation so no
  device ever materializes the full (L, L) score matrix or the full K/V.
  Memory per device is O(L/n), traffic is n-1 block transfers fully
  overlappable with the block matmuls. Causal masking is applied from
  global positions, so whole future blocks contribute zeros (XLA still
  executes them — static shapes — but no extra communication happens).
* **Ulysses all-to-all** (:func:`ulysses_attention`): one
  ``jax.lax.all_to_all`` re-shards sequence-sharded Q/K/V into
  head-sharded full-sequence tensors, attention runs *unsharded per
  head group* on each device, and a second all-to-all restores sequence
  sharding. Two collectives total, best when n divides the head count.

Both are written as *per-shard* functions to be called inside a
``shard_map`` (composable into larger SPMD programs — see
models/transformer.py, which runs them inside its dp x sp x tp train
step); ``make_ring_attention`` / ``make_ulysses_attention`` wrap them
into standalone jitted callables over global arrays.

Layout convention: activations are (batch, seq, heads, head_dim), the
TPU-friendly layout where the trailing two dims (heads*head_dim) tile
onto the MXU/VPU lanes and the sequence axis is shardable.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

__all__ = [
    "ring_self_attention",
    "ulysses_attention",
    "make_ring_attention",
    "make_ulysses_attention",
    "reference_attention",
    "resolve_attention_impl",
]

_NEG = -1e30  # large-negative mask value; -inf breaks the m-update exp


def _group_scores(q, kc, scale):
    """(B, Lq, H, D) x (B, Lk, Hkv, D) -> (B, H, Lq, Lk) scores with
    GQA grouping: q head h reads kv head h // (H // Hkv). The 5D einsum
    keeps the MXU contraction batched per kv head — no repeated K."""
    Hq, Hkv = q.shape[2], kc.shape[2]
    if Hq == Hkv:
        return jnp.einsum(
            "bqhd,bkhd->bhqk", q, kc, preferred_element_type=jnp.float32
        ) * scale
    B, Lq, _, D = q.shape
    g = Hq // Hkv
    q5 = q.reshape(B, Lq, Hkv, g, D)
    s5 = jnp.einsum(
        "bqhgd,bkhd->bhgqk", q5, kc, preferred_element_type=jnp.float32
    ) * scale
    # (hkv, g) flattens to h = hkv*g + g_idx — exactly q's head order
    return s5.reshape(B, Hq, Lq, kc.shape[1])


def _group_pv(p, vc):
    """(B, H, Lq, Lk) probs x (B, Lk, Hkv, D) values -> (B, Lq, H, D)
    f32, with the same GQA head grouping as :func:`_group_scores`."""
    Hq, Hkv = p.shape[1], vc.shape[2]
    vf = vc.astype(jnp.float32)
    if Hq == Hkv:
        return jnp.einsum(
            "bhqk,bkhd->bqhd", p, vf, preferred_element_type=jnp.float32
        )
    B, _, Lq, Lk = p.shape
    g = Hq // Hkv
    p5 = p.reshape(B, Hkv, g, Lq, Lk)
    o5 = jnp.einsum(
        "bhgqk,bkhd->bqhgd", p5, vf, preferred_element_type=jnp.float32
    )
    return o5.reshape(B, Lq, Hq, vc.shape[-1])


def _band_mask(qpos, kpos, causal, window):
    """(Lq, Lk) visibility: causal (kpos <= qpos) intersected with a
    sliding window of ``window`` positions (qpos - kpos < window) when
    set — the Mistral-style attention band. Returns None when nothing
    is masked."""
    mask = None
    if causal:
        mask = kpos[None, :] <= qpos[:, None]
    if window is not None:
        band = qpos[:, None] - kpos[None, :] < window
        mask = band if mask is None else jnp.logical_and(mask, band)
    return mask


def _block_update(q, kc, vc, o, m, l, qpos, kpos, scale, causal,
                  window=None):
    """One online-softmax accumulation step against K/V block (kc, vc).

    q: (B, Lq, H, D); kc/vc: (B, Lk, Hkv, D) where Hkv divides H (GQA;
    Hkv == H is plain MHA); o: (B, Lq, H, D) f32; m, l: (B, H, Lq) f32
    running max / normalizer.
    """
    s = _group_scores(q, kc, scale)
    mask = _band_mask(qpos, kpos, causal, window)
    if mask is not None:
        s = jnp.where(mask[None, None], s, _NEG)
    m_new = jnp.maximum(m, s.max(axis=-1))
    # rows with nothing visible yet keep m=_NEG; their p underflows to 0
    p = jnp.exp(s - m_new[..., None])
    if mask is not None:
        p = jnp.where(mask[None, None], p, 0.0)
    corr = jnp.exp(m - m_new)  # (B, H, Lq)
    l = l * corr + p.sum(axis=-1)
    o = o * corr.transpose(0, 2, 1)[..., None] + _group_pv(p, vc)
    return o, m_new, l


def ring_self_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    axis: str = "sp",
    causal: bool = False,
    scale: float | None = None,
    window: int | None = None,
) -> jax.Array:
    """Exact attention over ring-sharded sequence; call inside shard_map.

    Arguments are the *local* sequence chunks: (B, L/n, H, D) each. The
    K/V pair makes n-1 hops around the ring (``ppermute`` under a
    ``lax.scan``, so the loop is compiled once); the online-softmax
    carry (o, m, l) makes the result exact, not approximate. Returns the
    local (B, L/n, H, D) output chunk, in q's dtype.
    """
    n = jax.lax.axis_size(axis)
    me = jax.lax.axis_index(axis)
    Lc = q.shape[1]
    if scale is None:
        scale = q.shape[-1] ** -0.5
    qpos = me * Lc + jnp.arange(Lc)

    # derive the accumulators from q so they inherit its full set of
    # varying mesh axes (not just the ring axis — the enclosing
    # shard_map may span dp/tp too) and the scan carry types match
    o0 = q.astype(jnp.float32) * 0.0
    zeros = o0.sum(-1).transpose(0, 2, 1)  # (B, H, Lq)
    m0 = zeros + _NEG
    l0 = zeros
    perm = [(j, (j + 1) % n) for j in range(n)]

    # step 0: the resident block, no communication
    o, m, l = _block_update(
        q, k, v, o0, m0, l0, qpos, me * Lc + jnp.arange(Lc), scale,
        causal, window,
    )

    def step(carry, i):
        o, m, l, kc, vc = carry
        # rotate K/V one hop first, then accumulate — n-1 hops total, no
        # discarded final transfer
        kc = jax.lax.ppermute(kc, axis, perm)
        vc = jax.lax.ppermute(vc, axis, perm)
        src = (me - i) % n  # who originally owned the block we now hold
        kpos = src * Lc + jnp.arange(Lc)
        o, m, l = _block_update(
            q, kc, vc, o, m, l, qpos, kpos, scale, causal, window
        )
        return (o, m, l, kc, vc), None

    (o, m, l, _, _), _ = jax.lax.scan(
        step, (o, m, l, k, v), jnp.arange(1, n)
    )
    l = jnp.maximum(l, 1e-20)  # fully-masked rows (non-causal never hits)
    out = o / l.transpose(0, 2, 1)[..., None]
    return out.astype(q.dtype)


def ulysses_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    axis: str = "sp",
    causal: bool = False,
    scale: float | None = None,
    impl: str = "reference",
    window: int | None = None,
) -> jax.Array:
    """All-to-all sequence parallelism; call inside shard_map.

    Local chunks (B, L/n, H, D) are re-sharded by one ``all_to_all``
    into (B, L, H/n, D) — full sequence, head subset — attention runs
    locally, and the inverse all_to_all restores (B, L/n, H, D).
    Requires H % n == 0. ``impl="flash"`` runs the per-device attention
    as the fused Pallas kernel (ops/flash_attention.py) instead of the
    materializing reference — the memory-sane choice at long L, since
    the device holds the *full* sequence here.

    GQA/MQA: k/v may carry Hkv < H heads. When ``Hkv % n == 0`` the K/V
    all_to_all splits the kv heads like the q heads (Hkv/n per device,
    group alignment is automatic because H % Hkv == 0). When instead
    ``n % Hkv == 0`` the kv heads are first replicated n/Hkv-fold so the
    head axis reaches n and each device lands exactly the ONE kv head
    its q-head slice reads — K/V traffic grows back toward MHA only in
    this sp-overshard regime, and never beyond it. Anything else is
    rejected (q-head slices would straddle kv-head boundaries).
    """
    n = jax.lax.axis_size(axis)
    if q.shape[2] % n != 0:
        raise ValueError(
            f"ulysses needs heads ({q.shape[2]}) divisible by the "
            f"sequence-parallel degree ({n})"
        )
    Hkv = k.shape[2]
    if Hkv % n != 0:
        if n % Hkv != 0:
            raise ValueError(
                f"ulysses with GQA needs kv heads ({Hkv}) and the "
                f"sequence-parallel degree ({n}) to divide one another"
            )
        r = n // Hkv
        k = jnp.repeat(k, r, axis=2)  # now n heads; device d gets d//r
        v = jnp.repeat(v, r, axis=2)
    # (B, L/n, H, D) -> (B, L, H/n, D): split heads, concat sequence
    a2a = partial(
        jax.lax.all_to_all, axis_name=axis, split_axis=2, concat_axis=1,
        tiled=True,
    )
    qf, kf, vf = a2a(q), a2a(k), a2a(v)
    of = resolve_attention_impl(impl)(
        qf, kf, vf, causal=causal, scale=scale, window=window
    )
    # inverse: split sequence back out, concat heads
    return jax.lax.all_to_all(
        of, axis_name=axis, split_axis=1, concat_axis=2, tiled=True
    )


def resolve_attention_impl(impl: str):
    """Resolve a per-device (unsharded) attention kernel by name: the
    materializing ``"reference"`` oracle or the fused Pallas ``"flash"``
    kernel. Shared by Ulysses and the model configs so the accepted
    names cannot drift."""
    if impl == "flash":
        from ..ops.flash_attention import flash_attention

        return flash_attention
    if impl == "reference":
        return reference_attention
    raise ValueError(f"unknown attention impl {impl!r}")


def reference_attention(q, k, v, *, causal=False, scale=None,
                        window=None):
    """Plain full-materialization attention (the correctness oracle and
    the per-device kernel inside Ulysses). (B, L, H, D) layout; k/v may
    carry fewer (grouped) heads — GQA/MQA — expanded here by repeat,
    the obviously-correct oracle form. ``window`` adds the sliding-
    window band (qpos - kpos < window)."""
    if scale is None:
        scale = q.shape[-1] ** -0.5
    g = q.shape[2] // k.shape[2]
    if g > 1:
        k = jnp.repeat(k, g, axis=2)  # head h <- kv head h // g
        v = jnp.repeat(v, g, axis=2)
    s = jnp.einsum(
        "bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32
    ) * scale
    mask = _band_mask(
        jnp.arange(q.shape[1]), jnp.arange(k.shape[1]), causal, window
    )
    if mask is not None:
        s = jnp.where(mask[None, None], s, _NEG)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum(
        "bhqk,bkhd->bqhd", p, v.astype(p.dtype),
        preferred_element_type=jnp.float32,
    )
    return out.astype(q.dtype)


def _make_wrapped(inner, mesh: Mesh, axis: str, causal: bool, **kw):
    spec = P(None, axis, None, None)

    def per_shard(q, k, v):
        return inner(q, k, v, axis=axis, causal=causal, **kw)

    # check_vma must stay on except for Pallas-in-interpret-mode (i.e.
    # flash on a non-TPU backend): the Pallas HLO interpreter (CPU-mesh
    # test path) evaluates block dynamic_slices whose index operands
    # carry no vma, which trips shard_map's vma checker; JAX's own error
    # message prescribes this workaround. On TPU the kernel is compiled,
    # declares its vma (flash_attention._sds), and the check stays on.
    f = jax.shard_map(
        per_shard, mesh=mesh,
        in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=not _flash_interpreted(kw.get("impl")),
    )
    return jax.jit(f)


def _flash_interpreted(impl) -> bool:
    """True iff the flash kernel would run via the Pallas interpreter."""
    if impl != "flash":
        return False
    from ..ops.flash_attention import _use_interpret

    return _use_interpret()


def make_ring_attention(mesh: Mesh, *, axis: str = "sp",
                        causal: bool = False, window: int | None = None):
    """Jitted ring attention over global (B, L, H, D) arrays sequence-
    sharded along ``axis`` of ``mesh``."""
    return _make_wrapped(
        ring_self_attention, mesh, axis, causal, window=window
    )


def make_ulysses_attention(
    mesh: Mesh, *, axis: str = "sp", causal: bool = False,
    impl: str = "reference", window: int | None = None,
):
    """Jitted Ulysses attention over global (B, L, H, D) arrays."""
    return _make_wrapped(
        ulysses_attention, mesh, axis, causal, impl=impl, window=window
    )
