"""Pallas TPU kernel for ONE token of a state-space mixer's recurrence
(models/transformer.py ``ssm_half``; Mamba-2's, arXiv:2405.21060), with
the state updated where it lies.

A step of the serving tick runs, for every slot in every layer that
holds such a mixer, ``S = a S + B (dt x)^T; y = S^T C`` a head: ``S``
(state dim N x head dim P, float32; 128 KiB a head at 256 x 128), ``a``
and ``dt`` the token's own scalars a head, ``B`` and ``C`` (N) shared by
the heads of a group. Four operations an element of S against eight
bytes moved: the bytes bound it, and the kernel's whole business is
where S goes, which is ops/delta_rule.py's single-token kernel's story
(its header has the measurements that story rests on): a grid step
takes ``hb`` heads of ONE slot (a whole group's 16 at the published
widths: 2 MiB of S, read from HBM once, updated in VMEM head by head
on the VPU, written once), and the result is aliased to the operand AND
declared to live in HBM (``pltpu.HBM`` as its ``out_shape``), so that a
scan's carry is updated in place and the compiler carries no layer's
``f32[slots, heads, N, P]`` through its fast memory. As there, a
program whose ROOT is the kernel call with S donated is refused by the
compiler's verifier; every caller has an operation behind it.

* S is laid out (slots, heads, N, P): the state dim down the sublanes,
  the head dim along the lanes. A head's ``dt x`` and ``a`` are then
  ROWS (P lanes; ``a`` arrives on every lane, 128 floats a head beside
  its 32,768 of S), the result ``y`` is a sum over the sublanes and
  comes out a row, and B and C scale S's rows: they are needed down
  the sublanes. They arrive as rows, (slots, groups, 1, N), a group's
  once for its heads; the kernel sums the row's diagonal matrix along
  the lanes for the column, as ops/delta_rule.py does for its keys,
  and asks Mosaic for no transpose.
* no product goes over the MXU; every sum is float32 and only the
  order of the N-term sum differs from the plain step's
  (``transformer._ssm_step``). A row with ``a`` = 1 and ``dt`` = 0
  leaves S bit for bit.
* a head NARROWER than a lane tile (Granite-4.0-H's 128 heads of 64 at
  a state dim of 128) shares its tile: ``k = 128 / P`` neighbouring
  heads of one group lie side by side along the lanes, S kept (slots,
  heads / k, N, k P) (:func:`ssm_state_shape`; ``lane_pack`` says k).
  ``dt x``, ``a`` and ``y`` are rows a head, so k heads' rows ARE one
  row of 128 lanes as (slots, heads, P) arrays lie in memory, and B
  and C are the group's: the kernel is the same kernel on ``heads / k``
  heads of ``k P``, each lane decaying by its own head's ``a``. The
  other form weighed (S as published, (heads, P, N), the state dim
  along the lanes, B and C used as the rows they arrive as) needs ``dt
  x`` down the sublanes and hands ``y`` back as a column a head: two
  turns a head through the diagonal trick where this form has none,
  and a second kernel body beside the first.

``ssm_step_viable`` is the route's test (``ssm_half`` asks it through
``transformer.ssm_rule_route``). Inference-only: no VJP. Off the TPU the
kernel runs interpreted, as the flash and decode kernels do.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import _sds, _use_interpret

_LANE = 128
# the most of S a grid step takes: a group's 16 heads of 256 x 128 (its
# two buffers in and two out are 8 MiB of the 16 Mosaic grants unasked)
_STEP_BLOCK = 2 * 2 ** 20

__all__ = ["lane_pack", "ssm_state_shape", "ssm_step", "ssm_step_viable"]


def lane_pack(H: int, G: int, N: int, P: int) -> int:
    """Heads that share a lane tile in the state the kernel keeps: 1 at
    a head size of whole lane tiles (or where the kernel does not take
    the shape at all, :func:`ssm_step_viable`), ``128 / P`` for a head
    that divides a tile."""
    return _pack(P) if ssm_step_viable(H, G, N, P) else 1


def ssm_state_shape(H: int, G: int, N: int, P: int) -> tuple[int, int, int]:
    """One request's ``S`` as the step kernel wants it kept: ``(heads /
    k, N, k P)`` with ``k = lane_pack(...)``; packed head j's lanes
    ``[i P, (i + 1) P)`` are head ``j k + i``'s."""
    k = lane_pack(H, G, N, P)
    return H // k, N, k * P


def _pack(P: int) -> int:
    return _LANE // P if 0 < P < _LANE and _LANE % P == 0 else 1


def _heads_per_step(H: int, G: int, N: int, P: int) -> int:
    """Heads a grid step takes: the most that are heads of ONE group
    (a step reads one B and one C), whose rows of x are whole sublane
    tiles of 8 (or all there are), and whose states are at most
    ``_STEP_BLOCK``; 0 where none is."""
    r = H // G
    fits = lambda d: (r % d == 0 and (d % 8 == 0 or d == H)
                      and 4 * d * N * P <= _STEP_BLOCK)
    return max((d for d in range(1, r + 1) if fits(d)), default=0)


def ssm_step_viable(H: int, G: int, N: int, P: int) -> bool:
    """Whether the kernel takes these heads: the head size is whole
    lane tiles, or divides one so that ``k`` heads fill it; the state
    dim whole sublane tiles; a group serves a whole number of (packs
    of k) heads; and some number of them a grid step is legal
    (:func:`_heads_per_step`)."""
    k = _pack(P)
    return (H > 0 and G > 0 and (k * P) % _LANE == 0 and N % 8 == 0
            and H % (G * k) == 0
            and _heads_per_step(H // k, G, N, k * P) > 0)


def _kernel(u_ref, a_ref, b_ref, c_ref, s_ref, y_ref, so_ref):
    N = s_ref.shape[2]
    eye = (jax.lax.broadcasted_iota(jnp.int32, (N, N), 0)
           == jax.lax.broadcasted_iota(jnp.int32, (N, N), 1))
    # the group's N values down the sublanes (what scales S's rows):
    # the row's diagonal matrix summed along the lanes; exact
    column = lambda x: jnp.sum(jnp.where(eye, x, 0.0), axis=1, keepdims=True)
    Bc, Cc = column(b_ref[0, 0]), column(c_ref[0, 0])         # (N, 1)
    for h in range(u_ref.shape[1]):
        row = lambda ref: ref[0, h:h + 1, :]                  # (1, P)
        S = s_ref[0, h] * row(a_ref) + Bc * row(u_ref)
        so_ref[0, h] = S
        y_ref[0, h:h + 1, :] = jnp.sum(S * Cc, axis=0, keepdims=True)


def ssm_step(x, Bm, Cm, dA, dt, S, *, interpret: bool | None = None):
    """One token of the recurrence with ``S`` updated where it lies:
    ``S = exp(dA) S + B (dt x)^T``, ``y = S^T C``, the arithmetic of
    ``transformer._ssm_step`` in float32 on the VPU. x (B, H, P); Bm, Cm
    (B, G, N), a GROUP each; dA (the log decay) and dt (B, H); S (B, H,
    N, P), all float32; at a head narrower than a lane tile S is
    ``(B,) + ssm_state_shape(...)``, k heads a tile. Returns ``(y,
    S)``: y (B, H, P), without the skip ``D x``; S as it came."""
    if interpret is None:
        interpret = _use_interpret()
    (B, H, P), (_, G, N) = x.shape, Bm.shape
    if not ssm_step_viable(H, G, N, P):
        raise ValueError(
            f"{H} heads of {N} x {P} in {G} groups are not the "
            "single-token kernel's; use the plain step")
    k = _pack(P)
    u = x * dt[..., None]
    if k > 1:  # k heads' rows are one row of a whole lane tile
        u = u.reshape(B, H // k, k * P)
    y, S = ssm_step_call(u, jnp.exp(dA), Bm, Cm, S,
                         hb=_heads_per_step(H // k, G, N, k * P),
                         interpret=interpret)
    return (y.reshape(B, H, P) if k > 1 else y), S


@functools.partial(jax.jit, static_argnames=("hb", "interpret"))
def ssm_step_call(u, a, Bm, Cm, S, *, hb: int, interpret: bool):
    """The pallas_call, ``hb`` heads of one member and group a grid
    step: each head's S read from HBM once and written once to the
    buffer it came from. ``u = dt x`` (B, H, P), ``a`` the decay (B,
    H), or with k heads a lane tile u (B, H / k, k P) beside ``a`` (B,
    H): a lane takes its own head's decay. Jitted, so that the layers
    of a program share ONE traced and lowered kernel; a device trace
    shows the kernel as ``ssm_step``."""
    (B, H, P), (_, G, N) = u.shape, Bm.shape
    per = H // G // hb  # grid steps a group
    headed = pl.BlockSpec((1, hb, P), lambda b, h: (b, h, 0))
    grouped = pl.BlockSpec((1, 1, 1, N), lambda b, h: (b, h // per, 0, 0))
    state = pl.BlockSpec((1, hb, N, P), lambda b, h: (b, h, 0, 0))
    return pl.pallas_call(
        _kernel,
        grid=(B, H // hb),
        in_specs=[headed, headed, grouped, grouped, state],
        out_specs=[headed, state],
        out_shape=[_sds((B, H, P), jnp.float32, u),
                   pltpu.HBM(S.shape, jnp.float32)],
        input_output_aliases={4: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
        name="ssm_step",
    )(u, jnp.broadcast_to(a[..., None], a.shape + (H * P // a.shape[1],)
                          ).reshape(B, H, P), Bm[:, :, None],
      Cm[:, :, None], S)
