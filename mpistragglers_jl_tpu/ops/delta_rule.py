"""Pallas TPU kernels for the gated delta rule of a layer whose mixer
is ``gdn`` (models/transformer.py ``gdn_half``): the chunked form for
the prefill chunks (T > 1), and one token's step for the decode tick
(T = 1; the last part of this header).

The recurrence and its chunked form are ``_delta_rule_chunks``'
(models/transformer.py; the equations are in its docstring). What this
file changes is where the work runs: one grid step takes the rows of
ONE member and up to ``_HEADS`` value heads through all their
sub-chunks with each head's state ``S`` (Dk x Dv float32) resident in
VMEM, read once and written once (the input is aliased to the output).

* q, k and v are read where the depthwise conv left them: lane blocks
  out of the ``(B, T, 2 Hk Dk + Hv Dv)`` float32 array, the index map
  sending a step's value heads to their key heads (``h // (Hv //
  Hk)``); nothing is repeated, split or transposed outside. The l2
  norms of q and k, q's scale and the products q.k and k.k are taken
  once a key head and shared by its value heads.
* g (its running sum inside a sub-chunk, ``G``) and beta arrive as ROWS,
  ``(B, Hv, n, c)``: a sub-chunk's c values of a head along the lanes.
  What scales a ROW of an operand needs them down the sublanes; the
  kernel sums the row's diagonal matrix along the lanes for that and
  asks Mosaic for no transpose.
* the unit lower-triangular system of a sub-chunk, ``(I + A) u = beta
  (v - exp(G) k S)``, is solved by products: ``(I + A)^-1`` by doubling
  (:func:`_unit_lower_inverse`), every intermediate a block of the true
  inverse (no power of A is formed: a Neumann product cancels
  catastrophically where the keys of a sub-chunk are alike), then one
  product with the right-hand side.
* every product is ``Precision.HIGHEST`` on float32 operands (Mosaic's
  ``contract_precision<fp32>``), as the plain form's are: S crosses up
  to 16 chunks and then hundreds of ticks. On the v5e the kernel's S
  and rows are the plain form's to 2e-7 and 2e-8 at the cell's widths
  (PERF.md section 6, PR 40).

Measured on the v5e at the serving cell's shape (4 x 256 rows, 16 key /
32 value heads of 128, device time of the scope's part of ``gdn_half``;
PERF.md section 6, PR 40): the plain form 2.14 ms (1.38 of it the
triangular solve's custom call), the plain form with the solve in
product form 1.10, this kernel 0.69; its sub-chunk of 128 rows beats 64
(0.93: fewer, fuller products) and four heads a step beat one (0.87
against 0.99 before the strip). The kernel is bound by the MXU's
passes: at one bfloat16 pass a product it reads 0.43 where 0.90, and
the inverse is half of what is left.

``delta_rule_viable`` is the route's test (``gdn_half`` asks it through
``gdn_rule_route``; so does the serving scheduler for its span's
``gdn_rule`` argument): what the kernel can take follows from the
shapes alone. Inference-only: no VJP. Off the TPU the kernel runs
interpreted, as the flash and decode kernels do.

ONE TOKEN (``delta_rule_step``; ``delta_step_viable`` is its route's
test). A step of the tick runs ``S' = exp(g) S; S = S' + k (x) beta (v -
S'^T k); o = S^T q`` for every slot in every delta-rule layer: seven
operations an element of S against eight bytes moved, so the bytes
bound it by a factor of five and the kernel's whole business is where
S goes. A grid step takes the heads of ONE slot (all 32 at the cell's
widths: 2 MiB of S, read from HBM once, updated in VMEM head by head on
the VPU in float32, written once), and the result is aliased to the
operand AND declared to live in HBM (``pltpu.HBM`` as its
``out_shape``). The second half is what the gain rests on: the TPU
compiler assigns memory spaces on its own, and for the plain step, and
for an aliased kernel whose result says nothing, it carries each
layer's ``f32[16,32,128,128]`` through its fast memory space with
asynchronous copies and slices of its own (``copy-start`` /
``slice-start`` in the scan's body: in, and out again behind the
update), which cost more than the update. With the result pinned the
scan's carry is the kernel's operand, updated where it lies, and the
compiled body holds no copy, slice or fusion of that shape
(tests/test_decode_attention_tpu_compile.py). One thing that follows
from the pin: a program whose ROOT is the kernel call itself, with S
donated, is refused by the compiler's verifier (the result's memory
space against the parameter's none); any operation between the call
and the program's end, as every caller here has, lifts it.

* q and k arrive a KEY head each, (B, Hk, Dk), normed outside (they
  are a 128th of S); the index map hands a step its slot's key heads
  and the loop serves ``Hv / Hk`` value heads from each: nothing is
  repeated. What scales S's ROWS (k for both updates, q for the
  read-out) is needed down the sublanes: the kernel sums the row's
  diagonal matrix along the lanes, as the chunked kernel does for its
  gates, and asks Mosaic for no transpose; no lane-padded column is
  made in HBM.
* exp(g) and beta arrive on every lane, (B, Hv, Dv) beside v: 2 x 128
  floats a head beside its 16,384 of S, and a row of S is scaled by a
  sublane broadcast.
* no product goes over the MXU; every sum is float32 and only the
  order of a Dk-term sum differs from the plain step's (rows and S to
  1e-6 over 32 consecutive steps, tests/test_delta_rule_kernel.py). A
  row with g = 0 and beta = 0 leaves S bit for bit.

Measured on the v5e at the cell's shape (16 slots x 16 key / 32 value
heads of 128, three layers' states carried by one scan of 256 steps as
the tick carries them; milliseconds a step of all three layers, whose
bytes are 0.246 ms at 819 GB/s; PERF.md section 6, PR 43):

    form                                        ms a step   of the peak
    the plain step, the compiler's 4 copies
      of a state a step with it                   0.432        57%
    this kernel, 32 heads a grid step (2 MiB)     0.317        77%
    this kernel, 16 heads a grid step (1 MiB)     0.318        77%

and in the serving tick itself (traced, 496 steps): the plain step
0.196 under ``gdn_rule`` and 0.25 in the copies and slices that moved
the states, 0.45 together; the kernel 0.316 and no such move. The block
size is not what bounds it (the two read alike, and 77% is about what
a plain elementwise pass reaches on this chip), so a step takes the
most heads ``_STEP_BLOCK`` admits and the fewest grid steps.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import _sds, _use_interpret

_LANE = 128
# rows of a sub-chunk. The plain form's is 64 (``transformer.
# GDN_SUBCHUNK``), which it reads faster at (2.14 ms a layer's call of
# four chunks on the v5e where 128 reads 3.21: the solve's custom call);
# the kernel's products are fewer and fuller at 128: 0.69 where 64 reads
# 0.93
SUBCHUNK = 128
_HI = jax.lax.Precision.HIGHEST
_UNROLL = 4  # sub-chunks whose S-free part the scheduler may overlap
_HEADS = 4   # value heads a grid step takes at most
_STRIP = 16  # rows of the strip that holds the inverse's small blocks
# Mosaic grants a kernel 16 MiB of scoped VMEM unasked; it read 17.4 MiB
# at 1024 rows and four heads a step, 13 of them blocks and states: the
# working set of a head's sub-chunk is about a MiB and a quarter
_VMEM_CAP = 14 * 2 ** 20
_VMEM_HEAD = 5 * 2 ** 18

# the most of S a grid step of the single-token kernel takes: a slot's
# 32 heads of 128 x 128 (its two buffers in and two out are 8 MiB of
# the 16 Mosaic grants unasked)
_STEP_BLOCK = 2 * 2 ** 20

__all__ = ["SUBCHUNK", "chunked_delta_rule", "delta_rule_step",
           "delta_rule_viable", "delta_step_viable"]


def delta_rule_viable(T: int, Hk: int, Hv: int, Dk: int, Dv: int,
                      c: int = SUBCHUNK) -> bool:
    """Whether the kernel takes a call of T rows in sub-chunks of c: the
    head sizes are whole lane tiles, the rows whole sub-chunks, and some
    number of value heads a grid step fits the kernel's VMEM with v's
    first lane (behind q's and k's ``2 Hk Dk``) a whole block of
    them."""
    return (Dk % _LANE == 0 and Dv % _LANE == 0 and T % c == 0 and T >= c
            and c % _STRIP == 0 and c & (c - 1) == 0 and Hv % Hk == 0
            and _heads_per_step(T, Hk, Hv, Dk, Dv) > 0)


def _heads_per_step(T: int, Hk: int, Hv: int, Dk: int, Dv: int) -> int:
    """Value heads a grid step takes: the most, up to ``_HEADS``, that
    are the groups of whole key heads or a part of one key head's
    group, start v on a whole block, and whose rows of q, k, v and o
    (two buffers each), states and working set fit ``_VMEM_CAP``; 0
    where not even one does (a call of thousands of rows)."""
    r = Hv // Hk

    def fits(d):
        rows = 2 * 4 * T * (2 * max(1, d // r) * Dk + 2 * d * Dv)
        return (Hv % d == 0 and (d % r == 0 or r % d == 0)
                and (2 * Hk * Dk) % (d * Dv) == 0
                and rows + d * (16 * Dk * Dv + _VMEM_HEAD) <= _VMEM_CAP)

    return max((d for d in range(1, _HEADS + 1) if fits(d)), default=0)


def _dot(a, b, dims=(((1,), (0,)), ((), ()))):
    return jax.lax.dot_general(a, b, dims, precision=_HI,
                               preferred_element_type=jnp.float32)


def _block_masks(row, col, c: int) -> dict:
    """The masks :func:`_unit_lower_inverse` selects with, made once a
    kernel and not once a sub-chunk: ``two`` and ``strip`` (one block
    of the diagonal at block sizes 2 and ``_STRIP``) and, for every
    level m, the part that joins the two m-blocks of a 2m-block."""
    # m is a power of two: a shift, where ``//`` lowers to a division
    # and two signs an operand (a fifth of the kernel's lowering time)
    same = lambda m: (row >> (m.bit_length() - 1)) == (
        col >> (m.bit_length() - 1))
    joins, m = {}, 2
    while m < c:
        joins[m] = same(2 * m) & ~same(m)
        m *= 2
    return {"eye": row == col, "two": same(2), "strip": same(_STRIP),
            "joins": joins}


def _unit_lower_inverse(A, masks: dict, c: int):
    """``(I + A)^-1`` of a strictly lower-triangular (c, c) ``A`` by
    doubling the block size of the inverted diagonal. With X the
    inverse of the block diagonal at block size m and L the part of A
    that joins the two blocks of each pair, ``X - X L X`` is the
    inverse at 2m; 2 x 2 blocks are ``I - A``, exact. Only the rows of
    a pair's second block change, so only they are pushed through the
    MXU: up to blocks of ``_STRIP`` rows the diagonal blocks lie side
    by side along the lanes of ONE strip of ``_STRIP`` rows (a strip
    times a block-diagonal matrix is the strip of the product), above
    that the second blocks' rows are sliced out and put back
    (sublane-aligned: ``_STRIP`` is two tiles of 8). On the v5e at
    c = 128: every level on all c rows 0.87 ms a layer's call of four
    chunks, second blocks alone 0.79, with the strip 0.69."""
    s, nb = _STRIP, c // _STRIP
    joins = lambda m: jnp.where(masks["joins"][m], A, 0.0)
    X = jnp.where(masks["eye"], 1.0, jnp.where(masks["two"], -A, 0.0))
    strip = sum(X[b * s:(b + 1) * s] for b in range(1, nb)) + X[:s]
    blocks = lambda strip: jnp.where(
        masks["strip"], jnp.concatenate([strip] * nb, 0), 0.0)
    m = 2
    while m < s:
        strip = strip - _dot(_dot(strip, joins(m)), blocks(strip))
        m *= 2
    X = blocks(strip)
    while m < c:
        second = [slice((2 * i + 1) * m, (2 * i + 2) * m)
                  for i in range(c // (2 * m))]
        low = jnp.concatenate([X[rows] for rows in second], 0)
        low = low - _dot(_dot(low, joins(m)), X)
        X = jnp.concatenate([
            part for i, rows in enumerate(second)
            for part in (X[rows.start - m:rows.start],
                         low[i * m:(i + 1) * m])], 0)
        m *= 2
    return X


def _kernel(q_ref, k_ref, v_ref, g_ref, b_ref, gl_ref, s_ref, o_ref, so_ref,
            *, c: int, n: int, hb: int, r: int, scale: float):
    row = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
    masks = _block_masks(row, col, c)
    eye, tri, strict = masks["eye"], row >= col, row > col
    Dk, Dv = s_ref.shape[2:]
    kb = q_ref.shape[2] // Dk  # key heads of this step
    l2 = lambda a: a * jax.lax.rsqrt(
        jnp.sum(a * a, axis=-1, keepdims=True) + 1e-6)
    # a row of c values down the sublanes: its diagonal matrix summed
    # along the lanes (the XLU's; no transpose is asked of Mosaic)
    column = lambda x: jnp.sum(jnp.where(eye, x, 0.0), axis=1, keepdims=True)

    def sub(j, Ss):
        rows = pl.ds(pl.multiple_of(j * c, c), c)
        keyed = []
        for i in range(kb):
            q = l2(q_ref[0, rows, i * Dk:(i + 1) * Dk]) * scale
            k = l2(k_ref[0, rows, i * Dk:(i + 1) * Dk])
            qkk = _dot(jnp.concatenate([q, k], 0), k,
                       (((1,), (1,)), ((), ())))              # (2c, c)
            keyed.append((q, k, qkk[:c], qkk[c:]))
        out = []
        for h, S in enumerate(Ss):
            q, k, qk, kk = keyed[h // r if kb > 1 else 0]
            v = v_ref[0, rows, h * Dv:(h + 1) * Dv]
            Gr = g_ref[0, h, pl.ds(j, 1), :]                  # (1, c)
            Gc, bc = column(Gr), column(b_ref[0, h, pl.ds(j, 1), :])
            Gl = gl_ref[0, h, pl.ds(j, 1), :]     # G_c on every lane
            # exp(G_t - G_s) on and below the diagonal, 0 above: masked
            # before the exponential (above it the difference is positive)
            decay = jnp.exp(jnp.where(tri, Gc - Gr, -jnp.inf))
            X = _unit_lower_inverse(
                jnp.where(strict, kk * decay * bc, 0.0), masks, c)
            eG = jnp.exp(Gc)
            kqS = _dot(jnp.concatenate([k, q], 0), S)         # (2c, Dv)
            u = _dot(X, bc * (v - eG * kqS[:c]))
            o_ref[0, rows, h * Dv:(h + 1) * Dv] = (
                eG * kqS[c:] + _dot(qk * decay, u))
            out.append(S * jnp.exp(Gl) + _dot(
                k * jnp.exp(Gl[:, :1] - Gc), u, (((0,), (0,)), ((), ()))))
        return tuple(out)

    Ss = jax.lax.fori_loop(
        0, n, sub, tuple(s_ref[0, h] for h in range(hb)),
        unroll=n if n <= _UNROLL else 1)
    for h in range(hb):
        so_ref[0, h] = Ss[h]


def chunked_delta_rule(qkv, g, beta, S, *, Hk: int, Hv: int, Dk: int,
                       Dv: int, c: int = SUBCHUNK,
                       interpret: bool | None = None):
    """The gated delta rule over T rows from the state ``S``, c rows a
    sub-chunk. ``qkv`` (B, T, 2 Hk Dk + Hv Dv) float32, laid out ``[q |
    k | v]`` by heads as the conv leaves it (q and k not yet normed);
    ``g`` (log decay), ``beta`` (B, T, Hv) float32; ``S`` (B, Hv, Dk,
    Dv) float32. A row with g = 0 and beta = 0 leaves S as it was
    (padding). Returns ``(o, S)``: o (B, T, Hv Dv) float32."""
    if interpret is None:
        interpret = _use_interpret()
    T = qkv.shape[1]
    if not delta_rule_viable(T, Hk, Hv, Dk, Dv, c):
        raise ValueError(
            f"{T} rows of {Hk} / {Hv} heads of {Dk} x {Dv} in sub-chunks "
            f"of {c} are not the kernel's; use the plain form")
    return delta_rule_call(
        qkv, g, beta, S, Hk=Hk, c=c, hb=_heads_per_step(T, Hk, Hv, Dk, Dv),
        interpret=interpret)


@functools.partial(jax.jit, static_argnames=("Hk", "c", "hb", "interpret"))
def delta_rule_call(qkv, g, beta, S, *, Hk: int, c: int, hb: int,
                    interpret: bool):
    """The pallas_call, ``hb`` value heads a grid step. Jitted, so that
    the layers of a program share ONE traced and lowered kernel (as
    ``paged_decode_attention`` is); a device trace shows the kernel as
    ``delta_rule``."""
    (B, T, _), (_, Hv, Dk, Dv) = qkv.shape, S.shape
    n, r = T // c, Hv // Hk
    kb = max(1, hb // r)  # key heads a step reads
    # a head's values of a sub-chunk along the lanes: (B, Hv, n, c)
    lanes = lambda a: jnp.moveaxis(a.reshape(B, n, c, Hv), 3, 1)
    G = lanes(jnp.cumsum(g.reshape(B, n, c, Hv), axis=2))
    Gl = jnp.broadcast_to(G[..., -1:], (B, Hv, n, Dv))  # G_c, lane-wide
    kern = functools.partial(_kernel, c=c, n=n, hb=hb, r=r,
                             scale=Dk ** -0.5)
    vec = pl.BlockSpec((1, hb, n, c), lambda b, h: (b, h, 0, 0))
    state = pl.BlockSpec((1, hb, Dk, Dv), lambda b, h: (b, h, 0, 0))
    o, S = pl.pallas_call(
        kern,
        grid=(B, Hv // hb),
        in_specs=[
            pl.BlockSpec((1, T, kb * Dk),
                         lambda b, h: (b, 0, h * hb // (r * kb))),
            pl.BlockSpec((1, T, kb * Dk),
                         lambda b, h: (b, 0, Hk // kb + h * hb // (r * kb))),
            pl.BlockSpec((1, T, hb * Dv),
                         lambda b, h: (b, 0, 2 * Hk * Dk // (hb * Dv) + h)),
            vec, vec,
            pl.BlockSpec((1, hb, n, Dv), lambda b, h: (b, h, 0, 0)),
            state,
        ],
        out_specs=[pl.BlockSpec((1, T, hb * Dv), lambda b, h: (b, 0, h)),
                   state],
        out_shape=[_sds((B, T, Hv * Dv), jnp.float32, qkv),
                   _sds(S.shape, jnp.float32, S)],
        input_output_aliases={6: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
        name="delta_rule",
    )(qkv, qkv, qkv, G, lanes(beta), Gl, S)
    return o, S


# -- one token ----------------------------------------------------------------


def delta_step_viable(Hk: int, Hv: int, Dk: int, Dv: int) -> bool:
    """Whether the single-token kernel takes these heads: the head
    sizes are whole lane tiles, a key head serves a whole number of
    value heads, and some number of value heads a grid step is legal
    (:func:`_heads_per_token`)."""
    return (Dk % _LANE == 0 and Dv % _LANE == 0 and Hv % Hk == 0
            and _heads_per_token(Hk, Hv, Dk, Dv) > 0)


def _heads_per_token(Hk: int, Hv: int, Dk: int, Dv: int) -> int:
    """Value heads a grid step of the single-token kernel takes: the
    most that are the groups of whole key heads, whose rows of v and of
    q and k are whole sublane tiles of 8 (or all there are), and whose
    states are at most ``_STEP_BLOCK``; 0 where none is."""
    r = Hv // Hk
    rows = lambda d, H: d % 8 == 0 or d == H
    fits = lambda d: (Hv % d == 0 and d % r == 0 and rows(d, Hv)
                      and rows(d // r, Hk)
                      and 4 * d * Dk * Dv <= _STEP_BLOCK)
    return max((d for d in range(1, Hv + 1) if fits(d)), default=0)


def _step_kernel(q_ref, k_ref, v_ref, a_ref, b_ref, s_ref, o_ref, so_ref,
                 *, r: int):
    Dk, Dv = s_ref.shape[2:]
    eye = (jax.lax.broadcasted_iota(jnp.int32, (Dk, Dk), 0)
           == jax.lax.broadcasted_iota(jnp.int32, (Dk, Dk), 1))
    # a key head's Dk values down the sublanes (what scales S's rows):
    # the row's diagonal matrix summed along the lanes, as in the
    # chunked kernel; exact, and no transpose is asked of Mosaic
    column = lambda x: jnp.sum(jnp.where(eye, x, 0.0), axis=1, keepdims=True)
    for i in range(q_ref.shape[1]):
        q, k = column(q_ref[0, i:i + 1, :]), column(k_ref[0, i:i + 1, :])
        for h in range(i * r, (i + 1) * r):
            row = lambda ref: ref[0, h:h + 1, :]              # (1, Dv)
            S = s_ref[0, h] * row(a_ref)                      # exp(g) S
            mem = jnp.sum(S * k, axis=0, keepdims=True)       # S'^T k
            S = S + k * ((row(v_ref) - mem) * row(b_ref))
            so_ref[0, h] = S
            o_ref[0, h:h + 1, :] = jnp.sum(S * q, axis=0, keepdims=True)


def delta_rule_step(q, k, v, g, beta, S, *, interpret: bool | None = None):
    """One token of the gated delta rule with ``S`` updated where it
    lies: ``S' = exp(g) S``, ``S = S' + k (x) beta (v - S'^T k)``, ``o =
    S^T q``, the arithmetic of ``transformer._delta_rule_step`` in
    float32 on the VPU (no product goes over the MXU; only the order
    of the Dk-term sums may differ). q, k (B, Hk, Dk), normed and
    scaled, a KEY head each; v (B, Hv, Dv); g, beta (B, Hv); S (B, Hv,
    Dk, Dv), all float32. Returns ``(o, S)``: o (B, Hv, Dv)."""
    if interpret is None:
        interpret = _use_interpret()
    (_, Hk, Dk), (_, Hv, Dv) = q.shape, v.shape
    if not delta_step_viable(Hk, Hv, Dk, Dv):
        raise ValueError(
            f"{Hk} / {Hv} heads of {Dk} x {Dv} are not the single-token "
            "kernel's; use the plain step")
    return delta_step_call(q, k, v, g, beta, S,
                           hb=_heads_per_token(Hk, Hv, Dk, Dv),
                           interpret=interpret)


@functools.partial(jax.jit, static_argnames=("hb", "interpret"))
def delta_step_call(q, k, v, g, beta, S, *, hb: int, interpret: bool):
    """The pallas_call, ``hb`` value heads of one member a grid step:
    each head's S read from HBM once and written once to the buffer it
    came from. Jitted for the reason :func:`delta_rule_call` is; a
    device trace shows the kernel as ``delta_rule_step``."""
    (B, Hk, Dk), (_, Hv, Dv) = q.shape, v.shape
    r = Hv // Hk
    # a head's decay and beta on every lane (the kernel scales rows of
    # Dv lanes by them): 2 x 128 floats a head beside its 16,384 of S
    lanes = lambda a: jnp.broadcast_to(a[..., None], (B, Hv, Dv))
    keyed = pl.BlockSpec((1, hb // r, Dk), lambda b, h: (b, h, 0))
    valued = pl.BlockSpec((1, hb, Dv), lambda b, h: (b, h, 0))
    state = pl.BlockSpec((1, hb, Dk, Dv), lambda b, h: (b, h, 0, 0))
    return pl.pallas_call(
        functools.partial(_step_kernel, r=r),
        grid=(B, Hv // hb),
        in_specs=[keyed, keyed, valued, valued, valued, state],
        out_specs=[valued, state],
        out_shape=[_sds((B, Hv, Dv), jnp.float32, v),
                   pltpu.HBM(S.shape, jnp.float32)],
        input_output_aliases={5: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
        name="delta_rule_step",
    )(q, k, v, lanes(jnp.exp(g)), lanes(beta), S)
