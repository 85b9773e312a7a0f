"""Plain reference of the decoder block the ``qwen3_next`` configuration
runs (``Qwen/Qwen3-Next-80B-A3B-Instruct``): RMSNorm before each half
and none after, three gated delta-rule layers to one gated
full-attention layer, and in every layer softmax top-k experts beside a
gated shared expert, of which this chip holds a share; an untied head.

Straightforward ``jax.numpy`` in float32 at ``Precision.HIGHEST``: no
kernel, no cache, no grouping of tokens by expert, and the delta rule
as the recurrence itself, one token at a time (``lax.scan`` over the
sequence carrying ``S``). It imports nothing of the program (only its
sibling reference's shared pieces) and is handed only arrays that the
benchmark made. To fit beside the bfloat16 weights on one chip it works
layer by layer and, inside a layer, over blocks of the stream's rows
(``layer_forward_rows``): the delta rule's state and the conv's last
rows are carried from block to block, a block of query rows attends
the keys so far one head at a time, and the routed experts compute the
(row, expert) pairs the router chose and no other. ``layer_forward`` is
the same block over the whole stream at once, with the whole score
matrix and every row through every held expert: it is what the tests
compare the blocked forms with, and it cannot take a stream of tens of
thousands of rows (16 heads x 47k x 47k scores are 141 GB, 256 experts
over every row 76 PFLOP a layer).

The layer, with what the published ``config.json`` does not carry
marked A (each is how the ``qwen3_next`` modelling code has it, and each
is listed under ``assumed`` in the configuration file):

* ``x0 = emb[tok]``; ``x = x + mixer(RMSNorm(x))``;
  ``x = x + experts(RMSNorm(x))``; eps ``rms_norm_eps``, no bias
  anywhere (A).
* gated attention (a layer with ``wq``): ``a = RMSNorm(x)``; q and a
  gate as wide as q, k, v from a; q and k each RMSNorm over the head
  (A); rotary, base ``rope_theta``, on the first ``partial_rotary_factor
  * head_dim`` dims of a head, pairs ``(i, i + 32)`` (A: the pairing);
  causal attention at scale ``Dh ** -0.5``;
  ``x = x + (o * sigmoid(gate)) wo`` (A).
* gated delta rule (a layer with ``gdn_wqkvz``): ``[q | k | v | z] = a
  Wqkvz``, ``[b | a] = a Wba``; ``(q, k, v) = silu(causal depthwise
  conv over the sequence)``, ``linear_conv_kernel_dim`` taps, no bias
  (A); ``q = l2norm(q) / sqrt(Dk)``, ``k = l2norm(k)`` (eps 1e-6 inside
  the root, A); key head j serves value heads ``[j r, (j + 1) r)``;
  per value head, in float32, ``beta = sigmoid(b)``,
  ``g = -exp(A_log) softplus(a + dt_bias)``; ``S' = exp(g) S``;
  ``S = S' + k (x) beta (v - S'^T k)``; ``o = S^T q``;
  ``y = RMSNorm_Dv(o) * silu(z)``; ``x = x + y Wout`` (A, all of it:
  the config carries the sizes alone).
* experts: ``p = softmax(h router)`` over ALL experts; the ``k``
  largest; ``w = p / sum of the k`` (``norm_topk_prob``);
  ``m = sigmoid(h ws_sgate) shared(h) + sum over the chosen experts
  HELD HERE of w_e expert_e(h)``, written as a plain sum over all the
  held experts with weight zero for the ones not chosen. The matrices
  handed in are those of experts ``[held_lo, held_lo + E_held)``; what
  the others would add is left out, as in the program.
* ``logits = RMSNorm(x) head``.

``precision`` other than ``"float32"`` is the *control* of the output
check (chipbench/control.py): the same mathematics with both inputs of
every matrix product, the recurrence's two among them, rounded to a
lower precision first.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

# a product with both inputs rounded to the control's precision, and
# grouped-query attention over a causal band, one (T, T) score matrix
# at a time: the dense block's reference has both
from chipbench.references.dense_transformer import HIGHEST, _mm, attention

RMS_EPS = 1e-6        # rms_norm_eps
TOP_K = 10            # num_experts_per_tok
ROPE_BASE = 1e7       # rope_theta
ROPE_DIMS = 64        # partial_rotary_factor 0.25 of head_dim 256
KEY_HEADS = 16        # linear_num_key_heads
KEY_DIM = 128         # linear_key_head_dim
EXPERT_BLOCK = 8      # experts upcast and multiplied at a time


def rms_norm(x, s, eps: float = RMS_EPS):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * s


def l2_norm(x):
    return x * jax.lax.rsqrt((x * x).sum(-1, keepdims=True) + 1e-6)


def rope_partial(x, pos, dims: int, base: float):
    """x: (B, T, H, Dh); the first ``dims`` of a head rotate, pairs
    (i, i + dims/2) by pos * base^(-i/(dims/2)); the rest pass."""
    half = dims // 2
    freqs = 1.0 / (base ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = pos.astype(jnp.float32)[:, None] * freqs[None, :]
    cos = jnp.cos(ang)[None, :, None, :]
    sin = jnp.sin(ang)[None, :, None, :]
    x1, x2, rest = x[..., :half], x[..., half:dims], x[..., dims:]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x1 * sin + x2 * cos, rest], -1)


def gated_mlp(h, w_gate, w_up, w_down, precision: str):
    a = jax.nn.silu(_mm("btd,df->btf", h, w_gate, precision))
    return _mm("btf,fd->btd", a * _mm("btd,df->btf", h, w_up, precision),
               w_down, precision)


def route_weights(h, router, top_k: int, precision: str):
    """(B, T, E) weights over ALL experts: the softmax score of each
    chosen expert over the sum of the chosen, zero for the others."""
    p = jax.nn.softmax(_mm("btd,de->bte", h, router, precision), axis=-1)
    _, idx = jax.lax.top_k(p, top_k)
    w = p * jax.nn.one_hot(idx, p.shape[-1], dtype=jnp.float32).sum(-2)
    return w / w.sum(-1, keepdims=True)


def experts_sum(h, lp, w, precision: str, block: int = EXPERT_BLOCK):
    """sum_e w[..., e] * expert_e(h) over the experts whose matrices
    ``lp`` holds (``w``: their columns), ``block`` of them at a time."""
    E = lp["we_gate"].shape[0]
    block = math.gcd(E, block)
    blocks = lambda a: a.reshape((E // block, block) + a.shape[1:])

    def one_block(acc, args):
        wg, wu, wd, wb = args
        f32 = lambda a: a.astype(jnp.float32)
        a = jax.nn.silu(_mm("btd,edf->ebtf", h, f32(wg), precision))
        a = a * _mm("btd,edf->ebtf", h, f32(wu), precision)
        y = _mm("ebtf,efd->ebtd", a, f32(wd), precision)
        return acc + jnp.einsum("ebtd,bte->btd", y, wb, precision=HIGHEST), None

    wb = jnp.moveaxis(w.reshape(w.shape[:-1] + (E // block, block)), -2, 0)
    acc, _ = jax.lax.scan(
        one_block, jnp.zeros_like(h),
        (blocks(lp["we_gate"]), blocks(lp["we_up"]), blocks(lp["we_down"]), wb),
    )
    return acc


def experts_routed(h, lp, w, precision: str, top_k: int,
                   capacity: int | None = None, block: int = EXPERT_BLOCK):
    """``experts_sum`` over the (row, expert) pairs whose weight is not
    zero, and no other: the weights elsewhere are exactly zero, so it
    is the same sum, in another order of additions. For each held
    expert the rows that chose it, ``capacity`` of them a pass (an
    expert with more gets further passes, so no pair is dropped), go
    through its three matrices; a row then adds up what its own pairs
    gave, each at its weight: at most ``top_k`` of them.
    h: (1, T, D); w: (1, T, E_held)."""
    h2, w2 = h[0], w[0]
    T, E = w2.shape
    D = h2.shape[-1]
    C = capacity or max(8, -(-T // 16))
    block = math.gcd(E, block)
    blocks = lambda a: a.reshape((E // block, block) + a.shape[1:])
    chosen = w2 != 0
    load = chosen.sum(0)                         # rows that chose an expert
    # an expert's rows first, in their order, and a row's place among them
    order = jnp.argsort(jnp.logical_not(chosen), axis=0, stable=True)
    order = jnp.concatenate([order, jnp.full((C, E), T, order.dtype)])
    place = jnp.cumsum(chosen, axis=0) - 1       # (T, E)
    h_pad = jnp.concatenate([h2, jnp.zeros((1, D), h2.dtype)])  # row T: none
    # a row's own pairs: its non-zero weights are among its top_k largest
    wk, ek = jax.lax.top_k(w2, min(top_k, E))    # (T, k)
    place_k = jnp.take_along_axis(place, ek, axis=1)

    def one_pass(state):
        p, acc = state
        rows = jax.lax.dynamic_slice_in_dim(order, p * C, C, axis=0)
        live = (p * C + jnp.arange(C))[:, None] < load[None, :]
        rows = jnp.where(live, rows, T).T        # (E, C)

        def one_block(_, args):
            wg, wu, wd, rb = args
            f32 = lambda a: a.astype(jnp.float32)
            hb = h_pad[rb]                       # (block, C, D)
            a = jax.nn.silu(_mm("ecd,edf->ecf", hb, f32(wg), precision))
            a = a * _mm("ecd,edf->ecf", hb, f32(wu), precision)
            return None, _mm("ecf,efd->ecd", a, f32(wd), precision)

        _, y = jax.lax.scan(
            one_block, None,
            (blocks(lp["we_gate"]), blocks(lp["we_up"]),
             blocks(lp["we_down"]), blocks(rows)))
        at = place_k - p * C
        mine = (wk != 0) & (at >= 0) & (at < C)
        y = y.reshape(E * C, D)[jnp.where(mine, ek * C + at, 0)]  # (T, k, D)
        return p + 1, acc + jnp.einsum(
            "tkd,tk->td", y, jnp.where(mine, wk, 0.0), precision=HIGHEST)

    _, acc = jax.lax.while_loop(
        lambda state: state[0] * C < load.max(), one_pass,
        (jnp.int32(0), jnp.zeros_like(h2)))
    return acc[None]


def attention_rows(q, k, v, row0: int, precision: str):
    """``attention`` for the query rows ``row0..`` alone against the
    keys so far. q: (B, R, H, Dh); k, v: (B, row0 + R, Hkv, Dh), the
    stream's keys up to the block's last row. One head's (R, row0 + R)
    scores at a time."""
    B, R, H, Dh = q.shape
    K = k.shape[1]
    group = H // k.shape[2]
    mask = (row0 + jnp.arange(R))[:, None] >= jnp.arange(K)[None, :]
    scale = 1.0 / math.sqrt(Dh)

    def one_head(args):
        qh, kh, vh = args
        s = _mm("qd,kd->qk", qh, kh, precision) * scale
        p = jax.nn.softmax(jnp.where(mask, s, -1e30), axis=-1)
        return _mm("qk,kd->qd", p, vh, precision)

    heads = lambda t: t.transpose(0, 2, 1, 3)
    qf = heads(q).reshape(B * H, R, Dh)
    kf = jnp.repeat(heads(k), group, axis=1).reshape(B * H, K, Dh)
    vf = jnp.repeat(heads(v), group, axis=1).reshape(B * H, K, Dh)
    o = jax.lax.map(one_head, (qf, kf, vf))
    return o.reshape(B, H, R, Dh).transpose(0, 2, 1, 3)


def gated_attention(a, f, *, rope_dims: int, precision: str,
                    rows: int | None = None):
    """The attention mixer on normed a: (B, T, D); f: float32 leaves.
    With ``rows``, a block of that many query rows at a time against
    the keys so far; without, the whole (T, T) score matrix of a head."""
    T = a.shape[1]
    pos = jnp.arange(T)
    k = rms_norm(_mm("btd,dhk->bthk", a, f["wk"], precision), f["kn_s"])
    k = rope_partial(k, pos, rope_dims, ROPE_BASE)
    v = _mm("btd,dhk->bthk", a, f["wv"], precision)

    def block(r0, r1):
        ab = a[:, r0:r1]
        q = rms_norm(_mm("btd,dhk->bthk", ab, f["wq"], precision), f["qn_s"])
        q = rope_partial(q, pos[r0:r1], rope_dims, ROPE_BASE)
        gate = _mm("btd,dhk->bthk", ab, f["wog"], precision)
        if rows is None:
            o = attention(q, k, v, None, precision)
        else:
            o = attention_rows(q, k[:, :r1], v[:, :r1], r0, precision)
        return _mm("bthk,hkd->btd", o * jax.nn.sigmoid(gate), f["wo"],
                   precision)

    if rows is None:
        return block(0, T)
    return jnp.concatenate(
        [block(r0, r0 + rows) for r0 in range(0, T, rows)], axis=1)


def gated_delta(a, f, *, key_heads: int, key_dim: int, precision: str,
                carry=None):
    """The gated delta-rule mixer on normed a: (B, T, D), one token at
    a time, from a zero state; or, given ``carry`` (the state ``S`` and
    the last ``taps - 1`` rows that went into the conv, as an earlier
    block of the stream's rows left them), from there, and then the
    block's own ``(S, rows)`` is returned beside the result."""
    B, T, _ = a.shape
    Hv, Dv = f["gdn_A_log"].shape[0], f["gdn_norm_s"].shape[0]
    Hk, Dk = key_heads, key_dim
    kw, vw = Hk * Dk, Hv * Dv
    qkvz = _mm("btd,dc->btc", a, f["gdn_wqkvz"], precision)
    ba = _mm("btd,dc->btc", a, f["gdn_wba"], precision)
    qkv, z = qkvz[..., :2 * kw + vw], qkvz[..., 2 * kw + vw:]
    taps = f["gdn_conv_w"].shape[0]
    if carry is None:
        S0 = jnp.zeros((B, Hv, Dk, Dv), jnp.float32)
        back = jnp.pad(qkv, ((0, 0), (taps - 1, 0), (0, 0)))
    else:
        S0 = carry[0]
        back = jnp.concatenate([carry[1], qkv], axis=1)
    y = jax.nn.silu(sum(
        back[:, j:j + T] * f["gdn_conv_w"][j] for j in range(taps)))
    q = l2_norm(y[..., :kw].reshape(B, T, Hk, Dk)) / math.sqrt(Dk)
    k = l2_norm(y[..., kw:2 * kw].reshape(B, T, Hk, Dk))
    v = y[..., 2 * kw:].reshape(B, T, Hv, Dv)
    q = jnp.repeat(q, Hv // Hk, axis=2)
    k = jnp.repeat(k, Hv // Hk, axis=2)
    beta = jax.nn.sigmoid(ba[..., :Hv])
    g = -jnp.exp(f["gdn_A_log"]) * jax.nn.softplus(
        ba[..., Hv:] + f["gdn_dt_bias"])

    def token(S, xs):
        q, k, v, g, beta = xs  # (B, Hv, D*), (B, Hv)
        S = S * jnp.exp(g)[..., None, None]
        delta = (v - _mm("bhkv,bhk->bhv", S, k, precision)) * beta[..., None]
        S = S + k[..., None] * delta[..., None, :]
        return S, _mm("bhkv,bhk->bhv", S, q, precision)

    by_token = lambda t: jnp.moveaxis(t, 1, 0)
    S, o = jax.lax.scan(
        token, S0, tuple(by_token(t) for t in (q, k, v, g, beta)))
    o = rms_norm(jnp.moveaxis(o, 0, 1), f["gdn_norm_s"])
    o = o * jax.nn.silu(z.reshape(B, T, Hv, Dv))
    out = _mm("btc,cd->btd", o.reshape(B, T, vw), f["gdn_wout"], precision)
    return out if carry is None else (out, (S, back[:, T:]))


def shared_expert(h, f, precision: str):
    """The gated shared expert every row goes through."""
    m = gated_mlp(h, f["ws_gate"], f["ws_up"], f["ws_down"], precision)
    return m * jax.nn.sigmoid(_mm("btd,do->bto", h, f["ws_sgate"], precision))


def layer_forward(x, lp, *, precision: str = "float32", top_k: int = TOP_K,
                  held_lo: int = 0, key_heads: int = KEY_HEADS,
                  key_dim: int = KEY_DIM, rope_dims: int = ROPE_DIMS):
    """One block on float32 activations x: (B, T, D). ``lp`` holds the
    block's weights in whatever type they are kept; they are read as
    float32 here (the stacked experts block by block)."""
    stacked = ("we_gate", "we_up", "we_down")
    f = {n: a.astype(jnp.float32) for n, a in lp.items() if n not in stacked}
    a = rms_norm(x, f["ln1_s"])
    if "gdn_wqkvz" in lp:
        x = x + gated_delta(a, f, key_heads=key_heads, key_dim=key_dim,
                            precision=precision)
    else:
        x = x + gated_attention(a, f, rope_dims=rope_dims,
                                precision=precision)
    h = rms_norm(x, f["ln2_s"])
    w = route_weights(h, f["router"], top_k, precision)
    held = lp["we_gate"].shape[0]
    return x + shared_expert(h, f, precision) + experts_sum(
        h, lp, w[..., held_lo:held_lo + held], precision)


def row_block(T: int, most: int = 4096) -> int:
    """The largest divisor of T that is at most ``most``: the rows of a
    block that ``layer_forward_rows`` takes at a time."""
    return max(r for r in range(1, min(T, most) + 1) if T % r == 0)


def layer_forward_rows(x, lp, *, rows: int, precision: str = "float32",
                       top_k: int = TOP_K, held_lo: int = 0,
                       key_heads: int = KEY_HEADS, key_dim: int = KEY_DIM,
                       rope_dims: int = ROPE_DIMS):
    """``layer_forward`` for one long stream, x: (1, T, D), ``rows`` of
    its rows at a time (T a whole number of them): the mixer block by
    block with what it carries, then the experts of each block over the
    pairs the router chose (``experts_routed``)."""
    stacked = ("we_gate", "we_up", "we_down")
    f = {n: a.astype(jnp.float32) for n, a in lp.items() if n not in stacked}
    T, D = x.shape[1:]
    a = rms_norm(x, f["ln1_s"])
    if "gdn_wqkvz" in lp:
        Hv, Dv = f["gdn_A_log"].shape[0], f["gdn_norm_s"].shape[0]
        taps, chans = f["gdn_conv_w"].shape

        def delta(carry, ab):
            out, carry = gated_delta(
                ab[None], f, key_heads=key_heads, key_dim=key_dim,
                precision=precision, carry=carry)
            return carry, out[0]

        zero = (jnp.zeros((1, Hv, key_dim, Dv), jnp.float32),
                jnp.zeros((1, taps - 1, chans), jnp.float32))
        _, mixed = jax.lax.scan(delta, zero, a[0].reshape(-1, rows, D))
        x = x + mixed.reshape(1, T, D)
    else:
        x = x + gated_attention(a, f, rope_dims=rope_dims,
                                precision=precision, rows=rows)
    held = lp["we_gate"].shape[0]

    def experts(xb):
        h = rms_norm(xb[None], f["ln2_s"])
        w = route_weights(h, f["router"], top_k, precision)
        return (shared_expert(h, f, precision) + experts_routed(
            h, lp, w[..., held_lo:held_lo + held], precision, top_k))[0]

    return x + jax.lax.map(experts, x[0].reshape(-1, rows, D)).reshape(1, T, D)


def head_logits(x, head, lnf_s, precision: str = "float32"):
    x = rms_norm(x, lnf_s.astype(jnp.float32))
    return _mm("td,vd->tv", x, head.astype(jnp.float32), precision)


@functools.lru_cache(maxsize=None)
def _jitted_layer(rows, precision, top_k, held_lo, key_heads, key_dim,
                  rope_dims):
    return jax.jit(functools.partial(
        layer_forward_rows, rows=rows, precision=precision, top_k=top_k,
        held_lo=held_lo, key_heads=key_heads, key_dim=key_dim,
        rope_dims=rope_dims,
    ))


@functools.lru_cache(maxsize=None)
def _jitted_head(precision):
    return jax.jit(functools.partial(head_logits, precision=precision))


def stream_logits(params, tokens, first_row: int, n_rows: int, *,
                  precision: str = "float32", top_k: int = TOP_K,
                  held_lo: int = 0, key_heads: int = KEY_HEADS,
                  key_dim: int = KEY_DIM, rope_dims: int = ROPE_DIMS):
    """Logits (n_rows, vocab) of rows first_row.. of one token sequence
    (tokens: (T,) int32, already padded to the length to compile for):
    row j predicts token j + 1. Which layers are delta-rule layers is
    read from their leaves. The head is over the rows asked for only."""
    x = params["emb"][tokens].astype(jnp.float32)[None]
    layer = _jitted_layer(row_block(len(tokens)), precision, top_k, held_lo,
                          key_heads, key_dim, rope_dims)
    for lp in params["layers"]:
        x = layer(x, lp)
    rows = jax.lax.dynamic_slice_in_dim(x[0], first_row, n_rows, axis=0)
    return _jitted_head(precision)(rows, params["head"], params["lnf_s"])
