"""Plain reference of the Granite-4.0-H block (``granitemoehybrid``):
a Mamba-2 state-space mixer ALONE in most layers (state and no K/V
row), grouped-query attention WITHOUT rotary in the others
(``layer_types``), and behind every mixer the same feed-forward: the
ten largest of 72 router logits choose small gated experts, weighted by
a softmax over those ten, beside one always-on gated MLP; the family's
four constants stand where the published implementation applies them;
the head is the embedding.

Straightforward ``jax.numpy`` in float32 with every product at
``Precision.HIGHEST`` (``_mm`` of ``dense_transformer.py``; the jitted
functions are also traced under ``jax.default_matmul_precision(
"highest")``), layer by layer, importing nothing of the program (only
its sibling references' shared pieces). The recurrence is a scan over
the rows, one row at a time, NOT chunked, in the published
``(head_dim, d_state)`` layout; attention is the full causal softmax,
one head's (T, T) scores at a time; no cache, no batching, no grouping
of rows by expert. To fit beside the program's
bfloat16 weights on one chip a layer's float32 copy exists only inside
that layer's call, the experts are upcast and multiplied
``EXPERT_BLOCK`` at a time (every row through every held expert, at
weight zero where the row did not choose it), and the head is made
over ``HEAD_BLOCKS`` blocks of the vocabulary.

The equations, with the configuration's keys (``Sizes`` carries them):

    x0 = emb[tok] * embedding_multiplier
    every layer:
    x  = x + residual_multiplier * Mixer(RMSNorm(x))
    g  = RMSNorm(x)
    x  = x + residual_multiplier * (MoE(g) + Shared(g))
    mamba layer (a layer with ``ssm_win``), Mamba-2 (arXiv:2405.21060):
         [z | xBC | dt] = W_in h
         [x | B | C] = silu(conv1d(xBC) + b): depthwise, causal,
         mamba_d_conv taps
         dt = softplus(dt + dt_bias); a = exp(-exp(A_log) dt)
         S_t = a_t S_(t-1) + dt_t x_t B_t^T  (head_dim x d_state a
         head, B and C of the head's group); y_t = S_t C_t + D x_t
         y = RMSNorm(y * silu(z)) over each of the n_groups spans of
         the joined heads (one group: all of them); out = W_out y
    attention layer (a layer with ``wq``): q, k, v from h, NO rotary
         (position_embedding_type "nope"); causal
         softmax(q k^T * attention_multiplier) v, query head h reads
         K/V head h // (H / Hkv); the out-projection
    MoE(g): l = W_r g (no bias); the num_experts_per_tok largest
         logits; w = softmax over those; sum over the chosen experts
         HELD HERE of w_e W_down_e (silu(W_gate_e g) * W_up_e g). The
         matrices handed in are those of experts [held_lo, held_lo +
         E_held); what the others would add is left out, as in the
         program.
    Shared(g): W_down (silu(W_gate g) * W_up g), unweighted
    logits = emb^T RMSNorm(x) / logits_scaling

Departures from the published description, each also under the
configuration's ``departures``:

* RMSNorm scales multiply directly (``x * s``) and are all one.
* q, k, v and the out-projection are separate matrices laid out
  (hidden, heads, head_dim); the mixer's in-projection is one matrix
  laid out ``[z | x | B | C | dt]`` as published; an expert's gate and
  up matrices are two (the checkpoint stores them as one input matrix
  of twice the width). With seeded random weights each is a relabelling
  of columns.
* ``time_step_limit`` is (0, inf), the reference implementation's
  default: no clamp is applied to dt.
* The recurrence is run as the row-by-row scan it is defined by; the
  published implementation's chunked scan (``mamba_chunk_size``) is
  another order of the same float32 sums.

``precision`` other than ``"float32"`` is the control of the output
check: ``"fp8"`` / ``"int8"`` / ``"bfloat16"`` round both inputs of
every matrix product first (the router's among them); ``"s_bf16"``
keeps every product in float32 and rounds the state S to bfloat16
after every row.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

from chipbench.references.dense_transformer import HIGHEST, _mm
# the norm and the precision a control gives the products: Falcon-H1's
# reference has both, of the same mixer
from chipbench.references.falcon_h1 import _products, rms_norm

EXPERT_BLOCK = 4   # experts upcast and multiplied at a time
HEAD_BLOCKS = 8    # blocks of the vocabulary the head is made in
STACKED = ("we_gate", "we_up", "we_down")


class Sizes(NamedTuple):
    """The mixer's sizes, the router's and the family's constants, as
    the configuration file states them (the defaults are
    granite-4.0-h-small's)."""

    heads: int = 128
    head_dim: int = 64
    state: int = 128
    groups: int = 1
    conv: int = 4
    eps: float = 1e-5
    top_k: int = 10
    held_lo: int = 0
    embedding_multiplier: float = 12.0
    residual_multiplier: float = 0.22
    attention_multiplier: float = 0.0078125
    logits_scaling: float = 16.0


def attention(q, k, v, scale: float, precision: str):
    """q: (T, H, Dh); k, v: (T, Hkv, Dh); no rotary. Query head h reads
    K/V head h // (H / Hkv). One (T, T) score matrix at a time."""
    T, H, Dh = q.shape
    group = H // k.shape[1]
    pos = jnp.arange(T)
    mask = pos[:, None] >= pos[None, :]

    @jax.checkpoint
    def one_head(args):
        qh, kh, vh = args  # (T, Dh) each
        s = _mm("qd,kd->qk", qh, kh, precision) * scale
        p = jax.nn.softmax(jnp.where(mask, s, -1e30), axis=-1)
        return _mm("qk,kd->qd", p, vh, precision)

    heads = lambda t: t.transpose(1, 0, 2)
    o = jax.lax.map(one_head, (
        heads(q), jnp.repeat(heads(k), group, axis=0),
        jnp.repeat(heads(v), group, axis=0)))
    return heads(o)


def attention_mixer(h, f, z: Sizes, precision: str):
    """The attention on the normed input h (T, D), f: float32 leaves."""
    q = _mm("td,dhk->thk", h, f["wq"], precision)
    k = _mm("td,dhk->thk", h, f["wk"], precision)
    v = _mm("td,dhk->thk", h, f["wv"], precision)
    o = attention(q, k, v, z.attention_multiplier, precision)
    return _mm("thk,hkd->td", o, f["wo"], precision)


def ssm_rows(x, Bm, Cm, dt, A, S, s_bf16: bool):
    """``S_t = exp(dt_t A) S_(t-1) + dt_t x_t B_t^T``, ``y_t = S_t
    C_t``, a row at a time in the published layout: x (T, H, P); Bm, Cm
    (T, G, N); dt (T, H); A (H,); S (H, P, N). Returns ``(y (T, H, P),
    S)``. ``falcon_h1.ssm_rows`` but for the control's rounding: there
    it is ``astype(bfloat16).astype(float32)``, which the TPU compiler
    takes out (excess precision is allowed: that control's S came back
    bit for bit the float32 one's, my chip run, PR 51, call 4); here it
    is ``reduce_precision``, which stays."""
    r = x.shape[1] // Bm.shape[1]

    def row(S, xs):
        x, Bm, Cm, dt = xs
        Bh, Ch = jnp.repeat(Bm, r, axis=0), jnp.repeat(Cm, r, axis=0)
        S = jnp.exp(dt * A)[:, None, None] * S + (
            (dt[:, None] * x)[:, :, None] * Bh[:, None, :])
        if s_bf16:
            S = jax.lax.reduce_precision(S, exponent_bits=8, mantissa_bits=7)
        return S, (S * Ch[:, None, :]).sum(axis=-1)

    S, y = jax.lax.scan(row, S, (x, Bm, Cm, dt))
    return y, S


def ssm_mixer(h, f, z: Sizes, precision: str, length=None):
    """The state-space mixer on the normed input h (T, D) from a zero
    state. Returns ``(out (T, D), S (H, P, N), conv rows (conv - 1,
    channels))``: the state and the conv's last inputs are what a cache
    would hold behind the T rows (the tests compare them). With
    ``length``, the rows from ``length`` on are padding to S: their dt
    is zero, so each leaves S as it was (decay one, nothing added) and
    S is what a cache would hold behind row ``length - 1``."""
    T = h.shape[0]
    H, P, N, G = z.heads, z.head_dim, z.state, z.groups
    wide, gn = H * P, G * N
    mm = _products(precision)
    zxbcdt = _mm("td,dc->tc", h, f["ssm_win"], mm)
    gate, xbc = zxbcdt[:, :wide], zxbcdt[:, wide:2 * wide + 2 * gn]
    dt = zxbcdt[:, 2 * wide + 2 * gn:]
    seen = jnp.concatenate(
        [jnp.zeros((z.conv - 1, xbc.shape[1]), jnp.float32), xbc])
    y = sum(seen[j:j + T] * f["ssm_conv_w"][j] for j in range(z.conv))
    y = jax.nn.silu(y + f["ssm_conv_b"])
    x = y[:, :wide].reshape(T, H, P)
    Bm = y[:, wide:wide + gn].reshape(T, G, N)
    Cm = y[:, wide + gn:].reshape(T, G, N)
    dt = jax.nn.softplus(dt + f["ssm_dt_bias"])
    if length is not None:
        dt = jnp.where(jnp.arange(T)[:, None] < length, dt, 0.0)
    o, S = ssm_rows(x, Bm, Cm, dt, -jnp.exp(f["ssm_A_log"]),
                    jnp.zeros((H, P, N), jnp.float32),
                    precision == "s_bf16")
    o = (o + f["ssm_D"][:, None] * x).reshape(T, wide) * jax.nn.silu(gate)
    o = rms_norm(o.reshape(T, G, wide // G), 1.0, z.eps).reshape(T, wide)
    out = _mm("tc,cd->td", o * f["ssm_norm_s"], f["ssm_wout"], mm)
    return out, S, seen[T:]


def route_weights(g, router, top_k: int, precision: str):
    """(T, E) weights over ALL the router's experts: the ``top_k``
    largest LOGITS, a softmax over those, zero for the others."""
    logits = _mm("td,de->te", g, router, precision)
    top, idx = jax.lax.top_k(logits, top_k)
    w = jax.nn.softmax(top, axis=-1)
    rows = jnp.arange(g.shape[0])[:, None]
    return jnp.zeros_like(logits).at[rows, idx].set(w)


def gated_mlp(g, w_gate, w_up, w_down, precision: str):
    a = jax.nn.silu(_mm("td,df->tf", g, w_gate, precision))
    return _mm("tf,fd->td", a * _mm("td,df->tf", g, w_up, precision),
               w_down, precision)


def experts_sum(g, lp, w, precision: str, block: int = EXPERT_BLOCK):
    """sum_e w[:, e] * expert_e(g) over the experts whose matrices
    ``lp`` holds (``w``: their columns), ``block`` of them at a time,
    every row through every one of them."""
    E = lp["we_gate"].shape[0]
    block = math.gcd(E, block)
    blocks = lambda a: a.reshape((E // block, block) + a.shape[1:])

    def one_block(acc, args):
        wg, wu, wd, wb = args
        f32 = lambda a: a.astype(jnp.float32)
        a = jax.nn.silu(_mm("td,edf->etf", g, f32(wg), precision))
        a = a * _mm("td,edf->etf", g, f32(wu), precision)
        y = _mm("etf,efd->etd", a, f32(wd), precision)
        return acc + jnp.einsum("etd,te->td", y, wb, precision=HIGHEST), None

    wb = jnp.moveaxis(w.reshape(w.shape[0], E // block, block), 1, 0)
    acc, _ = jax.lax.scan(
        one_block, jnp.zeros_like(g),
        (blocks(lp["we_gate"]), blocks(lp["we_up"]), blocks(lp["we_down"]),
         wb))
    return acc


def feed_forward(g, lp, f, z: Sizes, precision: str):
    """``MoE(g) + Shared(g)`` on the normed input g (T, D): the routed
    experts held here and the always-on gated MLP, counted once."""
    held = lp["we_gate"].shape[0]
    w = route_weights(g, f["router"], z.top_k, precision)
    return experts_sum(g, lp, w[:, z.held_lo:z.held_lo + held], precision) \
        + gated_mlp(g, f["ws_gate"], f["ws_up"], f["ws_down"], precision)


def layer_forward(x, lp, *, z: Sizes = Sizes(), precision: str = "float32",
                  state: bool = False, length=None):
    """One block on float32 activations x: (T, D), the whole sequence at
    once. ``state``: also return a mamba layer's ``(S, conv rows)``
    (None for an attention layer, which keeps neither); ``length``:
    the rows S stands behind (:func:`ssm_mixer`)."""
    f = {n: a.astype(jnp.float32) for n, a in lp.items() if n not in STACKED}
    mm = _products(precision)
    h = rms_norm(x, f["ln1_s"], z.eps)
    kept = None
    if "ssm_win" in lp:
        mixed, S, conv = ssm_mixer(h, f, z, precision, length)
        kept = (S, conv)
    else:
        mixed = attention_mixer(h, f, z, mm)
    x = x + z.residual_multiplier * mixed
    g = rms_norm(x, f["ln2_s"], z.eps)
    x = x + z.residual_multiplier * feed_forward(g, lp, f, z, mm)
    return (x, kept) if state else x


def head_logits(x, emb, lnf_s, z: Sizes = Sizes(),
                precision: str = "float32"):
    """The final norm and the tied head, a block of the vocabulary at
    a time (the most of ``HEAD_BLOCKS`` that divides it), then the
    division by ``logits_scaling``."""
    x = rms_norm(x, lnf_s.astype(jnp.float32), z.eps)
    V, D = emb.shape
    nb = max(b for b in range(1, HEAD_BLOCKS + 1) if V % b == 0)
    block = lambda w: _mm("td,vd->tv", x, w.astype(jnp.float32),
                          _products(precision))
    lg = jax.lax.map(block, emb.reshape(nb, V // nb, D))
    return jnp.moveaxis(lg, 0, 1).reshape(x.shape[0], V) / z.logits_scaling


def forward(params, tokens, *, z: Sizes = Sizes(),
            precision: str = "float32", state: bool = False):
    """Logits (T, vocab) of one token sequence; with ``state`` also
    every layer's ``(S, conv rows)`` behind the last row (None for an
    attention layer)."""
    with jax.default_matmul_precision("highest"):
        x = params["emb"][tokens].astype(jnp.float32) \
            * z.embedding_multiplier
        states = []
        for lp in params["layers"]:
            x, st = layer_forward(x, lp, z=z, precision=precision,
                                  state=True)
            states.append(st)
        lg = head_logits(x, params["emb"], params["lnf_s"], z, precision)
    return (lg, states) if state else lg


@functools.lru_cache(maxsize=None)
def _jitted_layer(z, precision):
    def layer(x, lp, length):
        with jax.default_matmul_precision("highest"):
            x, kept = layer_forward(x, lp, z=z, precision=precision,
                                    state=True, length=length)
        return x, None if kept is None else kept[0]

    return jax.jit(layer)


@functools.lru_cache(maxsize=None)
def _jitted_head(z, precision):
    def head(x, w, s):
        with jax.default_matmul_precision("highest"):
            return head_logits(x, w, s, z, precision)

    return jax.jit(head)


def _walk(params, tokens, length, z, precision):
    """The padded sequence through every layer: the last layer's rows
    and each layer's S behind row ``length - 1`` (None for an attention
    layer). A layer's program is traced once a kind of layer (its
    leaves' names and shapes), so ten layers compile two."""
    x = params["emb"][tokens].astype(jnp.float32) * z.embedding_multiplier
    layer = _jitted_layer(z, precision)
    states = []
    for lp in params["layers"]:
        x, S = layer(x, lp, jnp.int32(length))
        states.append(S)
    return x, states


def stream_logits(params, tokens, first_row: int, n_rows: int, *,
                  z: Sizes = Sizes(), precision: str = "float32"):
    """Logits (n_rows, vocab) of rows first_row.. of one token sequence
    (tokens: (T,) int32, already padded to the length to compile for):
    row j predicts token j + 1. The head is over the rows asked for
    only."""
    x, _ = _walk(params, tokens, tokens.shape[0], z, precision)
    rows = jax.lax.dynamic_slice_in_dim(x, first_row, n_rows, axis=0)
    return _jitted_head(z, precision)(rows, params["emb"], params["lnf_s"])


def stream_states(params, tokens, length: int, *, z: Sizes = Sizes(),
                  precision: str = "float32"):
    """The mamba layers' S (H, P, N) behind row ``length - 1`` of one
    token sequence padded like :func:`stream_logits`'s (the same
    programs): what a cache holds for a request that has been fed
    ``length`` rows."""
    _, states = _walk(params, tokens, length, z, precision)
    return [S for S in states if S is not None]
