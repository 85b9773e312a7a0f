"""Plain reference of the Falcon-H1 block (``falcon_h1``): in EVERY layer
grouped-query attention and a Mamba-2 state-space mixer read one normed
input side by side and their results join the residual in one add; a
SwiGLU feed-forward follows; the family's multipliers stand where the
published implementation applies them; an untied head.

Straightforward ``jax.numpy`` in float32 with every product at
``Precision.HIGHEST`` (``_mm`` of ``dense_transformer.py``; the jitted
functions are also traced under ``jax.default_matmul_precision(
"highest")``), layer by layer, importing nothing of the program. The
recurrence is a scan over the rows, one row at a time, NOT chunked;
attention is the full causal softmax (``dense_transformer.attention``:
one head's (T, T) scores at a time); no cache, no batching. To fit
beside the program's bfloat16 weights on one chip a layer's float32
copy exists only inside that layer's call, the feed-forward runs over
``FFN_ROWS`` rows at a time and the head over ``HEAD_BLOCKS`` blocks of
the vocabulary (its float32 copy whole would be 5.3 GB).

The equations, with the configuration's keys (``Sizes`` carries them):

    x0 = emb[tok] * embedding_multiplier
    h  = RMSNorm(x)
    a  = Attn(h * attention_in_multiplier) * attention_out_multiplier
         q, k, v from the input; k * key_multiplier; rotary over the
         whole head at rope_theta, pairs (i, i + head_dim / 2); causal
         softmax(q k^T / sqrt(head_dim)) v; the out-projection
    s  = SSM(h * ssm_in_multiplier) * ssm_out_multiplier
         [z | x | B | C | dt] = (W_in u) * mup_vector, the five
         ssm_multipliers over the five spans
         [x | B | C] = silu(conv1d(.) + b): depthwise, causal,
         mamba_d_conv taps
         dt = softplus(dt + dt_bias); a = exp(dt * -exp(A_log))
         S_t = a_t S_(t-1) + dt_t x_t B_t^T  (head_dim x d_state a
         head, B and C of the head's group); y_t = S_t C_t + D x_t
         y = RMSNorm_grouped(y * silu(z)) (mamba_norm_before_gate
         false: the gate first; the norm over each of the n_groups
         spans of the joined heads); out = W_out y
    x  = x + (s + a)
    x  = x + W_down(silu(W_gate g * m_gate) * (W_up g)) * m_down,
         g = RMSNorm(x), mlp_multipliers = [m_gate, m_down]
    logits = head(RMSNorm(x)) * lm_head_multiplier

Departures from the published description, each also under the
configuration's ``departures``:

* RMSNorm scales multiply directly (``x * s``) and are all one.
* q, k, v and the out-projection are separate matrices laid out
  (hidden, heads, head_dim); the state-space mixer's in-projection is
  one matrix laid out ``[z | x | B | C | dt]`` as published. With seeded
  random weights any other interleaving is a relabelling of columns.
* ``time_step_limit`` is (0, inf), the reference implementation's
  default: no clamp is applied to dt.
* The recurrence is run as the row-by-row scan it is defined by; the
  published implementation's chunked scan (``mamba_chunk_size``) is
  another order of the same float32 sums.

``precision`` other than ``"float32"`` is the control of the output
check: ``"fp8"`` / ``"int8"`` / ``"bfloat16"`` round both inputs of
every matrix product first; ``"s_bf16"`` keeps every product in
float32 and rounds the state S to bfloat16 after every row.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

from chipbench.references.dense_transformer import _mm, attention

FFN_ROWS = 256     # rows the feed-forward takes at once
HEAD_BLOCKS = 8    # blocks of the vocabulary the head is made in


class Sizes(NamedTuple):
    """The state-space mixer's sizes and the family's constants, as the
    configuration file states them (the defaults are the 34B's)."""

    heads: int = 32
    head_dim: int = 128
    state: int = 256
    groups: int = 2
    conv: int = 4
    eps: float = 1e-5
    rope_theta: float = 1e11
    embedding_multiplier: float = 5.656854249492381
    lm_head_multiplier: float = 0.0078125
    attention_in_multiplier: float = 1.0
    attention_out_multiplier: float = 0.0375
    key_multiplier: float = 0.011048543456039804
    ssm_in_multiplier: float = 0.25
    ssm_out_multiplier: float = 0.08838834764831845
    ssm_multipliers: tuple = (0.3535533905932738, 0.25, 0.1767766952966369,
                              0.5, 0.3535533905932738)
    mlp_multipliers: tuple = (0.1767766952966369, 0.011160714285714284)


def _products(precision: str) -> str:
    """The precision of the matrix products' inputs under a control."""
    return "float32" if precision == "s_bf16" else precision


def rms_norm(x, s, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * s


def rope(x, pos, theta: float):
    """x: (T, H, Dh); pairs (i, i + Dh/2) rotate by pos * theta^(-i/(Dh/2))."""
    half = x.shape[-1] // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = pos.astype(jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def attention_mixer(h, f, z: Sizes, precision: str):
    """The attention on the normed input h (T, D), f: float32 leaves."""
    u = h * z.attention_in_multiplier
    pos = jnp.arange(h.shape[0])
    q = rope(_mm("td,dhk->thk", u, f["wq"], precision), pos, z.rope_theta)
    k = _mm("td,dhk->thk", u, f["wk"], precision) * z.key_multiplier
    k = rope(k, pos, z.rope_theta)
    v = _mm("td,dhk->thk", u, f["wv"], precision)
    o = attention(q[None], k[None], v[None], None, precision)[0]
    return _mm("thk,hkd->td", o, f["wo"], precision) \
        * z.attention_out_multiplier


def mup_vector(z: Sizes):
    """The five ``ssm_multipliers`` over the spans ``[z | x | B | C |
    dt]`` of the in-projection."""
    wide, gn = z.heads * z.head_dim, z.groups * z.state
    return jnp.concatenate([
        jnp.full((n,), m, jnp.float32) for n, m in zip(
            (wide, wide, gn, gn, z.heads), z.ssm_multipliers)])


def ssm_rows(x, Bm, Cm, dt, A, S, s_bf16: bool):
    """``S_t = exp(dt_t A) S_(t-1) + dt_t x_t B_t^T``, ``y_t = S_t
    C_t``, a row at a time: x (T, H, P); Bm, Cm (T, G, N); dt (T, H); A
    (H,); S (H, P, N). Returns ``(y (T, H, P), S)``."""
    r = x.shape[1] // Bm.shape[1]

    def row(S, xs):
        x, Bm, Cm, dt = xs
        Bh, Ch = jnp.repeat(Bm, r, axis=0), jnp.repeat(Cm, r, axis=0)
        S = jnp.exp(dt * A)[:, None, None] * S + (
            (dt[:, None] * x)[:, :, None] * Bh[:, None, :])
        if s_bf16:
            S = S.astype(jnp.bfloat16).astype(jnp.float32)
        return S, (S * Ch[:, None, :]).sum(axis=-1)

    S, y = jax.lax.scan(row, S, (x, Bm, Cm, dt))
    return y, S


def ssm_mixer(h, f, z: Sizes, precision: str):
    """The state-space mixer on the normed input h (T, D) from a zero
    state. Returns ``(out (T, D), S (H, P, N), conv rows (conv - 1,
    channels))``: the state and the conv's last inputs are what a cache
    would hold behind the T rows (the tests compare them)."""
    T = h.shape[0]
    H, P, N, G = z.heads, z.head_dim, z.state, z.groups
    wide, gn = H * P, G * N
    mm = _products(precision)
    u = h * z.ssm_in_multiplier
    zxbcdt = _mm("td,dc->tc", u, f["ssm_win"], mm) * mup_vector(z)
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
    o, S = ssm_rows(x, Bm, Cm, dt, -jnp.exp(f["ssm_A_log"]),
                    jnp.zeros((H, P, N), jnp.float32),
                    precision == "s_bf16")
    o = (o + f["ssm_D"][:, None] * x).reshape(T, wide) * jax.nn.silu(gate)
    o = rms_norm(o.reshape(T, G, wide // G), 1.0, z.eps).reshape(T, wide)
    out = _mm("tc,cd->td", o * f["ssm_norm_s"], f["ssm_wout"], mm)
    return out * z.ssm_out_multiplier, S, seen[T:]


def feed_forward(g, f, z: Sizes, precision: str):
    m_gate, m_down = z.mlp_multipliers
    a = jax.nn.silu(_mm("td,df->tf", g, f["w_gate"], precision) * m_gate)
    return _mm("tf,fd->td", a * _mm("td,df->tf", g, f["w_up"], precision),
               f["w_down"], precision) * m_down


def layer_forward(x, lp, *, z: Sizes = Sizes(), precision: str = "float32",
                  state: bool = False):
    """One block on float32 activations x: (T, D), the whole sequence at
    once. ``state``: also return the mixer's ``(S, conv rows)``."""
    f = jax.tree.map(lambda a: a.astype(jnp.float32), lp)
    mm = _products(precision)
    h = rms_norm(x, f["ln1_s"], z.eps)
    s, S, conv = ssm_mixer(h, f, z, precision)
    x = x + (s + attention_mixer(h, f, z, mm))
    T, D = x.shape
    rows = math.gcd(T, FFN_ROWS)
    ffn = lambda xb: feed_forward(rms_norm(xb, f["ln2_s"], z.eps), f, z, mm)
    x = x + jax.lax.map(ffn, x.reshape(-1, rows, D)).reshape(T, D)
    return (x, (S, conv)) if state else x


def head_logits(x, head, lnf_s, z: Sizes = Sizes(),
                precision: str = "float32"):
    """The final norm and the head, a block of the vocabulary at a
    time (the most of ``HEAD_BLOCKS`` that divides it)."""
    x = rms_norm(x, lnf_s.astype(jnp.float32), z.eps)
    V, D = head.shape
    nb = max(b for b in range(1, HEAD_BLOCKS + 1) if V % b == 0)
    block = lambda w: _mm("td,vd->tv", x, w.astype(jnp.float32),
                          _products(precision))
    lg = jax.lax.map(block, head.reshape(nb, V // nb, D))
    return jnp.moveaxis(lg, 0, 1).reshape(x.shape[0], V) \
        * z.lm_head_multiplier


def forward(params, tokens, *, z: Sizes = Sizes(),
            precision: str = "float32", state: bool = False):
    """Logits (T, vocab) of one token sequence; with ``state`` also
    every layer's ``(S, conv rows)`` behind the last row."""
    with jax.default_matmul_precision("highest"):
        x = params["emb"][tokens].astype(jnp.float32) \
            * z.embedding_multiplier
        states = []
        for lp in params["layers"]:
            x, st = layer_forward(x, lp, z=z, precision=precision,
                                  state=True)
            states.append(st)
        lg = head_logits(x, params["head"], params["lnf_s"], z, precision)
    return (lg, states) if state else lg


@functools.lru_cache(maxsize=None)
def _jitted_layer(z, precision):
    def layer(x, lp):
        with jax.default_matmul_precision("highest"):
            return layer_forward(x, lp, z=z, precision=precision)

    return jax.jit(layer)


@functools.lru_cache(maxsize=None)
def _jitted_head(z, precision):
    def head(x, w, s):
        with jax.default_matmul_precision("highest"):
            return head_logits(x, w, s, z, precision)

    return jax.jit(head)


def stream_logits(params, tokens, first_row: int, n_rows: int, *,
                  z: Sizes = Sizes(), precision: str = "float32"):
    """Logits (n_rows, vocab) of rows first_row.. of one token sequence
    (tokens: (T,) int32, already padded to the length to compile for):
    row j predicts token j + 1. The head is over the rows asked for
    only."""
    x = params["emb"][tokens].astype(jnp.float32) * z.embedding_multiplier
    layer = _jitted_layer(z, precision)
    for lp in params["layers"]:
        x = layer(x, lp)
    rows = jax.lax.dynamic_slice_in_dim(x, first_row, n_rows, axis=0)
    return _jitted_head(z, precision)(rows, params["head"], params["lnf_s"])
