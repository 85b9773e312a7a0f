"""Plain reference of the MiniCPM-SALA block (``minicpm_sala``): one
``minicpm4`` layer (grouped-query attention that reads a SELECTION of
its key blocks, InfLLM v2) to three ``lightning-attn`` layers (decayed
linear attention), a dense SwiGLU feed-forward, the family's three
scalings, an untied head.

Straightforward ``jax.numpy`` in float32 at ``Precision.HIGHEST``, layer
by layer, importing nothing of the program (``_mm`` and ``rope`` come
from ``dense_transformer.py``). The equations are the configuration's
``assumed`` (chipbench/configs/minicpm-sala-9b-serve.json):

* ``x0 = emb[tok] * scale_emb``; each half ``x += (scale_depth /
  sqrt(published layers)) * half(RMSNorm(x))``; ``logits =
  head(RMSNorm(x) / (hidden / dim_model_base))``.
* ``minicpm4``: q, an output gate as wide as q, k and v from the
  normed input; RMSNorm over each head of q and k; no rotary; a query
  that sees ``n`` rows attends all of them while ``n <= dense_len``,
  else the rows of the blocks that stand (:func:`block_picks`).
* ``lightning-attn``: q, k, v and a gate z; RMSNorm over each head of
  q and k, rotary over the whole head; per head ``S_t = lam S_(t-1) +
  k_t^T v_t``, ``o_t = q_t S_t / sqrt(d)``, one row at a time
  (:func:`lightning_rows`: a plain scan); ``out = (RMSNorm(o) *
  sigmoid(z)) Wo``, the norm over the joined heads.

A stream of tens of thousands of rows goes through a layer a block of
rows at a time (:func:`layer_forward_rows`: a block of query rows
against the keys so far, the recurrence's state carried from block to
block, the feed-forward block by block), so that it fits beside the
weights; ``forward`` is the whole-sequence form the tests compare it
with and read the picks from.

``precision`` other than ``"float32"`` is the control of the output
check: both inputs of every matrix product rounded to it first.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

from chipbench.references.dense_transformer import _mm, rope

EPS = 1e-6


class Sizes(NamedTuple):
    """The selection's seven sizes and the family's scalings, as the
    configuration file states them."""

    block: int = 64
    topk: int = 64
    kernel: int = 32
    stride: int = 16
    init_blocks: int = 1
    window: int = 2048
    dense_len: int = 8192
    scale_emb: float = 12.0
    residual: float = 1.4 / math.sqrt(32)
    head_divisor: float = 4096 / 256


def rms_norm(x, s):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + EPS) * s


def pooled_keys(k, z: Sizes):
    """``c_j = mean(k[j stride : j stride + kernel])`` for every window
    that lies whole inside the T rows: k (T, Hkv, D) -> (windows, Hkv,
    D), a gather of each window's own rows."""
    n = (k.shape[0] - z.kernel) // z.stride + 1
    rows = (jnp.arange(n) * z.stride)[:, None] + jnp.arange(z.kernel)
    return k[rows].mean(axis=1)


def block_picks(q, k, row0: int, z: Sizes, precision: str = "float32"):
    """Which key blocks the queries at rows ``row0..`` attend: q (R, H,
    D), k (K, Hkv, D) the keys of rows 0.. (at least up to the last
    query's) -> (R, Hkv, blocks) bool, ``blocks = ceil(K / block)``.

    The query at row t sees n = t + 1 rows. While ``n <= dense_len``
    every block it sees stands. Else, per K/V head: ``p_h =
    softmax_j(q_h . c_j / sqrt(D))`` over the windows whole inside the
    n rows, ``s[j]`` the sum of ``p_h`` over the head's query heads; a
    block's score the largest ``s[j]`` over the windows that touch it
    (a window that lies across two blocks counts for both); the first
    ``init_blocks`` blocks and the blocks holding the last ``window``
    rows stand; of the others the ``topk`` best, the earlier block
    where two score alike (a stable sort)."""
    R, H, D = q.shape
    K, Hkv = k.shape[0], k.shape[1]
    nb = -(-K // z.block)
    n = row0 + jnp.arange(R) + 1
    c = pooled_keys(k, z)
    nw = c.shape[0]
    start = jnp.arange(nw) * z.stride
    whole = (start + z.kernel)[None, :] <= n[:, None]          # (R, nw)
    qh = q.reshape(R, Hkv, H // Hkv, D)
    w = _mm("rhgd,jhd->hgrj", qh, c, precision) / math.sqrt(D)
    p = jax.nn.softmax(jnp.where(whole, w, -1e30), axis=-1)
    s = jnp.where(whole, p.sum(axis=1), -1.0)                  # (Hkv, R, nw)
    # a window touches the block of its first row and of its last
    score = jnp.full((Hkv, R, nb), -1.0)
    score = score.at[..., start // z.block].max(s)
    score = score.at[..., (start + z.kernel - 1) // z.block].max(s)
    b = jnp.arange(nb)
    sees = b[None, :] <= ((n - 1) // z.block)[:, None]         # (R, nb)
    held = (b[None, :] < z.init_blocks) | (
        b[None, :] >= (jnp.maximum(n - z.window, 0) // z.block)[:, None])
    held = held & sees
    others = jnp.where((sees & ~held)[None], score, -2.0)
    order = jnp.argsort(-others, axis=-1, stable=True)
    rank = jnp.argsort(order, axis=-1, stable=True)
    best = (rank < z.topk) & (others >= 0.0)
    stands = jnp.where((n > z.dense_len)[None, :, None],
                       held[None] | best, sees[None])
    return stands.transpose(1, 0, 2)


def sparse_attention_rows(q, k, v, row0: int, z: Sizes, precision: str):
    """The query rows ``row0..`` against the rows of their standing
    blocks: q (R, H, D); k, v (K, Hkv, D), the stream's keys (at least
    up to the last query's row; the causal mask hides the rest). One
    head's (R, K) scores at a time. Returns ``(o (R, H, D), picks)``."""
    R, H, D = q.shape
    K, Hkv = k.shape[0], k.shape[1]
    g = H // Hkv
    picks = block_picks(q, k, row0, z, precision)              # (R, Hkv, nb)
    causal = (row0 + jnp.arange(R))[:, None] >= jnp.arange(K)[None, :]
    of_row = jnp.arange(K) // z.block

    def one_head(args):
        qh, h = args
        kh, vh = k[:, h // g], v[:, h // g]
        mask = causal & picks[:, h // g][:, of_row]
        s = _mm("qd,kd->qk", qh, kh, precision) / math.sqrt(D)
        p = jax.nn.softmax(jnp.where(mask, s, -1e30), axis=-1)
        return _mm("qk,kd->qd", p, vh, precision)

    o = jax.lax.map(one_head, (q.transpose(1, 0, 2), jnp.arange(H)))
    return o.transpose(1, 0, 2), picks


def minicpm4_mixer(a, f, z: Sizes, precision: str, rows: int | None = None):
    """The attention mixer on normed a: (T, D); f: float32 leaves.
    ``rows``: that many query rows at a time (T a whole number of
    them; None: all at once), each block against all T keys under the
    causal mask, so that one block's program serves them all. Returns
    ``(out (T, D), picks (T, Hkv, blocks))``."""
    T, D = a.shape
    k = rms_norm(_mm("td,dhk->thk", a, f["wk"], precision), f["kn_s"])
    v = _mm("td,dhk->thk", a, f["wv"], precision)

    def block(r0, ab):
        q = rms_norm(_mm("td,dhk->thk", ab, f["wq"], precision), f["qn_s"])
        gate = _mm("td,dhk->thk", ab, f["wog"], precision)
        o, picks = sparse_attention_rows(q, k, v, r0, z, precision)
        return _mm("thk,hkd->td", o * jax.nn.sigmoid(gate), f["wo"],
                   precision), picks

    if rows is None:
        return block(0, a)
    out, picks = jax.lax.map(
        lambda xs: block(*xs),
        (jnp.arange(0, T, rows), a.reshape(-1, rows, D)))
    return out.reshape(T, D), picks.reshape((T,) + picks.shape[2:])


def lightning_rows(q, k, v, slope, S, precision: str):
    """``S_t = exp(-slope) S_(t-1) + k_t^T v_t``, ``o_t = q_t S_t``, a
    row at a time: q, k, v (T, H, D), slope (H,), S (H, D, D). Returns
    ``(o (T, H, D), S)``."""
    lam = jnp.exp(-slope)[:, None, None]

    def row(S, xs):
        q, k, v = xs
        S = lam * S + _mm("hk,hv->hkv", k, v, precision)
        return S, _mm("hk,hkv->hv", q, S, precision)

    S, o = jax.lax.scan(row, S, (q, k, v))
    return o, S


def lightning_mixer(a, f, precision: str, S=None, row0: int = 0):
    """The linear-attention mixer on normed a: (T, D) at rows
    ``row0..``, from a zero state or from ``S`` as an earlier block of
    rows left it. Returns ``(out (T, D), S)``."""
    T = a.shape[0]
    H, D = f["la_wq"].shape[1:]
    pos = row0 + jnp.arange(T)
    heads = lambda w, s: rope(rms_norm(
        _mm("td,dhk->thk", a, w, precision), s)[None], pos)[0]
    q, k = heads(f["la_wq"], f["la_qn_s"]), heads(f["la_wk"], f["la_kn_s"])
    v = _mm("td,dhk->thk", a, f["la_wv"], precision)
    zg = _mm("td,dc->tc", a, f["la_wz"], precision)
    if S is None:
        S = jnp.zeros((H, D, D), jnp.float32)
    o, S = lightning_rows(q, k, v, f["la_slope"], S, precision)
    o = rms_norm((o / math.sqrt(D)).reshape(T, H * D), f["la_norm_s"])
    return _mm("tc,cd->td", o * jax.nn.sigmoid(zg), f["la_wo"],
               precision), S


def feed_forward(h, f, precision: str):
    g = jax.nn.silu(_mm("td,df->tf", h, f["w_gate"], precision))
    return _mm("tf,fd->td", g * _mm("td,df->tf", h, f["w_up"], precision),
               f["w_down"], precision)


def layer_forward(x, lp, *, z: Sizes = Sizes(), precision: str = "float32"):
    """One block on float32 activations x: (T, D), the whole sequence at
    once. Returns ``(x, picks)`` (picks None for a lightning layer)."""
    f = jax.tree.map(lambda a: a.astype(jnp.float32), lp)
    a = rms_norm(x, f["ln1_s"])
    if "la_wq" in lp:
        mixed, picks = lightning_mixer(a, f, precision)[0], None
    else:
        mixed, picks = minicpm4_mixer(a, f, z, precision)
    x = x + z.residual * mixed
    return x + z.residual * feed_forward(
        rms_norm(x, f["ln2_s"]), f, precision), picks


def row_block(T: int, most: int = 2048) -> int:
    """The largest divisor of T that is at most ``most``."""
    return max(r for r in range(1, min(T, most) + 1) if T % r == 0)


def layer_forward_rows(x, lp, *, rows: int, z: Sizes = Sizes(),
                       precision: str = "float32"):
    """``layer_forward`` for one long stream, x: (T, D), ``rows`` of its
    rows at a time (T a whole number of them)."""
    f = jax.tree.map(lambda a: a.astype(jnp.float32), lp)
    T, D = x.shape
    a = rms_norm(x, f["ln1_s"])
    if "la_wq" in lp:
        H, Dh = f["la_wq"].shape[1:]

        def block(S, xs):
            ab, r0 = xs
            out, S = lightning_mixer(ab, f, precision, S, r0)
            return S, out

        _, mixed = jax.lax.scan(
            block, jnp.zeros((H, Dh, Dh), jnp.float32),
            (a.reshape(-1, rows, D), jnp.arange(0, T, rows)))
        mixed = mixed.reshape(T, D)
    else:
        mixed = minicpm4_mixer(a, f, z, precision, rows)[0]
    x = x + z.residual * mixed
    ffn = lambda xb: feed_forward(rms_norm(xb, f["ln2_s"]), f, precision)
    return x + z.residual * jax.lax.map(
        ffn, x.reshape(-1, rows, D)).reshape(T, D)


def head_logits(x, head, lnf_s, z: Sizes = Sizes(),
                precision: str = "float32"):
    x = rms_norm(x, lnf_s.astype(jnp.float32)) / z.head_divisor
    return _mm("td,vd->tv", x, head.astype(jnp.float32), precision)


def forward(params, tokens, *, z: Sizes = Sizes(),
            precision: str = "float32"):
    """Logits (T, vocab) of one token sequence, every layer over the
    whole sequence at once, and the attention layers' picks (a list of
    (T, Hkv, blocks) bool): what the tests read."""
    x = params["emb"][tokens].astype(jnp.float32) * z.scale_emb
    picks = []
    for lp in params["layers"]:
        x, p = layer_forward(x, lp, z=z, precision=precision)
        if p is not None:
            picks.append(p)
    return head_logits(x, params["head"], params["lnf_s"], z,
                       precision), picks


@functools.lru_cache(maxsize=None)
def _jitted_layer(rows, z, precision):
    return jax.jit(functools.partial(
        layer_forward_rows, rows=rows, z=z, precision=precision))


@functools.lru_cache(maxsize=None)
def _jitted_head(z, precision):
    return jax.jit(functools.partial(head_logits, z=z, precision=precision))


def stream_logits(params, tokens, first_row: int, n_rows: int, *,
                  z: Sizes = Sizes(), precision: str = "float32"):
    """Logits (n_rows, vocab) of rows first_row.. of one token sequence
    (tokens: (T,) int32, already padded to the length to compile for):
    row j predicts token j + 1. Which layers are lightning layers is
    read from their leaves. The head is over the rows asked for only."""
    x = params["emb"][tokens].astype(jnp.float32) * z.scale_emb
    layer = _jitted_layer(row_block(len(tokens)), z, precision)
    for lp in params["layers"]:
        x = layer(x, lp)
    rows = jax.lax.dynamic_slice_in_dim(x, first_row, n_rows, axis=0)
    return _jitted_head(z, precision)(rows, params["head"], params["lnf_s"])
