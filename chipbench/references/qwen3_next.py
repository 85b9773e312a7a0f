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
layer by layer, head by head inside attention and expert block by
expert block.

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


def gated_attention(a, f, *, rope_dims: int, precision: str):
    """The attention mixer on normed a: (B, T, D); f: float32 leaves."""
    pos = jnp.arange(a.shape[1])
    q = rms_norm(_mm("btd,dhk->bthk", a, f["wq"], precision), f["qn_s"])
    k = rms_norm(_mm("btd,dhk->bthk", a, f["wk"], precision), f["kn_s"])
    v = _mm("btd,dhk->bthk", a, f["wv"], precision)
    gate = _mm("btd,dhk->bthk", a, f["wog"], precision)
    q = rope_partial(q, pos, rope_dims, ROPE_BASE)
    k = rope_partial(k, pos, rope_dims, ROPE_BASE)
    o = attention(q, k, v, None, precision) * jax.nn.sigmoid(gate)
    return _mm("bthk,hkd->btd", o, f["wo"], precision)


def gated_delta(a, f, *, key_heads: int, key_dim: int, precision: str):
    """The gated delta-rule mixer on normed a: (B, T, D), from a zero
    state, one token at a time."""
    B, T, _ = a.shape
    Hv, Dv = f["gdn_A_log"].shape[0], f["gdn_norm_s"].shape[0]
    Hk, Dk = key_heads, key_dim
    kw, vw = Hk * Dk, Hv * Dv
    qkvz = _mm("btd,dc->btc", a, f["gdn_wqkvz"], precision)
    ba = _mm("btd,dc->btc", a, f["gdn_wba"], precision)
    qkv, z = qkvz[..., :2 * kw + vw], qkvz[..., 2 * kw + vw:]
    taps = f["gdn_conv_w"].shape[0]
    back = jnp.pad(qkv, ((0, 0), (taps - 1, 0), (0, 0)))
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
    _, o = jax.lax.scan(
        token, jnp.zeros((B, Hv, Dk, Dv), jnp.float32),
        tuple(by_token(t) for t in (q, k, v, g, beta)))
    o = rms_norm(jnp.moveaxis(o, 0, 1), f["gdn_norm_s"])
    o = o * jax.nn.silu(z.reshape(B, T, Hv, Dv))
    return _mm("btc,cd->btd", o.reshape(B, T, vw), f["gdn_wout"], precision)


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
    m = gated_mlp(h, f["ws_gate"], f["ws_up"], f["ws_down"], precision)
    m = m * jax.nn.sigmoid(_mm("btd,do->bto", h, f["ws_sgate"], precision))
    return x + m + experts_sum(h, lp, w[..., held_lo:held_lo + held],
                               precision)


def head_logits(x, head, lnf_s, precision: str = "float32"):
    x = rms_norm(x, lnf_s.astype(jnp.float32))
    return _mm("td,vd->tv", x, head.astype(jnp.float32), precision)


@functools.lru_cache(maxsize=None)
def _jitted_layer(precision, top_k, held_lo, key_heads, key_dim, rope_dims):
    return jax.jit(functools.partial(
        layer_forward, precision=precision, top_k=top_k, held_lo=held_lo,
        key_heads=key_heads, key_dim=key_dim, rope_dims=rope_dims,
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
    read from their leaves."""
    x = params["emb"][tokens].astype(jnp.float32)[None]
    layer = _jitted_layer(precision, top_k, held_lo, key_heads, key_dim,
                          rope_dims)
    for lp in params["layers"]:
        x = layer(x, lp)
    rows = jax.lax.dynamic_slice_in_dim(x[0], first_row, n_rows, axis=0)
    return _jitted_head(precision)(rows, params["head"], params["lnf_s"])
