"""Plain reference of the decoder block the ``afmoe`` configuration runs
(``arcee-ai/Trinity-Mini``, ``model_type`` ``afmoe``): RMSNorm before
and after each half, grouped-query attention with a head size of its
own, q/k norms and an output gate, three sliding-window layers to one
full-attention layer, a gated feed-forward that is dense in the leading
layers and, after them, sigmoid top-k experts beside a shared expert,
an untied output head.

Straightforward ``jax.numpy`` in float32 at ``Precision.HIGHEST``: no
kernel, no cache, no grouping of tokens by expert. It imports nothing
of the program (only its sibling reference's three shared pieces) and is
handed only arrays that the benchmark made. To
fit beside the bfloat16 weights on one chip it works layer by layer
(a layer's float32 copy exists only inside that layer's call), head by
head inside attention, and expert block by expert block.

The layer, with what the published ``config.json`` does not carry
marked A (each is how the ``afmoe`` modelling code has it, and each is
listed under ``assumed`` in the configuration file):

* ``x0 = emb[tok] * sqrt(hidden)`` (``mup_enabled``).
* attention half: ``a = RMSNorm(x)``; ``q = a wq`` as H heads of Dh,
  ``k, v`` as Hkv heads of Dh; ``g = a wog``, as wide as q (A);
  ``q, k`` each RMSNorm over the head's Dh (A); rotary, base 10000, on
  sliding-window layers only and none on full layers (A); causal
  attention at scale ``Dh ** -0.5`` over the layer's span;
  ``o = o * sigmoid(g)`` (A); ``x = x + RMSNorm(o wo)`` (a norm before
  and after each half, A).
* feed-forward half: ``h = RMSNorm(x)``. A dense layer:
  ``m = w_down(silu(w_gate h) * w_up h)``. An expert layer:
  ``s = sigmoid(h router)``; the ``k`` experts with the largest
  ``s + bias`` are chosen, the bias selecting and not weighing (A: the
  bias is a buffer of the checkpoint); ``w = s[chosen]``,
  ``w = w / (sum(w) + 1e-20) * route_scale``;
  ``m = shared(h) + sum_e w_e expert_e(h)`` written as a plain sum over
  ALL experts with weight zero for the ones not chosen. No token is
  ever dropped. Then ``x = x + RMSNorm(m)``.
* ``logits = RMSNorm(x) head``.

Which layers are dense is read from the layer's own leaves (an expert
layer has a ``router``); which are full attention from ``window``, one
entry per layer (None: every earlier position).

``precision`` other than ``"float32"`` is the *control* of the output
check (chipbench/control.py): the same mathematics with both inputs of
every matrix product rounded to a lower precision first.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

# what the two blocks share is taken from the dense block's reference:
# a product with both inputs rounded to the control's precision, the
# rotary embedding (base 10000, pairs (i, i + Dh/2)) and grouped-query
# attention over a causal band, one (T, T) score matrix at a time
from chipbench.references.dense_transformer import (
    HIGHEST,
    _mm,
    attention,
    rope,
)

RMS_EPS = 1e-5
TOP_K = 8            # num_experts_per_tok
ROUTE_SCALE = 2.826  # route_scale
EXPERT_BLOCK = 8     # experts upcast and multiplied at a time


def rms_norm(x, s):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + RMS_EPS) * s


def gated_mlp(h, w_gate, w_up, w_down, precision: str):
    a = jax.nn.silu(_mm("btd,df->btf", h, w_gate, precision))
    return _mm("btf,fd->btd", a * _mm("btd,df->btf", h, w_up, precision),
               w_down, precision)


def route_weights(h, router, bias, top_k: int, route_scale: float,
                  precision: str):
    """(B, T, E) weights: the normalised, scaled sigmoid score of each
    chosen expert, zero for the others."""
    s = jax.nn.sigmoid(_mm("btd,de->bte", h, router, precision))
    _, idx = jax.lax.top_k(s + bias, top_k)
    chosen = jax.nn.one_hot(idx, s.shape[-1], dtype=jnp.float32).sum(-2)
    w = s * chosen
    return w / (w.sum(-1, keepdims=True) + 1e-20) * route_scale


def experts_sum(h, lp, w, precision: str, block: int = EXPERT_BLOCK):
    """sum_e w[..., e] * expert_e(h) over ALL experts, ``block`` of them
    at a time; each block's weights become float32 inside its own step."""
    E = lp["we_gate"].shape[0]
    block = math.gcd(E, block)
    blocks = lambda a: a.reshape((E // block, block) + a.shape[1:])

    def one_block(acc, args):
        wg, wu, wd, wb = args  # (block, D, F) x2, (block, F, D), (B, T, block)
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


def layer_forward(x, lp, *, window: int | None, precision: str = "float32",
                  top_k: int = TOP_K, route_scale: float = ROUTE_SCALE):
    """One block on float32 activations x: (B, T, D). ``lp`` holds the
    block's weights in whatever type they are kept; they are read as
    float32 here (the stacked experts block by block)."""
    stacked = ("we_gate", "we_up", "we_down")
    f = {n: a.astype(jnp.float32) for n, a in lp.items() if n not in stacked}
    pos = jnp.arange(x.shape[1])
    a = rms_norm(x, f["ln1_s"])
    q = rms_norm(_mm("btd,dhk->bthk", a, f["wq"], precision), f["qn_s"])
    k = rms_norm(_mm("btd,dhk->bthk", a, f["wk"], precision), f["kn_s"])
    v = _mm("btd,dhk->bthk", a, f["wv"], precision)
    g = _mm("btd,dhk->bthk", a, f["wog"], precision)
    if window is not None:  # rotary on sliding-window layers only
        q, k = rope(q, pos), rope(k, pos)
    o = attention(q, k, v, window, precision) * jax.nn.sigmoid(g)
    x = x + rms_norm(_mm("bthk,hkd->btd", o, f["wo"], precision), f["ln1p_s"])
    h = rms_norm(x, f["ln2_s"])
    if "router" in lp:
        w = route_weights(h, f["router"], f["router_bias"], top_k,
                          route_scale, precision)
        m = gated_mlp(h, f["ws_gate"], f["ws_up"], f["ws_down"], precision)
        m = m + experts_sum(h, lp, w, precision)
    else:
        m = gated_mlp(h, f["w_gate"], f["w_up"], f["w_down"], precision)
    return x + rms_norm(m, f["ln2p_s"])


def head_logits(x, head, lnf_s, precision: str = "float32"):
    x = rms_norm(x, lnf_s.astype(jnp.float32))
    return _mm("td,vd->tv", x, head.astype(jnp.float32), precision)


@functools.lru_cache(maxsize=None)
def _jitted_layer(window, precision, top_k, route_scale):
    return jax.jit(functools.partial(
        layer_forward, window=window, precision=precision, top_k=top_k,
        route_scale=route_scale,
    ))


@functools.lru_cache(maxsize=None)
def _jitted_head(precision):
    return jax.jit(functools.partial(head_logits, precision=precision))


def stream_logits(params, tokens, first_row: int, n_rows: int, *,
                  window, precision: str = "float32", top_k: int = TOP_K,
                  route_scale: float = ROUTE_SCALE):
    """Logits (n_rows, vocab) of rows first_row.. of one token sequence
    (tokens: (T,) int32, already padded to the length to compile for):
    row j predicts token j + 1. ``window``: one entry per layer, an
    int for a sliding-window layer and None for a full-attention one."""
    window = tuple(window)
    if len(window) != len(params["layers"]):
        raise ValueError(
            f"window names {len(window)} layers, the weights have "
            f"{len(params['layers'])}"
        )
    emb = params["emb"]
    x = emb[tokens].astype(jnp.float32)[None] * math.sqrt(emb.shape[1])
    for lp, w in zip(params["layers"], window):
        x = _jitted_layer(w, precision, top_k, float(route_scale))(x, lp)
    rows = jax.lax.dynamic_slice_in_dim(x[0], first_row, n_rows, axis=0)
    return _jitted_head(precision)(rows, params["head"], params["lnf_s"])
