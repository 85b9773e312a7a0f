"""Plain reference of the decoder block the ``xing4_0`` configuration runs
(``XingChen-AGI/Xing4.0-29B-A4B``): a residual path of ``hc_mult``
streams mixed by matrices made from the token itself
(manifold-constrained hyper-connections, arXiv:2512.24880, on
hyper-connections, arXiv:2409.19606), multi-head latent attention
(DeepSeek-V2's, with YaRN's rotary frequencies), a gated feed-forward
that is dense in the leading layers and, after them, sigmoid top-k
experts beside a shared expert, an untied head.

Straightforward ``jax.numpy`` in float32 at ``Precision.HIGHEST``: no
kernel, no cache, no absorption of an up-projection into the query
(every position's keys and values are expanded for all heads and
attended head by head), the experts as a plain sum in blocks, the
Sinkhorn rounds on each token's own 4 x 4. It imports nothing of the
program (only its sibling references' shared pieces) and is handed only
arrays that the benchmark made.

The layer, with what the published ``config.json`` does not carry
marked A (each listed under ``assumed`` in the configuration file):

* streams: ``X0[j] = emb[tok]`` for j < n = ``hc_mult`` (A: the
  hyper-connections paper's fan-out); after the last layer ``x = sum_j
  X[j]`` (A: its fold); ``logits = RMSNorm(x) head``.
* each half of each layer, with its own ``phi`` (n d, 2 n + n n, laid
  out ``[pre | post | res]``), scalars ``alpha`` (3) and biases ``b``
  (A: mHC's parameterisation): ``u = RMSNorm(vec(X))`` over the n d
  values, no learned scale; ``Hpre = sigmoid(alpha_pre u phi_pre +
  b_pre)``; ``Hpost = 2 sigmoid(alpha_post u phi_post + b_post)``;
  ``M = exp(clamp(alpha_res mat(u phi_res) + b_res,
  mhc_h_res_clamp_min, mhc_h_res_clamp_max))`` and then
  ``hc_sinkhorn_iters`` times: every column divided by its sum +
  ``hc_eps``, then every row by its sum + ``hc_eps``; ``Hres = M``.
  ``h = sum_j Hpre[j] X[j]``; ``y = half(RMSNorm(h))``;
  ``X'[i] = sum_j Hres[i, j] X[j] + Hpost[i] y``.
* attention half on ``a``: ``cq = RMSNorm(a Wdq)``; ``q = cq Wuq`` as H
  heads of ``[nope | rope]``; ``[ckv | kr] = a Wdkv``, ``ckv =
  RMSNorm(ckv)``; rotary on each head's ``rope`` dims and on ``kr`` (one
  key head for all), pairs ``(i, i + rope / 2)`` (A: the pairing),
  frequencies YaRN's (:func:`yarn_frequencies`), cos and sin times
  ``g(mscale) / g(mscale_all_dim)``, ``g(s) = 0.1 s ln(factor) + 1``;
  ``[kn | v] = ckv Wukv`` as H heads; causal softmax of ``(qn . kn + qr
  . kr) * (nope + rope) ** -0.5 * m ** 2``, ``m = g(mscale_all_dim)``;
  ``o Wo``. No bias.
* feed-forward half: a leading layer ``w_down(silu(w_gate h) * w_up
  h)``; the others ``s = sigmoid(h router)`` in float32, the ``k``
  largest ``s + bias`` (the bias selects and does not weigh), ``w = s /
  sum * routed_scaling_factor``, plus one ungated shared expert.

``precision`` other than ``"float32"`` is the *control* of the output
check (chipbench/control.py): the same mathematics with both inputs of
every matrix product rounded to a lower precision first.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

# a product with both inputs rounded to the control's precision (the
# dense block's reference), and the gated feed-forward, the sigmoid
# router and the experts as a plain sum in blocks (the afmoe block's)
from chipbench.references.afmoe import experts_sum, gated_mlp, route_weights
from chipbench.references.dense_transformer import HIGHEST, _mm

RMS_EPS = 1e-6              # rms_norm_eps
TOP_K = 4                   # num_experts_per_tok
ROUTE_SCALE = 2.0           # routed_scaling_factor
KV_RANK = 512               # kv_lora_rank
NOPE, ROPE = 128, 64        # qk_nope_head_dim, qk_rope_head_dim
HC_ITERS = 20               # hc_sinkhorn_iters
HC_EPS = 1e-6               # hc_eps
HC_CLAMP = (-30.0, 30.0)    # mhc_h_res_clamp_min / _max
# rope_theta and rope_scaling: (theta, factor, original_max_position_
# embeddings, beta_fast, beta_slow, mscale, mscale_all_dim)
YARN = (10000.0, 64.0, 4096, 32.0, 1.0, 1.0, 1.0)


def rms_norm(x, s=1.0, eps: float = RMS_EPS):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * s


def yarn_mscale(scale: float, factor: float) -> float:
    return 0.1 * scale * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn_frequencies(dims: int, yarn=YARN) -> list:
    """The angle a position of each of ``dims / 2`` rotary pairs, pair
    by pair: ``f = theta ** (-2 i / dims)`` blended with ``f / factor``
    by a ramp that is 0 up to the pair turning ``beta_fast`` times
    inside ``original_max`` positions and 1 from the pair turning
    ``beta_slow`` times (the ramp's ends the floor and ceiling of those
    two dims, clamped to the head)."""
    theta, factor, original, fast, slow = yarn[:5]

    def dim_of(turns):
        return (dims * math.log(original / (turns * 2 * math.pi))
                / (2 * math.log(theta)))

    lo = max(math.floor(dim_of(fast)), 0)
    hi = min(math.ceil(dim_of(slow)), dims - 1)
    out = []
    for i in range(dims // 2):
        f = theta ** (-2.0 * i / dims)
        ramp = min(max((i - lo) / (hi - lo if hi != lo else 1e-3), 0.0), 1.0)
        out.append(f * (1.0 - ramp) + f / factor * ramp)
    return out


def rope(x, pos, yarn=YARN):
    """x: (B, T, H, R); pairs (i, i + R/2) rotate by pos * YaRN's
    frequency of pair i, cos and sin scaled by YaRN's magnitude ratio."""
    half = x.shape[-1] // 2
    freqs = jnp.asarray(yarn_frequencies(x.shape[-1], yarn), jnp.float32)
    ang = pos.astype(jnp.float32)[:, None] * freqs[None, :]
    mag = yarn_mscale(yarn[5], yarn[1]) / yarn_mscale(yarn[6], yarn[1])
    cos = (jnp.cos(ang) * mag)[None, :, None, :]
    sin = (jnp.sin(ang) * mag)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def hc_matrices(X, phi, alpha, b, *, iters: int, precision: str):
    """Each token's ``(Hpre (B, T, n), Hpost (B, T, n), Hres (B, T, n,
    n))`` from its streams X: (B, T, n, D)."""
    B, T, n, D = X.shape
    u = rms_norm(X.reshape(B, T, n * D))
    c = _mm("btc,cf->btf", u, phi, precision)
    pre = jax.nn.sigmoid(alpha[0] * c[..., :n] + b[:n])
    post = 2.0 * jax.nn.sigmoid(alpha[1] * c[..., n:2 * n] + b[n:2 * n])
    res = (alpha[2] * c[..., 2 * n:] + b[2 * n:]).reshape(B, T, n, n)
    M = jnp.exp(jnp.clip(res, *HC_CLAMP))
    for _ in range(iters):
        M = M / (M.sum(axis=-2, keepdims=True) + HC_EPS)  # columns
        M = M / (M.sum(axis=-1, keepdims=True) + HC_EPS)  # rows
    return pre, post, M


def hc_half(X, lp, name: str, half, *, iters: int, precision: str):
    """One half over the streams: ``X' = Hres X + Hpost half(h)``, ``h
    = Hpre X``."""
    pre, post, res = hc_matrices(
        X, lp[name + "_phi"], lp[name + "_alpha"], lp[name + "_b"],
        iters=iters, precision=precision)
    y = half(jnp.einsum("btj,btjd->btd", pre, X, precision=HIGHEST))
    return (jnp.einsum("btij,btjd->btid", res, X, precision=HIGHEST)
            + post[..., None] * y[:, :, None])


def latent_attention(a, f, *, kv_rank: int, nope: int, yarn, precision: str):
    """The attention mixer on normed a: (B, T, D); f: float32 leaves.
    Expanded: every position's keys and values for all heads, one head's
    (T, T) scores at a time."""
    B, T, _ = a.shape
    pos = jnp.arange(T)
    cq = rms_norm(_mm("btd,dr->btr", a, f["mla_wdq"], precision),
                  f["mla_qn_s"])
    q = _mm("btr,rhk->bthk", cq, f["mla_wuq"], precision)
    ckr = _mm("btd,dr->btr", a, f["mla_wdkv"], precision)
    ckv = rms_norm(ckr[..., :kv_rank], f["mla_kvn_s"])
    kr = rope(ckr[:, :, None, kv_rank:], pos, yarn)       # (B, T, 1, R)
    qn, qr = q[..., :nope], rope(q[..., nope:], pos, yarn)
    kv = _mm("btr,rhk->bthk", ckv, f["mla_wukv"], precision)
    kn, v = kv[..., :nope], kv[..., nope:]
    H = q.shape[2]
    m = yarn_mscale(yarn[6], yarn[1])
    scale = q.shape[-1] ** -0.5 * m * m
    seen = pos[None, :] <= pos[:, None]

    @jax.checkpoint
    def one_head(args):
        qh, kh, vh = args  # (T, nope + R), (T, nope + R), (T, v)
        s = _mm("qd,kd->qk", qh, kh, precision) * scale
        p = jax.nn.softmax(jnp.where(seen, s, -1e30), axis=-1)
        return _mm("qk,kd->qd", p, vh, precision)

    heads = lambda t: t.transpose(0, 2, 1, 3).reshape((B * H, T, -1))
    kfull = jnp.concatenate(
        [kn, jnp.broadcast_to(kr, kn.shape[:-1] + kr.shape[-1:])], -1)
    o = jax.lax.map(one_head, (heads(jnp.concatenate([qn, qr], -1)),
                               heads(kfull), heads(v)))
    o = o.reshape(B, H, T, -1).transpose(0, 2, 1, 3)
    return _mm("bthk,hkd->btd", o, f["wo"], precision)


def layer_forward(X, lp, *, precision: str = "float32", top_k: int = TOP_K,
                  route_scale: float = ROUTE_SCALE, kv_rank: int = KV_RANK,
                  nope: int = NOPE, iters: int = HC_ITERS, yarn=YARN):
    """One block on float32 streams X: (B, T, n, D). ``lp`` holds the
    block's weights in whatever type they are kept; they are read as
    float32 here (the stacked experts block by block)."""
    stacked = ("we_gate", "we_up", "we_down")
    f = {n: a.astype(jnp.float32) for n, a in lp.items() if n not in stacked}

    def attn(h):
        return latent_attention(rms_norm(h, f["ln1_s"]), f, kv_rank=kv_rank,
                                nope=nope, yarn=yarn, precision=precision)

    def ffn(h):
        h = rms_norm(h, f["ln2_s"])
        if "router" not in lp:
            return gated_mlp(h, f["w_gate"], f["w_up"], f["w_down"],
                             precision)
        w = route_weights(h, f["router"], f["router_bias"], top_k,
                          route_scale, precision)
        return (gated_mlp(h, f["ws_gate"], f["ws_up"], f["ws_down"],
                          precision) + experts_sum(h, lp, w, precision))

    X = hc_half(X, f, "hc1", attn, iters=iters, precision=precision)
    return hc_half(X, f, "hc2", ffn, iters=iters, precision=precision)


def head_logits(x, head, lnf_s, precision: str = "float32"):
    x = rms_norm(x, lnf_s.astype(jnp.float32))
    return _mm("td,vd->tv", x, head.astype(jnp.float32), precision)


@functools.lru_cache(maxsize=None)
def _jitted_layer(**kw):
    return jax.jit(functools.partial(layer_forward, **kw))


@functools.lru_cache(maxsize=None)
def _jitted_head(precision):
    return jax.jit(functools.partial(head_logits, precision=precision))


def stream_logits(params, tokens, first_row: int, n_rows: int, *,
                  hc_mult: int, precision: str = "float32", **sizes):
    """Logits (n_rows, vocab) of rows first_row.. of one token sequence
    (tokens: (T,) int32, already padded to the length to compile for):
    row j predicts token j + 1. ``sizes``: :func:`layer_forward`'s
    keywords, for a configuration of other sizes than the published
    ones. Which layers are dense is read from their leaves."""
    x = params["emb"][tokens].astype(jnp.float32)[None]
    X = jnp.broadcast_to(x[:, :, None], x.shape[:2] + (hc_mult, x.shape[-1]))
    layer = _jitted_layer(precision=precision, **sizes)
    for lp in params["layers"]:
        X = layer(X, lp)
    rows = jax.lax.dynamic_slice_in_dim(X.sum(axis=2)[0], first_row, n_rows,
                                        axis=0)
    return _jitted_head(precision)(rows, params["head"], params["lnf_s"])
