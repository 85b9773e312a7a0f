"""Plain reference of the decoder block the StarCoder2 configurations
run: pre-LayerNorm, grouped-query attention with rotary positions and a
sliding causal window, a biased tanh-GELU MLP, a tied output head.

Straightforward ``jax.numpy`` in float32 at ``Precision.HIGHEST``: no
kernel, no cache, no batching tricks. It imports nothing of the program
and is handed only arrays that the benchmark made (chipbench/weights.py).
To fit beside nothing else on one chip it works layer by layer (each
layer's float32 copy of the weights exists only inside that layer's
call) and head by head inside attention.

Departures from the published model, all shared with the program under
test and listed in the configurations under ``assumed``: rotary base
10000 with the half-split pairing, tanh-approximated GELU, LayerNorm
epsilon 1e-5, no bias on the attention projections.

``precision`` other than ``"float32"`` is the *control* of the output
check (chipbench/control.py): the same mathematics with both inputs of
every matrix product rounded to a lower precision first.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
LN_EPS = 1e-5
ROPE_BASE = 10000.0


def _round_to(x, precision: str):
    """x rounded through a lower precision and back (straight-through
    for gradients). fp8 and int8 are scaled per tensor to their range."""
    if precision == "float32":
        return x
    if precision == "bfloat16":
        q = x.astype(jnp.bfloat16).astype(jnp.float32)
    elif precision == "fp8":
        s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
        q = (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
    elif precision == "int8":
        s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 127.0
        q = jnp.round(x / s) * s
    else:
        raise ValueError(f"unknown precision {precision!r}")
    return x + jax.lax.stop_gradient(q - x)


def _mm(eq: str, a, b, precision: str):
    return jnp.einsum(
        eq, _round_to(a, precision), _round_to(b, precision),
        precision=HIGHEST, preferred_element_type=jnp.float32,
    )


def layer_norm(x, s, b):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + LN_EPS) * s + b


def rope(x, pos):
    """x: (B, T, H, Dh); pairs (i, i + Dh/2) rotate by pos * base^(-i/(Dh/2))."""
    half = x.shape[-1] // 2
    freqs = 1.0 / (ROPE_BASE ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = pos.astype(jnp.float32)[:, None] * freqs[None, :]
    cos = jnp.cos(ang)[None, :, None, :]
    sin = jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def attention(q, k, v, window: int | None, precision: str):
    """q: (B, T, H, Dh); k, v: (B, T, Hkv, Dh). Query head h reads K/V
    head h // (H / Hkv). One (T, T) score matrix at a time."""
    B, T, H, Dh = q.shape
    group = H // k.shape[2]
    pos = jnp.arange(T)
    dist = pos[:, None] - pos[None, :]
    mask = dist >= 0
    if window is not None:
        mask = mask & (dist < window)
    scale = 1.0 / math.sqrt(Dh)

    @jax.checkpoint
    def one_head(args):
        qh, kh, vh = args  # (T, Dh) each
        s = _mm("qd,kd->qk", qh, kh, precision) * scale
        s = jnp.where(mask, s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        return _mm("qk,kd->qd", p, vh, precision)

    qf = q.transpose(0, 2, 1, 3).reshape(B * H, T, Dh)
    kf = jnp.repeat(k.transpose(0, 2, 1, 3), group, axis=1).reshape(B * H, T, Dh)
    vf = jnp.repeat(v.transpose(0, 2, 1, 3), group, axis=1).reshape(B * H, T, Dh)
    o = jax.lax.map(one_head, (qf, kf, vf))
    return o.reshape(B, H, T, Dh).transpose(0, 2, 1, 3)


def layer_forward(x, lp, *, window: int | None, precision: str = "float32"):
    """One block on float32 activations x: (B, T, D). ``lp`` holds the
    block's weights in whatever type they are kept; they are read as
    float32 here."""
    lp = jax.tree.map(lambda a: a.astype(jnp.float32), lp)
    pos = jnp.arange(x.shape[1])
    h = layer_norm(x, lp["ln1_s"], lp["ln1_b"])
    q = rope(_mm("btd,dhk->bthk", h, lp["wq"], precision), pos)
    k = rope(_mm("btd,dhk->bthk", h, lp["wk"], precision), pos)
    v = _mm("btd,dhk->bthk", h, lp["wv"], precision)
    o = attention(q, k, v, window, precision)
    x = x + _mm("bthk,hkd->btd", o, lp["wo"], precision)
    h = layer_norm(x, lp["ln2_s"], lp["ln2_b"])
    a = jax.nn.gelu(_mm("btd,df->btf", h, lp["w1"], precision) + lp["b1"],
                    approximate=True)
    return x + _mm("btf,fd->btd", a, lp["w2"], precision) + lp["b2"]


def head_logits(x, emb, lnf_s, lnf_b, precision: str = "float32"):
    x = layer_norm(x, lnf_s.astype(jnp.float32), lnf_b.astype(jnp.float32))
    return _mm("td,vd->tv", x, emb.astype(jnp.float32), precision)


# -- serving: teacher-forced logits of one stream ---------------------------


@functools.lru_cache(maxsize=None)
def _jitted_layer(window, precision):
    return jax.jit(
        functools.partial(layer_forward, window=window, precision=precision)
    )


@functools.lru_cache(maxsize=None)
def _jitted_head(precision):
    return jax.jit(functools.partial(head_logits, precision=precision))


def stream_logits(params, tokens, first_row: int, n_rows: int, *,
                  window: int | None, precision: str = "float32"):
    """Logits (n_rows, vocab) of rows first_row.. of one token sequence
    (tokens: (T,) int32, already padded to the length to compile for):
    row j predicts token j + 1."""
    x = params["emb"][tokens].astype(jnp.float32)[None]
    layer = _jitted_layer(window, precision)
    for lp in params["layers"]:
        x = layer(x, lp)
    rows = jax.lax.dynamic_slice_in_dim(x[0], first_row, n_rows, axis=0)
    return _jitted_head(precision)(
        rows, params["emb"], params["lnf_s"], params["lnf_b"]
    )


# -- training: losses, gradient norms and parameter change ------------------


def _head_loss(x_row, tgt_row, emb, lnf_s, lnf_b, denom, precision):
    logits = head_logits(x_row, emb, lnf_s, lnf_b, precision)
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, tgt_row[:, None], axis=-1)[:, 0]
    return (lse - picked).sum() / denom


@functools.lru_cache(maxsize=None)
def _jitted_head_grad(precision):
    def run(x_row, tgt_row, emb, lnf_s, lnf_b, denom):
        f32 = lambda a: a.astype(jnp.float32)
        return jax.value_and_grad(
            functools.partial(_head_loss, precision=precision),
            argnums=(0, 2, 3, 4),
        )(x_row, tgt_row, f32(emb), f32(lnf_s), f32(lnf_b), denom)

    return jax.jit(run)


@functools.lru_cache(maxsize=None)
def _jitted_layer_vjp(window, precision):
    def run(x, lp, dy):
        lp32 = jax.tree.map(lambda a: a.astype(jnp.float32), lp)
        _, vjp = jax.vjp(
            functools.partial(layer_forward, window=window,
                              precision=precision), x, lp32,
        )
        return vjp(dy)

    return jax.jit(run)


def _sgd_stored(p, g, lr):
    """The configured update on parameters kept in ``p.dtype``: the
    gradient is rounded to that type, scaled by the learning rate and
    subtracted, each in that type."""
    return p - lr * g.astype(p.dtype)


@jax.jit
def _norm(a):
    return jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32))))


@jax.jit
def _diff_norm(a, b):
    return jnp.sqrt(jnp.sum(jnp.square(
        a.astype(jnp.float32) - b.astype(jnp.float32))))


def train_steps(params, batches, *, lr: float, window: int | None,
                precision: str = "float32"):
    """Follow ``len(batches)`` SGD steps from ``params`` (kept in their
    own type, as the configuration stores them). Each batch is
    (inputs, targets), (B, L) int32. Returns the loss of every step, the
    norm of every leaf's first gradient and of every leaf's change over
    all the steps, the latter two as pytrees shaped like ``params``."""
    start = params
    losses, grad_norms = [], None
    layer = _jitted_layer(window, precision)
    layer_vjp = _jitted_layer_vjp(window, precision)
    head_grad = _jitted_head_grad(precision)
    upd = jax.jit(_sgd_stored, static_argnums=2)
    for step, (inp, tgt) in enumerate(batches):
        B, L = inp.shape
        xs = [params["emb"][inp].astype(jnp.float32)]
        for lp in params["layers"]:
            xs.append(layer(xs[-1], lp))
        denom = jnp.float32(B * L)
        loss = 0.0
        dx_rows, g_emb, g_s, g_b = [], 0.0, 0.0, 0.0
        for r in range(B):
            nll, (dx, de, ds, db) = head_grad(
                xs[-1][r], tgt[r], params["emb"], params["lnf_s"],
                params["lnf_b"], denom,
            )
            loss = loss + nll
            dx_rows.append(dx)
            g_emb, g_s, g_b = g_emb + de, g_s + ds, g_b + db
        losses.append(float(loss))
        dx = jnp.stack(dx_rows)
        del dx_rows
        new_layers, layer_norms = [], []
        for li in reversed(range(len(params["layers"]))):
            lp = params["layers"][li]
            dx, g_lp = layer_vjp(xs[li], lp, dx)
            xs[li + 1] = None
            if step == 0:
                layer_norms.append(jax.tree.map(_norm, g_lp))
            new_layers.append(jax.tree.map(lambda p, g: upd(p, g, lr), lp, g_lp))
            del g_lp
        new_layers.reverse()
        layer_norms.reverse()
        g_emb = g_emb + jnp.zeros_like(g_emb).at[inp.reshape(-1)].add(
            dx.reshape(-1, dx.shape[-1]))
        if step == 0:
            grad_norms = {
                "emb": _norm(g_emb), "layers": layer_norms,
                "lnf_s": _norm(g_s), "lnf_b": _norm(g_b),
            }
        params = {
            "emb": upd(params["emb"], g_emb, lr),
            "layers": new_layers,
            "lnf_s": upd(params["lnf_s"], g_s, lr),
            "lnf_b": upd(params["lnf_b"], g_b, lr),
        }
        del xs, dx, g_emb
    change = jax.tree.map(_diff_norm, params, start)
    to_float = lambda t: jax.tree.map(float, t)
    return losses, to_float(grad_norms), to_float(change)
