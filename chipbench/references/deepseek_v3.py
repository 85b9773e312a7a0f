"""Plain reference of the decoder the ``deepseek_v3`` configuration runs
(``deepseek-ai/DeepSeek-V3``, arXiv:2412.19437 sections 2.1 and 2.2):
multi-head latent attention with YaRN's rotary frequencies on one
pre-norm residual stream, a gated feed-forward that is dense in the
leading layers and, after them, sigmoid experts chosen under a group
limit beside a shared expert, an untied head, and behind the model its
multi-token-prediction module: one more block of the same kind whose
row i guesses token i + 2.

Straightforward ``jax.numpy`` in float32 at ``Precision.HIGHEST``: no
kernel, no cache, no absorption, no step of one or two tokens. A whole
sequence goes through the model once, teacher-forced, and the module
goes over the same sequence once more. It imports nothing of the
program (only its sibling references' shared pieces) and is handed only
arrays that the benchmark made, the requests' sampling keys among them.

The equations, with what the published ``config.json`` does not carry
marked A (each listed under ``assumed`` in the configuration file):

* ``x = emb[tok]``; each layer ``x += attn(RMSNorm(x))``, ``x +=
  ffn(RMSNorm(x))``; ``logits = RMSNorm(x) head``.
* attention: references/xing4_0.py's ``latent_attention`` (the same
  keys: q through a rank-``q_lora_rank`` bottleneck with its norm to
  heads of ``[nope | rope]``, keys and values through the normalised
  latent of ``kv_lora_rank`` beside ONE rotated key, YaRN, the scale
  ``(nope + rope) ** -0.5 * m ** 2``).
* experts: ``s = sigmoid(h Wr)`` over all ``router_experts`` in
  float32; ``s' = s + b`` (b selects and does not weigh); the experts
  as ``n_group`` equal runs, a group's score the sum of its two largest
  ``s'``; the ``topk_group`` best groups stay and the other groups'
  ``s'`` are masked; the ``top_k`` largest of what is left are chosen;
  ``w = s`` of the chosen, divided by their sum + 1e-20, times
  ``routed_scaling_factor``; plus one ungated shared expert. Of the
  routed experts only those this chip holds are summed (the matrices
  handed in are experts ``[held_lo, held_lo + E_held)``): what the
  others would add is a further chip's, left out as in the program.
* the module (A: the paper's equations 21 to 23; the config has
  ``num_nextn_predict_layers`` alone): ``h'_i = [RMSNorm_h(h_i) ;
  RMSNorm_e(emb[t_{i+1}])] M`` with ``h_i`` the model's last block
  output at position i, before the final norm; one block as above;
  ``q_i = RMSNorm_m(block(h')_i) head`` with the MODEL'S embedding and
  head. Row i guesses token i + 2.
* sampling (A: the program's own rule): the token at position j + 1 is
  ``argmax(logits_j / T + G(key, j))``, ``G`` standard Gumbel noise
  drawn with ``jax.random`` from the request's key folded with j (and
  with row 0). A draft of the token at j + 1 is the same rule on the
  module's row j - 1 with the SAME noise ``G(key, j)``.

``precision`` other than ``"float32"`` is the *control* of the output
check (chipbench/control.py): the same mathematics with both inputs of
every matrix product rounded to a lower precision first.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from chipbench.references.afmoe import experts_sum, gated_mlp
from chipbench.references.dense_transformer import _mm
from chipbench.references.xing4_0 import latent_attention, rms_norm

TOP_K = 8                    # num_experts_per_tok
ROUTE_SCALE = 2.5            # routed_scaling_factor
N_GROUP, TOPK_GROUP = 8, 4   # n_group, topk_group
KV_RANK = 512                # kv_lora_rank
NOPE, ROPE = 128, 64         # qk_nope_head_dim, qk_rope_head_dim
EXPERT_BLOCK = 4             # held experts upcast and multiplied at a time
# rope_theta and rope_scaling: (theta, factor, original_max_position_
# embeddings, beta_fast, beta_slow, mscale, mscale_all_dim)
YARN = (10000.0, 40.0, 4096, 32.0, 1.0, 1.0, 1.0)


def group_limited_weights(h, router, bias, *, top_k: int, route_scale: float,
                          n_group: int, topk_group: int, precision: str):
    """(B, T, E) weights over ALL the router's experts: the normalised,
    scaled sigmoid score of each chosen expert, zero for the others;
    chosen among the experts of the ``topk_group`` best groups only."""
    s = jax.nn.sigmoid(_mm("btd,de->bte", h, router, precision))
    sel = s + bias
    B, T, E = sel.shape
    groups = sel.reshape(B, T, n_group, E // n_group)
    score = jnp.sort(groups, axis=-1)[..., -2:].sum(-1)       # (B, T, G)
    kth = jnp.sort(score, axis=-1)[..., n_group - topk_group]
    keep = score >= kth[..., None]
    sel = jnp.where(keep[..., None], groups, -jnp.inf).reshape(B, T, E)
    _, idx = jax.lax.top_k(sel, top_k)
    chosen = jax.nn.one_hot(idx, E, dtype=jnp.float32).sum(-2)
    w = s * chosen
    return w / (w.sum(-1, keepdims=True) + 1e-20) * route_scale


def attention_half(x, lp, *, kv_rank: int, nope: int, yarn, precision: str):
    """``x + attn(RMSNorm(x))`` on float32 x: (B, T, D)."""
    names = ("ln1_s", "mla_wdq", "mla_qn_s", "mla_wuq", "mla_wdkv",
             "mla_kvn_s", "mla_wukv", "wo")
    f = {n: lp[n].astype(jnp.float32) for n in names}
    return x + latent_attention(
        rms_norm(x, f["ln1_s"]), f, kv_rank=kv_rank, nope=nope, yarn=yarn,
        precision=precision)


def ffn_half(x, lp, *, top_k: int, route_scale: float, n_group: int,
             topk_group: int, held_lo: int, precision: str):
    """``x + ffn(RMSNorm(x))``: the dense feed-forward of a leading
    layer, else the shared expert plus the held routed experts."""
    f = lambda n: lp[n].astype(jnp.float32)
    h = rms_norm(x, f("ln2_s"))
    if "router" not in lp:
        return x + gated_mlp(h, f("w_gate"), f("w_up"), f("w_down"),
                             precision)
    w = group_limited_weights(
        h, f("router"), f("router_bias"), top_k=top_k,
        route_scale=route_scale, n_group=n_group, topk_group=topk_group,
        precision=precision)
    held = lp["we_gate"].shape[0]
    return (x + gated_mlp(h, f("ws_gate"), f("ws_up"), f("ws_down"),
                          precision)
            + experts_sum(h, lp, w[..., held_lo:held_lo + held], precision,
                          EXPERT_BLOCK))


def mtp_input(h, emb_next, mp, precision: str):
    """``[RMSNorm_h(h) ; RMSNorm_e(emb[next token])] M``."""
    f = lambda n: mp[n].astype(jnp.float32)
    x = jnp.concatenate([rms_norm(h, f("hn_s")),
                         rms_norm(emb_next, f("en_s"))], axis=-1)
    return _mm("btc,cd->btd", x, f("eh_proj"), precision)


def head_logits(x, head, lnf_s, precision: str = "float32"):
    x = rms_norm(x, lnf_s.astype(jnp.float32))
    return _mm("td,vd->tv", x, head.astype(jnp.float32), precision)


@functools.lru_cache(maxsize=None)
def _jitted(fn, **kw):
    return jax.jit(functools.partial(fn, **kw))


def _block(x, lp, precision, sizes):
    """One block, its two halves as two programs (a half's float32
    copies of its weights are gone before the other's are made)."""
    attn = {k: sizes[k] for k in ("kv_rank", "nope", "yarn")}
    ffn = {k: sizes[k] for k in ("top_k", "route_scale", "n_group",
                                 "topk_group", "held_lo")}
    x = _jitted(attention_half, precision=precision, **attn)(x, lp)
    return _jitted(ffn_half, precision=precision, **ffn)(x, lp)


def stream_logits(params, tokens, first_row: int, n_rows: int, *,
                  precision: str = "float32", top_k: int = TOP_K,
                  route_scale: float = ROUTE_SCALE, n_group: int = N_GROUP,
                  topk_group: int = TOPK_GROUP, held_lo: int = 0,
                  kv_rank: int = KV_RANK, nope: int = NOPE, yarn=YARN):
    """``(logits, mtp_logits)``, each (n_rows, vocab), of rows
    first_row.. of one token sequence (tokens: (T,) int32, already
    padded to the length to compile for): row j of the first predicts
    token j + 1, row j of the second (the module's, from the model's
    last block output at j and token j + 1) token j + 2; None where the
    weights have no module. Which layers are dense is read from their
    leaves."""
    sizes = dict(top_k=top_k, route_scale=float(route_scale),
                 n_group=n_group, topk_group=topk_group, held_lo=held_lo,
                 kv_rank=kv_rank, nope=nope, yarn=tuple(yarn))
    x = params["emb"][tokens].astype(jnp.float32)[None]
    for lp in params["layers"]:
        x = _block(x, lp, precision, sizes)
    rows = lambda a: jax.lax.dynamic_slice_in_dim(a[0], first_row, n_rows,
                                                  axis=0)
    head = _jitted(head_logits, precision=precision)
    logits = head(rows(x), params["head"], params["lnf_s"])
    mp = params.get("mtp")
    if mp is None:
        return logits, None
    after = params["emb"][jnp.roll(tokens, -1)].astype(jnp.float32)[None]
    m = _jitted(mtp_input, precision=precision)(
        x, after, {k: v for k, v in mp.items() if k != "block"})
    m = _block(m, mp["block"], precision, sizes)
    return logits, head(rows(m), params["head"], mp["lnf_s"])


def gumbel_rows(key, first_position: int, n_rows: int, vocab: int):
    """(n_rows, vocab) standard Gumbel noise, row j drawn from the
    request's ``key`` folded with position ``first_position + j`` and
    then with row 0: what the program's sampling adds to the logits at
    that position before it takes the largest."""
    def one(pos):
        k = jax.random.fold_in(jax.random.fold_in(key, pos), 0)
        return jax.random.gumbel(k, (vocab,), jnp.float32)

    return jax.vmap(one)(first_position + jnp.arange(n_rows))
