"""Speculative decoding with n-gram (prompt-lookup) drafting.

Serving-side throughput for the flagship decode path (models/decode.py):
instead of one forward per token, draft ``k`` candidate tokens by
bigram lookup in the already-generated context, verify all of them in
ONE ``k+1``-token forward against the KV cache (the chunked-extend
program shape), and accept the longest matching prefix plus the
model's own correction token. Every iteration emits between 1 and
``k+1`` tokens.

**The output is exactly the greedy stream** — speculation is a
scheduling transform, not an approximation: a draft token is accepted
only when it equals the argmax the model produces at that position
teacher-forced on the exact accepted prefix, and the first rejected
position emits that argmax instead. tests/test_speculative.py pins
token-for-token equality with ``generate_dense`` on random, repetitive,
and adversarial prompts; the speedup is the only thing that varies
(acceptance depends on how self-predictable the stream is — lookup
drafting wins on loops, templates, and copy-heavy continuations).

Cache-consistency argument (why rejected drafts never poison the KV
cache): the verify forward at cursor ``c`` writes positions
``[c-1, c+k-1]`` *before* attending (``_incremental_layer`` updates
then reads). After accepting ``m+1`` tokens the next verify starts at
``c' = c+m+1 <= c+k+1``, so its write window ``[c'-1, c'+k-1]`` covers
every stale position ``[c', c+k-1]`` left by the rejected tail —
garbage is always overwritten before any read reaches it.

Two drafters share the one verify loop (any draft is CORRECT — a bad
one just lowers acceptance, never the output):

* **n-gram (prompt lookup)**, the default: find the most recent
  earlier occurrence of the current bigram and propose the ``k``
  tokens that followed it; with no match, repeat the last token.
  Free (no extra model FLOPs) and strong on self-predictable streams
  (loops, templates, copy-heavy continuations).
* **truncated-layer model draft** (``draft_layers=d``): the first
  ``d`` layers of the SAME checkpoint plus the shared head act as the
  draft model, with their own KV cache carried through the loop. Each
  iteration teacher-forces the (k+1)-token trailing window through the
  draft stack (idempotent rewrites cover every position a rejected
  tail left stale — same overwrite-before-read argument as the verify
  cache below) and then drafts ``k`` tokens autoregressively. Costs
  ~``(d/L)·(2k)`` extra forward-fractions per iteration; wins when its
  acceptance on non-self-predictable streams beats lookup's by more
  than that — the spec rung measures both on the same stream.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from .decode import (
    _check_decode_mesh,
    _incremental_forward,
    init_cache,
    prefill_dense,
)
from .transformer import TransformerConfig

__all__ = [
    "generate_speculative_dense",
    "make_speculative_dense",
    "make_speculative",
]


def _bigram_draft(buf, cursor, k: int):
    """(L,) token buffer, known through ``cursor`` -> (k,) draft.

    Proposes the continuation of the most recent earlier occurrence of
    the last known bigram ``(buf[cursor-2], buf[cursor-1])``; falls
    back to repeating the last token. Pure device ops, O(L) compare."""
    L = buf.shape[0]
    idx = jnp.arange(L)
    a0, a1 = buf[cursor - 2], buf[cursor - 1]
    nxt = jnp.roll(buf, -1)
    match = (buf == a0) & (nxt == a1) & (idx < cursor - 2)
    p = jnp.max(jnp.where(match, idx, -1))
    has = p >= 0
    start = jnp.where(has, p + 2, cursor - 1)
    dr = jax.lax.dynamic_slice(buf, (start,), (k,))
    return jnp.where(has, dr, buf[cursor - 1])


def _make_model_draft(params_d, cfg_d: TransformerConfig, Lbuf: int,
                      k: int, **fwd_kwargs):
    """Truncated-layer draft model: ``(draft_init, draft_step)`` over a
    draft-cache state. ``draft_init(prompt, cache_d)`` prefills;
    ``draft_step(buf, cursor, cache_d) -> (draft (k,), cache_d)``
    teacher-forces the trailing (k+1) window (covering every position a
    rejected tail left stale — rewrites are idempotent) then drafts k
    tokens autoregressively."""

    def draft_init(prompt, cache_d):
        _, cache_d = _incremental_forward(
            params_d, prompt, cache_d, jnp.int32(0), cfg_d,
            prefill=True, **fwd_kwargs,
        )
        return cache_d

    def draft_step(buf, cursor, cache_d):
        off = jnp.maximum(cursor - 1 - k, 0)
        chunk = jax.lax.dynamic_slice(buf, (off,), (k + 1,))[None]
        lg, cache_d = _incremental_forward(
            params_d, chunk, cache_d, off, cfg_d, prefill=False,
            **fwd_kwargs,
        )
        # logits at local index (cursor-1)-off predict position cursor
        t0 = jnp.argmax(
            jnp.take(lg[0], cursor - 1 - off, axis=0)
        ).astype(buf.dtype)

        def sstep(carry, i):
            tok, cache_d = carry
            lg1, cache_d = _incremental_forward(
                params_d, tok[None, None], cache_d, cursor + i, cfg_d,
                prefill=False, **fwd_kwargs,
            )
            nt = jnp.argmax(lg1[0, 0]).astype(buf.dtype)
            return (nt, cache_d), tok

        (last, cache_d), toks = jax.lax.scan(
            sstep, (t0, cache_d), jnp.arange(k - 1)
        )
        return jnp.concatenate([toks, last[None]]), cache_d

    return draft_init, draft_step


def _spec_loop(prefill, step, cache, prompt, Tp: int, n_new: int,
               k: int, draft=None, dstate=()):
    """THE draft/verify loop — the exact-greedy acceptance contract
    lives here once, shared by the dense and sharded programs and by
    both drafters.

    ``prefill(prompt, cache) -> (logits (1, Tp, V), cache)``;
    ``step(chunk (1, k+1), cache, offset) -> (logits, cache)``;
    ``draft(buf, cursor, dstate) -> (draft (k,), dstate)`` — defaults
    to the stateless n-gram lookup.
    Returns the packed ``(n_new + 1,)`` array: tokens + the verify-
    forward count in the last slot (one array = one D2H fetch
    instead of two)."""
    if prompt.shape[1] != Tp:
        raise ValueError(
            f"program compiled for Tp={Tp}, got prompt of "
            f"{prompt.shape[1]} tokens: positions past the prompt "
            "would attend unwritten zero K/V and diverge silently"
        )
    if draft is None:
        def draft(buf, cursor, dstate):
            return _bigram_draft(buf, cursor, k), dstate

    Lbuf = Tp + n_new + k + 1  # slack: the last verify may overrun
    logits, cache = prefill(prompt, cache)
    first = jnp.argmax(logits[0, -1]).astype(prompt.dtype)
    buf = jnp.zeros((Lbuf,), prompt.dtype)
    buf = jax.lax.dynamic_update_slice(buf, prompt[0], (0,))
    buf = buf.at[Tp].set(first)

    def cond(state):
        _, cursor, _, _, _ = state
        return cursor < Tp + n_new

    def body(state):
        buf, cursor, cache, dstate, iters = state
        dr, dstate = draft(buf, cursor, dstate)  # (k,)
        chunk = jnp.concatenate(
            [jax.lax.dynamic_slice(buf, (cursor - 1,), (1,)), dr]
        )[None]  # (1, k+1) at positions cursor-1 .. cursor+k-1
        lg, cache = step(chunk, cache, cursor - 1)
        greedy = jnp.argmax(lg[0], axis=-1).astype(buf.dtype)  # (k+1,)
        # greedy[i] is the model's token for position cursor+i given
        # the exact prefix; accept drafts while they match it
        acc = jnp.cumprod((greedy[:k] == dr).astype(jnp.int32))
        m = jnp.sum(acc, dtype=jnp.int32)  # accepted drafts, 0..k
        draft_ext = jnp.concatenate([dr, dr[-1:]])
        # emit[i<m] = draft[i] (== greedy[i]); emit[m] = greedy[m]
        # (the correction); entries past m are dead — overwritten
        # by later iterations before any read
        emit = jnp.where(jnp.arange(k + 1) < m, draft_ext, greedy)
        buf = jax.lax.dynamic_update_slice(buf, emit, (cursor,))
        return buf, cursor + m + 1, cache, dstate, iters + 1

    buf, cursor, _, _, iters = jax.lax.while_loop(
        cond, body, (buf, jnp.int32(Tp + 1), cache, dstate,
                     jnp.int32(0))
    )
    return jnp.concatenate(
        [buf[Tp:Tp + n_new], iters.astype(buf.dtype)[None]]
    )


def _truncated(params, d: int):
    """Draft params: the first ``d`` layers + the shared embedding and
    final norm of the SAME checkpoint (no extra weights to manage)."""
    return {**params, "layers": params["layers"][:d]}


def _check_draft_layers(cfg: TransformerConfig, draft_layers):
    if cfg.state_layers:
        # the cache-consistency argument above is about ROWS: a rejected
        # draft's K/V are overwritten before they are read. A recurrent
        # state that has swallowed a rejected token cannot be rewound
        raise ValueError(
            "speculative decoding verifies drafts by overwriting cache "
            "rows; this configuration has gated delta-rule layers, "
            "whose state cannot be rolled back past a rejected draft"
        )
    if cfg.sparse_layers:
        raise ValueError(
            "speculative decoding verifies drafts by overwriting cache "
            "rows; this configuration keeps sums of keys beside its "
            "rows (the pooled cells of a selection of key blocks), "
            "from which a rejected draft's key cannot be taken back"
        )
    if cfg.latent_layers or cfg.hc_mult > 1:
        raise ValueError(
            "speculative decoding's draft and verify programs are "
            "written for K/V row caches under one residual stream; this "
            "configuration has "
            + ("latent-attention layers (one row a position)"
               if cfg.latent_layers else
               f"a residual path of {cfg.hc_mult} streams, which a "
               "truncated draft would fold before they are mixed")
        )
    if draft_layers is None:
        return None
    d = int(draft_layers)
    if not 0 < d < cfg.n_layers:
        raise ValueError(
            f"draft_layers must be in [1, {cfg.n_layers - 1}] "
            f"(a strict truncation of the model), got {draft_layers}"
        )
    return d


@functools.lru_cache(maxsize=64)
def _spec_runner(cfg: TransformerConfig, Tp: int, n_new: int, k: int,
                 draft_layers: int | None = None):
    Lbuf = Tp + n_new + k + 1

    @jax.jit
    def run(params, prompt):
        cache = init_cache(cfg, 1, Lbuf)
        draft, dstate = None, ()
        if draft_layers is not None:
            cfg_d = dataclasses.replace(cfg, n_layers=draft_layers)
            params_d = _truncated(params, draft_layers)
            draft_init, draft = _make_model_draft(
                params_d, cfg_d, Lbuf, k
            )
            dstate = draft_init(prompt, init_cache(cfg_d, 1, Lbuf))
        return _spec_loop(
            lambda pr, c: prefill_dense(params, pr, c, cfg),
            lambda ch, c, off: _incremental_forward(
                params, ch, c, off, cfg, prefill=False
            ),
            cache, prompt, Tp, n_new, k, draft=draft, dstate=dstate,
        )

    return run


def make_speculative_dense(
    cfg: TransformerConfig, Tp: int, n_new: int, k: int = 4,
    *, draft_layers: int | None = None,
):
    """The raw jitted program: ``run(params, prompt (1, Tp)) ->
    (n_new + 1,) device array`` of tokens plus the verify-forward count
    in the last slot (one array = one D2H fetch). For callers that
    manage fencing themselves (benchmarks chaining several generations
    per fence); everyone else wants
    :func:`generate_speculative_dense`. ``draft_layers=d`` swaps the
    n-gram drafter for the truncated-layer model draft."""
    return _spec_runner(
        cfg, int(Tp), int(n_new), int(k),
        _check_draft_layers(cfg, draft_layers),
    )


def generate_speculative_dense(
    params, prompt, n_new: int, cfg: TransformerConfig, *, k: int = 4,
    draft_layers: int | None = None,
):
    """Greedy generation via draft-k/verify-in-one-forward speculation.

    ``prompt``: (1, Tp) int tokens, Tp >= 2 (the bigram draft needs
    one). Returns ``(tokens (1, n_new), n_forwards)`` — the token
    stream is EXACTLY ``generate_dense``'s greedy stream; the decode
    forward count is what speculation buys: ``1 + n_forwards`` total
    model calls (prefill + verifies) instead of ``1 + (n_new - 1)``,
    i.e. ``(n_new - 1) / n_forwards`` tokens per decode forward (> 1
    whenever drafts are being accepted; each verify forward is k+1
    tokens wide, so the FLOPs per forward rise — the win is real when
    decode is bandwidth/latency-bound, which is what the cache reads
    make it). Greedy only (sampling breaks the exact-equality
    contract this implementation pins)."""
    B, Tp = prompt.shape
    if B != 1:
        raise ValueError(
            f"speculative decode is per-stream (B=1), got batch {B}; "
            "vmap/shard the stream level instead"
        )
    if Tp < 2:
        raise ValueError(f"bigram drafting needs a prompt >= 2, got {Tp}")
    if n_new < 1:
        raise ValueError(f"n_new must be >= 1, got {n_new}")
    if k < 1:
        raise ValueError(f"draft length k must be >= 1, got {k}")
    packed = np.asarray(
        _spec_runner(
            cfg, Tp, n_new, int(k), _check_draft_layers(cfg, draft_layers)
        )(params, prompt)
    )
    return packed[None, :n_new], int(packed[n_new])


def make_speculative(cfg: TransformerConfig, mesh, Tp: int, n_new: int,
                     *, k: int = 4, draft_layers: int | None = None):
    """Sharded speculative generation over a (dp=1, tp) mesh:
    ``run(params, prompt (1, Tp)) -> (n_new + 1,)`` packed tokens +
    forward count, same contract as :func:`make_speculative_dense`.

    The draft/verify while_loop (``_spec_loop`` — shared with the
    dense program, so the exact-greedy acceptance logic lives once)
    runs inside ONE shard_map jit: every tp member computes identical
    post-psum logits, hence the identical argmax, draft, and
    acceptance — the speculation control flow replicates for free,
    exactly like greedy ``make_generate``'s token picks. Per-stream
    (B=1): speculation is a latency optimization for one sequence;
    shard extra streams over dp by running one program per stream.
    Dense configs only: the MoE all_to_all marks the loop carries
    varying over ep, which the replicated-control-flow scheme cannot
    express — MoE serving uses :func:`~.decode.make_generate`."""
    from jax.sharding import PartitionSpec as P

    from .decode import (
        _cache_heads_global,
        _zero_cache_layer,
        make_kv_slice,
    )
    from .transformer import param_specs

    _check_decode_mesh(cfg, mesh)
    if cfg.n_experts:
        raise ValueError(
            "sharded speculative decoding supports dense configs only "
            "(MoE expert-parallel carries cannot replicate across the "
            "speculation loop); serve MoE with make_generate"
        )
    if int(mesh.shape["dp"]) != 1:
        raise ValueError(
            "speculative decode is per-stream: use dp=1 (run one "
            "program per stream for batch serving)"
        )
    if Tp < 2 or n_new < 1 or k < 1:
        raise ValueError(f"need Tp >= 2, n_new >= 1, k >= 1; got "
                         f"{(Tp, n_new, k)}")
    draft_layers = _check_draft_layers(cfg, draft_layers)
    Lbuf = Tp + n_new + k + 1

    def local(params, prompt):
        kv_slice = make_kv_slice(cfg)
        Hc = _cache_heads_global(cfg, mesh)
        tp = mesh.shape["tp"]
        cache = [
            _zero_cache_layer(1, Lbuf, Hc // tp, cfg.head_dim,
                              cfg.dtype, False)
            for _ in range(cfg.n_layers)
        ]
        draft, dstate = None, ()
        if draft_layers is not None:
            # the draft stack shards exactly like the verify stack
            # (same tp psum, same kv slicing), so its argmax — and
            # hence the speculation control flow — replicates too
            cfg_d = dataclasses.replace(cfg, n_layers=draft_layers)
            params_d = _truncated(params, draft_layers)
            draft_init, draft = _make_model_draft(
                params_d, cfg_d, Lbuf, k,
                kv_slice=kv_slice, tp_psum=True,
            )
            cache_d = [
                _zero_cache_layer(1, Lbuf, Hc // tp, cfg.head_dim,
                                  cfg.dtype, False)
                for _ in range(draft_layers)
            ]
            dstate = draft_init(prompt, cache_d)
        return _spec_loop(
            lambda pr, c: _incremental_forward(
                params, pr, c, jnp.int32(0), cfg, prefill=True,
                kv_slice=kv_slice, tp_psum=True,
            ),
            lambda ch, c, off: _incremental_forward(
                params, ch, c, off, cfg, prefill=False,
                kv_slice=kv_slice, tp_psum=True,
            ),
            cache, prompt, Tp, n_new, k, draft=draft, dstate=dstate,
        )

    # prompt replicated (dp=1 enforced above): every member runs the
    # identical control flow on identical post-psum logits, so the
    # packed output is unvarying on every mesh axis
    f = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(param_specs(cfg, mesh), P()),
        out_specs=P(),
    )
    return jax.jit(f)
