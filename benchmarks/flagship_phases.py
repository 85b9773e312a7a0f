"""Per-phase attribution of the 134M flagship train step (VERDICT r3 #1).

The 134M rung ran at MFU 0.57-0.76 across sessions while the 470M rung
hit 0.81 in the same run — a third of the chip unattributed. This bench
breaks the step into its four phases, each timed as its own jitted
fwd+bwd program on the real chip with the same shapes the full step
uses, pipelined-chain + fence-RTT-subtracted methodology
(docs/PERF.md):

* ``attention`` — the flash kernel (fwd + custom-vjp bwd, all three
  input grads) at (B, L, H, Dh), once per layer;
* ``mlp_proj``  — LN + QKV/out projections + MLP einsums per layer with
  attention replaced by a cheap mix (the dense-GEMM body), weight grads
  included;
* ``head_loss`` — final LN + tied (B, L, V) logits einsum + token NLL
  (+ backward incl. the embedding grad), from a (B, L, D) activation;
* ``embed``     — token lookup + its scatter-add backward.

Methodology notes (docs/PERF.md): every
program RETURNS every gradient it claims to compute (an unused grad is
DCE'd by XLA and silently not timed), and each chain is fenced by a
scalar sum over ALL final outputs, so the fence data-depends on every
one of them.

Each phase's matmul FLOPs are known in closed form, so the table gives
per-phase TF/s and time share vs FLOP share — the two columns whose
mismatch names the MFU eater. ``sum_of_phases`` vs the measured full
step bounds what the decomposition misses (inter-phase fusion, the
residual adds, LN outside the phases' scopes).

Run: ``PYTHONPATH=. python benchmarks/flagship_phases.py [--quick|--gqa]``
"""

from __future__ import annotations

import time

import numpy as np

__all__ = ["profile_flagship_phases"]


def _timed(thunk) -> float:
    t0 = time.perf_counter()
    thunk()
    return time.perf_counter() - t0


def profile_flagship_phases(
    *,
    batch: int = 8,
    seq: int = 2048,
    d_model: int = 1024,
    n_layers: int = 8,
    n_heads: int = 8,
    d_ff: int = 4096,
    vocab: int = 32768,
    n_kv_heads: int | None = None,
    steps: int = 4,
    chains: int = 2,
    block_q: int = 1024,
    block_k: int = 1024,
    full: bool = True,
) -> dict:
    import jax
    import jax.numpy as jnp

    from mpistragglers_jl_tpu.ops.flash_attention import flash_attention

    B, L, D, F, V, H = batch, seq, d_model, d_ff, vocab, n_heads
    Hkv = n_kv_heads or H
    Dh = D // H
    dt = jnp.bfloat16
    rng = np.random.default_rng(0)
    dev = jax.devices()[0]

    def put(*shape):
        return jax.device_put(
            rng.standard_normal(shape).astype(np.float32) * 0.02, dev
        ).astype(dt)

    # fence RTT: measured, subtracted from every chain
    tiny = jax.device_put(np.ones((8,), np.float32), dev)
    tiny_fence = jax.jit(jnp.sum)
    float(tiny_fence(tiny))
    rtt = min(_timed(lambda: float(tiny_fence(tiny))) for _ in range(5))

    # fence = scalar sum over EVERY leaf of the final outputs
    @jax.jit
    def fence_all(tree):
        return sum(
            x.astype(jnp.float32).sum() for x in jax.tree.leaves(tree)
        )

    def run_chain(step, carry0, *consts):
        """``step(carry, *consts) -> (carry, aux)``; ``steps`` calls
        back-to-back (carry serializes the chain), ONE all-leaf fence;
        min over ``chains``."""
        carry, aux = step(carry0, *consts)  # compile
        float(fence_all((carry, aux)))
        best = None
        for _ in range(chains):
            t0 = time.perf_counter()
            for _ in range(steps):
                carry, aux = step(carry, *consts)
            float(fence_all((carry, aux)))
            dt_ = (time.perf_counter() - t0 - rtt) / steps
            best = dt_ if best is None else min(best, dt_)
        return best

    phases = {}

    # ---- attention phase: n_layers x flash fwd+bwd ---------------------
    qkv0 = {"q": put(B, L, H, Dh), "k": put(B, L, Hkv, Dh),
            "v": put(B, L, Hkv, Dh)}

    def attn_loss(qkv):
        # each layer's output feeds the next layer's query — WITHOUT
        # this dependency XLA CSE's the n_layers identical flash calls
        # into one and the phase reads 8x too fast (first run of this
        # bench did exactly that: "attention at 296 TF/s", above the
        # chip ceiling)
        q = qkv["q"]
        for _ in range(n_layers):
            q = flash_attention(
                q, qkv["k"], qkv["v"], causal=True,
                block_q=block_q, block_k=block_k,
            )
        return q.astype(jnp.float32).sum()

    @jax.jit
    def attn_step(qkv):
        g = jax.grad(attn_loss)(qkv)  # all three grads, returned whole
        return g, ()

    attn_flops = 3.0 * n_layers * 2 * B * L * L * Dh * H
    t = run_chain(attn_step, qkv0)
    phases["attention"] = {"s": t, "flops": attn_flops}

    # ---- mlp + projections phase (attention = cheap mix) ----------------
    lp = {
        "ln1_s": put(D), "ln1_b": put(D),
        "wq": put(D, H, Dh), "wk": put(D, Hkv, Dh), "wv": put(D, Hkv, Dh),
        "wo": put(H, Dh, D),
        "ln2_s": put(D), "ln2_b": put(D),
        "w1": put(D, F), "b1": put(F), "w2": put(F, D), "b2": put(D),
    }
    x0 = put(B, L, D)

    def body_loss(x, lp):
        from mpistragglers_jl_tpu.models.transformer import _ln, _mlp

        for _ in range(n_layers):
            h = _ln(x, lp["ln1_s"], lp["ln1_b"])
            q = jnp.einsum("bld,dhk->blhk", h, lp["wq"])
            k = jnp.einsum("bld,dhk->blhk", h, lp["wk"])
            v = jnp.einsum("bld,dhk->blhk", h, lp["wv"])
            o = q + (k + v).sum(2, keepdims=True)  # stand-in for attn
            x = x + jnp.einsum("blhk,hkd->bld", o, lp["wo"])
            h2 = _ln(x, lp["ln2_s"], lp["ln2_b"])
            x = x + _mlp(h2, lp) + lp["b2"]
        return x.astype(jnp.float32).sum()

    @jax.jit
    def body_step(x, lp):
        g_x, g_w = jax.grad(body_loss, argnums=(0, 1))(x, lp)
        return g_x.astype(dt), g_w

    body_flops = 3.0 * n_layers * (
        2 * B * L * D * D                 # wq
        + 2 * 2 * B * L * D * Hkv * Dh    # wk + wv
        + 2 * B * L * D * D               # wo
        + 4 * B * L * D * F               # mlp up + down
    )
    t = run_chain(body_step, x0, lp)
    phases["mlp_proj"] = {"s": t, "flops": body_flops}

    # ---- head + loss phase ---------------------------------------------
    emb = put(V, D)
    lnf_s, lnf_b = put(D), put(D)
    tgt = jax.device_put(rng.integers(0, V, (B, L), dtype=np.int32), dev)

    def head_loss(x, emb):
        from mpistragglers_jl_tpu.models.transformer import _ln

        h = _ln(x, lnf_s, lnf_b)
        logits = jnp.einsum("bld,vd->blv", h, emb)
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        nll = -jnp.take_along_axis(logp, tgt[..., None], axis=-1)
        return nll.mean()

    @jax.jit
    def head_step(x, emb):
        g_x, g_emb = jax.grad(head_loss, argnums=(0, 1))(x, emb)
        return g_x.astype(dt), g_emb

    head_flops = 3.0 * 2 * B * L * D * V
    t = run_chain(head_step, x0, emb)
    phases["head_loss"] = {"s": t, "flops": head_flops}

    # ---- embed phase ----------------------------------------------------
    toks = jax.device_put(rng.integers(0, V, (B, L), dtype=np.int32), dev)

    @jax.jit
    def embed_step(emb, toks):
        def f(emb):
            return emb[toks].astype(jnp.float32).sum()

        return jax.grad(f)(emb).astype(dt), ()

    t = run_chain(embed_step, emb, toks)
    phases["embed"] = {"s": t, "flops": 0.0}

    out = {
        "metric": "flagship-phase-profile",
        "batch": batch, "seq": seq, "d_model": d_model,
        "n_layers": n_layers, "vocab": vocab, "n_kv_heads": Hkv,
        "block_q": block_q, "block_k": block_k,
        "fence_rtt_s": round(rtt, 4),
        "sum_of_phases_s": round(sum(p["s"] for p in phases.values()), 4),
        "phases": {},
    }

    # ---- the full step, same session, for the comparison ----------------
    if full:
        from benchmarks.transformer_train_bench import bench_transformer_train

        f = bench_transformer_train(
            batch=batch, seq=seq, d_model=d_model, n_layers=n_layers,
            n_heads=n_heads, d_ff=d_ff, vocab=vocab, steps=steps,
            chains=chains, oracle=False,
        )
        out["full_step_s"] = f["value"]
        out["full_mfu"] = f["mfu_vs_raw_matmul"]
        out["raw_bf16_tflops_per_s"] = f["raw_bf16_tflops_per_s"]
        raw = f["raw_bf16_tflops_per_s"]
    else:
        raw = None

    total_flops = sum(p["flops"] for p in phases.values())
    for name, p in phases.items():
        out["phases"][name] = {
            "s": round(p["s"], 4),
            "time_share_of_sum": round(
                p["s"] / sum(q["s"] for q in phases.values()), 3
            ),
            "flop_share": round(p["flops"] / total_flops, 3),
            "tflops_per_s": round(p["flops"] / p["s"] / 1e12, 1)
            if p["flops"] else None,
            "mfu": round(p["flops"] / p["s"] / 1e12 / raw, 3)
            if p["flops"] and raw else None,
        }
    return out


if __name__ == "__main__":
    import json
    import os
    import sys

    sys.path.insert(
        0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    )
    kw = {}
    if "--quick" in sys.argv:
        kw = dict(steps=2, chains=1, n_layers=2)
    if "--gqa" in sys.argv:
        kw["n_kv_heads"] = 2
    for a in sys.argv[1:]:
        if a.startswith("--block_k="):
            kw["block_k"] = int(a.split("=")[1])
        if a.startswith("--block_q="):
            kw["block_q"] = int(a.split("=")[1])
        if a.startswith("--seq="):
            kw["seq"] = int(a.split("=")[1])
    print(json.dumps(profile_flagship_phases(**kw), indent=1))
