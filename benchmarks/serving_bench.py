"""Continuous-batching serving throughput: aggregate decode tokens/s
at S concurrent requests vs S=1 (VERDICT r4 next-#1).

The economics being priced: a B=1 decode step is weight-read-bound —
every step streams the full parameter bytes from HBM to emit ONE token
(docs/PERF.md round 4), so every cache-side win is capped. The
scheduler's batched step streams the same weights once for S tokens;
until KV-cache reads (S x W window rows) rival the weight bytes,
aggregate throughput scales near-linearly with S. This rung measures
that scaling on the real chip through the actual scheduler tick
(admission excluded — steady-state decode is the claim; admission cost
is bounded per tick by one prefill chunk and measured separately).

Methodology: each tick is one device scan of ``n_inner`` steps for all
S slots plus one host fetch of the (S, n_inner) token block; the
measured fence RTT is subtracted per tick, the same correction every
decode rung applies (transformer_train_bench).
"""

from __future__ import annotations

import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

__all__ = ["bench_serving", "bench_paged_vs_slot"]


def bench_serving(
    *,
    slot_counts: tuple[int, ...] = (1, 4, 8),
    prompt_len: int = 512,
    window: int = 1024,
    n_inner: int = 64,
    ticks: int = 6,
    chains: int = 3,
    d_model: int = 1024,
    n_layers: int = 8,
    n_heads: int = 8,
    n_kv_heads: int | None = 2,
    d_ff: int = 4096,
    vocab: int = 32768,
) -> dict:
    import jax
    import jax.numpy as jnp

    from benchmarks.transformer_train_bench import _fence_rtt, _timed
    from mpistragglers_jl_tpu.models.serving import ServingScheduler
    from mpistragglers_jl_tpu.models.transformer import (
        TransformerConfig,
        init_params,
    )

    cfg = TransformerConfig(
        vocab=vocab, d_model=d_model, n_heads=n_heads,
        n_kv_heads=n_kv_heads, n_layers=n_layers, d_ff=d_ff,
        attn="ulysses", attn_impl="flash", dtype=jnp.bfloat16,
        attn_window=window,
    )
    params = init_params(cfg, seed=0)
    rng = np.random.default_rng(0)
    rtt = _fence_rtt(jax.devices()[0])

    rungs = {}
    compile_s = 0.0
    # int8 sub-rungs at the largest S: at B=1 the int8 cache LOSES
    # (weight-read-bound, docs/PERF.md) — but at S slots the cache
    # reads are S x W rows while the weight read stays constant, so
    # batching is where quantization's byte model has real leverage.
    # TWO int8 variants make the decode-path claim driver-verifiable:
    # the AUTO routing (S >= KERNEL_MIN_BATCH routes the batched
    # Pallas ring kernel inside the tick) and the forced einsum-dequant
    # path — their ratio IS the kernel's in-scan win/loss, measured
    # through the real scheduler every run.
    from mpistragglers_jl_tpu.models.decode import use_decode_kernel

    Smax = max(slot_counts)
    variants = [(S, False, None) for S in slot_counts]
    variants.append((Smax, True, None))    # AUTO: kernel at S >= 4
    variants.append((Smax, True, False))   # forced einsum dequant
    for S, q8, forced in variants:
        use_decode_kernel(forced)
        try:
            sched = ServingScheduler(
                params, cfg, slots=S, n_inner=n_inner,
                prompt_chunk=prompt_len, max_prompt=prompt_len,
                quantize_kv=q8,
            )
        finally:
            use_decode_kernel(None)  # routing snapshots at construction
        for _ in range(S):
            # budget sized so no request retires mid-measurement: every
            # tick decodes all S rows (steady state, no admission)
            sched.submit(
                rng.integers(0, vocab, prompt_len, dtype=np.int32),
                max_new=n_inner * (ticks + 2) * (chains + 2),
            )
        t0 = time.perf_counter()
        sched.step()  # admit all S + first decode tick (compiles)
        compile_s += time.perf_counter() - t0
        best = None
        for _ in range(chains):
            dt = _timed(lambda: [sched.step() for _ in range(ticks)])
            dt -= rtt * ticks  # one (S, n_inner) token fetch per tick
            best = dt if best is None else min(best, dt)
        tokens = S * n_inner * ticks
        per_tok_ms = best / tokens * 1e3
        name = f"S{S}" + (
            ("_int8_einsum" if forced is False else "_int8") if q8
            else ""
        )
        rungs[name] = {
            "aggregate_tokens_per_s": round(tokens / best, 1),
            "ms_per_token_aggregate": round(per_tok_ms, 4),
            "ms_per_step": round(best / (n_inner * ticks) * 1e3, 3),
        }
        if q8:
            # record what the tick actually ran — a "kernel win" row
            # with kernelized: false would be self-refuting
            rungs[name]["kernelized"] = bool(sched.use_kernel)

    base_n = 1 if 1 in slot_counts else min(slot_counts)
    base = rungs[f"S{base_n}"]["aggregate_tokens_per_s"]
    for S in slot_counts:
        r = rungs[f"S{S}"]
        r[f"vs_S{base_n}"] = round(
            r["aggregate_tokens_per_s"] / base, 2
        )
    for q8name in (f"S{Smax}_int8", f"S{Smax}_int8_einsum"):
        rungs[q8name]["vs_bf16"] = round(
            rungs[q8name]["aggregate_tokens_per_s"]
            / rungs[f"S{Smax}"]["aggregate_tokens_per_s"], 2
        )
    # the tentpole ratio: batched kernel tick vs the einsum dequant
    # tick, same slots, same int8 cache
    rungs[f"S{Smax}_int8"]["vs_int8_einsum"] = round(
        rungs[f"S{Smax}_int8"]["aggregate_tokens_per_s"]
        / rungs[f"S{Smax}_int8_einsum"]["aggregate_tokens_per_s"], 2
    )
    return {
        "metric": "serving-continuous-batching",
        "prompt_len": prompt_len,
        "attn_window": window,
        "n_inner": n_inner,
        "ticks": ticks,
        "chains_min_of": chains,
        "fence_rtt_s": round(rtt, 4),
        "compile_s": round(compile_s, 1),
        **rungs,
    }


def bench_paged_vs_slot(
    *,
    d_model: int = 256,
    n_layers: int = 2,
    n_heads: int = 8,
    n_kv_heads: int = 2,
    d_ff: int = 1024,
    vocab: int = 8192,
    window: int = 512,
    page_tokens: int = 64,
    slot_ref: int = 8,
    sys_len: int = 256,
    user_len: int = 16,
    n_submit: int = 80,
    decode_slots: int = 8,
    n_inner: int = 32,
    ticks: int = 4,
    chains: int = 2,
) -> dict:
    """Round-11 capacity rung: at a FIXED cache byte budget — the
    slot-ring arena of ``slot_ref`` slots — how many concurrent
    requests does the paged cache admit? Two scenarios: unique
    prompts (the right-sized-residency win alone) and a shared
    ``sys_len``-token system prompt (plus prefix sharing, the
    multi-tenant case), with the prefill skip COUNTER-verified through
    ``PagePool.share_hits``, not inferred from timing. The byte model:
    a slot-ring request costs ``W`` rows of residency regardless of
    length; a paged request costs ``ceil(min(W, Tp + max_new +
    n_inner) / P)`` pages minus the shared prefix (docs/PERF.md).

    The decode leg prices the indirection: aggregate steady-state
    decode tokens/s at ``decode_slots`` slots, slot ring vs paged
    (einsum gather fallback — the kernel path's win is the int8 rung's
    claim), same config, same fence-RTT correction as
    :func:`bench_serving`. The acceptance gate is a <= 5% regression.
    """
    import jax

    from benchmarks.transformer_train_bench import _fence_rtt, _timed
    from mpistragglers_jl_tpu.models.serving import ServingScheduler
    from mpistragglers_jl_tpu.models.transformer import (
        TransformerConfig,
        init_params,
    )
    import jax.numpy as jnp

    cfg = TransformerConfig(
        vocab=vocab, d_model=d_model, n_heads=n_heads,
        n_kv_heads=n_kv_heads, n_layers=n_layers, d_ff=d_ff,
        attn="ulysses", attn_impl="flash", dtype=jnp.bfloat16,
        attn_window=window,
    )
    params = init_params(cfg, seed=0)
    rng = np.random.default_rng(0)
    P = page_tokens
    max_pages = window // P
    budget_pages = slot_ref * max_pages  # byte-equal to the slot arena
    kv_bytes = 2 * n_layers * cfg.kv_heads * cfg.head_dim * 2  # k+v bf16
    max_new = 16
    sys_prompt = rng.integers(0, vocab, sys_len, dtype=np.int32)

    def prompts(shared: bool):
        out = []
        for _ in range(n_submit):
            user = rng.integers(0, vocab, user_len, dtype=np.int32)
            head = sys_prompt if shared else rng.integers(
                0, vocab, sys_len, dtype=np.int32
            )
            out.append(np.concatenate([head, user]))
        return out

    def capacity(shared: bool) -> tuple[int, int]:
        sched = ServingScheduler(
            params, cfg, slots=min(n_submit, budget_pages),
            n_inner=4, prompt_chunk=sys_len + user_len,
            max_prompt=sys_len + user_len, page_tokens=P,
            cache_pages=budget_pages + 1,
        )
        for p in prompts(shared):
            sched.submit(p, max_new=max_new)
        sched.step()  # one admission wave against a fresh pool
        return sched.active, sched.pool.share_hits

    t0 = time.perf_counter()
    cap_unique, _ = capacity(shared=False)
    cap_shared, share_hits = capacity(shared=True)

    # decode-throughput leg: slot ring vs paged gather, same slots
    rtt = _fence_rtt(jax.devices()[0])
    tok_s = {}
    for paged in (False, True):
        kw = dict(page_tokens=P) if paged else {}
        sched = ServingScheduler(
            params, cfg, slots=decode_slots, n_inner=n_inner,
            prompt_chunk=sys_len, max_prompt=sys_len, **kw,
        )
        for _ in range(decode_slots):
            sched.submit(
                rng.integers(0, vocab, sys_len, dtype=np.int32),
                max_new=n_inner * (ticks + 2) * (chains + 2),
            )
        sched.step()  # admit + first tick (compiles)
        best = None
        for _ in range(chains):
            dt = _timed(lambda: [sched.step() for _ in range(ticks)])
            dt -= rtt * ticks
            best = dt if best is None else min(best, dt)
        tok_s["paged" if paged else "slot"] = (
            decode_slots * n_inner * ticks / best
        )

    return {
        "metric": "serving-paged-capacity",
        "page_tokens": P,
        "byte_budget_mb": round(
            budget_pages * P * kv_bytes / 2 ** 20, 2
        ),
        "prompt_len": sys_len + user_len,
        "max_new": max_new,
        "slot_capacity": slot_ref,
        "paged_capacity": cap_unique,
        "paged_capacity_shared": cap_shared,
        "capacity_x": round(cap_unique / slot_ref, 2),
        "capacity_x_shared": round(cap_shared / slot_ref, 2),
        "prefill_pages_skipped": int(share_hits),
        "prefill_skip_verified": bool(share_hits > 0),
        "slot_tok_s": round(tok_s["slot"], 1),
        "paged_tok_s": round(tok_s["paged"], 1),
        "paged_vs_slot_tok_s": round(tok_s["paged"] / tok_s["slot"], 3),
        "elapsed_s": round(time.perf_counter() - t0, 1),
    }


if __name__ == "__main__":
    import json

    print(json.dumps({
        "serving": bench_serving(),
        "paged_vs_slot": bench_paged_vs_slot(),
    }))
