"""BASELINE config 4: LT/rateless-coded GEMM 16384^2, 16 workers.

The pool returns on the *variable* decodability predicate
(``nwait(epoch, repochs)``, ops/lt.py) — not at a fixed count but at the
first arrival set whose shards peel. Two injected stragglers never make
the epoch; decode runs on device over the arrived shards
(``LTCodedGemm.result_device``). A and B are generated on device
(jax.random), so the ~1 GB operands never cross the host<->device edge;
``vs_baseline`` is the straggler-mitigation factor: the same epoch
forced to wait for all 16 workers over the predicate epoch.
"""

from __future__ import annotations

import json
import os
import sys
import time


sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

from mpistragglers_jl_tpu import AsyncPool, asyncmap, waitall
from mpistragglers_jl_tpu.ops import LTCodedGemm

M = KDIM = NCOLS = 16384
N_WORKERS = 16
K = 8
STRAGGLERS = (3, 11)
DELAY_S = 5.0
EPOCHS = 3


def _run_chained(A, B, precision, C_ref, ref_scale, fence, maxabs, *,
                 n_workers=N_WORKERS, k=K, delay_s=DELAY_S,
                 epochs=EPOCHS, stragglers=STRAGGLERS, chains=3):
    """One precision rung: chained epochs, one fence, min of ``chains``.
    Returns (t_coded, err, fresh_counts, rtt, t_all)."""
    import numpy as np

    delay_fn = lambda i, e: delay_s if i in stragglers else 0.0
    lt = LTCodedGemm(
        A, n_workers, k,
        delay_fn=delay_fn,
        precision=precision,
    )
    pool = AsyncPool(n_workers)
    try:
        asyncmap(pool, B, lt.backend, nwait=lt.nwait)  # warmup
        float(fence(lt.result_device(pool)))
        waitall(pool, lt.backend, timeout=3 * delay_s + 10)

        z = jax.device_put(np.ones(8, np.float32), lt.devices[0])
        float(fence(z))
        rtts = []
        for _ in range(3):
            t0 = time.perf_counter()
            float(fence(z))
            rtts.append(time.perf_counter() - t0)
        rtt = min(rtts)

        chain_s, fresh_counts = [], []
        for _ in range(chains):
            t0 = time.perf_counter()
            for _ in range(epochs):
                repochs = asyncmap(pool, B, lt.backend, nwait=lt.nwait)
                fresh_counts.append(int((repochs == pool.epoch).sum()))
                C = lt.result_device(pool)
            float(fence(C))  # in-order device stream: covers every epoch
            chain_s.append((time.perf_counter() - t0 - rtt) / epochs)
        t_coded = min(chain_s)
        err = float(maxabs(C, C_ref)) / ref_scale
        waitall(pool, lt.backend, timeout=3 * delay_s + 10)

        # baseline: bulk-synchronous epoch, pays the injected stragglers
        t0 = time.perf_counter()
        asyncmap(pool, B, lt.backend, nwait=n_workers)
        C_all = lt.result_device(pool)
        float(fence(C_all))
        t_all = time.perf_counter() - t0
        return t_coded, err, fresh_counts, rtt, t_all
    finally:
        lt.backend.shutdown()


def bench_rung(m=8192, n_workers=16, k=8, delay_s=1.0, epochs=2,
               chains=2):
    """Scaled config-4 rung for bench.py's JSON contract: half-size
    operands and 1 s stragglers bound the runtime (the full-size CLI
    below is the comparable-to-BASELINE run). Same machinery: variable
    decodability nwait, chained epochs, one fence, straggler-mitigation
    factor vs the bulk-synchronous epoch."""
    key = jax.random.key(0)
    ka, kb = jax.random.split(key)
    A = jax.random.normal(ka, (m, m), jnp.float32)
    B = jax.random.normal(kb, (m, m), jnp.float32)
    fence = jax.jit(jnp.sum)
    maxabs = jax.jit(lambda c, r: jnp.max(jnp.abs(c - r)))
    C_ref = jax.jit(
        lambda a, b: jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)
    )(A, B)
    ref_scale = float(jnp.max(jnp.abs(C_ref)))
    stragglers = (3, 11) if n_workers > 11 else (1,)
    t_coded, err, fresh_counts, rtt, t_all = _run_chained(
        A, B, jax.lax.Precision.HIGHEST, C_ref, ref_scale, fence, maxabs,
        n_workers=n_workers, k=k, delay_s=delay_s, epochs=epochs,
        stragglers=stragglers, chains=chains,
    )
    return {
        "metric": f"lt-coded-gemm-{m}-{n_workers}w-scaled",
        "value": round(t_coded, 4),
        "unit": "s",
        "vs_nwait_all": round(t_all / t_coded, 2),
        "decode_rel_err": err,
        "fresh_at_return": fresh_counts,
        "gflops_per_chip": round(2.0 * m**3 / t_coded / 1e9, 1),
        "injected_straggler_delay_s": delay_s,
        "epochs_pipelined": epochs,
        "chains_min_of": chains,
        "fence_rtt_s": round(rtt, 4),
    }


def main():
    key = jax.random.key(0)
    ka, kb = jax.random.split(key)
    A = jax.random.normal(ka, (M, KDIM), jnp.float32)
    B = jax.random.normal(kb, (KDIM, NCOLS), jnp.float32)

    fence = jax.jit(jnp.sum)
    maxabs = jax.jit(lambda c, r: jnp.max(jnp.abs(c - r)))

    # on-device oracle for the exactness check
    C_ref = jax.jit(
        lambda a, b: jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)
    )(A, B)
    ref_scale = float(jnp.max(jnp.abs(C_ref)))

    t_coded, err, fresh_counts, rtt, t_all = _run_chained(
        A, B, jax.lax.Precision.HIGHEST, C_ref, ref_scale, fence, maxabs
    )
    # DEFAULT-precision rung: same epochs, same f32 decode — decode
    # success is unchanged, the worker matmuls ride the fast passes
    t_def, err_def, _, _, _ = _run_chained(
        A, B, None, C_ref, ref_scale, fence, maxabs
    )

    print(json.dumps({
        "metric": "lt-coded-gemm-16384-16w-wallclock",
        "value": round(t_coded, 4),
        "unit": "s",
        "vs_baseline": round(t_all / t_coded, 2),
        "nwait_all_epoch_s": round(t_all, 4),
        "decode_success": True,
        "fresh_at_return": fresh_counts,
        "decode_rel_err": err,
        "gflops_per_chip": round(2.0 * M * KDIM * NCOLS / t_coded / 1e9, 1),
        "injected_straggler_delay_s": DELAY_S,
        "epochs_pipelined": EPOCHS,
        "chains_min_of": 3,
        "fence_rtt_s": round(rtt, 4),
        "default_precision_rung": {
            "value": round(t_def, 4),
            "gflops_per_chip": round(
                2.0 * M * KDIM * NCOLS / t_def / 1e9, 1
            ),
            "decode_rel_err": err_def,
        },
    }))

def main_rateless():
    """Incremental redundancy under a PERMANENT straggler: the static
    window cannot decode (its shard never arrives), the rateless stream
    draws generation-1 shards from the live workers and decodes anyway.
    Reports the shards-consumed-vs-k overhead — the price of
    ratelessness (VERDICT round 1 item 2's measured contract)."""
    import numpy as np

    from mpistragglers_jl_tpu.ops.rateless import RatelessLTGemm

    m = kdim = ncols = 8192
    n, k = 12, 8
    rng = np.random.default_rng(0)
    A = rng.standard_normal((m, kdim)).astype(np.float32)
    B = rng.standard_normal((kdim, ncols)).astype(np.float32)
    dead = 0  # permanent straggler: never returns within any round

    # seed 16 + systematic=False: worker 0's CLASSIC-stream shard is
    # load-bearing — the static window minus it does NOT peel, so
    # decode REQUIRES generation-1 draws (the systematic default would
    # peel this trace within generation 0 and demonstrate nothing; its
    # overhead win is measured by bench.py's rateless_overhead rung)
    rg = RatelessLTGemm(
        A, n, k, seed=16, systematic=False,
        delay_fn=lambda i, e: 3600.0 if i == dead else 0.0,
        precision=jax.lax.Precision.HIGHEST,
    )
    try:
        pool = AsyncPool(n)
        # warmup: compile the worker matmul once, untimed, reusing the
        # full B so the timed shapes match. Fresh-generation draws may
        # still compile the (tiny) device encode once per new support
        # degree inside the timed run — noted in the output.
        import jax.numpy as jnp_

        from mpistragglers_jl_tpu.backends.base import WorkerError

        # B goes device-resident FIRST: a host payload would be
        # uploaded again (256 MB) inside every round and can blow the
        # round timeout (observed round 3); HBM residency is the coordinator working-memory
        # discipline every other config follows
        B_dev = jax.device_put(jnp_.asarray(B), jax.devices()[0])
        # classic streams build the device source stack on the first
        # fresh-generation draw — a full A upload; pull it off the
        # clock (and out of the round timeouts) like every other
        # one-time setup cost
        rg.prefetch_source()
        rg.backend.dispatch(1, B_dev, 0)
        warm = rg.backend.wait(1, timeout=600)
        if warm is None or isinstance(warm, WorkerError):
            raise RuntimeError(f"warmup failed: {warm!r}")
        t0 = time.perf_counter()
        C = rg.multiply(B_dev, pool, round_timeout=60.0, max_rounds=4)
        wall = time.perf_counter() - t0
        err = float(np.max(np.abs(C - A @ B))) / float(np.max(np.abs(C)))
        print(json.dumps({
            "metric": "lt-rateless-gemm-8192-permanent-straggler",
            "value": round(wall, 4),
            "unit": "s",
            "decode_success": bool(err < 1e-3),
            "decode_rel_err": err,
            "shards_used": rg.stats["shards_used"],
            "k": rg.stats["k"],
            "rateless_overhead": round(rg.stats["overhead"], 3),
            "max_generation": rg.stats["max_generation"],
            "note": "worker 0's shard is load-bearing and never "
            "arrives; decode required fresh-generation draws. Wall "
            "includes one 15 s round_timeout wait per extra round, the "
            "host-peel D2H of all collected shards, and a one-time "
            "device-encode compile per new support degree",
        }))
    finally:
        rg.backend.shutdown()


if __name__ == "__main__":
    main()
    main_rateless()
