"""Fused pool↔mesh epoch on the REAL chip (VERDICT r3 weak #4).

`benchmarks/fused_bench.py` grounds the fused path's host-orchestration
cost on the 8-device virtual CPU mesh; this bench runs the SAME
(n=8, k=6) coded workload on the real chip's 1-device mesh via the
round-4 folded-pool layout (`PoolMeshCodedGemm(n_workers=8)` on a
1-device mesh: all eight workers' blocks live in the chip's HBM, the
adopter stacks each device group on-device, the masked combine is one
compiled program) and compares it against the unfused
`ops/coded_gemm.CodedGemm` device-0 gather+solve on the same chip.

Methodology (docs/PERF.md): EPOCHS epochs chained back-to-back with ONE
scalar fence over the final decoded output, measured fence RTT
subtracted. The `assemble` host cost (group stack enqueue +
`make_array_from_single_device_arrays` metadata) is additionally timed
per call, host-side, since it is a pure dispatch-side cost.

Run: ``PYTHONPATH=. python benchmarks/fused_chip_bench.py``
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

M, D, NCOLS = 1536, 512, 512
N, K = 8, 6
EPOCHS = 10


def bench_fused_chip(epochs: int = EPOCHS) -> dict:
    from mpistragglers_jl_tpu.parallel import PoolMeshCodedGemm, make_mesh
    from mpistragglers_jl_tpu.pool import AsyncPool, waitall

    rng = np.random.default_rng(0)
    A = rng.standard_normal((M, D)).astype(np.float32)
    B = rng.standard_normal((D, NCOLS)).astype(np.float32)
    dev = jax.devices()[0]

    tiny = jax.device_put(np.ones((8,), np.float32), dev)
    tiny_fence = jax.jit(jnp.sum)
    float(tiny_fence(tiny))
    rtt = min(
        (lambda t0: (float(tiny_fence(tiny)), time.perf_counter() - t0)[1])(
            time.perf_counter()
        )
        for _ in range(5)
    )

    fence = jax.jit(lambda x: jnp.sum(x.astype(jnp.float32)))

    from mpistragglers_jl_tpu.ops import CodedGemm
    from mpistragglers_jl_tpu.pool import asyncmap

    mesh = make_mesh(1, devices=[dev])
    # batch=True: one stacked map program for the whole folded group +
    # zero-copy adoption of its result — the fully fused epoch.
    # batch_arrival="enqueue" on BOTH paths: completions post at
    # submission and epochs pipeline on the device; "ready" (the
    # library default) would wait for the device once per epoch before
    # the decode is dispatched.
    fg = PoolMeshCodedGemm(
        A, mesh, K, n_workers=N, dtype=np.float32, batch=True,
        batch_arrival="enqueue",
    )
    pool_f = AsyncPool(N)
    decoded = fg.epoch(pool_f, B)  # warmup/compile
    float(fence(decoded))
    waitall(pool_f, fg.backend)

    cg = CodedGemm(A, N, K, devices=[dev], batch=True,
                   batch_arrival="enqueue")
    pool_u = AsyncPool(N)
    asyncmap(pool_u, B, cg.backend, nwait=cg.nwait)
    Cd = cg.result_device(pool_u)
    float(fence(Cd))
    waitall(pool_u, cg.backend)

    # ALTERNATING chains: each rep times both paths back-to-back, so
    # slow drift lands on both alike and the min-over-reps compares
    # like-for-like conditions
    reps = 3
    fused_s = unfused_s = None
    for _ in range(reps):
        t0 = time.perf_counter()
        for _ in range(epochs):
            decoded = fg.epoch(pool_f, B)
            waitall(pool_f, fg.backend)
        float(fence(decoded))
        dt = (time.perf_counter() - t0 - rtt) / epochs
        fused_s = dt if fused_s is None else min(fused_s, dt)

        t0 = time.perf_counter()
        for _ in range(epochs):
            asyncmap(pool_u, B, cg.backend, nwait=cg.nwait)
            Cd = cg.result_device(pool_u)
            waitall(pool_u, cg.backend)
        float(fence(Cd))
        dt = (time.perf_counter() - t0 - rtt) / epochs
        unfused_s = dt if unfused_s is None else min(unfused_s, dt)

    # assemble cost alone (host dispatch side), per call
    ref = pool_f.results[int(np.flatnonzero(pool_f.repochs > 0)[0])]
    t0 = time.perf_counter()
    for _ in range(20):
        fg._adopter.assemble(pool_f, ref.shape, ref.dtype)
    assemble_ms = (time.perf_counter() - t0) / 20 * 1e3

    C = fg.full(decoded)
    err_f = float(np.max(np.abs(C - A @ B)) / np.max(np.abs(A @ B)))
    err_u = float(
        np.max(np.abs(np.asarray(Cd) - A @ B)) / np.max(np.abs(A @ B))
    )
    fg.shutdown()
    cg.backend.shutdown()

    # the library's own measured auto-selection (VERDICT r4 item 4):
    # on one device the paths sit inside the noise band, so
    # select_coded_gemm probes THIS session and the rung records the
    # decision it made
    from mpistragglers_jl_tpu.parallel import select_coded_gemm

    sel = select_coded_gemm(
        A, mesh, K, B, n_workers=N, dtype=np.float32, batch=True,
        batch_arrival="enqueue",
    )
    selection = sel.selection
    sel.shutdown()

    return {
        "auto_selection": selection,
        "metric": "fused-pool-mesh-real-chip",
        "shape": f"(n={N},k={K}) coded {M}x{D} @ {D}x{NCOLS} f32",
        "device": str(dev),
        "epochs": epochs,
        "chains_min_of": reps,
        "fence_rtt_s": round(rtt, 4),
        "fused_epoch_ms": round(fused_s * 1e3, 2),
        "assemble_ms_per_call": round(assemble_ms, 3),
        "unfused_device0_epoch_ms": round(unfused_s * 1e3, 2),
        "fused_vs_unfused": round(fused_s / unfused_s, 3),
        "fused_decode_rel_err": err_f,
        "unfused_decode_rel_err": err_u,
    }


if __name__ == "__main__":
    print(json.dumps(bench_fused_chip(), indent=1))
