"""Device-resident coordination: the host leaves the epoch hot path.

Two legs on one (n=8, k=6) MDS-coded GEMM fleet:

1. **The overhead race** — 256 epochs of the same workload, same
   per-epoch payload stream, coordinated two ways: the host
   ``asyncmap`` loop (dispatch, arrival bookkeeping and the decode
   trigger re-enter Python every epoch) vs ONE fused K=64 window per
   64 epochs (``asyncmap_fused`` + ``DeviceCoordinator`` — arrival
   masks, fastest-k selection and the MDS solve all inside one
   compiled program; the host only stages and harvests). The printed
   overhead multiple is the whole point of ROADMAP item 4.
2. **The semantics check** — a seeded straggling fleet (lognormal
   round trips + one permanent straggler) runs 128 epochs through the
   host loop on virtual time (``SimBackend``) and through fused
   windows on the SAME schedule: the per-epoch ``repochs`` histories
   must match bit for bit — fused coordination changes where the
   bookkeeping runs, never what it decides.

CPU-only, seconds. ``python examples/device_coord_demo.py``
"""

import time

import jax

from mpistragglers_jl_tpu.utils.compile_cache import wire_compile_cache

jax.config.update("jax_enable_x64", True)  # bit-identical parity leg
wire_compile_cache()

import numpy as np

from mpistragglers_jl_tpu import (
    AsyncPool,
    SimBackend,
    asyncmap,
    asyncmap_fused,
    waitall,
)
from mpistragglers_jl_tpu.ops.coded_gemm import CodedGemm
from mpistragglers_jl_tpu.utils import faults

N, K = 8, 6
EPOCHS, WINDOW = 256, 64


def main():
    rng = np.random.default_rng(0)
    A = rng.standard_normal((K * 4, 32))
    Bs = rng.standard_normal((EPOCHS, 32, 8))

    # -- leg 1: the overhead race (zero injected delays: pure
    # coordination cost) --------------------------------------------------
    cg = CodedGemm(A, N, K, dtype=np.float64)
    try:
        pool = AsyncPool(N)
        asyncmap(pool, Bs[0], cg.backend, nwait=K)  # warm compiles
        cg.result_device(pool)
        waitall(pool, cg.backend)
        t0 = time.perf_counter()
        for e in range(EPOCHS):
            asyncmap(pool, Bs[e], cg.backend, nwait=K)
            dec = cg.result_device(pool)
        dec.block_until_ready()
        waitall(pool, cg.backend)
        host_s = time.perf_counter() - t0
        print(
            f"host loop: {EPOCHS} epochs in {host_s:.2f}s "
            f"({host_s / EPOCHS * 1e3:.2f} ms/epoch, 2 + 3W host "
            "touches per epoch)"
        )

        coord = cg.coordinator()
        fpool = AsyncPool(N)
        asyncmap_fused(fpool, Bs[:WINDOW], coord, epochs=WINDOW)  # warm
        coord.reset()
        fpool = AsyncPool(N)
        t0 = time.perf_counter()
        for w in range(EPOCHS // WINDOW):
            asyncmap_fused(
                fpool, Bs[w * WINDOW : (w + 1) * WINDOW], coord,
                epochs=WINDOW,
            )
        fused_s = time.perf_counter() - t0
        last = np.asarray(coord.last_decoded)[-1]
        ref = A @ Bs[EPOCHS - 1]
        assert np.max(np.abs(last - ref)) / np.max(np.abs(ref)) < 1e-9
        print(
            f"fused K={WINDOW}: {EPOCHS} epochs in {fused_s:.2f}s "
            f"({fused_s / EPOCHS * 1e3:.3f} ms/epoch, 2 host touches "
            "per window, decode == A @ B)"
        )
        print(
            f"overhead multiple: {host_s / fused_s:.1f}x less host "
            "time per epoch"
        )
    finally:
        cg.backend.shutdown()

    # -- leg 2: semantics are untouched — repochs bit-identical under
    # a straggling fleet --------------------------------------------------
    base = faults.seeded_lognormal(0.01, 0.8, seed=5)

    def delay(w, e):
        return base(w, e) + (30.0 if w == 2 else 0.0)  # w2 straggles

    be = SimBackend(lambda i, p, e: p, N, delay_fn=delay)
    hpool = AsyncPool(N)
    B = Bs[0]
    host_hist = np.stack([
        asyncmap(hpool, B, be, nwait=K).copy() for _ in range(128)
    ])

    cg2 = CodedGemm(A, N, K, dtype=np.float64)
    try:
        coord2 = cg2.coordinator(delay_fn=delay)
        fpool2 = AsyncPool(N)
        fused_hist = np.concatenate([
            asyncmap_fused(fpool2, B, coord2, epochs=WINDOW)
            for _ in range(128 // WINDOW)
        ])
    finally:
        cg2.backend.shutdown()
    assert np.array_equal(host_hist, fused_hist)
    stale = int(np.sum(fused_hist[:, 2] == 0))
    print(
        f"repochs parity: 128 straggling epochs, host loop == fused "
        f"windows (bit-identical); straggler masked in {stale}/128 "
        "epochs"
    )
    print("device coord demo ok")


if __name__ == "__main__":
    main()
