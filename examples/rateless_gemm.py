"""Incremental redundancy walkthrough: rateless LT GEMM vs a permanent
straggler whose shard is load-bearing.

The fixed-window LT workload (``LTCodedGemm``) re-tasks a straggler with
the SAME shard — a permanent straggler whose shard the peeling decoder
needs makes the epoch undecodable forever. ``RatelessLTGemm`` draws
FRESH shards instead: every dispatch advances the worker's generation,
so decode rounds accumulate new information until the set peels.

Run (CPU is fine):

    PYTHONPATH=. python examples/rateless_gemm.py
"""

import sys

import numpy as np

from mpistragglers_jl_tpu import AsyncPool, asyncmap
from mpistragglers_jl_tpu.ops.coded_gemm import LTCodedGemm
from mpistragglers_jl_tpu.ops.lt import LTCode
from mpistragglers_jl_tpu.ops.rateless import RatelessLTGemm
from mpistragglers_jl_tpu.pool import DeadWorkerError

N, K, SEED = 6, 4, 0  # witness: window [0,6) peels, minus worker 0 doesn't


def permanent_straggler(i, epoch):
    return 30.0 if i == 0 else 0.0


def main():
    code = LTCode(K, seed=SEED)
    assert code.peelable(list(range(N)))
    assert not code.peelable(list(range(1, N)))
    print(f"witness: shards 1..{N - 1} alone do NOT peel (k={K})")

    rng = np.random.default_rng(0)
    A = rng.standard_normal((8, 5))
    B = rng.standard_normal((5, 3))

    # --- fixed window: undecodable, by construction -------------------
    lt = LTCodedGemm(
        A, N, K, seed=SEED, shard_ids=list(range(N)),
        delay_fn=permanent_straggler,
    )
    try:
        pool = AsyncPool(N)
        try:
            asyncmap(pool, B, lt.backend, nwait=lt.nwait, timeout=2.0)
            print("unexpected: fixed window decoded")
        except DeadWorkerError:
            print("fixed window: epoch never becomes decodable (timeout)")
    finally:
        lt.backend.shutdown()

    # --- rateless: generation-1 draws repair it -----------------------
    # systematic=False: this example demonstrates the CLASSIC stream's
    # incremental redundancy (fresh generation-1 draws rescuing an
    # undecodable window). The systematic default (round 3) peels this
    # trace within generation 0 — better in production, but then there
    # is nothing to demonstrate.
    rg = RatelessLTGemm(A, N, K, seed=SEED, delay_fn=permanent_straggler,
                        systematic=False)
    try:
        pool = AsyncPool(N)
        C = rg.multiply(B, pool, round_timeout=3.0, max_rounds=6)
        err = float(np.max(np.abs(C - A @ B)))
        print(
            f"rateless: decoded exactly (max err {err:.2e}) using "
            f"{rg.stats['shards_used']} shards for k={rg.stats['k']} "
            f"(overhead {rg.stats['overhead']:.2f}x, "
            f"max generation {rg.stats['max_generation']})"
        )
        # f32 on accelerators, f64 on CPU — either decodes exactly
        assert err < 1e-4 and rg.stats["max_generation"] >= 1
        print("done: re-tasks contributed fresh information")
    finally:
        rg.backend.shutdown()


if __name__ == "__main__":
    sys.exit(main())
