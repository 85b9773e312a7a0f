"""Adaptive vs fixed nwait under a drifting straggler pattern.

The reference hard-codes ``nwait`` everywhere (test/kmap2.jl:32 etc.);
this measures what that costs when the straggler MOVES. Workload: n=8
thread workers, 5 ms base latency; the straggler (75 ms) rotates to a
different worker every 20 epochs. Policies:

* ``full gather``   — nwait = 8 (pays the straggler every epoch)
* ``fixed k=6``     — the right constant for this fault pattern, if you
                      somehow knew it in advance
* ``adaptive``      — AdaptiveNwait with kmin=4, learning online

Metric: mean epoch wall-clock per policy over 100 epochs (+ fresh
results per epoch, since waiting for fewer buys time but less data).
Prints one JSON line per policy. CPU-only (threads), deterministic.

Run:  python benchmarks/adaptive_nwait_bench.py [epochs]
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

from mpistragglers_jl_tpu import AsyncPool, LocalBackend, asyncmap, waitall
from mpistragglers_jl_tpu.utils import AdaptiveNwait

N = 8
BASE_S = 0.005
STRAGGLE_S = 0.075
ROTATE_EVERY = 20


class RotatingStraggler:
    """The straggler moves to worker (epoch // rotate_every) % n."""

    def __init__(self, n: int = N, slow: float = STRAGGLE_S,
                 base: float = BASE_S, rotate_every: int = ROTATE_EVERY):
        self.n, self.slow, self.base = n, slow, base
        self.rotate_every = rotate_every

    def __call__(self, worker: int, epoch: int) -> float:
        hot = (epoch // self.rotate_every) % self.n
        return self.slow if worker == hot else self.base


def _echo(i, payload, epoch):
    return payload


def record_drifting_trace(path, epochs: int, n: int = N,
                          delay_fn=None) -> None:
    """Record one drifting-straggler trace (EpochTracer JSONL) that
    ``utils.faults.from_trace`` replays identically for every policy —
    the record -> replay loop as the A/B's controlled variable."""
    from mpistragglers_jl_tpu.utils import EpochTracer

    tracer = EpochTracer()
    backend = LocalBackend(
        _echo, n, delay_fn=delay_fn or RotatingStraggler(n)
    )
    try:
        pool = AsyncPool(n)
        for _ in range(epochs):
            asyncmap(pool, np.zeros(1), backend, nwait=n, tracer=tracer)
        waitall(pool, backend)
        tracer.dump_jsonl(path)
    finally:
        backend.shutdown()


def replay_policy(path, *, adaptive: bool, epochs: int, n: int = N,
                  kmin: int | None = None):
    """Replay the recorded trace under one nwait policy (thread
    workers). Returns (mean_ms, mean_fresh, final_nwait)."""
    from mpistragglers_jl_tpu.utils.faults import from_trace

    backend = LocalBackend(_echo, n, delay_fn=from_trace(path))
    ctl = AdaptiveNwait(
        n, kmin=n - 2 if kmin is None else kmin,
        min_samples=2, refit_every=5, seed=0,
    ) if adaptive else None
    try:
        pool = AsyncPool(n)
        walls, fresh = [], []
        for _ in range(epochs):
            nwait = ctl.nwait if ctl else n
            t0 = time.perf_counter()
            asyncmap(pool, np.zeros(1), backend, nwait=nwait)
            walls.append(time.perf_counter() - t0)
            fresh.append(int(pool.fresh_indices().size))
            if ctl:
                ctl.observe(pool)
        waitall(pool, backend)
        return (
            float(np.mean(walls)) * 1e3,
            float(np.mean(fresh)),
            ctl.nwait if ctl else n,
        )
    finally:
        backend.shutdown()


def run_policy(name: str, epochs: int):
    backend = LocalBackend(
        lambda i, p, e: p + i, N, delay_fn=RotatingStraggler()
    )
    ctl = (
        AdaptiveNwait(N, kmin=4, min_samples=2, refit_every=5, seed=0)
        if name == "adaptive"
        else None
    )
    fixed = (
        None if ctl is not None
        else {"full-gather": N, "fixed-k6": 6}[name]  # unknown: fail fast
    )
    try:
        pool = AsyncPool(N)
        walls, fresh_counts = [], []
        # the straggler rotation keys off pool.epoch (advanced inside
        # asyncmap), not a loop counter
        for _ in range(epochs):
            nwait = ctl.nwait if ctl is not None else fixed
            t0 = time.perf_counter()
            asyncmap(pool, np.zeros(1), backend, nwait=nwait)
            walls.append(time.perf_counter() - t0)
            fresh_counts.append(int(pool.fresh_indices().size))
            if ctl is not None:
                ctl.observe(pool)
        waitall(pool, backend)
        return {
            "metric": f"adaptive-nwait-{name}",
            "value": round(float(np.mean(walls)) * 1e3, 2) if walls else None,
            "unit": "ms/epoch",
            "fresh_mean": (
                round(float(np.mean(fresh_counts)), 2) if fresh_counts else None
            ),
            "epochs": epochs,
            # the controller's state AFTER its last observe/refit
            "final_nwait": ctl.nwait if ctl is not None else fixed,
        }
    finally:
        backend.shutdown()


def run_coded_sgd_policy(adaptive: bool, trace_path, epochs: int = 60):
    """BASELINE config 5 driven by the decision layer: gradient-coded
    SGD (s=2 redundancy) under a drifting straggler TRACE, adaptive vs
    the full-gather posture. The trace is recorded once (rotating
    straggler over thread workers) and replayed via
    ``utils.faults.from_trace`` so both policies face the identical
    latency pattern (VERDICT round 1 item 10)."""
    from mpistragglers_jl_tpu.models import CodedSGD
    from mpistragglers_jl_tpu.utils.faults import from_trace

    n, s_red = 8, 2
    path = trace_path
    rng = np.random.default_rng(0)
    X = rng.standard_normal((4096, 64)).astype(np.float32)
    w_true = rng.standard_normal(64)
    y = (X @ w_true > 0).astype(np.float32)
    sgd = CodedSGD(X, y, n, s_red, delay_fn=from_trace(path))
    try:
        ctl = AdaptiveNwait(
            n, kmin=n - s_red, min_samples=2, refit_every=5, seed=0
        ) if adaptive else None
        pool = AsyncPool(n)
        import jax.numpy as jnp

        w = jnp.zeros(64, dtype=jnp.float32)
        walls = []
        for _ in range(epochs):
            t0 = time.perf_counter()
            w = sgd.step(
                pool, w, 0.5, nwait=(ctl.nwait if ctl else n)
            )
            walls.append(time.perf_counter() - t0)
            if ctl:
                ctl.observe(pool)
        waitall(pool, sgd.backend)
        Xe, ye = sgd.eval_data()
        loss = float(sgd.model.loss(w, Xe, ye))
        return {
            "metric": "adaptive-nwait-codedsgd-"
            + ("adaptive" if adaptive else "full-gather"),
            "value": round(float(np.mean(walls)) * 1e3, 2),
            "unit": "ms/step",
            "final_loss": round(loss, 5),
            "final_nwait": ctl.nwait if ctl else n,
            "epochs": epochs,
        }
    finally:
        sgd.backend.shutdown()


def main():
    import tempfile
    import uuid

    epochs = int(sys.argv[1]) if len(sys.argv) > 1 else 100
    for name in ("full-gather", "fixed-k6", "adaptive"):
        print(json.dumps(run_policy(name, epochs)))
    # config 5 under the decision layer: ONE recorded trace, replayed
    # identically for both policies. The straggler is slowed to 0.6 s so
    # it dominates the device path's fixed per-step dispatch cost.
    sgd_epochs = min(epochs, 60)
    path = os.path.join(
        tempfile.gettempdir(), f"adpt-trace-{uuid.uuid4().hex[:8]}.jsonl"
    )
    record_drifting_trace(
        path, sgd_epochs, delay_fn=RotatingStraggler(slow=0.6)
    )
    try:
        for adaptive in (False, True):
            print(json.dumps(
                run_coded_sgd_policy(adaptive, path, sgd_epochs)
            ))
    finally:
        try:
            os.unlink(path)
        except OSError:
            pass


if __name__ == "__main__":
    main()
