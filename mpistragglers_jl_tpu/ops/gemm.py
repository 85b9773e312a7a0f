"""Distributed matrix multiplication over an async device pool.

Uncoded row-block GEMM (BASELINE config 2): ``C = A @ B`` with ``A`` row-
partitioned over n workers. Worker ``w`` holds its block ``A_w`` resident
on its device (placed once at setup — the reference's analog is each MPI
worker holding its data slice process-locally) and each epoch receives
``B`` as the broadcast payload, computing ``C_w = A_w @ B`` on the MXU.

The reference library is payload-agnostic and has no model/workload code
at all (SURVEY §5 "Long-context" row: the library is bytes-over-MPI,
src/MPIAsyncPools.jl:82-84); distributed GEMM is the north-star workload
BASELINE.json prescribes on top of the pool primitive. Design notes:

* blocks are placed device-resident once; only ``B`` moves per epoch —
  the HBM-friendly layout (A never re-crosses PCIe/ICI);
* the per-worker program is a single large matmul in the worker's native
  dtype (bf16/f32 on TPU MXU, f64 available on the CPU backend);
* ``nwait < n`` returns a row-partial product with ``repochs`` as the
  per-block freshness mask — the uncoded base case of the coded layer
  (ops/coding.py), which makes missing blocks recoverable.
"""

from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..backends.base import DelayFn
from ..backends.xla import XLADeviceBackend
from ..pool import AsyncPool


from functools import partial


@partial(jax.jit, static_argnames=("precision",))
def _block_matmul(a_block: jax.Array, b: jax.Array, precision=None) -> jax.Array:
    return jnp.matmul(a_block, b, precision=precision)


def gather_rows(
    pool: AsyncPool,
    epoch: int | None = None,
    *,
    row_splits: Sequence[int] | None = None,
) -> np.ndarray:
    """Assemble the row-stacked result from per-worker results.

    Rows from workers whose ``repochs[i] != epoch`` are zero-filled; the
    per-row-block freshness mask is ``pool.repochs == epoch`` (i.e. the
    value ``asyncmap`` returned) — callers needing staleness policy read
    that, this function only stacks. ``row_splits`` gives each worker's
    row count when blocks are heterogeneous (load-balanced splits);
    without it all blocks must be the same shape. Raises ``ValueError``
    if no worker has any result at all for the requested epoch.
    """
    if epoch is None:
        epoch = pool.epoch
    # convert only fresh blocks — stale device-resident results must not
    # pay a D2H transfer just to be replaced by zeros
    blocks = [
        np.asarray(pool.results[i])
        if pool.results[i] is not None and pool.repochs[i] == epoch
        else None
        for i in range(pool.n_workers)
    ]
    proto = next((b for b in blocks if b is not None), None)
    if proto is None:
        if all(r is None for r in pool.results):
            raise ValueError("no worker has returned any result yet")
        raise ValueError(f"no worker has a result for epoch {epoch}")
    if row_splits is None:  # homogeneous blocks: all shaped like proto
        row_splits = [proto.shape[0]] * pool.n_workers
    out = [
        b if b is not None
        else np.zeros((row_splits[i], *proto.shape[1:]), proto.dtype)
        for i, b in enumerate(blocks)
    ]
    return np.concatenate(out, axis=0)


class DistributedGemm:
    """``C = A @ B`` row-partitioned over an async pool of devices.

    >>> g = DistributedGemm(A, n_workers=8)
    >>> pool = AsyncPool(8)
    >>> repochs = asyncmap(pool, B, g.backend)   # broadcast B, fastest-k
    >>> C = g.result(pool)                       # stack fresh row blocks
    """

    def __init__(
        self,
        A: np.ndarray,
        n_workers: int,
        *,
        row_splits: Sequence[int] | None = None,
        devices: Sequence[jax.Device] | None = None,
        delay_fn: DelayFn | None = None,
        dtype=None,
        precision: jax.lax.Precision | None = jax.lax.Precision.HIGHEST,
        batch: bool = False,
        batch_arrival: str = "ready",
    ):
        # HIGHEST by default: the TPU MXU's native matmul accumulates in
        # bf16-ish precision (observed max err ~0.25 on a 512-deep f32
        # contraction vs 5e-5 at HIGHEST); coded decode paths need the
        # accuracy. Benchmarks may pass precision=None for peak MXU rate.
        #
        # ``batch=True``: coalesced dispatch — each device's workers run
        # as ONE fused stacked matmul per epoch (see CodedGemm);
        # requires homogeneous row_splits, incompatible with delay_fn.
        self.precision = precision
        m = A.shape[0]
        if row_splits is None:
            if m % n_workers != 0:
                raise ValueError(
                    f"rows {m} must divide evenly over {n_workers} workers "
                    "(or pass row_splits)"
                )
            row_splits = [m // n_workers] * n_workers
        else:
            row_splits = [int(r) for r in row_splits]
            if len(row_splits) != n_workers:
                raise ValueError(
                    f"row_splits has {len(row_splits)} entries for "
                    f"{n_workers} workers"
                )
            if any(r < 0 for r in row_splits) or sum(row_splits) != m:
                raise ValueError(
                    f"row_splits must be non-negative and sum to {m}, "
                    f"got {row_splits}"
                )
        if devices is None:
            devices = jax.devices()
        if dtype is not None:
            A = np.asarray(A, dtype=dtype)
        self.n_workers = n_workers
        self.row_splits = row_splits
        offsets = np.concatenate([[0], np.cumsum(row_splits)])
        self._group_of: dict[int, tuple] = {}
        if batch:
            if len(set(row_splits)) != 1:
                raise ValueError(
                    "batch=True needs homogeneous row_splits (the fused "
                    "program stacks equal-shaped blocks)"
                )
            from ._batch import build_device_groups

            # fused per-device stacks are the only device copy; the
            # per-worker blocks stay host-side views (ops/_batch.py)
            self.blocks = [
                A[offsets[i] : offsets[i + 1]]
                for i in range(n_workers)
            ]
            self._group_of = build_device_groups(
                self.blocks, n_workers, devices
            )
        else:
            # place each row block on its worker's device once, up front
            self.blocks = [
                jax.device_put(
                    A[offsets[i] : offsets[i + 1]],
                    devices[i % len(devices)],
                )
                for i in range(n_workers)
            ]
        self.backend = XLADeviceBackend(
            self._work, n_workers, devices=devices, delay_fn=delay_fn,
            batch_fn=self._batch_work if batch else None,
            batch_arrival=batch_arrival,
        )

    def _batch_work(self, ids, payload: jax.Array, epoch: int) -> jax.Array:
        """Fused dispatch: every worker's row-block matmul in one MXU
        program (shared machinery, ops/_batch.py)."""
        from ._batch import batch_dispatch

        return batch_dispatch(self._group_of, ids, payload, self.precision)

    @classmethod
    def load_balanced(
        cls, A: np.ndarray, model, **kwargs
    ) -> "DistributedGemm":
        """Split rows proportional to fitted worker speed — the uncoded
        straggler mitigation: slow workers get less work instead of
        being raced (``model`` is a fitted
        :class:`~..utils.straggle.PoolLatencyModel`).

        >>> model.observe_pool(pool)       # ... over some epochs
        >>> g = DistributedGemm.load_balanced(A, model)
        """
        splits = model.proportional_shares(A.shape[0])
        return cls(
            A, model.n_workers, row_splits=splits.tolist(), **kwargs
        )

    def _work(self, i: int, payload: jax.Array, epoch: int) -> jax.Array:
        return _block_matmul(self.blocks[i], payload, precision=self.precision)

    def result(self, pool: AsyncPool, epoch: int | None = None) -> np.ndarray:
        return gather_rows(pool, epoch, row_splits=self.row_splits)
