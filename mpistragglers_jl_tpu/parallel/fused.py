"""Fused pool ↔ mesh coded GEMM: asyncmap map step, in-place ICI decode.

This is the integration the two sides of the framework were built for:

* the **async pool** (pool.py + backends/xla.py) runs the straggle-exposed
  map step — one independent jitted program per mesh device, a slow chip
  delays nobody, ``repochs`` is the arrival mask (the reference's
  fastest-k contract, src/MPIAsyncPools.jl:145-188);
* the **masked psum_scatter decode** (parallel/collectives.py) consumes
  the pool's *device-resident* results **in place**: the per-worker
  ``pool.results[i]`` arrays — each already living on mesh device i —
  are assembled into one sharded global array with
  ``jax.make_array_from_single_device_arrays`` (zero copies, no
  device-0 gather, no host round-trip) and decoded by one
  reduce-scatter riding ICI.

Contrast with the two unfused paths:

* ``ops/coded_gemm.CodedGemm.result_device`` gathers every fresh shard
  onto a single device and solves there — a k·blocksize hot-spot on one
  chip's HBM;
* ``parallel/mesh_gemm.MeshCodedGemm.epoch`` is fully sharded but
  bulk-synchronous — its map step is a single ``shard_map`` program, so
  a straggling chip stalls the whole epoch and ``repochs`` must be
  synthesized by the caller.

Here ``repochs`` comes from the pool (real arrivals, real stragglers)
and the collective runs over data that never left the workers' HBM.

Straggler semantics of the decode collective: the combine is
weight-masked, so the *values* on stale devices never affect the output,
but every mesh device still participates in the collective (the XLA
bulk-synchronous contract — see parallel/collectives.py). A stale
worker's device runs the combine between its queued computations; a
permanently dead chip means reforming the mesh, which is the
``respawn``/``reaccept`` layer's job, not the decode's.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..backends.base import DelayFn
from ..backends.xla import XLADeviceBackend
from ..ops.coding import MDSCode, nwait_decodable
from ..ops.gemm import _block_matmul
from ..ops.matdot import MatDotCode, MatDotWeightCache, _matdot_worker
from ..pool import AsyncPool, asyncmap
from .collectives import masked_psum_scatter_combine, mds_decode_weights

__all__ = ["PoolMeshCodedGemm", "PoolMeshMatDotGemm", "select_coded_gemm"]


def _mesh_axis_devices(mesh: Mesh, axis: str) -> list[jax.Device]:
    """Device order along a 1-D pool mesh axis (pool worker i ↔ device i)."""
    if len(mesh.axis_names) != 1 or mesh.axis_names[0] != axis:
        raise ValueError(
            f"pool-fused GEMM needs a 1-D ({axis!r},) mesh, got "
            f"{mesh.axis_names}"
        )
    return list(mesh.devices.flatten())


class _ShardAdopter:
    """Zero-copy assembly of per-worker device-resident results into the
    sharded global (n, *shard) array a decode collective consumes.

    Each ``pool.results[i]`` already lives on mesh device ``i`` (the
    backend mapped worker i there), so
    ``jax.make_array_from_single_device_arrays`` just *adopts* the
    buffers — this is the "no device_put gather" the fusion exists for.
    Stale results whose shape/dtype no longer match the current epoch
    (caller changed B's width) and never-heard workers get a zero
    placeholder; both enter the combine with weight 0. The placeholder
    cache keeps only the latest shape per worker so a varying payload
    width cannot grow HBM pins without bound.
    """

    def __init__(self, mesh: Mesh, axis: str, devices: list[jax.Device],
                 fold: int = 1):
        self.mesh = mesh
        self.axis = axis
        self.devices = devices  # per-WORKER device (len n), block layout
        self.n = len(devices)
        self.fold = int(fold)  # workers per mesh device (1 = adoption)
        self._placeholders: dict[int, tuple] = {}  # i -> (shape, dtype, arr)

    def _placeholder(self, i: int, shape, dtype) -> jax.Array:
        cached = self._placeholders.get(i)
        if cached is not None and cached[0] == shape and cached[1] == dtype:
            return cached[2]
        ph = jax.device_put(jnp.zeros(shape, dtype=dtype), self.devices[i])
        self._placeholders[i] = (shape, dtype, ph)
        return ph

    def _result(self, pool: AsyncPool, i: int, ref_shape, ref_dtype):
        from ..backends.xla import StackedSlice

        r = pool.results[i]
        if isinstance(r, StackedSlice):
            r = r.materialize()  # device-side slice of the fused stack
        if (
            r is None
            or not isinstance(r, jax.Array)
            or r.shape != tuple(ref_shape)
            or r.dtype != ref_dtype
        ):
            r = self._placeholder(i, tuple(ref_shape), ref_dtype)
        return r

    def _group_stack(self, pool: AsyncPool, dd: int, ref_shape, ref_dtype):
        """One mesh device's (fold, *shard) block. Fast path: in batch
        mode the map step already computed the whole group as ONE
        stacked array on the device — every member is a StackedSlice
        into it, in group order — so that stack is adopted directly,
        zero copies. Otherwise the group is stacked device-side (one
        concat, no cross-device traffic)."""
        from ..backends.xla import StackedSlice

        lo = dd * self.fold
        group = [pool.results[lo + l] for l in range(self.fold)]
        first = group[0]
        if (
            isinstance(first, StackedSlice)
            and all(
                isinstance(r, StackedSlice)
                and r.stacked is first.stacked
                and r.index == l
                for l, r in enumerate(group)
            )
            and first.stacked.shape == (self.fold,) + tuple(ref_shape)
            and first.stacked.dtype == ref_dtype
        ):
            return first.stacked
        return jnp.stack(
            [
                self._result(pool, lo + l, ref_shape, ref_dtype)
                for l in range(self.fold)
            ]
        )

    def assemble(self, pool: AsyncPool, ref_shape, ref_dtype) -> jax.Array:
        if self.fold == 1:
            shards = [
                self._result(pool, i, ref_shape, ref_dtype)[None]
                for i in range(self.n)
            ]  # (1, *shard) on device i — pure adoption, no copies
        else:
            shards = [
                self._group_stack(pool, dd, ref_shape, ref_dtype)
                for dd in range(self.n // self.fold)
            ]
        return jax.make_array_from_single_device_arrays(
            (self.n,) + tuple(ref_shape),
            NamedSharding(self.mesh, P(self.axis)),
            shards,
        )


class PoolMeshCodedGemm:
    """(n, k) MDS-coded ``C = A @ B``: pool map step, in-place mesh decode.

    >>> mesh = make_mesh(8)
    >>> fg = PoolMeshCodedGemm(A, mesh, k=6)
    >>> pool = AsyncPool(8)
    >>> decoded = fg.epoch(pool, B)        # asyncmap + psum_scatter decode
    >>> C = fg.full(decoded)               # host gather on demand

    The map step is ``asyncmap`` over an :class:`XLADeviceBackend` whose
    worker i computes ``Ã_i @ B`` on mesh device i; the decode assembles
    ``pool.results`` into a sharded array *in place* and runs the masked
    reduce-scatter. Output block j lands on device j, still sharded.
    """

    def __init__(
        self,
        A: np.ndarray,
        mesh: Mesh,
        k: int,
        *,
        axis: str = "w",
        n_workers: int | None = None,
        parity: str = "cauchy",
        precision: jax.lax.Precision | None = jax.lax.Precision.HIGHEST,
        delay_fn: DelayFn | None = None,
        dtype=None,
        batch: bool = False,
        batch_arrival: str = "ready",
    ):
        """``n_workers`` defaults to the mesh axis size (one worker per
        device — the pure zero-copy layout). ``n_workers > mesh size``
        FOLDS the pool: contiguous groups of ``n/d`` workers share a
        device (the single-bench-chip case: an (8, 6) pool on a
        1-device mesh), the adopter stacks each group device-side, and
        the combine reduce-scatters groups (collectives.py ``fold``).

        ``batch=True`` coalesces each device's workers into ONE stacked
        map program per epoch (ops/_batch.py, like ops/coded_gemm's
        batch mode) — on a dispatch-latency-bound link this collapses
        ``fold`` enqueues into one, and the adopter then adopts the
        already-stacked group result with zero copies (the fully fused
        epoch: one map program + one combine program per device).
        ``batch_arrival`` defaults to ``"ready"`` like every other
        batch-capable workload — real completion order, so ``repochs``
        keeps its straggler meaning; pass ``"enqueue"`` only for
        dispatch-latency benches that fence explicitly."""
        if dtype is not None:
            A = np.asarray(A, dtype=dtype)
        d = mesh.shape[axis]
        n = int(n_workers) if n_workers is not None else d
        if n % d != 0:
            raise ValueError(
                f"n_workers {n} must be a multiple of the mesh axis "
                f"size {d} (whole worker groups per device)"
            )
        fold = n // d
        m = A.shape[0]
        if m % k != 0:
            raise ValueError(f"rows {m} must divide evenly into k={k} blocks")
        self.mesh = mesh
        self.axis = axis
        axis_devs = _mesh_axis_devices(mesh, axis)
        # blocked worker -> device map: group g = workers [g*fold, ...)
        self.devices = [axis_devs[i // fold] for i in range(n)]
        self.fold = fold
        self.code = MDSCode(n, k, parity=parity, dtype=A.dtype,
                            precision=precision)
        self.n, self.k = n, k
        self.block_rows = m // k
        self.precision = precision
        coded = self.code.encode_array(A)  # (n, m/k, d)
        self._group_of: dict = {}
        if batch:
            # batch mode: the fused per-device stacks are the only
            # device copy (ops/_batch.py); per-worker blocks stay host
            coded_host = np.asarray(coded)
            self.blocks = [coded_host[i] for i in range(n)]
            from ..ops._batch import build_device_groups

            self._group_of = build_device_groups(
                self.blocks, n, self.devices
            )
        else:
            # one committed coded block per worker slot — the worker-
            # resident operand of the map step (reference: per-worker
            # data lives with the worker; here "with" is the chip's HBM)
            self.blocks = [
                jax.device_put(coded[i], self.devices[i]) for i in range(n)
            ]
        self.backend = XLADeviceBackend(
            self._work, n, devices=self.devices, delay_fn=delay_fn,
            batch_fn=self._batch_work if batch else None,
            batch_arrival=batch_arrival,
        )
        self._combine = masked_psum_scatter_combine(mesh, axis, fold=fold)
        self._adopter = _ShardAdopter(mesh, axis, self.devices, fold=fold)
        # steady state re-uses one arrival pattern epoch after epoch; cache
        # the device-ready weight matrix per (pattern, dtype) so the hot
        # path pays neither the k×k inverse nor the H2D weights upload
        self._weights_cache: dict[tuple, jax.Array] = {}

    def _work(self, i: int, payload: jax.Array, epoch: int) -> jax.Array:
        return _block_matmul(self.blocks[i], payload, precision=self.precision)

    def _batch_work(self, ids, payload: jax.Array, epoch: int) -> jax.Array:
        """Fused dispatch: every worker in ``ids`` (one device's group)
        as one stacked matmul program."""
        from ..ops._batch import batch_dispatch

        return batch_dispatch(self._group_of, ids, payload, self.precision)

    @property
    def nwait(self):
        """Decodability predicate for ``asyncmap(nwait=...)``."""
        return nwait_decodable(self.k)

    def _check_pool(self, pool: AsyncPool) -> None:
        if pool.n_workers != self.n:
            raise ValueError(
                f"pool has {pool.n_workers} workers but this workload "
                f"is laid out for {self.n} (n_workers; {self.fold} per "
                "mesh device) — they must match one-to-one"
            )

    def decode_from_pool(
        self, pool: AsyncPool, epoch: int | None = None
    ) -> jax.Array:
        """Masked psum_scatter decode of the pool's device-resident
        results. Returns the decoded (n, m/k, cols) array, block j
        resident on device j (blocks j >= k are zeros)."""
        self._check_pool(pool)
        fresh = pool.fresh_indices(epoch)
        if fresh.size < self.k:
            raise ValueError(
                f"only {fresh.size} fresh shards at epoch "
                f"{pool.epoch if epoch is None else epoch}, need k={self.k}"
            )
        idx = fresh[: self.k]
        ref = pool.results[int(idx[0])]
        shards = self._adopter.assemble(pool, ref.shape, ref.dtype)
        key = (tuple(int(x) for x in idx), np.dtype(ref.dtype).str)
        weights = self._weights_cache.get(key)
        if weights is None:
            weights = jnp.asarray(
                mds_decode_weights(self.code, idx), dtype=ref.dtype
            )
            if len(self._weights_cache) >= 4096:  # C(n,k) patterns: bound
                self._weights_cache.clear()
            self._weights_cache[key] = weights
        return self._combine(shards, weights)

    # -- one fused epoch ---------------------------------------------------
    def epoch(
        self,
        pool: AsyncPool,
        B,
        *,
        nwait=None,
        epoch: int | None = None,
        timeout: float | None = None,
        tracer=None,
    ) -> jax.Array:
        """One full fused epoch: ``asyncmap`` map step (fastest-k, real
        arrivals) + in-place masked decode. ``repochs`` comes from the
        pool — never synthesized."""
        self._check_pool(pool)
        if nwait is None:
            nwait = self.nwait
        asyncmap(
            pool, B, self.backend,
            nwait=nwait, epoch=epoch, timeout=timeout, tracer=tracer,
        )
        return self.decode_from_pool(pool)

    def full(self, decoded: jax.Array) -> np.ndarray:
        """Host gather of the first k decoded blocks -> (m, cols)."""
        out = np.asarray(decoded)  # (n, m/k, cols)
        return out[: self.k].reshape(-1, out.shape[-1])

    def device_coordinator(self, *, delay_fn=None, nwait=None, **kw):
        """The fully device-resident form of this fused workload: a
        :class:`~.device_coord.DeviceCoordinator` running K epochs of
        map + arrival masking + the masked ``psum_scatter`` decode as
        ONE ``shard_map`` program over this mesh — the host stages and
        harvests per window instead of driving ``asyncmap`` +
        :meth:`decode_from_pool` per epoch. One worker per mesh device
        (``fold == 1``); folded pools keep the host loop."""
        if self.fold != 1:
            raise ValueError(
                f"device windows need one worker per mesh device, but "
                f"this workload folds {self.fold} workers per device"
            )
        from .device_coord import DeviceCoordinator

        return DeviceCoordinator(
            np.stack([np.asarray(b) for b in self.blocks]),
            decode="mds", G=self.code.G, k=self.k,
            nwait=self.k if nwait is None else nwait,
            mesh=self.mesh, axis=self.axis, delay_fn=delay_fn,
            precision=self.precision, backend=self.backend, **kw,
        )

    def shutdown(self) -> None:
        self.backend.shutdown()


class PoolMeshMatDotGemm:
    """MatDot-coded ``C = A @ B``: pool map step, decode = ONE weighted
    ``psum`` over the pool's device-resident evaluations.

    Same fusion as :class:`PoolMeshCodedGemm` but for MatDot codes
    (ops/matdot.py — inner-dimension partitioning, recovery threshold
    2p-1): worker i encodes B̃_i on its own device from the broadcast B
    and computes ``Ã_i @ B̃_i``; the decode scales each resident
    evaluation by its interpolation weight (0 for stale workers) and one
    ``psum`` yields the full product on every device.
    """

    def __init__(
        self,
        A: np.ndarray,
        mesh: Mesh,
        p: int,
        *,
        axis: str = "w",
        precision: jax.lax.Precision | None = jax.lax.Precision.HIGHEST,
        delay_fn: DelayFn | None = None,
        dtype=None,
    ):
        if dtype is not None:
            A = np.asarray(A, dtype=dtype)
        n = mesh.shape[axis]
        m, kd = A.shape
        if kd % p != 0:
            raise ValueError(
                f"inner dim {kd} must divide evenly into p={p} blocks"
            )
        self.mesh = mesh
        self.axis = axis
        self.devices = _mesh_axis_devices(mesh, axis)
        self.code = MatDotCode(p, n, dtype=A.dtype, precision=precision)
        self.p, self.n, self.k = p, n, self.code.k
        self.precision = precision
        blocks = jnp.asarray(A).reshape(m, p, kd // p).transpose(1, 0, 2)
        coded = self.code.encode_A(blocks)  # (n, m, kd/p)
        self.A_evals = [
            jax.device_put(coded[i], self.devices[i]) for i in range(n)
        ]
        self.B_weights = [
            jax.device_put(jnp.asarray(self.code.VB[i]), self.devices[i])
            for i in range(n)
        ]
        self.backend = XLADeviceBackend(
            self._work, n, devices=self.devices, delay_fn=delay_fn
        )

        def _wsum(ev, w):
            # ev: (1, m, cols) local evaluation; w: (n,) replicated
            i = jax.lax.axis_index(axis)
            return jax.lax.psum(w[i] * ev[0], axis)

        self._wsum = jax.jit(jax.shard_map(
            _wsum, mesh=mesh, in_specs=(P(axis), P()), out_specs=P(),
        ))
        self._adopter = _ShardAdopter(mesh, axis, self.devices)
        self._weights = MatDotWeightCache(self.code)

    def _work(self, i: int, payload: jax.Array, epoch: int) -> jax.Array:
        return _matdot_worker(
            self.A_evals[i], self.B_weights[i], payload, self.p,
            self.precision,
        )

    @property
    def nwait(self):
        """Decodability predicate: 2p-1 fresh evaluations."""
        return nwait_decodable(self.k)

    def _check_pool(self, pool: AsyncPool) -> None:
        if pool.n_workers != self.n:
            raise ValueError(
                f"pool has {pool.n_workers} workers but the mesh pool axis "
                f"has {self.n} devices; they must match one-to-one"
            )

    def decode_from_pool(
        self, pool: AsyncPool, epoch: int | None = None
    ) -> jax.Array:
        """One weighted psum over the pool's resident evaluations.
        Returns the full (m, cols) product, replicated over the mesh."""
        self._check_pool(pool)
        fresh = pool.fresh_indices(epoch)
        if fresh.size < self.k:
            raise ValueError(
                f"only {fresh.size} fresh evaluations, need 2p-1={self.k}"
            )
        sel = tuple(int(x) for x in fresh[: self.k])
        w = self._weights.get(sel)
        ref = pool.results[sel[0]]
        ev = self._adopter.assemble(pool, ref.shape, ref.dtype)
        wC = jax.device_put(
            jnp.asarray(w, dtype=ref.dtype),
            NamedSharding(self.mesh, P()),
        )
        return self._wsum(ev, wC)

    def epoch(
        self,
        pool: AsyncPool,
        B,
        *,
        nwait=None,
        epoch: int | None = None,
        timeout: float | None = None,
        tracer=None,
    ) -> jax.Array:
        self._check_pool(pool)
        if nwait is None:
            nwait = self.nwait
        asyncmap(
            pool, B, self.backend,
            nwait=nwait, epoch=epoch, timeout=timeout, tracer=tracer,
        )
        return self.decode_from_pool(pool)

    def shutdown(self) -> None:
        self.backend.shutdown()


class _UnfusedCodedGemm:
    """Adapter giving :class:`~..ops.coded_gemm.CodedGemm` (the
    device-0 gather+solve decode) the fused ``epoch()`` surface so
    :func:`select_coded_gemm` can drive either winner identically."""

    fused = False

    def __init__(self, cg):
        self.gemm = cg
        self.backend = cg.backend
        self.k = cg.code.k

    def epoch(self, pool: AsyncPool, B, *, nwait=None, epoch=None):
        asyncmap(pool, B, self.backend,
                 nwait=self.gemm.nwait if nwait is None else nwait,
                 epoch=epoch)
        return self.gemm.result_device(pool)

    def full(self, decoded) -> np.ndarray:
        return np.asarray(decoded)

    def shutdown(self) -> None:
        self.backend.shutdown()


def select_coded_gemm(
    A: np.ndarray,
    mesh: Mesh,
    k: int,
    B_probe,
    *,
    n_workers: int | None = None,
    probe_epochs: int = 3,
    chains: int = 2,
    **kw,
):
    """Measured fused-vs-unfused selection (VERDICT r4 item 4).

    On a multi-device mesh the fused path's structural win (no k-shard
    gather onto one device, decode riding ICI) is decisive; on ONE
    device the two paths differ only by dispatch economics that sit
    inside the session's noise band (measured 0.95-1.10x across rounds;
    earlier installation, not repeated on this one). So instead of
    hardcoding a loser, probe both on
    THIS machine: alternating timed chains of ``probe_epochs``
    epochs (the fused-bench discipline: alternation, so slow drift
    between chains lands on both candidates alike), keep the winner,
    shut the loser down. The decision and both measurements ride on
    ``winner.selection``:

    >>> g = select_coded_gemm(A, mesh, k, B_probe)
    >>> g.selection          # {"picked": ..., "fused_ms": ..., ...}
    >>> decoded = g.epoch(pool, B)

    ``**kw`` (``axis``, ``batch``, ``batch_arrival``, ``precision``,
    ``parity``, ``dtype``) is forwarded to both candidates.
    """
    import time

    from ..ops.coded_gemm import CodedGemm
    from ..pool import waitall

    # pop-and-forward: the axis names BOTH the probe's device order and
    # the fused candidate's mesh axis (dropping it here crashed every
    # non-default-axis mesh inside PoolMeshCodedGemm — regression-
    # pinned in tests/test_fused.py)
    axis = kw.pop("axis", "w")
    devices = _mesh_axis_devices(mesh, axis)
    n = int(n_workers) if n_workers is not None else len(devices)
    fused = PoolMeshCodedGemm(A, mesh, k, n_workers=n, axis=axis, **kw)
    dev_map = [devices[i * len(devices) // n] for i in range(n)]
    unfused = _UnfusedCodedGemm(CodedGemm(A, n, k, devices=dev_map, **kw))

    fence = jax.jit(lambda x: jnp.sum(x.astype(jnp.float32)))
    times = {True: None, False: None}
    pools = {True: AsyncPool(n), False: AsyncPool(n)}
    for g, is_fused in ((fused, True), (unfused, False)):  # warmup
        out = g.epoch(pools[is_fused], B_probe)
        float(fence(out))
        waitall(pools[is_fused], g.backend)
    for _ in range(chains):
        for g, is_fused in ((fused, True), (unfused, False)):
            pool = pools[is_fused]
            t0 = time.perf_counter()
            for _ in range(probe_epochs):
                out = g.epoch(pool, B_probe)
                waitall(pool, g.backend)
            float(fence(out))
            dt = (time.perf_counter() - t0) / probe_epochs
            prev = times[is_fused]
            times[is_fused] = dt if prev is None else min(prev, dt)
    pick_fused = times[True] <= times[False]
    winner, loser = (fused, unfused) if pick_fused else (unfused, fused)
    loser.shutdown()
    winner.selection = {
        "picked": "fused" if pick_fused else "unfused",
        "fused_ms": round(times[True] * 1e3, 2),
        "unfused_ms": round(times[False] * 1e3, 2),
        "probe_epochs": probe_epochs,
        "chains": chains,
        "mesh_devices": len(devices),
    }
    return winner
