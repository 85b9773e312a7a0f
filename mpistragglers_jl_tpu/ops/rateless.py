"""Genuinely rateless LT-coded GEMM: re-tasks draw *fresh* coded shards.

:class:`~.coded_gemm.LTCodedGemm` fixes one window of shard ids at
construction — a re-tasked straggler recomputes the same shard, so a
slow epoch gains nothing from extra work. This module supplies the
actual point of a rateless code: **incremental redundancy**. Every
dispatch a worker receives within an epoch advances its private shard
*generation*; the shard id is the deterministic function

    shard_id(worker, generation) = worker + n_workers * generation

so ids never repeat across workers or rounds and the shard stream is
unbounded (the LT property: any prefix of distinct ids is a valid code).
Workers encode their own coded block lazily from the source blocks —
the on-worker-encoding pattern of :mod:`.matdot` (its workers build
``B̃_i`` from the broadcast payload) applied to the ``A`` side — so a
fresh shard costs one short weighted-sum + the usual MXU matmul, no
re-setup.

Arrivals are *accumulated*, not replaced: a worker whose round-1 shard
landed and whose round-2 re-dispatch lands later contributes **two**
shards to the epoch's decode set. The pool machinery carries this
without modification — the decodability ``nwait`` predicate is
re-evaluated after every arrival (reference src/MPIAsyncPools.jl:152-158)
and closes over the epoch's collected-shard set; multi-round draws reuse
the reference's caller-chosen-epoch contract (``asyncmap(...,
epoch=e)`` with the same ``e``: re-dispatching idle workers at an
unchanged epoch is exactly src/MPIAsyncPools.jl:87's "no monotonicity is
enforced", SURVEY §2.1).

Decode is peeling (ops/lt.py), identical to the fixed-window path; the
only new state is the per-epoch ``(shard_id, shard)`` collection and a
``stats`` record of shards consumed vs ``k`` (the rateless overhead the
benchmark reports).
"""

from __future__ import annotations

import threading
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..backends.base import DelayFn
from ..backends.xla import XLADeviceBackend
from ..pool import AsyncPool, DeadWorkerError, asyncmap
from .gemm import _block_matmul
from .lt import LTCode


@jax.jit
def _encode_block(src, sup):
    """Ã_s = Σ source blocks in the shard's support — computed ON the
    worker's device from device-resident source blocks (one compile per
    support degree; degrees are <= k, so a handful of programs). The
    alternative — host-encoding then shipping the coded block — puts a
    block-sized H2D transfer on every fresh-shard draw."""
    return src[sup].sum(axis=0)

__all__ = ["RatelessLTGemm"]


class RatelessLTGemm:
    """Rateless LT-coded ``C = A @ B`` with incremental redundancy.

    >>> rg = RatelessLTGemm(A, n_workers=8, k=6)
    >>> pool = AsyncPool(8)
    >>> C = rg.multiply(B, pool)      # draws shards until the set peels
    >>> rg.stats["shards_used"]       # rateless overhead vs k

    ``multiply`` runs rounds: each round dispatches one fresh shard per
    idle worker and waits up to ``round_timeout`` for the collected set
    to become peelable; workers still busy with an earlier shard are
    left in flight (their eventual stale arrival is harvested and
    re-tasked with a *new* shard id by the pool's phase-1/phase-3
    machinery). A permanent straggler therefore costs one round of
    timeout, not decodability.
    """

    def __init__(
        self,
        A: np.ndarray,
        n_workers: int,
        k: int,
        *,
        devices: Sequence[jax.Device] | None = None,
        delay_fn: DelayFn | None = None,
        seed: int = 0,
        dtype=None,
        precision: jax.lax.Precision | None = jax.lax.Precision.HIGHEST,
        block_cache_size: int = 64,
        systematic: bool = True,
    ):
        """``systematic=True`` (default): the generation-0 window's
        first k shards ARE the source blocks, so a straggler-free epoch
        peels from k arrivals and a straggler costs only the draws
        until its missing block is covered — measured overhead drops
        from ~1.6x to ~1.25x of k at (n=8, k=8) (earlier
        installation, not repeated on this one).
        Set False for the classic all-soliton stream."""
        if dtype is not None:
            A = np.asarray(A, dtype=dtype)
        else:
            A = np.asarray(A)
        m = A.shape[0]
        if m % k != 0:
            raise ValueError(f"rows {m} must divide evenly into k={k} blocks")
        if devices is None:
            devices = jax.devices()
        self.code = LTCode(k, seed=seed, systematic=systematic)
        self.k = int(k)
        self.n = int(n_workers)
        self.devices = list(devices)
        self.block_rows = m // k
        self.precision = precision
        # generation 0 is host-encoded at setup (below); the device
        # copy of the source blocks is uploaded LAZILY on the first
        # fresh-generation draw, so a straggler-free run pays zero
        # extra HBM and fresh shards thereafter encode device-side
        self._src = np.ascontiguousarray(A.reshape(k, m // k, *A.shape[1:]))
        self._src_dev: dict = {}
        self._block_cache: dict[int, jax.Array] = {}
        self._block_cache_size = int(block_cache_size)
        self._gen: dict[tuple[int, int], int] = {}  # (epoch, worker) -> gen
        # per-epoch collected shards: {shard_id: device array}; appended
        # by worker threads at completion, read by the nwait predicate
        # and the decoder on the coordinator thread
        self._collected: dict[int, dict[int, jax.Array]] = {}
        # epoch whose shards _work may retain; None until the first
        # multiply() (direct Backend-API users collect every epoch)
        self._live_epoch: int | None = None
        # epoch -> the shard-id set the nwait predicate fired on
        self._satisfied: dict[int, list[int]] = {}
        self._lock = threading.Lock()
        self.stats: dict = {}
        # generation 0 = the static window [0, n): pre-encode on device
        for i in range(self.n):
            self._coded_block(i, i)
        self.backend = XLADeviceBackend(
            self._work, self.n, devices=devices, delay_fn=delay_fn
        )

    # -- shard plumbing ---------------------------------------------------
    def shard_id(self, worker: int, generation: int) -> int:
        """Deterministic unbounded shard stream, distinct across workers
        and rounds."""
        return int(worker) + self.n * int(generation)

    def _coded_block(self, worker: int, sid: int) -> jax.Array:
        """The device-resident coded block Ã_sid = Σ (support blocks),
        encoded lazily and cached (bounded).

        Generation 0 (``sid < n``, the setup window) encodes host-side
        and uploads once — no device source copy for straggler-free
        runs. Fresh generations encode ON the worker's device from the
        lazily-uploaded source blocks (one H2D per device, ever; the
        numpy array goes to ``dev`` directly, no default-device bounce).
        The encode runs OUTSIDE the lock — an XLA compile for a new
        support degree must not stall every worker completion and the
        decodability predicate; a racing duplicate encode is benign.
        """
        with self._lock:
            blk = self._block_cache.get(sid)
            if blk is not None:
                return blk
        dev = self.devices[worker % len(self.devices)]
        sup = self.code.shard_indices(sid)
        if sid < self.n:
            enc = self._src[sup[0]].copy()
            for j in sup[1:]:
                enc += self._src[j]
            blk = jax.device_put(enc, dev)
        else:
            blk = _encode_block(self._device_src(dev), jnp.asarray(sup))
        with self._lock:
            if len(self._block_cache) >= self._block_cache_size:
                # keep generation 0 (the steady-state window) resident
                for key in [
                    s for s in self._block_cache if s >= self.n
                ]:
                    del self._block_cache[key]
            return self._block_cache.setdefault(sid, blk)

    def _device_src(self, dev) -> jax.Array:
        """Device-resident (k, rows, cols) source stack, created ONCE
        per device — single-flight.

        The previous lazy pattern let every dispatcher thread race the
        None check, so a round of fresh-generation draws paid n-1
        SERIALIZED copies of the full source upload; on a slow H2D
        link that outlived every round timeout and presented as
        `DeadWorkerError: workers [0..n-1]` (round-3 diagnosis). Now
        the first thread builds, the rest wait on an Event.
        Systematic codes never touch the host at all:
        the generation-0 identity blocks ARE the source blocks and are
        already HBM-resident, so the stack is one device-side concat.
        """
        with self._lock:
            entry = self._src_dev.get(dev)
            owner = entry is None
            if owner:
                entry = {"ready": threading.Event(), "src": None}
                self._src_dev[dev] = entry
        if not owner:
            entry["ready"].wait()
            src = entry["src"]
            if src is None:
                raise RuntimeError("device source construction failed")
            return src
        try:
            if self.code.systematic:
                with self._lock:
                    cached = [
                        self._block_cache.get(s) for s in range(self.k)
                    ]
                parts = []
                for s, c in enumerate(cached):
                    if c is None:  # block never encoded (n < k corner)
                        c = jax.device_put(self._src[s], dev)
                    elif c.device != dev:
                        # identity block resident on a sibling device:
                        # D2D copy, still no host round trip
                        c = jax.device_put(c, dev)
                    parts.append(c)
                entry["src"] = jnp.stack(parts)
            else:
                entry["src"] = jax.device_put(self._src, dev)
            return entry["src"]
        finally:
            if entry["src"] is None:
                # Build failed (e.g. transient HBM pressure during the
                # device_put). Drop the dead entry under the lock BEFORE
                # releasing waiters so a later call can retry instead of
                # hitting a permanently poisoned device for the object's
                # lifetime; current waiters still get the RuntimeError.
                with self._lock:
                    if self._src_dev.get(dev) is entry:
                        del self._src_dev[dev]
            entry["ready"].set()

    def prefetch_source(self) -> None:
        """Build the per-device source stacks up front.

        The first fresh-generation draw otherwise pays the source
        construction (a full H2D upload for classic streams) inside a
        round timeout; benches and latency-sensitive callers warm it
        here, off the clock. Systematic streams make this nearly free
        (device-side concat of the resident identity blocks)."""
        seen = []
        for dev in self.devices[: self.n]:
            if not any(dev is d for d in seen):
                seen.append(dev)
                self._device_src(dev)

    def _work(self, i: int, payload: jax.Array, epoch: int):
        """Worker compute: advance this worker's generation, encode the
        fresh shard's block, multiply. Runs in the backend's per-worker
        dispatcher thread (the XLA pool's worker side)."""
        with self._lock:
            gen = self._gen.get((epoch, i), 0)
            self._gen[(epoch, i)] = gen + 1
        sid = self.shard_id(i, gen)
        out = _block_matmul(
            self._coded_block(i, sid), payload, precision=self.precision
        )
        out = jax.block_until_ready(out)
        with self._lock:
            # only the live epoch accumulates: a straggler still in
            # flight from a pruned epoch must not re-create its dict
            # (that entry would never be pruned again and would pin the
            # shard in HBM for the object's life — ADVICE r2). The
            # shard itself is still returned so the pool's stale-
            # arrival bookkeeping stays intact; it is simply not
            # retained here.
            if self._live_epoch is None or epoch == self._live_epoch:
                self._collected.setdefault(epoch, {})[sid] = out
        return sid, out

    # -- decode-side ------------------------------------------------------
    def collected_ids(self, epoch: int) -> list[int]:
        with self._lock:
            return sorted(self._collected.get(epoch, {}))

    def decodable(self, epoch: int) -> bool:
        return self.code.peelable(self.collected_ids(epoch))

    def nwait(self, epoch: int):
        """Decodability predicate over the epoch's *collected* shard set
        (not just the latest per-worker result): re-evaluated after
        every arrival, reference src/MPIAsyncPools.jl:152-158.

        When the predicate fires it snapshots the satisfying shard set:
        workers still in flight keep landing between the pool's return
        and the decode, and counting (or peeling) those would inflate
        the rateless-overhead statistic past the draw-until-peel value
        the code actually achieved — the decode needs exactly the
        prefix that peeled."""

        def pred(ep: int, repochs: np.ndarray) -> bool:
            ids = self.collected_ids(epoch)
            if self.code.peelable(ids):
                with self._lock:
                    self._satisfied.setdefault(epoch, ids)
                return True
            return False

        return pred

    def multiply(
        self,
        B,
        pool: AsyncPool,
        *,
        round_timeout: float = 5.0,
        max_rounds: int = 8,
    ) -> np.ndarray:
        """Compute ``A @ B``, drawing coded shards until the set peels.

        Round r re-enters ``asyncmap`` at the *same* epoch: idle workers
        (everyone who already delivered) are re-dispatched and — because
        their generation advanced — compute shards never seen before.
        Workers still in flight are untouched. Raises
        :class:`~..pool.DeadWorkerError` only if ``max_rounds`` rounds
        all time out (every worker dead)."""
        epoch = pool.epoch + 1
        with self._lock:
            # prune: only the live epoch's shards are retained, and
            # _work drops late arrivals from any other epoch from here
            # on (see _work)
            self._live_epoch = epoch
            self._collected = {epoch: {}}
            self._satisfied = {}
            self._gen = {k_: v for k_, v in self._gen.items()
                         if k_[0] == epoch}
        pred = self.nwait(epoch)
        last_err: DeadWorkerError | None = None
        for _ in range(max_rounds):
            try:
                asyncmap(
                    pool, B, self.backend,
                    nwait=pred, epoch=epoch, timeout=round_timeout,
                )
                last_err = None
                break
            except DeadWorkerError as e:
                # round timed out short of decodability: the next round
                # re-dispatches every idle worker with a fresh shard id
                # (incremental redundancy); stragglers stay in flight
                last_err = e
                if self.decodable(epoch):  # arrived during unwinding
                    # snapshot like pred does: without it _decode falls
                    # back to everything collected and the overhead
                    # statistic re-inflates on exactly the straggler
                    # traces it measures
                    with self._lock:
                        self._satisfied.setdefault(
                            epoch, sorted(self._collected.get(epoch, {}))
                        )
                    last_err = None
                    break
        if last_err is not None:
            raise last_err
        return self._decode(epoch)

    def _decode(self, epoch: int) -> np.ndarray:
        with self._lock:
            shards_map = dict(self._collected.get(epoch, {}))
            satisfied = self._satisfied.get(epoch)
        # decode exactly the prefix the predicate fired on (see nwait);
        # direct Backend-API users without a predicate fall back to
        # everything collected
        ids = (
            [s for s in satisfied if s in shards_map]
            if satisfied is not None
            else sorted(shards_map)
        )
        shards = np.stack([np.asarray(shards_map[s]) for s in ids])
        blocks = self.code.decode(shards, ids)
        self.stats = {
            "epoch": int(epoch),
            "shards_used": len(ids),
            "k": self.k,
            "overhead": len(ids) / self.k,
            "max_generation": max(s // self.n for s in ids) if ids else 0,
        }
        return blocks.reshape(-1, *blocks.shape[2:])
