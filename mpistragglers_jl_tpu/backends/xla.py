"""XLA device backend: pool workers are accelerator devices.

This is the TPU-native replacement for the reference's transport layer
(MPI.jl point-to-point over OS processes — SURVEY §2 component C8). The
mapping, per SURVEY §7 "the hard parts":

=====================  ==================================================
reference (MPI)         here (JAX/XLA)
=====================  ==================================================
worker process          an accelerator device (TPU chip / virtual CPU
                        device); several pool workers may time-slice one
                        device when the pool is larger than the slice
``MPI.Isend``           ``jax.device_put`` of the payload onto the
                        worker's device — an asynchronous H2D DMA whose
                        result is an *immutable* snapshot, so the
                        reference's ``isendbuf`` copy discipline
                        (src/MPIAsyncPools.jl:63-66,:130) is free
compute on worker       a jitted per-shard program dispatched on the
                        worker's device; XLA's async dispatch returns a
                        future-like ``jax.Array`` immediately
``MPI.Waitany!``        per-worker dispatcher threads block on
                        ``Array.block_until_ready`` and signal the shared
                        completion condition (backends/base.py), so the
                        coordinator's hot loop sleeps instead of spinning
=====================  ==================================================

Crucially there is **no collective in the straggle-exposed path**: each
worker's program is independent, so a slow or dead device delays nobody
else — a single ``pjit`` with a ``psum`` would re-introduce the very
bulk-synchronous straggler penalty this design exists to kill (SURVEY §7).
Collectives belong in the decode/combine step over the k winners (see
parallel/collectives.py).

Results are left device-resident; the decode/combine step can consume
them without a host round-trip (``pool.results[i]``), and only a caller-
provided ``recvbuf`` forces a D2H gather.
"""

from __future__ import annotations

from typing import Callable, Sequence

import jax

from .base import MailboxBackend, DelayFn


class _BatchDone:
    """A fused-dispatch group handed to a device's dispatcher thread."""

    __slots__ = ("items", "stacked")

    def __init__(self, items, stacked):
        self.items = items      # [(worker, seq, payload, epoch, tag)]
        self.stacked = stacked  # enqueued fused result, leading = member


class StackedSlice:
    """A pool worker's lazy view into a fused-dispatch result.

    In batch mode one device program computes every member's result
    stacked on the leading axis; slicing each member out eagerly would
    cost one device op per worker. Decode paths that consume the
    whole stack (ops/coded_gemm.py) read ``stacked`` + ``index``
    directly and never pay for slices; anything else (``recvbuf``
    bitcopies, generic callers) materializes transparently via
    ``__array__``/``materialize``."""

    __slots__ = ("stacked", "index")

    def __init__(self, stacked, index: int):
        self.stacked = stacked
        self.index = int(index)

    @property
    def nbytes(self) -> int:  # pool pre-dispatch recvbuf validation
        import numpy as _np

        shape = self.stacked.shape[1:]
        return int(_np.prod(shape)) * self.stacked.dtype.itemsize

    @property
    def shape(self) -> tuple:
        """The member result's shape (one row of the stack) — lets
        shape-driven consumers (the fused adopter) treat slices like
        the arrays they stand for."""
        return tuple(self.stacked.shape[1:])

    @property
    def dtype(self):
        return self.stacked.dtype

    def materialize(self):
        return self.stacked[self.index]

    def __array__(self, dtype=None, copy=None):
        import numpy as _np

        out = _np.asarray(self.materialize())
        if dtype is not None:
            out = out.astype(dtype, copy=False)
        return out

class WindowHandle:
    """One asynchronously-dispatched fused multi-epoch program.

    ``outputs`` is the program's raw pytree of future-like
    ``jax.Array``s (XLA async dispatch); :meth:`harvest` is the single
    consumption fence for the whole K-epoch window — device-side
    failures surface there, not as per-worker completions."""

    __slots__ = ("outputs", "epoch0", "epochs")

    def __init__(self, outputs, epoch0: int, epochs: int):
        self.outputs = outputs
        self.epoch0 = int(epoch0)
        self.epochs = int(epochs)

    def harvest(self):
        return jax.block_until_ready(self.outputs)


# work_fn(worker_index, device_payload, epoch) -> jax.Array (device-resident)
XLAWorkFn = Callable[[int, jax.Array, int], jax.Array]


class XLADeviceBackend(MailboxBackend):
    """n pool workers executing jitted programs on accelerator devices.

    Parameters
    ----------
    work_fn:
        ``work_fn(worker_index, payload, epoch) -> jax.Array``. Called in
        the worker's dispatcher thread with the payload already resident
        on the worker's device. It should be (or call) a jitted function;
        it may close over per-worker device-resident operands (e.g. a
        matrix shard placed at setup time). ``epoch`` is a Python int;
        pass it into jitted code as an array to avoid retracing.
    n_workers:
        Pool size. May exceed the device count (workers then time-slice
        devices round-robin — the single-real-chip case).
    devices:
        Devices to map workers onto; defaults to ``jax.devices()``.
    delay_fn:
        Deterministic straggler injection, seconds of host-side stall
        before dispatch as a function of ``(worker, epoch)``. On a real
        TPU slice stragglers are rare (SURVEY §7), so injection is the
        test mechanism of record.
    """

    def __init__(
        self,
        work_fn: XLAWorkFn,
        n_workers: int,
        *,
        devices: Sequence[jax.Device] | None = None,
        delay_fn: DelayFn | None = None,
        batch_fn=None,
        batch_arrival: str = "ready",
    ):
        """``batch_fn(worker_ids, payload, epoch) -> stacked`` (optional):
        coalesced dispatch. When pool workers share a device (the
        single-chip case; on a real slice each worker owns a chip), the
        per-worker programs of one epoch are submitted as ONE fused
        device program: dispatches buffer until the pool's
        :meth:`flush`, which calls ``batch_fn`` once per device with
        that device's worker ids and slices the stacked result back
        into per-worker completions. This removes the per-worker
        dispatch round-trip — the dominant epoch cost when one chip
        hosts many workers. Incompatible with ``delay_fn`` (per-worker
        injected stalls are meaningless inside one fused program)."""
        if batch_fn is not None and delay_fn is not None:
            raise ValueError(
                "batch_fn coalesces a device's workers into one program; "
                "per-worker delay_fn injection cannot apply inside it"
            )
        if batch_arrival not in ("ready", "enqueue"):
            raise ValueError(
                f"batch_arrival must be 'ready'|'enqueue', got {batch_arrival!r}"
            )
        # "ready": a dispatcher thread block_until_ready()s the fused
        # result — arrival means the device finished (true straggler
        # detection; the default). "enqueue": completions post as soon
        # as the fused program is submitted — XLA's async dispatch IS
        # the execution model, successive epochs pipeline on the device,
        # and the caller's consumption fence is the materialization
        # point. Enqueue mode is the single-chip throughput mode: with
        # every pool worker time-slicing one device there is no
        # independent-arrival information to detect anyway, and a
        # per-epoch host sync costs a full host<->device round trip.
        # Device-side failures then surface at the consumption fence,
        # not as per-worker WorkerFailure.
        self.batch_arrival = batch_arrival
        self.batch_fn = batch_fn
        self._pending: list = []  # buffered dispatches awaiting flush()
        if devices is None:
            devices = jax.devices()
        self.devices = [devices[i % len(devices)] for i in range(n_workers)]
        self.work_fn = work_fn
        # (device, epoch) -> device-resident payload. asyncmap broadcasts
        # ONE stable sendbuf to all idle workers per epoch (reference
        # src/MPIAsyncPools.jl:118-139), so workers sharing a device share
        # one H2D transfer; keyed by epoch so direct Backend-API users
        # dispatching fresh payloads at new epochs never see stale data.
        self._payload_cache: dict = {}
        self._cache_armed = False
        super().__init__(
            n_workers, delay_fn=delay_fn, join_timeout=5.0,
            thread_name="xla-worker",
        )

    def _snapshot(self, i: int, sendbuf, epoch: int) -> jax.Array:
        # Asynchronous H2D (or D2D) transfer onto the worker's device.
        # jax arrays are immutable, so this IS the payload snapshot: the
        # caller may mutate a numpy sendbuf immediately after dispatch.
        # The per-device cache is armed only between begin_epoch and
        # end_epoch (inside asyncmap, where the single-threaded
        # coordinator cannot mutate sendbuf mid-call); direct
        # Backend-API dispatches always re-snapshot, same contract as
        # the native backend.
        dev = self.devices[i]
        if not self._cache_armed:
            return jax.device_put(sendbuf, dev)
        key = (dev, epoch)
        payload = self._payload_cache.get(key)
        if payload is None:
            payload = jax.device_put(sendbuf, dev)
            self._payload_cache[key] = payload
        return payload

    def _compute(self, i: int, payload: jax.Array, epoch: int) -> jax.Array:
        result = self.work_fn(i, payload, epoch)
        # wait for the device computation to actually finish — this
        # thread *is* the arrival detector; block_until_ready releases
        # the GIL so n workers wait concurrently
        return jax.block_until_ready(result)

    # -- coalesced dispatch (batch_fn mode) -------------------------------
    def _start(self, i: int, sendbuf, epoch: int, seq: int, tag: int) -> None:
        if self.batch_fn is None:
            super()._start(i, sendbuf, epoch, seq, tag)
            return
        if self._closed:
            raise RuntimeError("backend has been shut down")
        payload = self._snapshot(i, sendbuf, epoch)
        self._pending.append((i, seq, payload, epoch, tag))

    def test(self, i: int, *, tag: int = 0):
        self.flush()  # a phase-3 re-task may be sitting in the buffer
        return super().test(i, tag=tag)

    def wait_any(self, indices, timeout=None, *, tags=None):
        self.flush()
        return super().wait_any(indices, timeout, tags=tags)

    def wait(self, i: int, timeout: float | None = None, *, tag: int = 0):
        self.flush()
        return super().wait(i, timeout, tag=tag)

    def flush(self) -> None:
        if self.batch_fn is None or not self._pending:
            return
        pending, self._pending = self._pending, []
        # one fused program per (device, payload, epoch): members of a
        # group MUST share the payload snapshot and epoch — direct
        # Backend-API users may dispatch distinct payloads back-to-back
        # (asyncmap's broadcast shares one snapshot per device, so the
        # epoch path stays a single group per device)
        groups: dict = {}
        for item in pending:
            key = (self.devices[item[0]], id(item[2]), item[3])
            groups.setdefault(key, []).append(item)
        for dev_items in groups.values():
            ids = tuple(item[0] for item in dev_items)
            _, _, payload, epoch, _ = dev_items[0]
            try:
                # enqueue is asynchronous; the fused program computes
                # every member's result stacked on the leading axis
                stacked = self.batch_fn(ids, payload, epoch)
            except BaseException as e:
                # a failed submission must not strand the group's slots
                # outstanding (waitall would hang forever) — fail every
                # member the way the worker loop does
                from .base import WorkerError

                for w, seq, _, _ep, tag in dev_items:
                    self._complete(w, seq, WorkerError(w, epoch, e), tag)
                continue
            if self.batch_arrival == "enqueue":
                # async-dispatch mode: submitted = arrived; the fused
                # result is a future the consumption fence materializes
                for j, (w, seq, _, _ep, tag) in enumerate(dev_items):
                    self._complete(w, seq, StackedSlice(stacked, j), tag)
                continue
            # the device's dispatcher thread becomes the arrival
            # detector for the whole group: one block_until_ready, then
            # per-member completions with their slice of the stack
            mbox_i = dev_items[0][0]
            self._mailboxes[mbox_i].put(
                (_BatchDone(dev_items, stacked), None, None, None)
            )

    def _worker_loop(self, i: int) -> None:  # overrides MailboxBackend
        if self.batch_fn is None:
            super()._worker_loop(i)
            return
        from .base import _SHUTDOWN, WorkerError

        mbox = self._mailboxes[i]
        while True:
            msg = mbox.get()
            if msg is _SHUTDOWN:
                return
            batch = msg[0]
            try:
                stacked = jax.block_until_ready(batch.stacked)
                for j, (w, seq, _, epoch, tag) in enumerate(
                    batch.items
                ):
                    self._complete(w, seq, StackedSlice(stacked, j), tag)
            except BaseException as e:  # surfaced on harvest, not lost
                for w, seq, _, epoch, tag in batch.items:
                    self._complete(w, seq, WorkerError(w, epoch, e), tag)

    # -- multi-epoch dispatch (fused K-epoch windows) ---------------------
    def submit_window(self, window_fn, *args, epoch0: int, epochs: int):
        """Multi-epoch dispatch: ONE asynchronous submission covering
        ``epochs`` epochs — the compiled K-epoch coordination program
        (parallel/device_coord.py) — with no per-epoch ``_start`` /
        mailbox round-trips and no dispatcher-thread arrival
        detection: XLA's async dispatch IS the in-flight state, and
        the returned :class:`WindowHandle`'s ``harvest()`` is the one
        fence. The transport layer keeps what it owns — the shutdown
        guard, and the failure envelope: a submission failure raises
        through :class:`~.base.WorkerError` (worker ``-1``: a fused
        window has no single owning worker) so callers see the same
        :class:`~.base.WorkerFailure` surface as per-epoch dispatch.
        """
        if self._closed:
            raise RuntimeError("backend has been shut down")
        from .base import WorkerError

        try:
            out = window_fn(*args)  # asynchronous: returns futures
        except BaseException as e:
            WorkerError(-1, int(epoch0), e).raise_()
        return WindowHandle(out, int(epoch0), int(epochs))

    def begin_epoch(self, epoch: int) -> None:
        # arm the shared-payload cache for this asyncmap call
        self._payload_cache = {}
        self._cache_armed = True

    def end_epoch(self) -> None:
        # disarm when asyncmap returns: any later direct dispatch of a
        # mutated host buffer must get a fresh device snapshot (same
        # contract as the native backend; base.py end_epoch). Clearing
        # also drops the device payload so it isn't pinned between calls.
        self._payload_cache = {}
        self._cache_armed = False
