"""Device-resident coordination: fused K-epoch pipelines that take the
host off the epoch hot path.

Every epoch of the host ``asyncmap`` loop (pool.py) re-enters the
interpreter: dispatch bookkeeping, arrival stamping, the decode
trigger — 2 + 3W host touches per epoch. With
transport zero-copy (round 12) and the decode batched (round 14) that
interpreter round-trip is the dominant per-epoch cost left — ROADMAP
item 4, the Amdahl item. This module inverts the control flow of the
core primitive, per PAPERS' numba-mpi frame (arxiv 2407.13712 —
coordination issued from inside JIT-compiled code, no interpreter on
the critical path):

* a :class:`DeviceCoordinator` compiles **K epochs** of the pool state
  machine into ONE program — a ``lax.scan`` over epochs (wrapped in
  ``jax.shard_map`` on a mesh) in which the per-shard **arrival
  masks**, the **fastest-``nwait`` selection**, and the **MDS / LT /
  hierarchical inner decode** all run on device;
* the host's role collapses to **stage + harvest**: it stages the
  payloads and the window's injected-delay schedule once per window,
  and harvests ``repochs`` history + decoded products every K epochs
  (2 host touches per window, 2/K per epoch amortized);
* the K-epoch harvest cadence is the latency/communication trade the
  map-shuffle-reduce straggler analysis (arxiv 1808.06583) prices —
  :func:`~..sim.tune.sweep_harvest_k` sweeps it on virtual time and
  refuses K that violates a staleness bound.

``repochs`` semantics are preserved **exactly**: the in-scan arrival
recurrence performs, step for step, the arithmetic the host loop
performs against a :class:`~..sim.backend.SimBackend` —

* epoch ``e`` opens at ``T`` (the previous completion time); in-flight
  arrivals ``<= T`` are drained stale (phase 1), every idle worker is
  dispatched at ``T`` (phase 2);
* each worker's *fresh-arrival candidate* is ``T + d[e, w]`` if it was
  just dispatched, else ``a_w + d[e, w]`` (its stale in-flight result
  lands at ``a_w`` and the worker is instantly re-tasked — the
  reference's phase-3 stale-harvest/re-task, src/MPIAsyncPools.jl:177-
  184);
* the epoch completes at the ``nwait``-th smallest candidate (or, for
  the hierarchical predicate, at the first sorted prefix whose arrived
  group set clears the outer floor); winners are stamped fresh,
  stale arrivals before completion are stamped with their dispatch
  epoch, and everyone else stays in flight **across the window
  boundary** — exactly as the host loop leaves them.

Because the recurrence uses the same floating-point operations on the
same absolute times, a fused window under ``jax_enable_x64`` produces
**bit-identical** ``repochs`` to the host loop on the same delay
schedule (pinned by tests/test_device_coord.py). Stale workers' shards
are masked by the on-device arrival mask exactly as the host loop
masks them: the per-epoch decode consumes only shards with
``repochs == epoch``, selected first-k in worker-index order
(``fresh_indices`` order).

Fidelity caveats (the :mod:`..sim` discipline — documented, not
silent):

* delays are **virtual seconds** staged up front (the injection
  mechanism of record, SURVEY §7); on real hardware a fused window has
  no per-worker arrival information *inside* the program, so
  production windows run ``nwait = n`` semantics with a zero schedule;
* with x64 disabled the staged times are float32 — ``repochs`` parity
  then holds for schedules whose arithmetic is f32-exact (zero/dyadic
  delays); generic floats can tie-break differently at ulp
  coincidences;
* exact ties between arrival times resolve by worker index here and by
  dispatch order in the host loop — measure-zero under continuous
  delay draws, and the parity tests use such schedules;
* ``timeout=``/``DeadWorkerError`` and ``tracer=`` are host-loop
  concerns a compiled window cannot express; ``flight=`` records
  harvest spans instead.
"""

from __future__ import annotations

import time
from typing import Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from ..backends.base import DelayFn
from ..ops.coding import _decode
from ..pool import AsyncPool

__all__ = ["DeviceCoordinator", "stage_delays"]


def stage_delays(
    delay_fn: DelayFn | None, n: int, epoch0: int, epochs: int
) -> np.ndarray:
    """Host-side staging of the window's injected-delay schedule: the
    (epochs, n) virtual round-trip each (epoch, worker) dispatch would
    pay — ``delay_fn(worker, epoch)`` clamped at 0 exactly like
    :class:`~..sim.backend.SimBackend` clamps it. ``None`` stages
    zeros (the production no-injection schedule)."""
    d = np.zeros((int(epochs), int(n)), dtype=np.float64)
    if delay_fn is not None:
        for j in range(int(epochs)):
            e = int(epoch0) + j
            for w in range(int(n)):
                d[j, w] = max(float(delay_fn(w, e)), 0.0)
    return d


class DeviceCoordinator:
    """Compiled K-epoch coordination for a coded-GEMM-style workload.

    Worker ``w`` owns coded block ``blocks[w]`` (an (n, r, d) stack);
    each epoch every worker computes ``blocks[w] @ payload`` and the
    on-device recurrence decides — from the staged delay schedule —
    which arrivals are fresh, which are stale-harvested and re-tasked,
    and when the epoch completes. The per-epoch decode consumes only
    the fresh mask:

    * ``decode="mds"`` — first-k fresh shards in index order, one
      ``k x k`` solve (the :func:`~..ops.coding._decode` arithmetic);
    * ``decode="lt"`` — masked normal equations over ALL fresh rows of
      the 0/1 generator (exact whenever the fresh set has full column
      rank; an integer ``nwait`` cannot promise peelability of every
      subset, so construct windows whose expected fresh sets decode —
      the host peeling path stays the arbiter for exotic sets);
    * ``decode="hierarchical"`` — the two-level rule: ALL groups'
      inner ``k_inner x k_inner`` MDS solves run as one vmapped batch
      (:func:`~..ops.hierarchical.decode_groups` — the round-14
      batched decode, embedded in the scan body), then the
      rate-(H-1)/H parity outer pass reconstructs at most one missing
      source group on device; completion is the first arrival prefix
      whose arrived-group set clears the outer floor (the
      :func:`~..ops.outer_code.hierarchical_nwait` decision, computed
      in-scan).

    ``mesh=`` (a 1-D pool mesh, one worker per device) runs the same
    program under ``jax.shard_map``: each device computes its own
    shard, the recurrence is evaluated replicated, and the decode is
    the masked weighted combine of parallel/collectives.py — one
    ``psum_scatter`` per epoch places source block j on device j, and
    the final epoch's blocks ride a ``ppermute`` ring all-gather back
    to every device for chained consumers. Flat (mds/lt single-
    program) and grouped decodes are the ``mesh=None`` path.

    ``backend=`` (an :class:`~..backends.xla.XLADeviceBackend`) routes
    window execution through the backend's multi-epoch dispatch
    (:meth:`~..backends.xla.XLADeviceBackend.submit_window`) so the
    failure envelope and shutdown guard stay in the transport layer.

    ``registry=`` / ``flight=`` follow the package opt-in contract
    (GC004; a dark coordinator pays only ``is None`` checks):
    ``devcoord_fused_epochs_total``, ``devcoord_harvests_total``, the
    harvest-latency histogram ``devcoord_harvest_seconds``, and the
    ``devcoord_epochs_per_harvest`` gauge.
    """

    def __init__(
        self,
        blocks,
        *,
        decode: str = "mds",
        G=None,
        k: int | None = None,
        groups: int | None = None,
        k_inner: int | None = None,
        inner_G=None,
        nwait: int | None = None,
        mesh: Mesh | None = None,
        axis: str = "w",
        delay_fn: DelayFn | None = None,
        precision=jax.lax.Precision.HIGHEST,
        backend=None,
        registry=None,
        flight=None,
    ):
        blocks = np.asarray(blocks)
        if blocks.ndim != 3:
            raise ValueError(
                f"blocks must be an (n, rows, d) stack, got {blocks.shape}"
            )
        self.n = int(blocks.shape[0])
        self.block_rows = int(blocks.shape[1])
        self.decode = str(decode)
        self.precision = precision
        self.delay_fn = delay_fn
        self._backend = backend
        self.mesh = mesh
        self.axis = axis
        n = self.n
        if self.decode in ("mds", "lt"):
            if G is None or k is None:
                raise ValueError(f"decode={decode!r} needs G and k")
            G = np.asarray(G)
            if G.shape[0] != n:
                raise ValueError(
                    f"G has {G.shape[0]} rows but the stack holds "
                    f"{n} worker blocks"
                )
            self.k = int(k)
            self.G = G
            if nwait is None:
                nwait = self.k
            if not (self.k <= int(nwait) <= n):
                raise ValueError(
                    f"nwait={nwait} must sit in [k={self.k}, n={n}]: "
                    "fewer than k fresh shards cannot decode, and a "
                    "compiled window cannot wait for more workers than "
                    "exist"
                )
            self.nwait = int(nwait)
            self._out_rows = self.k * self.block_rows
        elif self.decode == "hierarchical":
            if groups is None or k_inner is None or inner_G is None:
                raise ValueError(
                    "decode='hierarchical' needs groups, k_inner and "
                    "inner_G"
                )
            self.H = int(groups)
            if self.H < 2 or n % self.H != 0:
                raise ValueError(
                    f"{n} workers do not partition into {groups} "
                    "contiguous groups of >= 1 (parity outer needs "
                    "H >= 2)"
                )
            self.n_inner = n // self.H
            self.k_inner = int(k_inner)
            if not (0 < self.k_inner <= self.n_inner):
                raise ValueError(
                    f"need 0 < k_inner <= n_inner, got k_inner="
                    f"{k_inner}, n_inner={self.n_inner}"
                )
            self.L = self.H - 1  # rate-(H-1)/H parity outer
            inner_G = np.asarray(inner_G)
            if inner_G.shape[0] != self.n_inner:
                raise ValueError(
                    f"inner_G has {inner_G.shape[0]} rows but groups "
                    f"hold {self.n_inner} workers"
                )
            self.inner_G = inner_G
            if nwait is not None:
                raise ValueError(
                    "hierarchical windows complete on the two-level "
                    "predicate (inner floor per group, outer floor "
                    "across groups) — int nwait does not apply"
                )
            self.nwait = None
            self._out_rows = self.L * self.k_inner * self.block_rows
        else:
            raise ValueError(
                f"unknown decode {decode!r}; choose mds | lt | "
                "hierarchical"
            )
        if mesh is not None:
            if self.decode != "mds":
                raise ValueError(
                    "mesh windows implement the flat MDS psum_scatter "
                    f"decode; decode={decode!r} runs on the mesh=None "
                    "path"
                )
            if len(mesh.axis_names) != 1 or mesh.axis_names[0] != axis:
                raise ValueError(
                    f"device windows need a 1-D ({axis!r},) pool mesh, "
                    f"got {mesh.axis_names}"
                )
            if mesh.shape[axis] != n:
                raise ValueError(
                    f"mesh axis holds {mesh.shape[axis]} devices but "
                    f"the stack holds {n} worker blocks (one worker "
                    "per device)"
                )
        self._blocks_host = blocks
        if mesh is not None:
            # placed once: worker i's coded block lives on mesh device
            # i for every window this coordinator ever runs
            self._blocks = jax.device_put(
                jnp.asarray(blocks),
                jax.sharding.NamedSharding(mesh, P(axis)),
            )
        else:
            self._blocks = jnp.asarray(blocks)
        self._programs: dict = {}
        # cross-window continuation: the in-flight state the host loop
        # would keep in (pool.active, pool.sepochs, backend slots)
        self._carry = None
        self._carry_epoch: int | None = None
        self.last_decoded = None
        self.last_window: dict = {}
        self._m = None
        self._flight = flight
        if registry is not None:
            self._m = {
                "epochs": registry.counter(
                    "devcoord_fused_epochs_total",
                    help="epochs coordinated inside fused device "
                         "windows (no host touch)",
                ),
                "harvests": registry.counter(
                    "devcoord_harvests_total",
                    help="K-epoch windows staged and harvested by the "
                         "host",
                ),
                "harvest_s": registry.histogram(
                    "devcoord_harvest_seconds",
                    help="host wall per stage+run+harvest round trip",
                ),
                "k": registry.gauge(
                    "devcoord_epochs_per_harvest",
                    help="K of the most recent fused window",
                ),
            }

    # -- factories --------------------------------------------------------
    @classmethod
    def for_coded_gemm(cls, cg, *, delay_fn=None, nwait=None, **kw):
        """A coordinator sharing an existing
        :class:`~..ops.coded_gemm.CodedGemm`'s coded blocks and MDS
        generator (and, unless overridden, its backend for window
        submission)."""
        kw.setdefault("backend", cg.backend)
        return cls(
            np.stack([np.asarray(b) for b in cg.blocks]),
            decode="mds", G=cg.code.G, k=cg.k, nwait=nwait,
            delay_fn=delay_fn, precision=cg.precision, **kw,
        )

    @classmethod
    def for_lt_gemm(cls, ltg, *, delay_fn=None, nwait=None, **kw):
        """A coordinator for an :class:`~..ops.coded_gemm.LTCodedGemm`
        window: the 0/1 generator rows of its fixed shard window,
        decoded by masked normal equations."""
        kw.setdefault("backend", ltg.backend)
        return cls(
            np.stack([np.asarray(b) for b in ltg.blocks]),
            decode="lt",
            G=ltg.code.generator_rows(ltg.shard_ids),
            k=ltg.k, nwait=ltg.n if nwait is None else nwait,
            delay_fn=delay_fn, precision=ltg.precision, **kw,
        )

    @classmethod
    def for_hierarchical(cls, hg, *, delay_fn=None, **kw):
        """A coordinator for a :class:`~..ops.hierarchical.
        HierarchicalCodedGemm` fleet — MDS inner + parity outer only
        (the deployment default): the vmapped inner decode runs inside
        the scan body and the outer reconstruction is the on-device
        subtraction chain."""
        if hg.inner != "mds" or hg.outer.kind != "parity":
            raise ValueError(
                "device windows fuse the MDS-inner + parity-outer "
                f"construction; got inner={hg.inner!r} outer="
                f"{hg.outer.kind!r} (run those through the host loop)"
            )
        for g, members in enumerate(hg.group_indices):
            expect = np.arange(
                g * hg.n_inner, (g + 1) * hg.n_inner, dtype=np.int64
            )
            if not np.array_equal(np.asarray(members), expect):
                raise ValueError(
                    "device windows need the contiguous group layout "
                    f"(group {g} holds {list(members)})"
                )
        if hg.backend is not None:
            kw.setdefault("backend", hg.backend)
        return cls(
            np.stack([np.asarray(b) for b in hg.blocks]),
            decode="hierarchical", groups=hg.H, k_inner=hg.k_inner,
            inner_G=hg._inner_G, delay_fn=delay_fn,
            precision=hg.precision, **kw,
        )

    # -- the compiled window ----------------------------------------------
    def _completion_j(self, ranks):
        """Index (into the sorted candidate order) of the arrival that
        completes the epoch. Integer ``nwait`` is a static rank; the
        hierarchical rule evaluates the two-level predicate over every
        sorted prefix and takes the first satisfying one (always
        satisfiable: all n arrived clears both floors by
        construction)."""
        if self.nwait is not None:
            return self.nwait - 1
        n = self.n
        r_grid = jnp.arange(n, dtype=jnp.int32)[:, None, None]
        member_ranks = ranks.reshape(1, self.H, self.n_inner)
        cnt = jnp.sum(member_ranks <= r_grid, axis=-1)  # (n, H)
        done = jnp.sum(cnt >= self.k_inner, axis=-1) >= self.L
        return jnp.argmax(done)

    def _decode_fresh(self, shards, fresh):
        """The per-epoch decode over the on-device arrival mask —
        stale shards never enter (the host loop's ``fresh_indices``
        discipline, selection order included)."""
        if self.decode == "mds":
            sel = jnp.argsort(
                jnp.where(fresh, 0, 1), stable=True
            )[: self.k]
            G_S = jnp.asarray(self.G)[sel]
            blocks = _decode(G_S, shards[sel], self.precision)
            return blocks.reshape(-1, *blocks.shape[2:])
        if self.decode == "lt":
            Gd = jnp.asarray(self.G, dtype=shards.dtype)
            Gm = Gd * fresh.astype(shards.dtype)[:, None]  # (n, k)
            A_n = jnp.einsum(
                "nk,nj->kj", Gm, Gm, precision=jax.lax.Precision.HIGHEST
            )
            rhs = jnp.einsum(
                "nk,nrc->krc", Gm, shards,
                precision=jax.lax.Precision.HIGHEST,
            )
            blocks = _decode(A_n, rhs, self.precision)
            return blocks.reshape(-1, *blocks.shape[2:])
        # hierarchical: vmapped inner solves (ops/hierarchical.py's
        # round-14 batched decode) + the parity outer pass
        from ..ops.hierarchical import decode_groups

        H, ni, ki, L = self.H, self.n_inner, self.k_inner, self.L
        gmask = fresh.reshape(H, ni)
        sel = jnp.argsort(
            jnp.where(gmask, 0, 1), axis=-1, stable=True
        )[:, :ki]  # (H, ki) local first-k_inner fresh per group
        G_S = jnp.asarray(self.inner_G)[sel]  # (H, ki, ki)
        gsh = jnp.take_along_axis(
            shards.reshape(H, ni, *shards.shape[1:]),
            sel[:, :, None, None], axis=1,
        )  # (H, ki, r, c)
        blocks = decode_groups(G_S, gsh)  # (H, ki, r, c)
        gflat = blocks.reshape(H, ki * self.block_rows, -1)
        arrived = jnp.sum(gmask, axis=-1) >= ki  # (H,)
        srcs, parity = gflat[:L], gflat[L]
        total = jnp.sum(srcs, axis=0)
        recon = parity[None] - (total[None] - srcs)
        out = jnp.where(arrived[:L, None, None], srcs, recon)
        return out.reshape(L * ki * self.block_rows, -1)

    def _epoch_body(self, payload_static):
        """The scan body: ONE epoch of the pool state machine, no host.
        ``carry = (active, dspe, arr, rep, T)`` — the in-flight state
        the host keeps in (pool.active, pool.sepochs, backend arrival
        slots, pool.repochs, the clock)."""

        def body(carry, xs):
            active, dspe, arr, rep, T = carry
            if payload_static is None:
                d_e, e, payload = xs
            else:
                d_e, e = xs
                payload = payload_static
            shards = jnp.einsum(
                "nrd,dc->nrc", self._blocks, payload,
                precision=self.precision,
            )
            # phase 1: drain arrivals at or before the epoch opening
            drain = active & (arr <= T)
            rep = jnp.where(drain, dspe, rep)
            # phase 2: dispatch every idle worker at T
            newly = (~active) | drain
            cand = jnp.where(newly, T + d_e, arr + d_e)
            order = jnp.argsort(cand, stable=True)
            ranks = jnp.zeros(self.n, dtype=jnp.int32).at[order].set(
                jnp.arange(self.n, dtype=jnp.int32)
            )
            j_star = self._completion_j(ranks)
            T_next = cand[order[j_star]]
            winners = ranks <= j_star
            # phase 3: stale harvests before completion re-task; fresh
            # winners stamp the current epoch (overriding any stale
            # stamp their own re-task produced en route)
            stale_hit = active & (~drain) & (arr <= T_next) & (~winners)
            rep = jnp.where(stale_hit, dspe, rep)
            rep = jnp.where(winners, e, rep)
            dispatched = newly | (active & (~drain) & (arr <= T_next))
            dspe = jnp.where(dispatched, e, dspe)
            arr = jnp.where(dispatched, cand, arr)
            active = ~winners
            decoded = self._decode_fresh(shards, winners)
            return (
                (active, dspe, arr, rep, T_next),
                (rep, decoded, T_next),
            )

        return body

    def _flat_program(self, epochs: int, per_epoch_payload: bool):
        def program(payload, delays, e_arr, active, dspe, arr, rep, T):
            if per_epoch_payload:
                body = self._epoch_body(None)
                xs = (delays, e_arr, payload)
                shards_last = jnp.einsum(
                    "nrd,dc->nrc", self._blocks, payload[-1],
                    precision=self.precision,
                )
            else:
                body = self._epoch_body(payload)
                xs = (delays, e_arr)
                shards_last = jnp.einsum(
                    "nrd,dc->nrc", self._blocks, payload,
                    precision=self.precision,
                )
            carry, ys = jax.lax.scan(
                body, (active, dspe, arr, rep, T), xs, length=epochs
            )
            return carry, ys, shards_last

        return jax.jit(program)

    def _mesh_program(self, epochs: int, per_epoch_payload: bool):
        """The shard_map window: worker shards stay on their own
        devices, the recurrence runs replicated, the decode is one
        masked-weight ``psum_scatter`` per epoch (block j lands on
        device j, blocks >= k zero — parallel/collectives.py layout),
        and the final epoch's blocks return to every device over the
        ``ppermute`` ring."""
        n, k = self.n, self.k
        axis = self.axis
        Gh = self.G

        def window(block, payload, delays, e_arr, active, dspe, arr,
                   rep, T):
            # block: (1, r, d) this device's coded shard
            Gd = jnp.asarray(Gh)

            def body(carry, xs):
                active, dspe, arr, rep, T = carry
                if per_epoch_payload:
                    d_e, e, payload_e = xs
                else:
                    d_e, e = xs
                    payload_e = payload
                shard = jnp.einsum(
                    "rd,dc->rc", block[0], payload_e,
                    precision=self.precision,
                )
                drain = active & (arr <= T)
                rep = jnp.where(drain, dspe, rep)
                newly = (~active) | drain
                cand = jnp.where(newly, T + d_e, arr + d_e)
                order = jnp.argsort(cand, stable=True)
                ranks = jnp.zeros(n, dtype=jnp.int32).at[order].set(
                    jnp.arange(n, dtype=jnp.int32)
                )
                j_star = self.nwait - 1
                T_next = cand[order[j_star]]
                winners = ranks <= j_star
                stale_hit = (
                    active & (~drain) & (arr <= T_next) & (~winners)
                )
                rep = jnp.where(stale_hit, dspe, rep)
                rep = jnp.where(winners, e, rep)
                dispatched = newly | (
                    active & (~drain) & (arr <= T_next)
                )
                dspe = jnp.where(dispatched, e, dspe)
                arr = jnp.where(dispatched, cand, arr)
                active = ~winners
                # masked decode weights: rows j < k of W carry the
                # k x k inverse over the first-k fresh columns
                sel = jnp.argsort(
                    jnp.where(winners, 0, 1), stable=True
                )[:k]
                inv = jnp.linalg.inv(
                    Gd[sel].astype(shard.dtype)
                )
                W = jnp.zeros((n, n), dtype=shard.dtype)
                W = W.at[
                    jnp.arange(k)[:, None], sel[None, :]
                ].set(inv)
                me = jax.lax.axis_index(axis)
                contrib = W[:, me][:, None, None] * shard[None]
                dec = jax.lax.psum_scatter(
                    contrib, axis, scatter_dimension=0, tiled=True
                )  # (1, r, c): source block `me` of this epoch
                return (
                    (active, dspe, arr, rep, T_next),
                    (rep, dec, T_next),
                )

            if per_epoch_payload:
                xs = (delays, e_arr, payload)
                last_payload = payload[-1]
            else:
                xs = (delays, e_arr)
                last_payload = payload
            carry, (rep_hist, dec_hist, t_hist) = jax.lax.scan(
                body, (active, dspe, arr, rep, T), xs, length=epochs
            )
            shard_last = jnp.einsum(
                "rd,dc->rc", block[0], last_payload,
                precision=self.precision,
            )[None]
            # ppermute ring all-gather of the final decoded blocks —
            # every device leaves the window holding the full product
            # (chained consumers never touch the host)
            final = dec_hist[-1]  # (1, r, c) local source block
            perm = [(i, (i + 1) % n) for i in range(n)]
            me = jax.lax.axis_index(axis)
            out0 = jnp.zeros((n,) + final.shape[1:], final.dtype)
            out0 = jax.lax.dynamic_update_index_in_dim(
                out0, final[0], me, 0
            )

            def ring_step(c, _):
                recv, out, src = c
                nxt = jax.lax.ppermute(recv, axis, perm)
                src = (src - 1) % n
                out = jax.lax.dynamic_update_index_in_dim(
                    out, nxt, src, 0
                )
                return (nxt, out, src), None

            (_, gathered, _), _ = jax.lax.scan(
                ring_step, (final[0], out0, me), None, length=n - 1
            )
            last_full = gathered[:k].reshape(
                (1, k * final.shape[1]) + final.shape[2:]
            )
            return carry, rep_hist, dec_hist, t_hist, shard_last, \
                last_full

        pspec = P(None) if per_epoch_payload else P()
        f = jax.shard_map(
            window,
            mesh=self.mesh,
            in_specs=(P(axis), pspec, P(), P(), P(), P(), P(), P(),
                      P()),
            out_specs=(
                (P(), P(), P(), P(), P()),  # carry: replicated
                P(),                         # rep_hist
                P(None, axis),               # dec_hist: block j on dev j
                P(),                         # t_hist
                P(axis),                     # shards_last
                P(axis),                     # last_full (n copies)
            ),
        )
        return jax.jit(f)

    def _program(self, epochs: int, per_epoch_payload: bool):
        key = (int(epochs), bool(per_epoch_payload))
        prog = self._programs.get(key)
        if prog is None:
            if self.mesh is None:
                prog = self._flat_program(*key)
            else:
                prog = self._mesh_program(*key)
            self._programs[key] = prog
        return prog

    # -- host surface: stage + harvest ------------------------------------
    def reset(self) -> None:
        """Forget cross-window in-flight state (the elastic-recovery
        analog of :meth:`~..pool.AsyncPool.reset_worker`: a dropped
        window's dispatches can never complete)."""
        self._carry = None
        self._carry_epoch = None

    def _initial_carry(self, pool: AsyncPool):
        if (
            self._carry is not None
            and self._carry_epoch == int(pool.epoch)
        ):
            # back-to-back windows — but only if the pool still shows
            # THIS coordinator's end state (interleaving a second
            # coordinator or hand-editing the pool would silently
            # desynchronize the in-flight bookkeeping)
            if not (
                np.array_equal(np.asarray(self._carry[0]), pool.active)
                and np.array_equal(
                    np.asarray(self._carry[1]), pool.sepochs
                )
            ):
                raise ValueError(
                    "pool state diverged from this coordinator's "
                    "in-flight carry (another coordinator or manual "
                    "edits touched the pool mid-sequence); reset() "
                    "the coordinator and quiesce the pool first"
                )
            return self._carry
        if pool.active.any():
            raise ValueError(
                "pool has in-flight host-loop work; a fused window "
                "needs a quiescent pool (waitall first) or "
                "back-to-back fused windows on one coordinator"
            )
        zero = np.zeros(self.n, dtype=np.float64)
        return (
            jnp.asarray(np.zeros(self.n, dtype=bool)),
            jnp.asarray(pool.sepochs),
            jnp.asarray(zero),
            jnp.asarray(pool.repochs),
            jnp.asarray(np.float64(0.0)),
        )

    def run_window(
        self,
        pool: AsyncPool,
        sendbuf,
        *,
        epochs: int,
        store_results: bool = True,
    ) -> np.ndarray:
        """Stage + run + harvest one fused K-epoch window (host touch
        count: 2). Returns the (epochs, n) ``repochs`` HISTORY — row
        ``j`` is exactly what the host loop's epoch ``epoch0 + j``
        ``asyncmap`` call would have returned — and leaves the pool in
        the state the host loop would have left it in (``epoch``,
        ``repochs``, ``sepochs``, ``active``; workers still in flight
        at the window edge stay in flight for the next window).
        Decoded per-epoch products land in :attr:`last_decoded`
        (epochs-leading), window diagnostics in :attr:`last_window`.

        ``sendbuf``: one (d, cols) payload broadcast to every epoch of
        the window (the host loop's per-epoch broadcast of one stable
        buffer), or an (epochs, d, cols) stack staging per-epoch
        payloads up front.
        """
        epochs = int(epochs)
        if epochs < 1:
            raise ValueError(f"epochs must be >= 1, got {epochs}")
        if pool.n_workers != self.n:
            raise ValueError(
                f"pool has {pool.n_workers} workers but this window "
                f"is laid out for {self.n}"
            )
        t0 = time.perf_counter()
        epoch0 = int(pool.epoch) + 1
        payload = np.asarray(sendbuf)
        per_epoch = payload.ndim == 3
        if per_epoch and payload.shape[0] != epochs:
            raise ValueError(
                f"staged payloads carry {payload.shape[0]} epochs but "
                f"the window runs {epochs}"
            )
        delays = stage_delays(self.delay_fn, self.n, epoch0, epochs)
        e_arr = np.arange(epoch0, epoch0 + epochs, dtype=np.int64)
        carry = self._initial_carry(pool)
        prog = self._program(epochs, per_epoch)
        args = (
            jnp.asarray(payload), jnp.asarray(delays),
            jnp.asarray(e_arr), *carry,
        )
        if self.mesh is not None:
            args = (self._blocks,) + args
        if self._backend is not None:
            handle = self._backend.submit_window(
                prog, *args, epoch0=epoch0, epochs=epochs
            )
            outs = handle.harvest()
        else:
            outs = jax.block_until_ready(prog(*args))
        if self.mesh is None:
            carry_out, (rep_hist, dec_hist, t_hist), shards_last = outs
            last_full = None
        else:
            carry_out, rep_hist, dec_hist, t_hist, shards_last, \
                last_full = outs
        self._carry = carry_out
        self._carry_epoch = epoch0 + epochs - 1
        rep_np = np.asarray(rep_hist, dtype=np.int64)
        # harvest: the pool leaves the window exactly where the host
        # loop would have left it
        pool.epoch = epoch0 + epochs - 1
        pool.repochs[:] = rep_np[-1]
        pool.sepochs[:] = np.asarray(carry_out[1], dtype=np.int64)
        pool.active[:] = np.asarray(carry_out[0])
        if store_results:
            fresh_last = rep_np[-1] == pool.epoch
            sh = np.asarray(shards_last)
            for i in np.flatnonzero(fresh_last):
                pool.results[int(i)] = sh[int(i)]
        self.last_decoded = dec_hist
        self.last_window = {
            "epochs": epochs,
            "epoch0": epoch0,
            "virtual_s": float(
                np.asarray(t_hist)[-1] - np.asarray(carry[4])
            ),
            "epoch_ends": np.asarray(t_hist),
            "last_full": None if last_full is None
            else last_full[0],
        }
        dt = time.perf_counter() - t0
        if self._m is not None:
            self._m["epochs"].inc(epochs)
            self._m["harvests"].inc()
            self._m["harvest_s"].observe(dt)
            self._m["k"].set(epochs)
        if self._flight is not None:
            self._flight.span(
                f"devcoord window {epoch0}+{epochs}",
                t0, dt, track="devcoord",
                epochs=epochs, epoch0=epoch0,
            )
        return rep_np

    def full(self, decoded) -> np.ndarray:
        """Host gather of one epoch's decoded product -> (rows, cols):
        flat windows already emit the stacked source rows; mesh
        windows emit the collectives layout (n, r, c) with blocks
        >= k zero."""
        out = np.asarray(decoded)
        if self.mesh is not None and out.ndim == 3:
            return out[: self.k].reshape(-1, out.shape[-1])
        return out
