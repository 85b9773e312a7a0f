"""Hierarchical two-level coded GEMM: XOR/LT across hosts, MDS within.

The flat :class:`~.coded_gemm.CodedGemm` pays a full Reed–Solomon-style
solve over the whole fleet and its resilience unit is a single slow
*chip*: with (n, k) over H hosts of ``n_inner`` chips each, surviving a
whole-host failure forces ``k <= (H-1) * n_inner`` — and once a host is
down the decoder needs EVERY surviving chip, so one laggard anywhere
stalls the epoch, and the decode solves a ``k x k`` system with
``k ~ (H-1) * n_inner``. The two-level construction (ROADMAP item 3;
arxiv 1904.11563's Array BP-XOR hierarchy, priced against the
map-shuffle-reduce latency–communication trade-off of arxiv 1808.06583)
fixes both at once:

* **inner**: each host group runs the existing (n_inner, k_inner) MDS
  code (or a fixed-window LT code) over its chip mesh — per-chip
  straggler slack *within every host*;
* **outer**: a cheap sum-parity / LT code (``ops/outer_code.py``, the
  generator machinery :mod:`.rateless` draws from) striped ACROSS the
  H groups — any lost group is reconstructed from the survivors by 0/1
  subtraction chains, O(n) per element, never a solve.

Decode cost drops from one ``O(((H-1) n_inner)^3)`` solve + its
``O(k^2)``-per-row apply to ``L`` small ``O(k_inner^3)`` solves plus an
O(n) outer pass, and the epoch
returns the moment ``L`` groups each clear their *inner* floor — a
straggling or dead host is simply never waited on.

The pool wiring is the reference's functional-``nwait`` mechanism,
nothing new: :func:`~.outer_code.hierarchical_nwait` evaluates the
two-level completion rule over the live ``repochs`` after every
arrival, so ``asyncmap(pool, B, backend, nwait=hg.nwait)`` is the whole
coordinator loop. Fleet partitions come from
:func:`~..parallel.multihost.host_groups` on a real multi-host mesh
(inner code on ICI, outer stripe across DCN) or an even split in
single-host / simulated runs.
"""

from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..backends.base import DelayFn
from ..backends.xla import XLADeviceBackend
from ..pool import AsyncPool
from .coding import MDSCode, _decode
from .gemm import _block_matmul
from .lt import LTCode
from .outer_code import hierarchical_nwait, make_outer, partition_groups

__all__ = ["HierarchicalCodedGemm", "decode_groups"]


@jax.jit
def _decode_groups(G_S, shards):
    """ALL used groups' inner MDS decodes as ONE program: a vmapped
    batch of small ``k_inner x k_inner`` solves. One decode per group
    (the first cut) paid per-call dispatch overhead L times over —
    measured 0.84x the flat decode at the bench shape; batched, the
    decode does its ``L * O(k_inner^3)`` work in a single dispatch and
    the >= 2x decode-cost win is real (earlier installation, not
    repeated on this one).

    ``G_S``: (g, k, k) per-group generator submatrices; ``shards``:
    (g, k, rows, cols) per-group fresh shard stacks."""
    g, k = shards.shape[0], shards.shape[1]
    flat = shards.reshape(g, k, -1)
    X = jax.vmap(jax.scipy.linalg.solve)(G_S, flat)
    return X.reshape(shards.shape)


# Public traceable alias: the fused device-coordination scan body
# (parallel/device_coord.py) embeds this exact vmapped batch per epoch
# — jit-inside-jit inlines, so the round-14 decode arithmetic has ONE
# implementation whether the trigger is the host loop or a compiled
# K-epoch window.
decode_groups = _decode_groups


class HierarchicalCodedGemm:
    """``C = A @ B`` recoverable from any outer-floor-many host groups,
    each recoverable from any ``k_inner`` of its ``n_inner`` chips.

    >>> hg = HierarchicalCodedGemm(A, groups=4, n_inner=8, k_inner=6)
    >>> pool = AsyncPool(hg.n_workers)
    >>> asyncmap(pool, B, hg.backend, nwait=hg.nwait)   # 3 of 4 groups
    >>> C = hg.result(pool)                             # exact product

    ``groups`` is a group count (contiguous split) or an explicit
    partition from :func:`~..parallel.multihost.host_groups`. The outer
    code defaults to the rate-(H-1)/H sum parity (single-host-loss
    tolerance, O(n) recovery); pass ``outer_rate`` below that for LT
    multi-host tolerance. ``inner="mds"`` (any k_inner of n_inner,
    solve decode) or ``"lt"`` (fixed systematic window, peeling
    decode).

    ``device_backend=False`` skips building the
    :class:`~..backends.xla.XLADeviceBackend` (no dispatcher threads):
    simulated fleets drive the same math through
    ``SimBackend(hg.work, hg.n_workers, delay_fn=...)`` — the bench and
    the host-loss tests run exactly this way.

    ``registry=`` / ``flight=`` follow the package-wide opt-in contract
    (GC004; dark paths pay only ``is None`` checks): decode counters
    ``hier_inner_decode_total{group=...}``, ``hier_group_losses_total``,
    ``hier_outer_recoveries_total``, and a flight-recorder instant
    event on every outer-code recovery so host-loss postmortems are
    visible in ``/flight`` dumps.
    """

    def __init__(
        self,
        A: np.ndarray,
        *,
        groups: int | Sequence[Sequence[int]],
        n_inner: int | None = None,
        k_inner: int,
        inner: str = "mds",
        outer: str = "auto",
        outer_rate: float | None = None,
        outer_seed: int = 0,
        inner_seed: int = 0,
        parity: str = "cauchy",
        dtype=None,
        precision: jax.lax.Precision | None = jax.lax.Precision.HIGHEST,
        devices: Sequence[jax.Device] | None = None,
        delay_fn: DelayFn | None = None,
        device_backend: bool = True,
        registry=None,
        flight=None,
    ):
        if dtype is not None:
            A = np.asarray(A, dtype=dtype)
        else:
            A = np.asarray(A)
        if isinstance(groups, (int, np.integer)):
            if n_inner is None:
                raise ValueError(
                    "n_inner is required when groups is a count"
                )
            self.group_indices = partition_groups(
                int(groups) * int(n_inner), int(groups)
            )
        else:
            self.group_indices = partition_groups(
                sum(len(g) for g in groups), groups
            )
            if n_inner is not None and n_inner != len(self.group_indices[0]):
                raise ValueError(
                    f"explicit groups of size {len(self.group_indices[0])} "
                    f"contradict n_inner={n_inner}"
                )
        self.H = len(self.group_indices)
        self.n_inner = len(self.group_indices[0])
        self.k_inner = int(k_inner)
        if not 0 < self.k_inner <= self.n_inner:
            raise ValueError(
                f"need 0 < k_inner <= n_inner, got k_inner={k_inner}, "
                f"n_inner={self.n_inner}"
            )
        self.n_workers = self.H * self.n_inner
        self.outer = make_outer(
            self.H, rate=outer_rate, kind=outer, seed=outer_seed
        )
        self.L = self.outer.L
        m = A.shape[0]
        if m % (self.L * self.k_inner) != 0:
            raise ValueError(
                f"rows {m} must divide evenly into L*k_inner = "
                f"{self.L}*{self.k_inner} source blocks"
            )
        if devices is None:
            devices = jax.devices()
        self.devices = list(devices)
        self.precision = precision
        self.block_rows = m // (self.L * self.k_inner)
        # -- outer encode: one host-group block per group, 0/1 sums ----
        # (generator cast to A's dtype so the coded blocks — and the
        # bf16 rounding story — match what the workers will compute in)
        G_out = self.outer.generator_rows().astype(A.dtype)
        src = jnp.asarray(A).reshape(self.L, m // self.L, *A.shape[1:])
        group_blocks = jnp.einsum(
            "hl,lrc->hrc", jnp.asarray(G_out), src, precision=precision
        ).astype(A.dtype)
        # -- inner encode: the existing dense code over each group ----
        self.inner = str(inner)
        if self.inner == "mds":
            self._icode = MDSCode(
                self.n_inner, self.k_inner, parity=parity, dtype=A.dtype,
                precision=precision,
            )
            self._inner_G = self._icode.G
            self._inner_ids = list(range(self.n_inner))
        elif self.inner == "lt":
            self._icode = LTCode(
                self.k_inner, seed=inner_seed, systematic=True
            )
            # fixed shard window, LTCodedGemm discipline: slide until
            # the full window peels so nwait is always satisfiable
            # (systematic streams peel at the first window already)
            ids = list(range(self.n_inner))
            for _ in range(1000):
                if self._icode.peelable(ids):
                    break
                ids = [s + 1 for s in ids]
            else:
                raise ValueError(
                    f"no decodable window of {self.n_inner} LT shards "
                    f"for k_inner={self.k_inner}"
                )
            self._inner_ids = ids
            self._inner_G = self._icode.generator_rows(ids).astype(A.dtype)
        else:
            raise ValueError(f"unknown inner code {inner!r}")
        coded = jnp.einsum(
            "nk,hkrc->hnrc", jnp.asarray(self._inner_G),
            group_blocks.reshape(
                self.H, self.k_inner, self.block_rows, *A.shape[1:]
            ),
            precision=precision,
        ).astype(A.dtype)
        # worker w = group_indices[g][j] holds inner shard j of group g
        self.blocks: list = [None] * self.n_workers
        for g, members in enumerate(self.group_indices):
            for j, w in enumerate(members):
                self.blocks[int(w)] = jax.device_put(
                    coded[g, j], self.devices[int(w) % len(self.devices)]
                )
        # decode runs in at least f32 (bf16 solves are not a thing the
        # LAPACK path supports, and the outer subtraction chain should
        # not round at bf16 either); the generator values stay the
        # encode-time-rounded ones, exactly embedded
        self._decode_dtype = (
            np.float64 if A.dtype == np.float64 else np.float32
        )
        self.backend = (
            XLADeviceBackend(
                self._work, self.n_workers, devices=devices,
                delay_fn=delay_fn,
            )
            if device_backend else None
        )
        # opt-in telemetry (instruments resolved once; None = dark,
        # the decode path pays one `is None` check)
        self._m = None
        self._flight = flight
        if registry is not None:
            registry.gauge(
                "hier_groups", help="host groups H of the outer code"
            ).set(self.H)
            registry.gauge(
                "hier_outer_floor",
                help="groups needed to clear the outer code",
            ).set(self.L)
            self._m = {
                "outer_rec": registry.counter(
                    "hier_outer_recoveries_total",
                    help="source group blocks reconstructed by the "
                         "outer code (a host was lost or skipped)",
                ),
                "losses": registry.counter(
                    "hier_group_losses_total",
                    help="group-epochs not inner-decodable at decode "
                         "time (straggling or dead hosts skipped)",
                ),
                "inner": [
                    registry.counter(
                        "hier_inner_decode_total",
                        help="inner decodes consumed per group",
                        group=str(g),
                    )
                    for g in range(self.H)
                ],
            }

    # -- worker side ------------------------------------------------------
    def _work(self, i: int, payload, epoch: int):
        return _block_matmul(
            self.blocks[int(i)], payload, precision=self.precision
        )

    @property
    def work(self):
        """The ``work_fn(worker, payload, epoch)`` for externally-built
        backends — ``SimBackend(hg.work, hg.n_workers, ...)`` drives
        the identical per-chip math on virtual time."""
        return self._work

    # -- completion rule --------------------------------------------------
    def _group_arrived(self, g: int, fresh_mask: np.ndarray) -> bool:
        """Inner decodability floor of group ``g`` over a freshness
        mask: >= k_inner fresh shards (MDS) / a peelable fresh id set
        (LT)."""
        members = self.group_indices[g]
        local = np.flatnonzero(fresh_mask[members])
        if self.inner == "mds":
            return local.size >= self.k_inner
        if local.size < self.k_inner:
            return False
        return self._icode.peelable([self._inner_ids[j] for j in local])

    @property
    def nwait(self):
        """Two-level decodability predicate for ``asyncmap(nwait=...)``:
        arrive per group at the inner floor, complete at the outer
        floor."""
        return hierarchical_nwait(
            self.group_indices, self._group_arrived, self.outer
        )

    def arrived_groups(self, pool: AsyncPool, epoch: int | None = None) -> list[int]:
        """Groups whose inner floor is met by the pool's fresh results."""
        fresh = pool.fresh_indices(epoch)
        mask = np.zeros(self.n_workers, dtype=bool)
        mask[fresh] = True
        return [
            g for g in range(self.H) if self._group_arrived(g, mask)
        ]

    # -- decode -----------------------------------------------------------
    def _inner_decode(self, g: int, pool: AsyncPool, fresh_mask: np.ndarray) -> np.ndarray:
        """Group ``g``'s coded product block ``Ã_g @ B`` from its fresh
        shards — one small solve (MDS) or peel (LT), never fleet-sized."""
        members = self.group_indices[g]
        local = np.flatnonzero(fresh_mask[members])
        if self.inner == "mds":
            sel = local[: self.k_inner]
            shards = jnp.stack([
                jnp.asarray(pool.results[int(members[j])])
                for j in sel
            ]).astype(self._decode_dtype)
            G_S = jnp.asarray(
                self._inner_G[sel].astype(self._decode_dtype)
            )
            blocks = _decode(G_S, shards, self.precision)
            return np.asarray(blocks.reshape(-1, *blocks.shape[2:]))
        ids = [self._inner_ids[j] for j in local]
        shards = np.stack([
            np.asarray(pool.results[int(members[j])]) for j in local
        ]).astype(self._decode_dtype)
        blocks = self._icode.decode(shards, ids)
        return blocks.reshape(-1, *blocks.shape[2:])

    def result(self, pool: AsyncPool, epoch: int | None = None) -> np.ndarray:
        """Decode the full product from the arrived groups (host copy).

        Refuses — naming both floors — when the arrived set cannot
        decode; on a recovery (any source group missing) the outer code
        reconstructs it from the survivors and the event is counted /
        flight-recorded.
        """
        fresh = pool.fresh_indices(epoch)
        mask = np.zeros(self.n_workers, dtype=bool)
        mask[fresh] = True
        arrived = [
            g for g in range(self.H) if self._group_arrived(g, mask)
        ]
        if not self.outer.decodable(arrived):
            raise ValueError(
                f"only {len(arrived)} of {self.H} groups are "
                f"inner-decodable (floor {self.k_inner} fresh of "
                f"{self.n_inner}) at epoch "
                f"{pool.epoch if epoch is None else epoch}; the outer "
                f"floor needs {self.L} decodable groups"
            )
        used = self.outer.select(arrived)
        if self.inner == "mds":
            # ALL inner decodes in one vmapped program (see
            # _decode_groups), one host round-trip for the lot
            sels = [
                np.flatnonzero(mask[self.group_indices[g]])[: self.k_inner]
                for g in used
            ]
            # host-side gather, ONE transfer: stacking device shards
            # with nested jnp.stack costs one dispatch per shard
            # (measured 3.6 ms vs 0.45 ms for the numpy gather at the
            # bench shape; earlier installation, not repeated on this one)
            shards = jnp.asarray(np.stack([
                np.stack([
                    np.asarray(pool.results[int(self.group_indices[g][j])])
                    for j in sel
                ])
                for g, sel in zip(used, sels)
            ]).astype(self._decode_dtype))
            G_S = jnp.asarray(
                np.stack([self._inner_G[sel] for sel in sels])
                .astype(self._decode_dtype)
            )
            blocks = np.asarray(_decode_groups(G_S, shards))
            inner_blocks = [
                b.reshape(-1, *b.shape[2:]) for b in blocks
            ]
        else:
            inner_blocks = [
                self._inner_decode(g, pool, mask) for g in used
            ]
        lost = self.H - len(arrived)
        recovered = self.L - sum(1 for g in used if g < self.L)
        if self._m is not None:
            if lost:
                self._m["losses"].inc(lost)
            for g in used:
                self._m["inner"][g].inc()
            if recovered:
                self._m["outer_rec"].inc(recovered)
        if self._flight is not None and recovered:
            self._flight.event(
                "hier outer recovery",
                epoch=int(pool.epoch if epoch is None else epoch),
                missing_groups=[g for g in range(self.L) if g not in used],
                recovered_blocks=int(recovered),
                arrived=len(arrived),
            )
        sources = self.outer.decode(inner_blocks, used)
        return np.ascontiguousarray(
            sources.reshape(-1, *sources.shape[2:])
        )
