"""Mesh-collective coded GEMM: the fully-sharded ICI fast path.

Complement to ops/coded_gemm.CodedGemm (which runs the map step through
the asynchronous pool and decodes host-side/single-device). Here both
steps are sharded programs over a ``("w",)`` mesh:

* **map**: one ``shard_map`` matmul per epoch — device w computes
  ``Ã_w @ B`` with no cross-device communication at all (the straggler-
  exposed step stays embarrassingly parallel);
* **decode**: the masked ``psum_scatter`` combine
  (parallel/collectives.py) — stale workers enter with weight zero, one
  collective places source block j on device j.

Output stays sharded; ``full()`` gathers to host only on demand. This is
the path a real v5e-16 slice runs: coded blocks resident per chip,
per-epoch traffic = B broadcast + one reduce-scatter over ICI.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..ops.coding import MDSCode
from ..ops.matdot import MatDotCode, MatDotWeightCache, _matdot_worker
from .collectives import distributed_mds_decode

__all__ = ["MeshCodedGemm", "MeshMatDotGemm"]


class MeshCodedGemm:
    """(n, k) MDS-coded ``C = A @ B`` as sharded mesh programs.

    >>> mesh = make_mesh(8)
    >>> mg = MeshCodedGemm(A, mesh, k=6)
    >>> C_sharded = mg.epoch(B, repochs, epoch)   # blocks j<k on dev j
    >>> C = mg.full(C_sharded)                    # host gather
    """

    def __init__(
        self,
        A: np.ndarray,
        mesh: Mesh,
        k: int,
        *,
        axis: str = "w",
        parity: str = "cauchy",
        precision: jax.lax.Precision | None = jax.lax.Precision.HIGHEST,
    ):
        n = mesh.shape[axis]
        m = A.shape[0]
        if m % k != 0:
            raise ValueError(f"rows {m} must divide evenly into k={k} blocks")
        self.mesh = mesh
        self.axis = axis
        self.code = MDSCode(n, k, parity=parity, dtype=A.dtype,
                            precision=precision)
        self.n, self.k = n, k
        self.block_rows = m // k
        self.precision = precision
        coded = self.code.encode_array(A)  # (n, m/k, d)
        self.blocks = jax.device_put(
            coded, NamedSharding(mesh, P(axis)))  # block w on device w
        self._decode = distributed_mds_decode(mesh, self.code, axis)

        prec = precision

        def _map(blocks, B):
            # blocks: (1, m/k, d) local coded block; B replicated
            return jnp.matmul(blocks, B, precision=prec)

        self._map = jax.jit(jax.shard_map(
            _map, mesh=mesh, in_specs=(P(axis), P()), out_specs=P(axis)
        ))

    def map_step(self, B) -> jax.Array:
        """Per-device coded shard products (n, m/k, cols), sharded."""
        B = jax.device_put(jnp.asarray(B), NamedSharding(self.mesh, P()))
        return self._map(self.blocks, B)

    def epoch(self, B, repochs=None, epoch: int = 0) -> jax.Array:
        """One full coded epoch: map + masked decode. ``repochs``/``epoch``
        select the fresh shards (default: all fresh)."""
        shards = self.map_step(B)
        if repochs is None:
            repochs = np.full(self.n, epoch)
        return self._decode(shards, repochs, epoch)

    def full(self, decoded: jax.Array) -> np.ndarray:
        """Host gather of the first k decoded blocks -> (m, cols)."""
        out = np.asarray(decoded)  # (n, m/k, cols)
        return out[: self.k].reshape(-1, out.shape[-1])


class MeshMatDotGemm:
    """MatDot-coded ``C = A @ B`` as sharded mesh programs: the decode
    is ONE weighted ``psum`` over the mesh axis.

    MatDot's linear-functional decode (``C = Σ_i w_i C̃_i``, see
    ops/matdot.py) is the best-case shape for an ICI collective: each
    device scales its local evaluation by its decode weight and a single
    ``psum`` over the axis yields the full product — stale/straggling
    devices contribute with weight 0 exactly like the masked MDS
    combine, with no per-arrival-pattern recompilation (weights are a
    runtime array, shapes static).

    * **map**: device i computes ``Ã_i @ B̃_i`` with its resident A
      evaluation and a B̃ encoded on-device from the replicated B — no
      cross-device traffic;
    * **decode**: weights from the host-side 2p-1 × 2p-1 solve (tiny,
      float64, cached per arrival pattern), then ``psum(w_i * C̃_i)``.

    >>> mg = MeshMatDotGemm(A, mesh, p=2)
    >>> C = mg.epoch(B, repochs, epoch)      # (m, cols), replicated
    """

    def __init__(
        self,
        A: np.ndarray,
        mesh: Mesh,
        p: int,
        *,
        axis: str = "w",
        precision: jax.lax.Precision | None = jax.lax.Precision.HIGHEST,
    ):
        n = mesh.shape[axis]
        m, kd = A.shape
        if kd % p != 0:
            raise ValueError(
                f"inner dim {kd} must divide evenly into p={p} blocks"
            )
        self.mesh = mesh
        self.axis = axis
        self.code = MatDotCode(p, n, dtype=A.dtype, precision=precision)
        self.p, self.n, self.k = p, n, self.code.k
        self.precision = precision
        blocks = jnp.asarray(A).reshape(m, p, kd // p).transpose(1, 0, 2)
        coded = self.code.encode_A(blocks)  # (n, m, kd/p)
        self.A_evals = jax.device_put(
            coded, NamedSharding(mesh, P(axis)))  # evaluation i on device i
        self.B_weights = jax.device_put(
            jnp.asarray(self.code.VB), NamedSharding(mesh, P(axis))
        )  # (n, p) encode weights, row i on device i

        prec = precision
        pp = p

        def _epoch(A_eval, wB, B, wC):
            # A_eval: (1, m, kd/p) local; wB: (1, p); B replicated
            # (kd, cols); wC: (n,) decode weights (replicated). The
            # local B-encode + matmul is the pool path's worker program
            # (ops/matdot._matdot_worker) — one source of truth.
            Ct = _matdot_worker(A_eval[0], wB[0], B, pp, prec)
            i = jax.lax.axis_index(self.axis)
            return jax.lax.psum(wC[i] * Ct, self.axis)

        self._epoch = jax.jit(jax.shard_map(
            _epoch, mesh=mesh,
            in_specs=(P(axis), P(axis), P(), P()),
            out_specs=P(),
        ))
        self._weights = MatDotWeightCache(self.code)

    def decode_weights(self, repochs, epoch: int) -> np.ndarray:
        """Per-device combine weights from the arrival mask: the first
        2p-1 fresh devices carry the interpolation weights, everyone
        else 0."""
        fresh = np.flatnonzero(np.asarray(repochs) == epoch)
        if fresh.size < self.k:
            raise ValueError(
                f"only {fresh.size} fresh shards, need 2p-1={self.k}"
            )
        return self._weights.get(fresh[: self.k])

    def epoch(self, B, repochs=None, epoch: int = 0) -> jax.Array:
        """One coded epoch: on-device B encode + local matmul + one
        weighted psum. Returns the full (m, cols) product, replicated."""
        if repochs is None:
            repochs = np.full(self.n, epoch)
        w = self.decode_weights(repochs, epoch)
        B = jax.device_put(jnp.asarray(B), NamedSharding(self.mesh, P()))
        wC = jax.device_put(
            jnp.asarray(w, dtype=B.dtype),
            NamedSharding(self.mesh, P()),
        )
        return self._epoch(self.A_evals, self.B_weights, B, wC)
