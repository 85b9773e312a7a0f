"""Multi-host / multi-slice support: DCN-aware meshes and runtime init.

The reference scales across hosts by launching more MPI ranks under
``mpiexec`` — transport topology is libmpi's problem (SURVEY §1 L0/L1;
Project.toml:7). The TPU-native equivalent is explicit: every host runs
the same program, ``jax.distributed`` wires the hosts into one runtime,
and collectives ride ICI *within* a slice and DCN *across* slices. The
mesh layout decides which — so the helpers here put the designated
cross-slice axis (usually ``"dp"``: gradient combines tolerate DCN
latency) across processes and keep the bandwidth-hungry axes
(``"tp"``/``"sp"``: per-layer activations) inside a slice on ICI.

Single-process runs (tests, the one-chip bench) need none of this; every
function degrades to the local-device path so the same code runs
everywhere.
"""

from __future__ import annotations

from typing import Sequence

import jax
import numpy as np
from jax.sharding import Mesh

__all__ = [
    "initialize_multihost",
    "make_multihost_mesh",
    "local_worker_indices",
    "host_groups",
]

_initialized = False

def _in_cluster_env() -> bool:
    """True when the environment describes a *multi-host* cluster whose
    coordinates ``jax.distributed.initialize`` can auto-discover (an
    explicit coordinator address, multi-host TPU pod metadata, or a
    multi-node SLURM allocation). Single-host values — e.g. the one-chip
    environment sets ``TPU_WORKER_HOSTNAMES=localhost`` — do not count."""
    import os

    env = os.environ
    if any(
        env.get(m)
        for m in (
            "JAX_COORDINATOR_ADDRESS",
            "COORDINATOR_ADDRESS",
            "MEGASCALE_COORDINATOR_ADDRESS",
        )
    ):
        return True
    hosts = env.get("TPU_WORKER_HOSTNAMES", "")
    if len([h for h in hosts.split(",") if h.strip()]) > 1:
        return True
    try:
        if int(env.get("SLURM_JOB_NUM_NODES", "1")) > 1:
            return True
    except ValueError:
        pass
    return False


def initialize_multihost(
    coordinator_address: str | None = None,
    num_processes: int | None = None,
    process_id: int | None = None,
    local_device_ids: Sequence[int] | None = None,
) -> None:
    """Wire this process into a multi-host JAX runtime (idempotent).

    Guarded wrapper over ``jax.distributed.initialize`` — the analog of
    ``MPI.Init()`` (examples/iterative_example.jl:7). A bare call
    auto-discovers coordinates when a known multi-host cluster
    environment is detected (TPU pod metadata, SLURM, an explicit
    coordinator-address variable — see ``_in_cluster_env``) and is a
    no-op otherwise, so
    the same program text runs on a laptop, one chip, and a pod. Passing
    ``coordinator_address``/``num_processes`` explicitly always
    initializes (the escape hatch when detection misses your launcher).
    """
    global _initialized
    if _initialized:
        return
    explicit = coordinator_address is not None or (
        num_processes is not None and num_processes > 1
    )
    if not explicit and not _in_cluster_env():
        # nothing to coordinate: single-process (tests / one-chip bench)
        _initialized = True
        return
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id,
        local_device_ids=local_device_ids,
    )
    _initialized = True


def make_multihost_mesh(
    axis_sizes: Sequence[int] | int,
    axis_names: Sequence[str] | str = "w",
    *,
    dcn_axis: str | None = None,
) -> Mesh:
    """Build a mesh over *all* processes' devices, DCN axis outermost.

    ``dcn_axis`` names the one axis allowed to span slices/hosts; in a
    multi-process run its size must be a multiple of
    ``jax.process_count()`` and the mesh must span *all* global devices
    (a partial pod mesh cannot guarantee the DCN axis actually crosses
    processes). Every other axis is laid out within a slice so its
    collectives stay on ICI. With one process this is exactly
    ``make_mesh`` over the local devices — tests exercise the same code
    path the pod runs.

    >>> initialize_multihost()
    >>> mesh = make_multihost_mesh((4, 8), ("dp", "tp"), dcn_axis="dp")
    """
    if isinstance(axis_sizes, (int, np.integer)):
        axis_sizes = (int(axis_sizes),)
    if isinstance(axis_names, str):
        axis_names = (axis_names,)
    axis_sizes = tuple(int(s) for s in axis_sizes)
    axis_names = tuple(axis_names)
    if len(axis_sizes) != len(axis_names):
        raise ValueError(
            f"axis_sizes {axis_sizes} and axis_names {axis_names} "
            "must have equal length"
        )
    if dcn_axis is not None and dcn_axis not in axis_names:
        raise ValueError(f"dcn_axis {dcn_axis!r} not in {axis_names}")
    need = int(np.prod(axis_sizes))
    devices = jax.devices()  # global across processes, process-major order
    if len(devices) < need:
        raise ValueError(
            f"mesh {dict(zip(axis_names, axis_sizes))} needs {need} "
            f"devices, have {len(devices)} across "
            f"{jax.process_count()} process(es)"
        )
    n_proc = jax.process_count()
    if n_proc > 1 and dcn_axis is not None:
        # hybrid layout: split every axis into a DCN (cross-slice) factor
        # and an ICI (within-slice) factor; only dcn_axis crosses slices
        from jax.experimental import mesh_utils

        if need != len(devices):
            # a process-major device prefix may lie inside one process,
            # so a partial mesh cannot honor a cross-process axis
            raise ValueError(
                f"multi-process mesh with dcn_axis must span all "
                f"{len(devices)} global devices, but "
                f"{dict(zip(axis_names, axis_sizes))} covers {need}"
            )
        dcn_sizes = tuple(
            n_proc if name == dcn_axis else 1 for name in axis_names
        )
        if axis_sizes[axis_names.index(dcn_axis)] % n_proc != 0:
            raise ValueError(
                f"dcn_axis {dcn_axis!r} size "
                f"{axis_sizes[axis_names.index(dcn_axis)]} must be a "
                f"multiple of process count {n_proc}"
            )
        ici_sizes = tuple(
            size // dcn for size, dcn in zip(axis_sizes, dcn_sizes)
        )
        arr = mesh_utils.create_hybrid_device_mesh(
            ici_sizes, dcn_sizes, devices=devices
        )
        return Mesh(arr, axis_names)
    arr = np.array(devices[:need]).reshape(axis_sizes)
    return Mesh(arr, axis_names)


def host_groups(
    n_workers: int | None = None,
    *,
    mesh: Mesh | None = None,
    axis: str = "w",
    n_hosts: int | None = None,
) -> list[list[int]]:
    """Partition pool worker indices into host groups — the fleet
    layout :class:`~..ops.hierarchical.HierarchicalCodedGemm`'s outer
    code stripes across (inner MDS on ICI within a group, cheap XOR
    outer across groups over DCN).

    With ``mesh`` (a multi-host mesh from :func:`make_multihost_mesh`),
    positions along ``axis`` group by the process hosting their
    devices — exactly the ownership relation
    :func:`local_worker_indices` reports per host, assembled for every
    host, so group g's inner code runs on one host's chips. Groups must
    come out equal-sized (give the pool axis a per-host-uniform
    layout); a position spanning several processes is refused — such an
    axis cannot be a straggler-independence unit.

    Without a mesh (tests, sim fleets, a single host), ``n_workers``
    splits evenly into ``n_hosts`` contiguous groups — the same
    partition shape, simulated.

    >>> groups = host_groups(mesh=mesh)               # one per host
    >>> hg = HierarchicalCodedGemm(A, groups=groups, k_inner=6)
    """
    if mesh is None:
        if n_workers is None or n_hosts is None:
            raise ValueError(
                "without a mesh, host_groups needs n_workers and n_hosts"
            )
        # ONE even-split implementation: ops/outer_code.py owns the
        # partition contract (numpy-only, import-safe from here)
        from ..ops.outer_code import partition_groups

        return [
            g.tolist()
            for g in partition_groups(int(n_workers), int(n_hosts))
        ]
    if axis not in mesh.axis_names:
        raise ValueError(f"axis {axis!r} not in mesh axes {mesh.axis_names}")
    ax = mesh.axis_names.index(axis)
    moved = np.moveaxis(mesh.devices, ax, 0)
    flat = moved.reshape(moved.shape[0], -1)
    by_host: dict[int, list[int]] = {}
    for i in range(flat.shape[0]):
        owners = {d.process_index for d in flat[i]}
        if len(owners) != 1:
            raise ValueError(
                f"position {i} along {axis!r} spans processes "
                f"{sorted(owners)}; a host group must live on one host "
                "to be a straggler-independence unit"
            )
        by_host.setdefault(owners.pop(), []).append(i)
    groups = [by_host[p] for p in sorted(by_host)]
    if len({len(g) for g in groups}) != 1:
        raise ValueError(
            f"hosts own unequal worker counts "
            f"{[len(g) for g in groups]} along {axis!r}; lay the pool "
            "axis out per-host-uniform"
        )
    return groups


def local_worker_indices(mesh: Mesh, axis: str = "w") -> list[int]:
    """Positions along ``axis`` whose devices this process hosts.

    A multi-host pool runs one coordinator per host driving its local
    devices (dispatch is host-side, so only local workers are
    addressable); the cross-host combine is a collective over the full
    mesh. This returns the pool indices this host's coordinator owns.
    """
    if axis not in mesh.axis_names:
        raise ValueError(f"axis {axis!r} not in mesh axes {mesh.axis_names}")
    ax = mesh.axis_names.index(axis)
    pid = jax.process_index()
    moved = np.moveaxis(mesh.devices, ax, 0)
    flat = moved.reshape(moved.shape[0], -1)
    return [
        int(i)
        for i in range(flat.shape[0])
        if any(d.process_index == pid for d in flat[i])
    ]
