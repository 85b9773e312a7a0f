"""Device-mesh construction helpers for pool and collective layouts.

The reference's notion of topology is a flat list of MPI ranks
(src/MPIAsyncPools.jl:25); the TPU-native equivalent is a
``jax.sharding.Mesh`` whose axes map onto ICI. Pools put one worker per
device along a ``"w"`` (worker) axis; model-parallel workloads combine
``"dp"``/``"tp"``/``"sp"`` axes (see parallel/ring_attention.py and the
flagship train step).
"""

from __future__ import annotations

from typing import Sequence

import jax
import numpy as np
from jax.sharding import Mesh

__all__ = ["make_mesh"]


def make_mesh(
    axis_sizes: Sequence[int] | int,
    axis_names: Sequence[str] | str = "w",
    *,
    devices: Sequence[jax.Device] | None = None,
) -> Mesh:
    """Build a Mesh from the first ``prod(axis_sizes)`` devices.

    >>> make_mesh(8)                    # ('w',) pool mesh
    >>> make_mesh((2, 4), ("dp", "tp")) # model-parallel mesh
    """
    if isinstance(axis_sizes, (int, np.integer)):
        axis_sizes = (int(axis_sizes),)
    if isinstance(axis_names, str):
        axis_names = (axis_names,)
    if len(axis_sizes) != len(axis_names):
        raise ValueError(
            f"axis_sizes {axis_sizes} and axis_names {axis_names} "
            "must have equal length"
        )
    need = int(np.prod(axis_sizes))
    if devices is None:
        devices = jax.devices()
    if len(devices) < need:
        raise ValueError(
            f"mesh {dict(zip(axis_names, axis_sizes))} needs {need} "
            f"devices, have {len(devices)}"
        )
    arr = np.array(devices[:need]).reshape(tuple(axis_sizes))
    return Mesh(arr, tuple(axis_names))
