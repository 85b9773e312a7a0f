"""Test configuration: force an 8-device virtual CPU mesh.

Multi-chip TPU hardware is not available in CI; all sharding/collective
code is exercised on 8 virtual CPU devices (``JAX_PLATFORMS=cpu`` plus
``--xla_force_host_platform_device_count=8``, both set here before jax
is imported). The chip itself is exercised by ``chip_smoke.py``.
"""

import os
import sys

_FLAG = "--xla_force_host_platform_device_count=8"
_existing = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _existing:
    os.environ["XLA_FLAGS"] = (_existing + " " + _FLAG).strip()
os.environ["JAX_PLATFORMS"] = "cpu"

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402

# float64 must stay float64 in the coding-layer tests (the reference's
# tests are Float64 throughout, SURVEY §7 "the hard parts"); TPU-path
# tests pin float32 explicitly so this only affects CPU-mesh runs
jax.config.update("jax_enable_x64", True)

# Persistent XLA compilation cache: the suite's wall-clock is dominated
# by a flat ~1-3 s/test tail of small jit compiles (measured r5 —
# durations show no outliers above 9 s in the default tier, yet it
# spends 12+ min on one core). Caching compiled executables across runs
# turns every repeat run (local dev loops, the driver's green check,
# CI with a cached directory) into mostly cache hits. Correctness is
# unaffected: the cache key covers program, backend, and flags.
from mpistragglers_jl_tpu.utils.compile_cache import (  # noqa: E402
    wire_compile_cache,
)

wire_compile_cache()
