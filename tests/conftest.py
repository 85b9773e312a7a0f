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


def pytest_collection_modifyitems(items):
    """tests/chipbench/test_chunks_per_program.py (PR 33) asserts that
    ITS metric is ``per_layer[-1]`` of BENCHMARK.json, where it means
    that the entry is there and lists the serving cells. The driver's
    contract for a PR that adds a per-layer metric: new entries go at
    the END of their lists ("one put first or in the middle reads as a
    change to what was there", which refuses the PR before any run),
    and no file under the benchmark's ``paths`` is edited, that test
    among them. So since PR 34 the first assertion cannot hold, and
    only a ``benchmark`` PR may repair it (PERF.md section 7: look the
    entry up by name, then drop this hook). Until then
    ``tests/chipbench/test_serve_mla.py::
    test_the_manifest_still_lists_chunks_per_prefill_program`` holds
    the entry to everything that test asserts, by name, and the old
    test is a STRICT expected failure of that one assertion: it fails
    the run if it passes (the entry is last again: drop the hook) or if
    it stops for any other reason than the assertion.

    The same since PR 39 for tests/chipbench/test_serve_mla.py::
    test_the_manifest_lists_the_cell_where_the_issue_names_it (PR 34),
    which asserts that ``serve_xing4_mixed`` is the LAST cell on
    fourteen shared metrics' lists and the only one on three of its
    own, where it means that the cell is on them: a later cell is
    appended behind it (``chunks_per_prefill_program``'s list, which
    the test beside it holds to every serving cell, is among the
    fourteen, so the two cannot both hold once a serving cell is
    added). tests/chipbench/test_serve_dsv3.py::
    test_what_test_serve_mla_held_of_the_older_cell_still_holds holds
    everything else that test asserts, by name."""
    import pytest

    expected = {
        "test_chunks_per_program.py::"
        "test_the_manifest_lists_it_for_the_serving_cells":
            "asserts per_layer[-1]; metrics are appended after it since "
            "PR 34",
        "test_serve_mla.py::"
        "test_the_manifest_lists_the_cell_where_the_issue_names_it":
            "asserts workloads[-1]; a cell is appended after it since "
            "PR 39",
    }
    for item in items:
        for tail, reason in expected.items():
            if item.nodeid.endswith(tail):
                item.add_marker(pytest.mark.xfail(
                    reason=reason, raises=AssertionError, strict=True))
