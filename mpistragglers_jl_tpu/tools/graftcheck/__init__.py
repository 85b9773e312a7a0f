"""graftcheck: project-invariant static analysis for this repo.

Stdlib-``ast``-only analyzers for the invariants the codebase
otherwise encodes as prose and single runtime probes: the jax-free
package root (GC001), tracer hygiene inside jitted/scan code
(GC003), strictly opt-in observability (GC004), cross-thread lock
discipline (GC005), and — the v2 interprocedural set (ISSUE 8) —
lock-order acyclicity with no blocking calls under a lock (GC006),
RingAlloc slot/pin
lifetime (GC007), wall-clock discipline for the sim plane and the
timing-margin flake family (GC008), cross-language protocol
drift between transport.py and transport.cpp (GC009), and — ISSUE
18's dataflow set — interprocedural replay-purity taint for the
digest-bearing planes (GC012, on the shared :mod:`.analysis` engine)
plus stale-suppression detection (GC013). Run it:

.. code-block:: bash

    python -m mpistragglers_jl_tpu.tools.graftcheck mpistragglers_jl_tpu/

Exit 0 = clean (fresh findings none); non-zero otherwise. Suppress a
single deliberate site with ``# graftcheck: disable=GC003`` on (or
directly above) the line; park a documented false positive in
``baseline.json`` (capped; every entry needs a justification; stale
entries fail the run). The tier-1 suite self-runs the analyzer over
the whole package (tests/test_graftcheck.py), so every rule gates
every PR. See docs/API.md "Static analysis".
"""

from .core import (  # noqa: F401
    Baseline,
    BaselineError,
    Checker,
    Finding,
    ModuleInfo,
    RunResult,
    all_checkers,
    load_modules,
    register,
    run,
)

import os

#: the checked-in false-positive ledger the CLI defaults to
DEFAULT_BASELINE = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "baseline.json"
)

__all__ = [
    "Baseline",
    "BaselineError",
    "Checker",
    "Finding",
    "ModuleInfo",
    "RunResult",
    "all_checkers",
    "load_modules",
    "register",
    "run",
    "DEFAULT_BASELINE",
]
