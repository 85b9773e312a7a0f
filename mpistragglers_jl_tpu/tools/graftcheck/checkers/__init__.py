"""The shipped rule set. Importing this package registers every
checker with :mod:`..core`'s registry (the ``@register`` decorators
run at import); :func:`~..core.all_checkers` imports it lazily.

Rule catalog (details in each module's docstring and docs/API.md):

====== ==================== ==========================================
GC001  import-hygiene       package-root import closure stays free of
                            jax/accelerator stacks (module-level walk)
GC003  tracer-leak          no host clocks / host RNG / ``.item()`` /
                            casts or Python branches on traced args in
                            jitted functions and lax bodies
GC004  dark-path            registry/spans/tracer kwargs default None,
                            dereferences guarded; literal metric names
                            match the Prometheus grammar
GC005  lock-discipline      cross-thread attribute writes in
                            thread/lock classes happen under a lock
GC006  lock-order           per-class lock-acquisition graph stays
                            acyclic; no blocking call (recv, pickle,
                            timeout-less wait) under a held lock
GC007  slot-lifetime        RingAlloc acquire paths None-check (the
                            all-pinned fallback), release/register the
                            pin, and serve tracked views only as
                            ``memoryview(view)``
GC008  wall-clock           sim modules never read the OS clock; no
                            assert compares wall time to a sub-second
                            margin (``# graftcheck: real-smoke`` marks
                            the one sanctioned real test per family)
GC009  protocol-drift       transport.py KIND_* table and ctypes
                            argtypes/restype match transport.cpp's
                            constexpr constants and msgt_* signatures
GC010  shed-by-name         no bare drops: shed outcomes carry a
                            sibling shed_reason, shed/drop calls carry
                            an identifiable reason, and a literal
                            None/empty reason is flagged
GC011  witness-single-source sim digest witness written once: .ttft/
                            .latency assignments and `def digest` only
                            in sim/workload.py — the scalar loop and
                            the vectorized fast path share the
                            counter-stamping code
GC012  replay-purity        digest-bearing planes (sim/chaos/qos/
                            fleet, models.router/serving/disagg/
                            paging) are deterministic: no unseeded or
                            global RNG / uuid4 / urandom / environ
                            reads, and no set-iteration or id()/
                            hash() order reaching a digest, heap, or
                            sort key — interprocedural, on the
                            :mod:`..analysis` taint engine
GC013  stale-suppression    a `# graftcheck: disable=` comment that
                            suppresses zero findings is itself a
                            finding (mypy unused-ignore semantics)
====== ==================== ==========================================
"""

from . import (  # noqa: F401  (import == register)
    gc001_import_hygiene,
    gc003_tracer_leak,
    gc004_dark_path,
    gc005_lock_discipline,
    gc006_lock_order,
    gc007_slot_lifetime,
    gc008_wall_clock,
    gc009_protocol_drift,
    gc010_shed_by_name,
    gc011_witness_source,
    gc012_replay_purity,
    gc013_stale_suppression,
)
