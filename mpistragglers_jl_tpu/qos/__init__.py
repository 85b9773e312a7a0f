# graftcheck: hermetic-root  (GC001 walks this subpackage's closure as
# its own root: the QoS plane is pure stdlib — deciding WHO is served
# next must never require jax, an accelerator, or even numpy)
"""Multi-tenant QoS: SLO classes, fair admission, and priced isolation.

"Millions of users" means tenants with different contracts sharing one
fleet, and without this plane a single heavy tenant starves everyone:
admission was FIFO, pages were first-come, and any tenant's hedges
spent the whole fleet's slack (ROADMAP item 3). This package turns
tenancy into arithmetic the rest of the codebase consults:

* :mod:`.tenancy` — :class:`TenantContract` (SLO class ``latency`` |
  ``throughput`` | ``batch``, DRR ``weight``, token-rate budget with
  refill via :class:`TokenBucket`, KV page-pool quota, TTFT-hedge
  entitlement) and the :class:`TenantRegistry` every plane shares.
* :mod:`.drr` — :class:`DeficitScheduler`: weighted deficit-round-
  robin over per-tenant admission queues, work-conserving by
  construction (idle capacity always serves whoever is queued) with
  deficit counters that carry, so a starved tenant catches up
  *exactly*.

Consumers: :class:`~..models.serving.ServingScheduler` (``qos=``)
replaces FIFO admission with the DRR pick and enforces page quotas at
plan time with COW-aware cold-page reclaim;
:class:`~..models.router.RequestRouter` (``qos=``) charges token
buckets at submit (over-budget ``batch`` work is shed by name,
``outcome == "shed"``) and refuses hedges beyond a tenant's
entitlement; :class:`~..sim.workload.SimReplica` (``qos=``) runs the
identical DRR on virtual time so the isolation claim — a tenant
flooding 10x its budget moves compliant tenants' p99 TTFT by less
than a pinned epsilon while utilization stays above a floor — is
measured and replayed bit-identically (tests/test_qos.py).

Wall-clock purity: graftcheck GC008 covers ``qos/`` like ``sim/`` and
``fleet/`` — nothing here reads an OS clock; buckets refill from the
``now`` the caller injects.
"""

from .drr import DeficitScheduler
from .tenancy import (
    SHED_ORDER,
    SLO_CLASSES,
    TenantContract,
    TenantRegistry,
    TokenBucket,
    shed_rank,
)

__all__ = [
    "SHED_ORDER",
    "SLO_CLASSES",
    "DeficitScheduler",
    "TenantContract",
    "TenantRegistry",
    "TokenBucket",
    "shed_rank",
]
