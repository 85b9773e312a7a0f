"""Share of the prefill chunk program's device time spent in the
chunked form of the delta rule: operations of ``jit_serving_prefill_chunk``
traced under ``gdn_rule`` (l2 norms, gates, the triangular solves and
products of each 64-row sub-chunk, the scan that carries the state).
The chunk is what stretches a tick that admits a prompt. Layer: model
step."""
from chipbench.metrics._gdn_scopes import time_by_scope


def read(run):
    t = time_by_scope(run, "chunk")
    if t is None:
        return None
    return 100.0 * t["gdn_rule"] / t["whole"]
