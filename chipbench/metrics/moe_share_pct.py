"""Share of the decode tick program's device time spent in the expert
layers' own stages: operations traced under ``moe_route`` (scores,
top-k, sort by expert), ``moe_experts`` (rows gathered by expert, the
grouped products, the weighted way back) and ``moe_shared`` (the shared
expert). Layer: model step."""
from chipbench.metrics._moe_scopes import MOE_SCOPES, tick_time_by_scope


def read(run):
    t = tick_time_by_scope(run)
    if t is None:
        return None
    return 100.0 * sum(t[s] for s in MOE_SCOPES) / t["whole"]
