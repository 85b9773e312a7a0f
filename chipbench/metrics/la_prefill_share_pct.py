"""Share of the prefill chunk programs' device time spent in the
recurrence over a chunk's rows: operations of
``jit_serving_prefill_chunk`` traced under ``la_rule`` (the float32
products of the chunk's rows with each other and with the carried
state). Layer: model step."""
from chipbench.metrics._sala_scopes import time_by_scope


def read(run):
    t = time_by_scope(run, "chunk")
    if t is None:
        return None
    return 100.0 * t["la_rule"] / t["whole"]
