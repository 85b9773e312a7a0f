"""Share of the decode tick program's device time spent in the
linear-attention mixers: operations traced under ``la_proj``
(projections, q/k norms, rotary), ``la_rule`` (one step of the
recurrence a slot, reading and writing its state) and ``la_out`` (the
output norm, the gate, the out-projection). Layer: model step."""
from chipbench.metrics._sala_scopes import LA_SCOPES, time_by_scope


def read(run):
    t = time_by_scope(run, "tick")
    if t is None:
        return None
    return 100.0 * sum(t[s] for s in LA_SCOPES) / t["whole"]
