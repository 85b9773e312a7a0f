"""Share of the decode tick program's device time spent in the
state-space mixers: operations traced under ``ssm_proj`` (the
in-projection), ``ssm_conv`` (the depthwise conv), ``ssm_rule`` (one
step of the recurrence a slot, reading and writing its state) and
``ssm_out`` (the gate, the grouped norm, the out-projection). Layer:
model step."""
from chipbench.metrics._ssm_scopes import SCOPES, time_by_scope


def read(run):
    t = time_by_scope(run, "tick")
    if t is None:
        return None
    return 100.0 * sum(t[s] for s in SCOPES) / t["whole"]
