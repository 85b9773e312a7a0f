"""Share of the decode tick program's device time spent in the gated
delta-rule mixers: operations traced under ``gdn_proj`` (projections),
``gdn_conv`` (the depthwise conv), ``gdn_rule`` (one step of the
recurrence a slot, reading and writing its state) and ``gdn_out`` (the
gated norm and the out-projection). Layer: model step."""
from chipbench.metrics._gdn_scopes import GDN_SCOPES, time_by_scope


def read(run):
    t = time_by_scope(run, "tick")
    if t is None:
        return None
    return 100.0 * sum(t[s] for s in GDN_SCOPES) / t["whole"]
