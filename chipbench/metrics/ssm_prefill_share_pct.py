"""Share of the prefill chunk programs' device time spent in the
state-space mixers: operations of ``jit_serving_prefill_chunk`` traced
under ``ssm_proj``, ``ssm_conv``, ``ssm_rule`` (the float32 products of
the chunk's rows with each other and with the carried state) and
``ssm_out``. Layer: model step."""
from chipbench.metrics._ssm_scopes import SCOPES, time_by_scope


def read(run):
    t = time_by_scope(run, "chunk")
    if t is None:
        return None
    return 100.0 * sum(t[s] for s in SCOPES) / t["whole"]
