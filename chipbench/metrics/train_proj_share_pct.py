"""Share of the train step's device time under the scopes ``attn_qkv``
(norm, q/k/v projections, rotary) and ``attn_out`` (the out-projection
and the residual), forward and backward; the flash kernels between the
two are ``flash_share_pct``'s. Layer: trainer."""
from chipbench.metrics._scope_time import pct, train_step_time


def read(run):
    t = train_step_time(run)
    if t is None:
        return None
    return pct(t["scope"]["attn_qkv"] + t["scope"]["attn_out"], t)
