"""Share of the decode tick program's device time under the scope
``decode_attn`` (models/serving.py: the step's row written into the
cache, the scores over the rows a slot keeps, the softmax and the
product with the values, on the kernel route and the ring route
alike). None where the tick has no such scope (latent layers attend
under ``mla_attn``). Layer: model step."""
from chipbench.metrics._scope_time import pct, tick_time


def read(run):
    t = tick_time(run)
    if t is None or t["scope"]["decode_attn"] <= 0:
        return None
    return pct(t["scope"]["decode_attn"], t)
