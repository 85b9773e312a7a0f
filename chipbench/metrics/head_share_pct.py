"""Share of the decode tick program's device time under the scope
``head``: the final norm, the product with the output head and the
token pick (models/transformer.py ``head_logits``; ``_pick_rows`` and
``_eos_clamp`` in models/serving.py ``_scan_body``). The compiler fuses
the pick into the product, so one scope holds both. Layer: model
step."""
from chipbench.metrics._scope_time import pct, tick_time


def read(run):
    t = tick_time(run)
    return None if t is None else pct(t["scope"]["head"], t)
