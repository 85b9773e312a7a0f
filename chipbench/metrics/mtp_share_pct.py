"""Share of the decode tick program's device time under the scope
``mtp``: everything the multi-token-prediction module runs in a step
(its projection, its block over two rows a slot, its final norm, the
head's second product and the draft's pick). What drafting costs; what
it buys is ``tokens_per_step``. One block of six in this benchmark's
cut, one of 62 in the deployment. Layer: model step."""
from chipbench.metrics._mtp_scopes import tick_time
from chipbench.metrics._scope_time import pct


def read(run):
    t = tick_time(run)
    return None if t is None else pct(t["scope"]["mtp"], t)
