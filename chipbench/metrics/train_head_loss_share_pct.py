"""Share of the train step's device time under the scopes ``head``
(final norm and the product with the 49,152-row tied embedding) and
``loss`` (the float32 log-sum-exp and the target's logit), forward and
backward. Layer: trainer."""
from chipbench.metrics._scope_time import pct, train_step_time


def read(run):
    t = train_step_time(run)
    if t is None:
        return None
    return pct(t["scope"]["head"] + t["scope"]["loss"], t)
