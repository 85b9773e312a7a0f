"""Share of the train step's device time under the scope ``ffn``
(models/transformer.py ``ffn_half``: the norm, the MLP's two products,
the residual), forward and backward. Layer: trainer."""
from chipbench.metrics._scope_time import pct, train_step_time


def read(run):
    t = train_step_time(run)
    return None if t is None else pct(t["scope"]["ffn"], t)
