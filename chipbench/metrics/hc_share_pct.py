"""Share of the decode tick program's device time under ``hc_mix``: for
each of a layer's two halves the norm over the four streams, the
product with ``phi``, twenty Sinkhorn rounds on each token's 4 x 4, the
mix the half reads and the way its result goes back. Layer: model
step."""
from chipbench.metrics._mla_scopes import time_by_scope


def read(run):
    t = time_by_scope(run, "tick")
    if t is None:
        return None
    return 100.0 * t["hc_mix"] / t["whole"]
