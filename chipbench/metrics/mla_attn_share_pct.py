"""Share of the decode tick program's device time under ``mla_attn``:
each latent layer's write of the step's row, the absorbed queries'
scores over the slots' rows, the softmax and the probabilities' product
with the rows (plain ``jax.numpy`` over every slot's gathered pages). A
quarter is where PR 31's rule asks for a Pallas kernel. Layer: model
step."""
from chipbench.metrics._mla_scopes import time_by_scope


def read(run):
    t = time_by_scope(run, "tick")
    if t is None:
        return None
    return 100.0 * t["mla_attn"] / t["whole"]
