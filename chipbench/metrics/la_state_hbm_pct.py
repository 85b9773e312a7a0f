"""The single-row update of the linear-attention state against the
chip's memory bandwidth: ``S`` of every slot in every linear-attention
layer, read and written once a step (chipbench/counts_sala.py), in
every step of every traced tick, over the device time under ``la_rule``
in the tick program PLUS the compiler's own asynchronous copies in it
(``_gdn_scopes.MOVE_OPS`` outside the listed scopes: they carry the
state between HBM and the fast memory the update works from; what else
they move is counted against the state too, so the share is never
flattered). The update does one multiply-add a value of ``S``: memory
bounds it, and this is its share of that roofline. Layer: model step."""
from chipbench.metrics._sala_scopes import time_by_scope
from chipbench.metrics._util import peak


def read(run):
    t, bw = time_by_scope(run, "tick"), peak(run, "hbm_bytes_per_s")
    if t is None or bw is None or t["la_rule"] <= 0:
        return None
    moved = run.info["la_state_bytes"] * t["runs"] * run.info["n_inner"]
    return 100.0 * moved / ((t["la_rule"] + t["moves"]) * bw)
