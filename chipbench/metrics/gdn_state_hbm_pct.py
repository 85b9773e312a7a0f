"""The single-token delta-rule update against the chip's memory
bandwidth: the state ``S`` of every slot in every delta-rule layer,
read and written once a step (chipbench/counts_gdn.py), in every step
of every tick, over the device time under ``gdn_rule`` in the tick
program PLUS the time of the compiler's own asynchronous copies in it
(``_gdn_scopes.MOVE_OPS`` outside the ``gdn_*`` scopes: they are what
carries the state between HBM and the fast memory the update works
from; what else they move is counted against the state too, so the
share is never flattered). The update does a few operations a byte: memory bounds it,
and this is its share of that roofline. Layer: model step."""
from chipbench.metrics._gdn_scopes import time_by_scope
from chipbench.metrics._util import peak


def read(run):
    t, bw = time_by_scope(run, "tick"), peak(run, "hbm_bytes_per_s")
    if t is None or bw is None or t["gdn_rule"] <= 0:
        return None
    # the runner's count (chipbench/runners/serve_gdn.py): S of every
    # slot in every delta-rule layer, read and written, one step
    moved = run.info["state_S_bytes"] * t["runs"] * run.info["n_inner"]
    return 100.0 * moved / ((t["gdn_rule"] + t["moves"]) * bw)
