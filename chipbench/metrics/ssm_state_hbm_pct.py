"""The single-token update of the state-space mixer's state against the
chip's memory bandwidth: ``S`` of every slot in every layer, read and
written once a step (chipbench/counts_ssm.py, the runner's
``ssm_state_bytes``), in every step of every traced tick, over the
device time under ``ssm_rule`` in the tick program PLUS the compiler's
own asynchronous copies in it (``_gdn_scopes.MOVE_OPS`` outside the
listed scopes: where a state is carried through the fast memory they
are what carries it; what else they move is counted against the state
too, so the share is never flattered by leaving bytes' time out). The
update does two multiply-adds a value of ``S``: memory bounds it, and
this is the step kernel's share of that roofline. Layer: model step."""
from chipbench.metrics._ssm_scopes import time_by_scope
from chipbench.metrics._util import peak


def read(run):
    t, bw = time_by_scope(run, "tick"), peak(run, "hbm_bytes_per_s")
    moved = run.info.get("ssm_state_bytes")
    if t is None or bw is None or not moved or t["ssm_rule"] <= 0:
        return None
    moved = moved * t["runs"] * run.info["n_inner"]
    return 100.0 * moved / ((t["ssm_rule"] + t["moves"]) * bw)
