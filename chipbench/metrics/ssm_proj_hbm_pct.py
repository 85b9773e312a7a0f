"""The state-space mixers against the chip's memory bandwidth, read
from the trace alone: what a mixer has to move in a step (every such
layer's in- and out-projection, chipbench/counts_ssm_moe.py
``step_bytes``'s ``ssm_proj``, the runner's ``ssm_proj_bytes``: 204.5
MB a layer at Granite-4.0-H Small's widths; and every slot's ``S``
read and written, the runner's ``ssm_state_bytes``), in every step of
every traced tick, over the mixers' measured device time in the tick
program: the time under all four ``ssm_*`` scopes plus the compiler's
asynchronous copies outside them (``_gdn_scopes.MOVE_OPS``, exactly
what ``ssm_state_hbm_pct`` adds to ``ssm_rule``: in this tick the
conv's kept rows), times the peak bandwidth.

The projections' bytes are not put over ``ssm_proj`` + ``ssm_out``
alone (ISSUE 51's formula: it read 118%, my chip run, PR 51), because
no scope's time holds its own bytes here: the compiler fetches the
out-projection's weights into its fast memory BEHIND the in-projection
and the step kernel (``slice-start``; the product under ``ssm_out``
then reads no HBM and takes 30 us), so the projections and the state
share the bandwidth of one stretch of time, and only the sum of the
bytes over the sum of the time is a reading. The waits for weights
fetched ahead (``slice-done`` outside every scope) are NOT in the
time: the trace does not say whose weights a wait is for, and the
shared MLP's are among them. Charged to the mixers in full they would
lower the share by a thirteenth (75.2 where 81.3, PERF.md section 5),
so it may flatter the mixers by up to that; with none of the copies
counted either it reads 98, under 100 as it must. It moves with the
step kernel as well as with the two products: it is the whole mixer's
share of its roofline, under the name the issue gave it. At 16 rows a
step everything in it is memory-bound. None where the program opens no
such scope (a parent commit, another model) or the runner counts no
such bytes (Falcon-H1's). Layer: model step."""
from chipbench.metrics._ssm_scopes import SCOPES, time_by_scope
from chipbench.metrics._util import peak


def read(run):
    t, bw = time_by_scope(run, "tick"), peak(run, "hbm_bytes_per_s")
    proj = run.info.get("ssm_proj_bytes")
    if t is None or bw is None or not proj:
        return None
    steps = t["runs"] * run.info["n_inner"]
    moved = (proj + run.info["ssm_state_bytes"]) * steps
    return 100.0 * moved / ((sum(t[s] for s in SCOPES) + t["moves"]) * bw)
