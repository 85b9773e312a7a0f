"""The attention read against the chip's memory bandwidth: the bytes of
the cached K/V rows the decoding slots must ATTEND in the traced ticks
(chipbench/counts_attn_rows.py: from the requests' lengths after each
tick, times a row's bytes, in every step of the tick) over the device
time under ``decode_attn`` in the tick program. A decode step does a
few operations a byte of row, so memory bounds it and this is the
attention read's roofline share whatever implements it: it counts the
rows a request HAS, not the rows a route reads, so a route that reads
every slot's ring to ``max_context`` reads low here and a kernel that
later reads a selection of them is held to the same bytes. The traced
ticks are the window's first (the trace opens with the window). Layer:
model step."""
from chipbench import counts_attn_rows
from chipbench.metrics._scope_time import tick_time
from chipbench.metrics._util import peak


def read(run):
    t, bw = tick_time(run), peak(run, "hbm_bytes_per_s")
    rows = run.info.get("kv_rows_by_tick")
    if t is None or bw is None or not rows:
        return None
    seconds = t["scope"]["decode_attn"]
    if seconds <= 0 or t["runs"] <= 0:
        return None
    moved = counts_attn_rows.attended_bytes(
        rows[:t["runs"]], row_bytes=run.info["kv_row_bytes"],
        n_inner=run.info["n_inner"])
    return 100.0 * moved / (seconds * bw) if moved > 0 else None
