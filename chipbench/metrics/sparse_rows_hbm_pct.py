"""The selection against the chip's memory bandwidth: the bytes the
mechanism MUST read of the attention layer's cache for the traced
ticks' decoding slots (chipbench/counts_sala.py ``must_read_rows``, the
serving loop's bookkeeping after each tick: every row of a request up
to ``dense_len``, past it the rows of the standing blocks and the
visible pooled keys, from the requests' lengths alone; in every step
of the tick) over the device time under ``decode_attn`` +
``sparse_select`` in the tick program. Memory bounds a decode step's
attention, so this is the selection's roofline share whatever
implements it: a route that reads more than it must reads low here.
Layer: model step."""
from chipbench import counts_attn_rows
from chipbench.metrics._sala_scopes import time_by_scope
from chipbench.metrics._util import peak


def read(run):
    t, bw = time_by_scope(run, "tick"), peak(run, "hbm_bytes_per_s")
    rows = run.info.get("kv_rows_by_tick")
    if t is None or bw is None or not rows or t["runs"] <= 0:
        return None
    seconds = t["decode_attn"] + t["sparse_select"]
    if seconds <= 0:
        return None
    moved = counts_attn_rows.attended_bytes(
        rows[:t["runs"]], row_bytes=run.info["kv_row_bytes"],
        n_inner=run.info["n_inner"])
    return 100.0 * moved / (seconds * bw) if moved > 0 else None
