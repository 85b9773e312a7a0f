"""The latent read against the chip's memory bandwidth: the bytes of
the rows the decoding slots attend (the runner's mean of rows a tick,
summed over the latent layers, times a row's 584 bytes, in every step
of every tick) over the device time under ``mla_attn`` in the tick
program. Absorbed decode does 119 operations a byte of int8 row against
a ridge of 240 (chipbench/counts_mla.py): memory bounds it, and this is
its share of that roofline, whether plain ``jax.numpy`` or a kernel
implements it; a route that reads every slot's whole ring whatever the
slot holds reads low here.

The divisor is the time under ``mla_attn`` ALONE. The gather route
first copies every slot's pages into a ring once a tick, under
``kv_page_gather`` / ``kv_page_scatter`` outside the scan, and that
time is ``tick_gather_share_pct``'s and not in here: where it read 5.5
beside ``mla_attn_share_pct`` 7.6 (my chip run, PR 34) the route as a
whole spent 1.7 times the divisor, so this share flatters it by that
factor. A kernel that reads the pages in place has no such copies:
compare it with ``share * attn / (attn + gather)`` of the gather
route, not with this number as it stands. Layer: model step."""
from chipbench.metrics._mla_scopes import time_by_scope
from chipbench.metrics._util import peak


def read(run):
    t, bw = time_by_scope(run, "tick"), peak(run, "hbm_bytes_per_s")
    if t is None or bw is None or t["mla_attn"] <= 0:
        return None
    step_bytes = run.info["mean_kv_rows_per_tick"] * run.info["kv_row_bytes"]
    steps = t["runs"] * run.info["n_inner"]
    return 100.0 * step_bytes * steps / (t["mla_attn"] * bw)
