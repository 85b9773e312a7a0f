"""The decode tick against the chip's memory bandwidth: the bytes its
steps must read (the weights once per step, and the cached K/V rows
that the decoding slots attend: chipbench/counts.py) over the tick
program's device time. Layer: model step."""
from chipbench import counts
from chipbench.metrics._util import decode_tick_module, peak


def read(run):
    s, bw = run.summary, peak(run, "hbm_bytes_per_s")
    if s is None or bw is None:
        return None
    tick = decode_tick_module(s)
    if tick is None:
        return None
    seconds, n = s.module_seconds(lambda name: name == tick)
    if n == 0 or seconds <= 0:
        return None
    step_bytes = counts.decode_step_bytes(
        weight_bytes=run.info["weight_bytes"],
        kv_rows=run.info["mean_kv_rows_per_tick"],
        row_bytes=run.info["kv_row_bytes"],
    )
    return 100.0 * run.info["n_inner"] * step_bytes * n / (seconds * bw)
