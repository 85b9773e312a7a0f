"""The module against the chip's memory bandwidth: the bytes of
weights it reads in a step (chipbench/counts_dsv3.py ``mtp_step_bytes``:
its projection and norms, its block with the experts of its own layer
that got a row, the head once) in each of a tick's ``n_inner`` steps,
over the device time under the scope ``mtp`` in the tick program. At 32
rows a step every product is memory-bound, so this is the module's
roofline share. Layer: model step."""
from chipbench.metrics._mtp_scopes import tick_time
from chipbench.metrics._scope_time import program_steps
from chipbench.metrics._util import decode_tick_module, peak


def read(run):
    t, bw = tick_time(run), peak(run, "hbm_bytes_per_s")
    step_bytes = run.info.get("mtp_step_bytes")
    if t is None or bw is None or not step_bytes or t["scope"]["mtp"] <= 0:
        return None
    tick = decode_tick_module(run.summary)
    ticks = program_steps(run, lambda name: name == tick)
    return 100.0 * step_bytes * run.info["n_inner"] * ticks / (
        t["scope"]["mtp"] * bw)
