"""The head against the chip's memory bandwidth: the output head's
bytes (vocabulary x width x 2), read once in each of a tick's
``n_inner`` steps, over the device time under the scope ``head`` in
the tick program. At 16 rows a step the product is memory-bound, so
this is its roofline share. Layer: model step."""
from chipbench.metrics._scope_time import program_steps, tick_time
from chipbench.metrics._util import decode_tick_module, peak

BYTES_PER_WEIGHT = 2  # bfloat16, every serving configuration's


def read(run):
    t, bw = tick_time(run), peak(run, "hbm_bytes_per_s")
    if t is None or bw is None or t["scope"]["head"] <= 0:
        return None
    tick = decode_tick_module(run.summary)
    ticks = program_steps(run, lambda name: name == tick)
    head_bytes = (run.config["vocab_size"] * run.config["hidden_size"]
                  * BYTES_PER_WEIGHT)
    return 100.0 * head_bytes * run.info["n_inner"] * ticks / (
        t["scope"]["head"] * bw)
