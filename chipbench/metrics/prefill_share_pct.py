"""Share of the traced window in which the chip ran admission: every
program other than the decode tick (prefill chunks, the first token,
placing the prompt's K/V into pages). Ticks that admit a prompt are the
ones that stretch ``itl_p95_ms``. Layer: server."""
from chipbench.metrics._util import decode_tick_module


def read(run):
    s = run.summary
    if s is None:
        return None
    tick = decode_tick_module(s)
    if tick is None:
        return None
    seconds, _ = s.module_seconds(lambda name: name != tick)
    return 100.0 * seconds / s.window_s
