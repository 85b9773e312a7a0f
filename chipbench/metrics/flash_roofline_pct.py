"""The flash kernels against the bf16 peak: the attention operations the
traced steps require (band only; scores recomputed by the backward
kernels not counted) over the kernels' device time. They are bound by
compute, not by bytes: K and V of a 4096-wide band are read once per
block of queries. Layer: train kernels."""
import statistics

from chipbench.metrics._util import peak
from chipbench.metrics.flash_share_pct import is_flash


def read(run):
    s, flops = run.summary, peak(run, "bf16_flops_per_s")
    if s is None or flops is None:
        return None
    seconds = s.seconds_where(is_flash)
    runs = [d for name, rs in s.modules.items() if "step" in name
            for _, _, d in rs]
    if seconds <= 0 or not runs:
        return None
    steps = sum(runs) / statistics.median(runs)  # edge steps in part
    return 100.0 * run.info["flash_flops_per_step"] * steps / (
        seconds * s.chips * flops
    )
