"""Model FLOP/s utilization: the operations one step requires (forward
plus twice that, attention counted over the window's band only, nothing
recomputed: chipbench/counts.py) over the median step interval, against
the bf16 peak of the chips of the mesh. Layer: trainer."""
import statistics

from chipbench.metrics._util import peak


def read(run):
    flops, iv = peak(run, "bf16_flops_per_s"), run.info.get("intervals")
    if flops is None or not iv:
        return None
    chips = len(run.devices)
    return 100.0 * run.info["flops_per_step"] / (
        statistics.median(iv) * chips * flops
    )
