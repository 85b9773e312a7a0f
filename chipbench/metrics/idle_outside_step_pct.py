"""Share of the traced window in which no operation ran on the chip
while the host was outside every ``serving.tick``: the runner's own
bookkeeping between ticks, and the profiler. Layer: scheduler
(host)."""
from chipbench.metrics._program_spans import idle_pct


def read(run):
    return idle_pct(run, "outside")
