"""Share of the traced window in which no operation ran on the chip
while the host was inside ``serving.decode``: the page pass before the
tick, the tick's launch latency, and the return of the fence after it.
Layer: scheduler (host)."""
from chipbench.metrics._program_spans import idle_pct


def read(run):
    return idle_pct(run, "serving.decode")
