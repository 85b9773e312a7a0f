"""Share of the traced window in which no operation ran on the chip
while the host was inside ``serving.harvest`` (handing the tick's
tokens to their requests, retiring, freeing slots). Layer: scheduler
(host)."""
from chipbench.metrics._program_spans import idle_pct


def read(run):
    return idle_pct(run, "serving.harvest")
