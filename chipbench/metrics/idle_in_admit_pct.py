"""Share of the traced window in which no operation ran on the chip
while the host was inside ``serving.admit`` (advancing prefill chunks,
fetching a first token, admitting from the queue). The four
``idle_*`` metrics add up to ``serve_device_idle_pct``. Layer:
scheduler (host)."""
from chipbench.metrics._program_spans import idle_pct


def read(run):
    return idle_pct(run, "serving.admit")
