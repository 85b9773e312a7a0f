"""Mean share of the slots held by a prompt still in prefill when a
tick begins: the ``admitting`` argument of ``serving.tick``, the
scheduler's own count, over the slots. Such a slot delivers nothing
in that tick. Layer: scheduler (host)."""
from chipbench.metrics._program_spans import mean_tick_argument


def read(run):
    mean, slots = mean_tick_argument(run, "admitting"), run.info.get("slots")
    if mean is None or not slots:
        return None
    return 100.0 * mean / slots
