"""Share of the decode tick's device time spent materializing each
slot's ring view out of the page pool and writing it back: operations
traced under ``kv_page_gather`` / ``kv_page_scatter``, and the copies
without a scope that the compiler puts beside them, outside the tick's
scan. Layer: scheduler (host)."""
from chipbench.metrics._program_spans import gather_share_of_tick


def read(run):
    return gather_share_of_tick(run)
