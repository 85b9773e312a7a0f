"""Share of the cache pages in use that belong to the full-attention
layers' pool: ``pages_full`` over ``pages_full + pages_window``, the
scheduler's own counts on ``serving.tick``, a mean over the window's
ticks. Layer: cache manager."""
from chipbench.metrics._moe_scopes import mean_span_argument
from chipbench.metrics._program_spans import TICK


def read(run):
    full = mean_span_argument(run, TICK, "pages_full")
    window = mean_span_argument(run, TICK, "pages_window")
    if full is None or window is None or full + window <= 0:
        return None
    return 100.0 * full / (full + window)
