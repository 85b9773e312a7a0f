"""Share of a decode step's (token, chosen expert) pairs that fell on
an expert this chip holds: the ``pairs_local`` argument of
``serving.harvest`` (the tick's own count, a mean over its expert
layers and steps) over slots x experts a token. The router scores all
the experts and this chip holds half, so about 50 by design; the rest
is a further chip's part of the sum. Layer: router."""
from chipbench.metrics._moe_scopes import HARVEST, mean_span_argument


def read(run):
    local = mean_span_argument(run, HARVEST, "pairs_local")
    if local is None:
        return None
    return 100.0 * local / (
        run.info["slots"] * run.config["num_experts_per_tok"])
