"""Tokens a decoding slot was delivered in one inner step: the
window's sum of ``tokens`` on ``serving.harvest`` over its sum of
``drafted`` on ``serving.tick`` (the decode steps of live requests).
One without a drafter; between one and two with it, a little under 1 +
the acceptance rate (the second of two tokens is dropped where the
first filled the request's budget). Layer: server."""
from chipbench.metrics._mtp_scopes import HARVEST, window_sum
from chipbench.metrics._program_spans import TICK


def read(run):
    steps = window_sum(run, TICK, "drafted")
    tokens = window_sum(run, HARVEST, "tokens")
    if not steps or tokens is None:
        return None
    return tokens / steps
