"""Median ``serving.first_token_wait``: how long admission holds the
host, and with it the dispatch of the tick that follows, for one
request's first token to come back from the chip. Layer: scheduler
(host)."""
from chipbench.metrics._program_spans import FIRST_TOKEN_WAIT, median_span_ms


def read(run):
    return median_span_ms(run, FIRST_TOKEN_WAIT)
