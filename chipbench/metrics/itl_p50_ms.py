"""The median, over every output token after a request's first, of the
time since that request's previous delivery divided by the tokens in
this delivery (a tick delivers up to n_inner tokens to each slot): the
middle of the distribution whose tail is the end-to-end ``itl_p95_ms``.
Layer: server."""
from chipbench.common import weighted_percentile


def read(run):
    gaps, weights = run.info.get("token_gaps", ((), ()))
    if not gaps:
        return None
    return 1e3 * weighted_percentile(gaps, weights, 50.0)
