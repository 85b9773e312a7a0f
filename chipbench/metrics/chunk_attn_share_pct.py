"""Share of the prefill programs' device time under the scope
``chunk_attn``: a chunk's walk over the key blocks its rows can see
(models/decode.py ``_chunk_attention``). A quarter is where PR 31's
rule asks for a Pallas kernel. Layer: model step."""
from chipbench.metrics._scope_time import pct, prefill_time


def read(run):
    t = prefill_time(run)
    return None if t is None else pct(t["scope"]["chunk_attn"], t)
