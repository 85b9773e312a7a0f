"""Share of the prefill chunk programs' device time (the lone chunk's
and the grouped one's) under ``mla_attn``: a chunk's 256 absorbed
queries a head walking the key blocks of up to 4,096 latent rows, the
online softmax and the row writes. The prefill programs are three
tenths of the mixed window, so this moves the tokens a second (and
the tail, which this cell does not report: PERF.md section 2).
Layer: model step."""
from chipbench.metrics._mla_scopes import time_by_scope


def read(run):
    t = time_by_scope(run, "chunk")
    if t is None:
        return None
    return 100.0 * t["mla_attn"] / t["whole"]
