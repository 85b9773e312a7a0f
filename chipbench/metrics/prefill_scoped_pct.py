"""Share of the prefill programs' device time (every program whose
name starts ``jit_serving_prefill_chunk``) under any of the program's
scopes, old or new. What is left is listed by operation in ``note
prefill_time_by_scope_ms``. Layer: model step."""
from chipbench.metrics._scope_time import pct, prefill_time


def read(run):
    t = prefill_time(run)
    return None if t is None else pct(t["any"], t)
