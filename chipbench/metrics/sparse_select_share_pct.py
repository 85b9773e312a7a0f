"""What the selector costs: the device time under ``sparse_pool`` (a
row's key into its pooled cell) and ``sparse_select`` (the query
against the pooled keys, the blocks' scores, the pick, the list of
pages a head) in the tick program and in the prefill programs, as a
share of those programs' whole device time in the traced window.
Layer: model step."""
from chipbench.metrics._sala_scopes import SELECT_SCOPES, time_by_scope


def read(run):
    found = [t for t in (time_by_scope(run, "tick"),
                         time_by_scope(run, "chunk")) if t is not None]
    if not found:
        return None
    return 100.0 * sum(t[s] for t in found for s in SELECT_SCOPES) / sum(
        t["whole"] for t in found)
