"""Bytes of the cached K/V rows a tick's decode steps must ATTEND: the
yardstick of ``attn_rows_hbm_pct``. It counts the rows the requests
HAVE, from their lengths, not the rows a route happens to read: a
kernel that reads fewer (a window, a selection of blocks) is held to
the same bytes and reads above a route that reads whole rings. Checked
against a hand-worked case in tests/chipbench/test_counts_attn_rows.py.
"""

from __future__ import annotations


def attended_bytes(rows_by_tick, *, row_bytes: int, n_inner: int) -> int:
    """``rows_by_tick``: for each tick, the cached rows its decoding
    slots attend in one step, summed over the slots and the layers that
    keep rows (the serving loop's bookkeeping after the tick, from the
    lengths of the requests' tokens); ``row_bytes``: K and V of one
    position in one layer; every one of the tick's ``n_inner`` steps
    reads them (the few rows the steps themselves add are left out, so
    the count is never too high)."""
    return int(sum(rows_by_tick)) * int(row_bytes) * int(n_inner)
