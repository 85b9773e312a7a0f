"""chipbench/counts_attn_rows.py against a hand-worked case; the readers
of ``attn_rows_hbm_pct`` and ``decode_attn_share_pct`` on hand-made
scope times."""

from __future__ import annotations

import types

import pytest

from chipbench import counts_attn_rows
from chipbench.metrics import attn_rows_hbm_pct, decode_attn_share_pct


def test_attended_bytes_by_hand():
    # three ticks whose decoding slots attend 100, 250 and 0 rows a
    # step; a row 1,040 bytes; 8 steps a tick
    assert counts_attn_rows.attended_bytes(
        [100, 250, 0], row_bytes=1040, n_inner=8) == 350 * 1040 * 8
    assert counts_attn_rows.attended_bytes([], row_bytes=1040, n_inner=8) == 0


def _run(decode_attn_s, runs, rows, whole=1.0):
    scope = {"decode_attn": decode_attn_s, "head": 0.1}
    return types.SimpleNamespace(
        summary=types.SimpleNamespace(ops=[types.SimpleNamespace(
            name="while.1", module="jit_tick", dur=1.0)]),
        info={"scope_time_tick": {"whole": whole, "runs": runs,
                                  "scope": scope},
              "kv_rows_by_tick": rows, "kv_row_bytes": 1040, "n_inner": 8},
        peaks={"hbm_bytes_per_s": 819e9})


def test_the_readers_on_hand_made_scope_times():
    # two traced ticks of a window of four: the first two ticks' rows
    run = _run(2e-3, 2, [100_000, 150_000, 999_999, 999_999])
    assert attn_rows_hbm_pct.read(run) == pytest.approx(
        100 * 250_000 * 1040 * 8 / (2e-3 * 819e9))
    assert decode_attn_share_pct.read(run) == pytest.approx(0.2)


def test_the_readers_find_nothing_where_there_is_nothing():
    none = types.SimpleNamespace(summary=None, info={}, peaks=None)
    assert attn_rows_hbm_pct.read(none) is None
    assert decode_attn_share_pct.read(none) is None
    # a tick without the scope (latent layers attend under mla_attn):
    # no share of a roofline is ever reported as 0
    assert attn_rows_hbm_pct.read(_run(0.0, 2, [5, 5])) is None
    assert decode_attn_share_pct.read(_run(0.0, 2, [5, 5])) is None
    # no slot decoding in the traced ticks
    assert attn_rows_hbm_pct.read(_run(1e-3, 2, [0, 0])) is None
    assert attn_rows_hbm_pct.read(_run(1e-3, 0, [5, 5])) is None
