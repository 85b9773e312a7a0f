"""Bytes and operations the chunked gated delta rule needs for ONE
prefill chunk in ONE delta-rule layer: the yardstick of
``gdn_rule_roofline_pct`` (the kernel ``ops/delta_rule.py`` since PR 40;
``chipbench/counts_gdn.py`` ``delta_rule_chunk_flops`` counts the plain
form, which solves for ``Dv + Dk`` right-hand sides, and stays for that
form). The same rules as chipbench/counts.py: from shapes alone, what
the algorithm needs and not what an implementation happens to do; each
is checked against a hand-worked case in
tests/chipbench/test_counts_gdn_rule.py.
"""

from __future__ import annotations

SUB_CHUNK = 128   # rows the rule takes at a time between two states
F32_BYTES = 4
# A float32 product on the MXU at full precision is six passes of
# bfloat16 pieces; the configuration keeps ``S``, the gates and the
# rule's products in float32 (``assumed.state``), so the operations'
# side of the roofline is at a sixth of the bfloat16 peak.
F32_PASSES = 6


def chunk_rule_bytes(*, rows: int, key_heads: int, value_heads: int,
                     key_dim: int, value_dim: int) -> int:
    """What the rule must move for ``rows`` rows of one layer, each
    once: q and k as the conv leaves them (a row a key head, float32,
    not repeated for the value heads that share it), v in and ``o``
    out (a row a value head), the gates g and beta (a number a row and
    value head), and the state ``S`` read and written."""
    qk = 2 * rows * key_heads * key_dim
    vo = 2 * rows * value_heads * value_dim
    gates = 2 * rows * value_heads
    state = 2 * value_heads * key_dim * value_dim
    return F32_BYTES * (qk + vo + gates + state)


def chunk_rule_flops(*, rows: int, key_heads: int, value_heads: int,
                     key_dim: int, value_dim: int,
                     sub: int = SUB_CHUNK) -> int:
    """Operations of the chunked form on ``rows`` rows of one layer, a
    multiply-add as two. Per sub-chunk of ``sub`` rows: q.k and k.k
    once a KEY head (``2 sub^2 Dk`` each); and a value head: the two
    products of the rows with the carried state, k.S and q.S
    (``2 sub Dk Dv`` each), the unit-triangular system for the updates
    by substitution (``sub^2 Dv``), the scores times the updates
    (``2 sub^2 Dv``) and the state's update (``2 sub Dk Dv``)."""
    n_sub = -(-rows // sub)
    per_key_head = 2 * 2 * sub * sub * key_dim
    per_value_head = (2 * 2 * sub * key_dim * value_dim
                      + sub * sub * value_dim
                      + 2 * sub * sub * value_dim
                      + 2 * sub * key_dim * value_dim)
    return n_sub * (key_heads * per_key_head + value_heads * per_value_head)


def chunk_rule_floor_s(*, hbm_bytes_per_s: float, bf16_flops_per_s: float,
                       **shape) -> tuple[float, str]:
    """(the least seconds one chunk of one layer can take, what bounds
    it): its bytes at the memory's peak or its operations at the
    float32 pace of the MXU, whichever is longer."""
    by_bytes = chunk_rule_bytes(**shape) / hbm_bytes_per_s
    by_ops = chunk_rule_flops(**shape) * F32_PASSES / bf16_flops_per_s
    return (by_ops, "mxu") if by_ops >= by_bytes else (by_bytes, "hbm")
