"""Operations of the train step's weight products, by the scope that
holds them, on chipbench/counts.py's shapes: what
``train_matmul_mxu_pct`` divides by a device time and the bf16 peak.
Forward plus twice that for the backward, nothing recomputed; the
attention kernels' operations are ``counts.flash_train_flops``, and
``counts.transformer_train_flops`` is the sum of both. Checked against
a hand-worked case in tests/chipbench/test_scope_time.py."""

from __future__ import annotations


def matmul_train_flops(*, batch: int, seq: int, d_model: int,
                       n_heads: int, kv_heads: int, d_ff: int,
                       n_layers: int, vocab: int) -> dict:
    """{'projections': q, k, v and out (scopes ``attn_qkv`` +
    ``attn_out``), 'feed_forward': the MLP's two matrices (``ffn``),
    'head': the product with the vocabulary (``head``; its backward
    runs under ``head`` and ``loss``)}, operations a step."""
    tokens = batch * seq
    head_dim = d_model // n_heads
    projections = (2 * d_model * n_heads * head_dim
                   + 2 * d_model * kv_heads * head_dim)
    return {
        "projections": 3 * n_layers * 2 * tokens * projections,
        "feed_forward": 3 * n_layers * 2 * tokens * 2 * d_model * d_ff,
        "head": 3 * 2 * tokens * d_model * vocab,
    }
