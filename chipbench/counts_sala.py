"""Bytes of a model whose layers are decayed linear-attention mixers
beside attention that reads a SELECTION of its key blocks, over a dense
gated feed-forward: what a decode step reads of weights and of state,
and what the selection MUST read of a request's cache, from the
request's length alone. The same yardstick rules as chipbench/counts.py
(from shapes and lengths, what the mechanism needs and not what an
implementation happens to do); each is checked against a hand-worked
case in tests/chipbench/test_counts_sala.py.
"""

from __future__ import annotations

from chipbench.counts_moe import attention_params, gated_mlp_params

POOLED_BYTES = 2  # a pooled key's value, as a deployment keeps it


def la_matrix_params(d_model: int, heads: int, head_dim: int) -> int:
    """Weights of one linear-attention mixer kept in the model's type:
    q, k, v, the gate's and the out projection (each d_model x heads x
    head_dim), the q and k norms' scales and the output norm's."""
    wide = heads * head_dim
    return 5 * d_model * wide + 2 * head_dim + wide


def la_state_bytes(*, heads: int, head_dim: int) -> int:
    """One request's state in ONE linear-attention layer: ``S`` (heads
    x head_dim x head_dim) float32."""
    return 4 * heads * head_dim * head_dim


def step_state_bytes(*, slots: int, la_layers: int, heads: int,
                     head_dim: int) -> int:
    """What one decode step moves of recurrent state: every slot's
    ``S`` in every linear-attention layer, read and written."""
    return 2 * slots * la_layers * la_state_bytes(
        heads=heads, head_dim=head_dim)


def matrix_params(*, d_model: int, n_heads: int, kv_heads: int,
                  head_dim: int, la_heads: int, la_head_dim: int, d_ff: int,
                  n_layers: int, la_layers: int, vocab: int) -> int:
    """Parameters kept in the model's type that one decode step reads:
    each layer's mixer (attention with its gate and q/k norms, or
    linear attention), its two norms and its dense gated feed-forward;
    the final norm and the untied head."""
    attn = attention_params(d_model, n_heads, kv_heads, head_dim) \
        + 2 * head_dim
    la = la_matrix_params(d_model, la_heads, la_head_dim)
    return ((n_layers - la_layers) * attn + la_layers * la
            + n_layers * (2 * d_model + gated_mlp_params(d_model, d_ff))
            + vocab * d_model + d_model)


def step_weight_bytes(*, bytes_per_weight: int = 2, **sizes) -> int:
    """Bytes of weights one decode step reads once:
    :func:`matrix_params` in the model's type and every
    linear-attention layer's float32 decay exponents. Embedding rows
    are left out (a row a slot)."""
    return (bytes_per_weight * matrix_params(**sizes)
            + 4 * sizes["la_layers"] * sizes["la_heads"])


def model_params(**sizes) -> int:
    """Every parameter of the model: what a step reads, the decay
    exponents and the embedding."""
    return (matrix_params(**sizes) + sizes["la_layers"] * sizes["la_heads"]
            + sizes["vocab"] * sizes["d_model"])


def standing_blocks(n: int, *, block: int, topk: int, init_blocks: int,
                    window: int, dense_len: int) -> tuple[int, int]:
    """``(blocks a K/V head attends, blocks it sees)`` for a query that
    sees ``n`` rows: all of them up to ``dense_len`` rows; past it the
    first ``init_blocks``, the blocks that hold the last ``window``
    rows and ``topk`` of the others (all of them where there are
    fewer)."""
    sees = (n - 1) // block + 1
    if n <= dense_len:
        return sees, sees
    first = max(n - window, 0) // block
    held = (sees - first) + min(init_blocks, first)
    return held + min(topk, sees - held), sees


def must_read_rows(n: int, *, block: int, topk: int, kernel: int,
                   stride: int, init_blocks: int, window: int,
                   dense_len: int, kv_heads: int, head_dim: int,
                   row_bytes: int) -> float:
    """What the mechanism must read of the attention layer's cache for
    ONE decode step of a request that sees ``n`` rows, in ROWS of
    ``row_bytes`` (K and V of a position for all K/V heads): every row
    up to ``dense_len``; past it the rows of the standing blocks (the
    last block as far as the query's own row) and the pooled keys of
    the windows that lie whole inside the n rows (``POOLED_BYTES`` a
    value, a key a K/V head and window), in rows' worth of bytes."""
    if n <= dense_len:
        return float(n)
    attended, _ = standing_blocks(
        n, block=block, topk=topk, init_blocks=init_blocks, window=window,
        dense_len=dense_len)
    rows = (attended - 1) * block + (n - 1) % block + 1
    windows = (n - kernel) // stride + 1
    pooled = windows * kv_heads * head_dim * POOLED_BYTES
    return rows + pooled / row_bytes
