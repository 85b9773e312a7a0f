"""chipbench/counts_sala.py against hand-worked cases."""

from __future__ import annotations

import pytest

from chipbench import counts_sala

PUBLISHED = dict(block=64, topk=64, init_blocks=1, window=2048,
                 dense_len=8192)
WIDTHS = dict(d_model=4096, n_heads=32, kv_heads=2, head_dim=128,
              la_heads=32, la_head_dim=128, d_ff=16384, n_layers=4,
              la_layers=3, vocab=73448)


def test_the_models_parameters_are_issue_42s():
    # a minicpm4 layer 253.8M, a lightning-attn layer 285.2M, embedding
    # and head 601.7M: one period 1,711M
    ffn = 3 * 4096 * 16384
    attn = 3 * 4096 * 4096 + 2 * 4096 * 256 + 2 * 128 + 2 * 4096 + ffn
    la = (5 * 4096 * 4096 + 2 * 128 + 4096 + 32) + 2 * 4096 + ffn
    assert round(attn / 1e6, 1) == 253.8 and round(la / 1e6, 1) == 285.2
    total = attn + 3 * la + 2 * 73448 * 4096 + 4096
    assert counts_sala.model_params(**WIDTHS) == total
    assert round(total / 1e6) == 1711
    # a step reads all of it but the embedding; the decay exponents are
    # float32
    assert counts_sala.step_weight_bytes(**WIDTHS) == 2 * (
        total - 73448 * 4096 - 3 * 32) + 4 * 3 * 32


def test_a_slots_state_is_two_megabytes_a_layer():
    assert counts_sala.la_state_bytes(heads=32, head_dim=128) == 2097152
    assert counts_sala.step_state_bytes(
        slots=16, la_layers=3, heads=32, head_dim=128) == 2 * 16 * 3 * 2097152


@pytest.mark.parametrize("n,attended,sees", [
    (100, 2, 2),            # dense: every block it sees
    (8192, 128, 128),       # the last dense length
    (8193, 98, 129),        # rows 6145..8192: blocks 96..128 (33), block
                            # 0 and 64 more
    (8256, 97, 129),        # rows 6208..8255: blocks 97..128 (32)
    (8257, 98, 130),        # rows 6209..8256: blocks 97..129
    (20000, 98, 313),       # rows 17952..19999: blocks 280..312 (33)
    (20032, 97, 313),       # rows 17984..20031: blocks 281..312 (32)
])
def test_standing_blocks(n, attended, sees):
    assert counts_sala.standing_blocks(n, **PUBLISHED) == (attended, sees)


def test_fewer_others_than_the_top_k_all_stand():
    # 10 blocks seen, window 2 blocks (rows 63..78 -> blocks 7..9: 3),
    # block 0: 4 held, 6 others, top-8 takes them all
    assert counts_sala.standing_blocks(
        79, block=8, topk=8, init_blocks=1, window=16, dense_len=32) == (
        10, 10)


def test_what_a_step_must_read_of_a_long_request():
    kw = dict(kernel=32, stride=16, kv_heads=2, head_dim=128, row_bytes=528,
              **PUBLISHED)
    assert counts_sala.must_read_rows(5000, **kw) == 5000.0
    # 20,000 rows: 97 whole blocks and 32 rows of the last; 1,249
    # windows (rows 19968..19999 the last) of 2 x 128 values at 2 bytes
    assert counts_sala.must_read_rows(20000, **kw) == pytest.approx(
        97 * 64 + 32 + 1249 * 512 / 528)
    # a fifth of the rows the request has, and it stays there
    assert counts_sala.must_read_rows(28000, **kw) < 0.3 * 28000
