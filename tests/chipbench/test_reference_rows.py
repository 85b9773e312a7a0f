"""The forms of chipbench/references/qwen3_next.py that take a stream of
tens of thousands of rows (a block of rows at a time, the experts over
the chosen pairs only) against the forms they replace on the timed
path, which stay in the file as what they are compared with: the whole
score matrix of a head, every row through every held expert.

Tolerance. Every form computes the same float32 sums in another order:
a product over a block's rows is blocked otherwise by XLA's CPU dot
than the same product over the whole stream (so not even the attention
blocks are equal to the last bit here, though no row's mathematics
changed), and the routed sum adds a row's ten pairs in the order of
their weights where the dense form adds 256 terms, 246 of them exactly
zero, expert by expert. A float32 rounding is 6e-8 of a value; sums of
some hundred terms of either sign differ by a few of them. The limit is
4e-6 of the result's largest entry, thirty times what was read (1.5e-7
to 2.5e-7 at this size), and three orders under the bfloat16 rounding
(4e-3) a lower-precision form would show."""

from __future__ import annotations

import numpy as np
import pytest

import test_serve_gdn as tg
from chipbench.references import qwen3_next as ref
from chipbench.runners import serve_gdn

RTOL = 4e-6
KW = dict(top_k=4, held_lo=0, key_heads=2, key_dim=8, rope_dims=4)
T = 96


@pytest.fixture(scope="module")
def made():
    import jax
    import jax.numpy as jnp

    params = serve_gdn.make_params(tg.TINY, 2**31 + 77)
    x = jax.random.normal(jax.random.PRNGKey(3), (1, T, 32), jnp.float32)
    return params, x


def close(got, want):
    got, want = np.asarray(got), np.asarray(want)
    assert got.shape == want.shape
    assert np.abs(got - want).max() <= RTOL * np.abs(want).max()


def f32(lp):
    import jax.numpy as jnp

    return {n: a.astype(jnp.float32) for n, a in lp.items()}


@pytest.mark.parametrize("rows", [96, 32, 8])
def test_attention_over_blocks_of_query_rows(made, rows):
    params, x = made
    f = f32(params["layers"][3])
    a = ref.rms_norm(x, f["ln1_s"])
    whole = ref.gated_attention(a, f, rope_dims=4, precision="float32")
    close(ref.gated_attention(a, f, rope_dims=4, precision="float32",
                              rows=rows), whole)


def test_a_block_of_rows_sees_no_later_key(made):
    """The last block's rows against the keys so far, changed nowhere
    by what comes after: the stream cut short gives the same rows."""
    params, x = made
    f = f32(params["layers"][3])
    a = ref.rms_norm(x, f["ln1_s"])
    long = ref.gated_attention(a, f, rope_dims=4, precision="float32",
                               rows=24)
    short = ref.gated_attention(a[:, :48], f, rope_dims=4,
                                precision="float32", rows=24)
    close(long[:, :48], short)


@pytest.mark.parametrize("rows", [48, 24])
def test_the_delta_rule_carried_from_block_to_block(made, rows):
    import jax.numpy as jnp

    params, x = made
    f = f32(params["layers"][0])
    a = ref.rms_norm(x, f["ln1_s"])
    kw = dict(key_heads=2, key_dim=8, precision="float32")
    whole = ref.gated_delta(a, f, **kw)
    carry = (jnp.zeros((1, 4, 8, 8)),
             jnp.zeros((1, 3, f["gdn_conv_w"].shape[1])))
    outs = []
    for r0 in range(0, T, rows):
        out, carry = ref.gated_delta(a[:, r0:r0 + rows], f, carry=carry, **kw)
        outs.append(out)
    close(jnp.concatenate(outs, axis=1), whole)
    # a state dropped between two blocks is not the same stream
    out, _ = ref.gated_delta(a[:, rows:2 * rows], f, carry=(
        jnp.zeros((1, 4, 8, 8)), jnp.zeros_like(carry[1])), **kw)
    assert np.abs(np.asarray(out) - np.asarray(whole[:, rows:2 * rows])
                  ).max() > 1e-3 * np.abs(np.asarray(whole)).max()


@pytest.mark.parametrize("capacity", [None, 2, 5, 96])
def test_the_experts_over_the_chosen_pairs_only(made, capacity):
    """``capacity`` 2 and 5 give an expert several passes (its rows at
    this size number up to some forty), 96 holds every row at once."""
    params, x = made
    lp = params["layers"][0]
    f = f32(lp)
    h = ref.rms_norm(x, f["ln2_s"])
    w = ref.route_weights(h, f["router"], 4, "float32")[..., :8]
    assert int((np.asarray(w) != 0).sum(1).max()) > 5  # more than a pass
    close(ref.experts_routed(h, lp, w, "float32", 4, capacity=capacity),
          ref.experts_sum(h, lp, w, "float32"))


def test_a_row_no_held_expert_was_chosen_for_adds_nothing(made):
    import jax.numpy as jnp

    params, x = made
    lp = params["layers"][0]
    f = f32(lp)
    h = ref.rms_norm(x, f["ln2_s"])
    w = ref.route_weights(h, f["router"], 4, "float32")[..., :8]
    w = w.at[:, 5:9].set(0.0)
    got = np.asarray(ref.experts_routed(h, lp, w, "float32", 4))
    assert np.abs(got[0, 5:9]).max() == 0.0
    close(got, ref.experts_sum(h, lp, w, "float32"))


@pytest.mark.parametrize("rows", [96, 48, 8])
def test_a_layer_by_blocks_of_rows_is_the_layer(made, rows):
    params, x = made
    for lp in params["layers"]:
        close(ref.layer_forward_rows(x, lp, rows=rows, **KW),
              ref.layer_forward(x, lp, **KW))


def test_row_block_is_the_largest_divisor_within_bounds():
    assert ref.row_block(4096) == 4096 and ref.row_block(49152) == 4096
    assert ref.row_block(4352) == 2176        # 17 x 256
    assert ref.row_block(96) == 96 and ref.row_block(97, 10) == 1


@pytest.mark.parametrize("name,stream,want", [
    # the mixed cell: a chat stream a whole block, a long one the cap
    ("q3next-80b-a3b-serve", (300, 200), (4096, 256)),
    ("q3next-80b-a3b-serve", (4096, 64), (4352, 256)),
    # the long cell: the round's three kinds of stream
    ("q3next-80b-a3b-serve-long", (464, 110), (4096, 256)),
    ("q3next-80b-a3b-serve-long", (19484, 362), (20480, 1024)),
    ("q3next-80b-a3b-serve-long", (27554, 724), (28672, 1024)),
    ("q3next-80b-a3b-serve-long", (32768, 1024), (33792, 1024)),
])
def test_the_shapes_the_reference_compiles_for(name, stream, want):
    import json

    cfg = json.loads((tg.REPO / "chipbench/configs"
                      / f"{name}.json").read_text())
    assert serve_gdn.reference_shape(cfg, *stream) == want
    with pytest.raises(ValueError):
        serve_gdn.reference_shape(cfg, cfg["program"]["max_context"], 1)
    with pytest.raises(ValueError):
        serve_gdn.reference_shape(cfg, 10, 1025)
