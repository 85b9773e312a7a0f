"""The plain reference describes the architecture the program runs: at
a tiny size on the CPU, in float32, the two forwards agree to rounding;
and the reference's window, grouping and precisions do what they say."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import weights
from chipbench.references import dense_transformer as ref


@pytest.fixture(scope="module")
def tiny():
    from mpistragglers_jl_tpu.models.transformer import TransformerConfig

    cfg = TransformerConfig(
        vocab=128, d_model=32, n_heads=4, n_kv_heads=2, n_layers=2,
        d_ff=64, attn="ulysses", attn_impl="reference", attn_window=8,
    )
    shapes = weights.transformer_shapes(
        d_model=32, n_heads=4, kv_heads=2, d_ff=64, n_layers=2, vocab=128,
        dtype=jnp.float32)
    params = weights.make_params(shapes, 2**31 + 3, d_model=32, n_layers=2)
    # biases away from zero, so that the reference's use of them is seen
    params = jax.tree.map(lambda a: a + 0.01 if a.ndim == 1 else a, params)
    return cfg, params


def test_forward_agrees_with_the_programs_dense_forward(tiny):
    from mpistragglers_jl_tpu.models.transformer import forward_dense

    cfg, params = tiny
    tokens = weights.make_tokens(5, (24,), cfg.vocab)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(forward_dense(params, tokens[None], cfg)[0])
    got = np.asarray(ref.stream_logits(params, tokens, 0, 24, window=8))
    assert np.abs(got - want).max() <= 2e-5 * np.abs(want).max()


def test_the_window_is_what_limits_attention(tiny):
    cfg, params = tiny
    tokens = weights.make_tokens(6, (24,), cfg.vocab)
    other = tokens.at[0].set((tokens[0] + 1) % cfg.vocab)
    a = np.asarray(ref.stream_logits(params, tokens, 0, 24, window=8))
    b = np.asarray(ref.stream_logits(params, other, 0, 24, window=8))
    # position 0 reaches position p through at most n_layers windows
    assert np.abs(a[:15] - b[:15]).max() > 0
    assert np.abs(a[16:] - b[16:]).max() == 0


def test_lower_precisions_move_the_logits_in_order(tiny):
    cfg, params = tiny
    tokens = weights.make_tokens(7, (24,), cfg.vocab)
    full = np.asarray(ref.stream_logits(params, tokens, 0, 24, window=8))
    err = {}
    for p in ("bfloat16", "int8", "fp8"):
        low = np.asarray(ref.stream_logits(params, tokens, 0, 24,
                                           window=8, precision=p))
        err[p] = np.abs(low - full).max()
    assert 0 < err["bfloat16"] < err["int8"] < err["fp8"]


def test_seeds_above_two_to_the_31_are_distinct_and_repeatable():
    a = weights.make_tokens(2**31 + 1, (8,), 1000)
    b = weights.make_tokens(2**31 + 1, (8,), 1000)
    c = weights.make_tokens(1, (8,), 1000)
    assert (a == b).all() and (a != c).any()


def test_weights_follow_the_stated_initialisation(tiny):
    _, params = tiny
    shapes = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                          params)
    fresh = weights.make_params(shapes, 9, d_model=32, n_layers=2)
    lp = fresh["layers"][0]
    assert float(jnp.abs(lp["b1"]).max()) == 0.0
    assert float(jnp.abs(lp["ln1_s"] - 1).max()) == 0.0
    assert float(lp["w1"].std()) == pytest.approx(32 ** -0.5, rel=0.1)
    assert float(lp["w2"].std()) == pytest.approx(
        32 ** -0.5 / 2.0, rel=0.1)


def test_shapes_written_out_match_the_programs_init_params(tiny):
    from mpistragglers_jl_tpu.models.transformer import init_params

    cfg, _ = tiny
    want = jax.eval_shape(lambda: init_params(cfg, 0))
    got = weights.transformer_shapes(
        d_model=cfg.d_model, n_heads=cfg.n_heads, kv_heads=cfg.kv_heads,
        d_ff=cfg.d_ff, n_layers=cfg.n_layers, vocab=cfg.vocab,
        dtype=cfg.dtype,
    )
    assert jax.tree.structure(want) == jax.tree.structure(got)
    assert all(jax.tree.leaves(jax.tree.map(
        lambda a, b: (a.shape, a.dtype) == (b.shape, b.dtype), want, got)))
