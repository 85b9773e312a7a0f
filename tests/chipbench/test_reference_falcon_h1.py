"""chipbench/references/falcon_h1.py against itself: the blocked forms
(a stream's rows read, the head in blocks of the vocabulary, the
feed-forward in blocks of rows) against the whole forward, the
mechanisms by hand on small cases, and the controls."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.references import falcon_h1 as ref

Z = ref.Sizes(heads=4, head_dim=8, state=6, groups=2, conv=4, eps=1e-5,
              rope_theta=1e4, embedding_multiplier=2.0,
              lm_head_multiplier=0.5, attention_in_multiplier=0.9,
              attention_out_multiplier=0.7, key_multiplier=0.5,
              ssm_in_multiplier=0.8, ssm_out_multiplier=1.3,
              ssm_multipliers=(0.9, 1.1, 0.8, 1.2, 0.7),
              mlp_multipliers=(0.6, 1.4))
D, V, F = 32, 48, 40


def make_params(seed=0, layers=2):
    rng = np.random.default_rng(seed)
    f = lambda *s: jnp.asarray(rng.standard_normal(s) / np.sqrt(s[0]),
                               jnp.float32)
    wide, gn = 32, 12
    layer = lambda: {
        "ln1_s": jnp.ones((D,)), "ln2_s": jnp.ones((D,)),
        "wq": f(D, 4, 8), "wk": f(D, 2, 8), "wv": f(D, 2, 8),
        "wo": f(4, 8, D).reshape(4, 8, D),
        "ssm_win": f(D, 2 * wide + 2 * gn + 4),
        "ssm_conv_w": jnp.asarray(rng.uniform(-.5, .5, (4, wide + 2 * gn)),
                                  jnp.float32),
        "ssm_conv_b": jnp.asarray(rng.uniform(-.5, .5, (wide + 2 * gn,)),
                                  jnp.float32),
        "ssm_A_log": jnp.asarray(np.log(rng.uniform(1, 16, 4)), jnp.float32),
        "ssm_dt_bias": jnp.asarray(rng.standard_normal(4), jnp.float32),
        "ssm_D": jnp.ones((4,)), "ssm_norm_s": jnp.ones((wide,)),
        "ssm_wout": f(wide, D),
        "w_gate": f(D, F), "w_up": f(D, F), "w_down": f(F, D),
    }
    return {"emb": f(V, D), "layers": [layer() for _ in range(layers)],
            "lnf_s": jnp.ones((D,)), "head": f(V, D)}


def tokens(n, seed=1):
    return jnp.asarray(np.random.default_rng(seed).integers(0, V, (n,)),
                       jnp.int32)


def test_a_streams_rows_are_the_whole_forwards():
    params, toks = make_params(), tokens(24)
    whole = ref.forward(params, toks, z=Z)
    assert whole.shape == (24, V)
    got = ref.stream_logits(params, toks, 5, 8, z=Z)
    np.testing.assert_allclose(got, whole[5:13], atol=1e-6)


def test_padding_behind_a_stream_changes_no_row_before_it():
    params, toks = make_params(), tokens(24)
    padded = jnp.concatenate([toks[:17], jnp.zeros((7,), jnp.int32)])
    a = ref.stream_logits(params, toks, 0, 17, z=Z)
    b = ref.stream_logits(params, padded, 0, 17, z=Z)
    np.testing.assert_allclose(a, b, atol=1e-6)


@pytest.mark.parametrize("blocks", [1, 3, 8])
def test_the_head_in_blocks_is_the_head(monkeypatch, blocks):
    params = make_params()
    x = jnp.asarray(np.random.default_rng(2).standard_normal((5, D)),
                    jnp.float32)
    want = (ref.rms_norm(x, params["lnf_s"], Z.eps) @ params["head"].T
            * Z.lm_head_multiplier)
    monkeypatch.setattr(ref, "HEAD_BLOCKS", blocks)
    got = ref.head_logits(x, params["head"], params["lnf_s"], Z)
    np.testing.assert_allclose(got, want, atol=1e-6)


def test_the_feed_forward_in_blocks_of_rows(monkeypatch):
    params, toks = make_params(), tokens(24)
    whole = ref.forward(params, toks, z=Z)
    monkeypatch.setattr(ref, "FFN_ROWS", 8)
    np.testing.assert_allclose(ref.forward(params, toks, z=Z), whole,
                               atol=1e-6)


def test_the_recurrence_by_hand():
    """Two rows of one head: S_1 = dt_1 x_1 B_1^T; S_2 = a_2 S_1 + dt_2
    x_2 B_2^T; y_t = S_t C_t."""
    rng = np.random.default_rng(3)
    x, Bm, Cm = (rng.standard_normal(s) for s in ((2, 1, 3), (2, 1, 2),
                                                  (2, 1, 2)))
    dt, A = np.array([[0.5], [0.25]]), np.array([-2.0])
    S1 = dt[0, 0] * np.outer(x[0, 0], Bm[0, 0])
    S2 = np.exp(dt[1, 0] * A[0]) * S1 + dt[1, 0] * np.outer(x[1, 0],
                                                            Bm[1, 0])
    y, S = ref.ssm_rows(*(jnp.asarray(a, jnp.float32)
                          for a in (x, Bm, Cm, dt, A)),
                        jnp.zeros((1, 3, 2)), False)
    np.testing.assert_allclose(S[0], S2, atol=1e-6)
    np.testing.assert_allclose(y[0, 0], S1 @ Cm[0, 0], atol=1e-6)
    np.testing.assert_allclose(y[1, 0], S2 @ Cm[1, 0], atol=1e-6)


def test_a_groups_heads_share_b_and_c():
    """Heads 0, 1 read group 0's B and C; heads 2, 3 group 1's."""
    rng = np.random.default_rng(4)
    f = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)
    x, Bm, Cm = f(5, 4, 3), f(5, 2, 2), f(5, 2, 2)
    dt, A = jax.nn.softplus(f(5, 4)), -jnp.exp(f(4))
    y, _ = ref.ssm_rows(x, Bm, Cm, dt, A, jnp.zeros((4, 3, 2)), False)
    for h in range(4):
        g = h // 2
        yh, _ = ref.ssm_rows(x[:, h:h + 1], Bm[:, g:g + 1], Cm[:, g:g + 1],
                             dt[:, h:h + 1], A[h:h + 1],
                             jnp.zeros((1, 3, 2)), False)
        np.testing.assert_allclose(y[:, h], yh[:, 0], atol=1e-6)


def test_the_five_multipliers_lie_over_their_spans():
    v = np.asarray(ref.mup_vector(Z))
    assert v.shape == (2 * 32 + 2 * 12 + 4,)
    for (a, b), m in zip(((0, 32), (32, 64), (64, 76), (76, 88), (88, 92)),
                         Z.ssm_multipliers):
        assert (v[a:b] == np.float32(m)).all()


def test_the_mixers_state_is_what_a_cache_would_hold():
    """The conv's rows behind T rows are the in-projection's last three
    rows of [x | B | C]; S is the scan's."""
    params, toks = make_params(), tokens(11)
    f = params["layers"][0]
    h = jnp.asarray(np.random.default_rng(5).standard_normal((11, D)),
                    jnp.float32)
    out, S, conv = ref.ssm_mixer(h, f, Z, "float32")
    assert out.shape == (11, D) and S.shape == (4, 8, 6)
    proj = (h * Z.ssm_in_multiplier) @ f["ssm_win"] * ref.mup_vector(Z)
    np.testing.assert_allclose(conv, proj[8:, 32:88], atol=1e-5)
    # a causal mixer: the first 7 rows do not depend on the last 4
    out7, _, _ = ref.ssm_mixer(h[:7], f, Z, "float32")
    np.testing.assert_allclose(out[:7], out7, atol=1e-6)


def test_one_residual_add_for_both_mixers():
    params, toks = make_params(layers=1), tokens(9)
    f = params["layers"][0]
    x = params["emb"][toks] * Z.embedding_multiplier
    h = ref.rms_norm(x, f["ln1_s"], Z.eps)
    mixed = x + (ref.ssm_mixer(h, f, Z, "float32")[0]
                 + ref.attention_mixer(h, f, Z, "float32"))
    want = mixed + ref.feed_forward(ref.rms_norm(mixed, f["ln2_s"], Z.eps),
                                    f, Z, "float32")
    np.testing.assert_allclose(ref.layer_forward(x, f, z=Z), want, atol=5e-6)


@pytest.mark.parametrize("precision", ["bfloat16", "fp8", "int8", "s_bf16"])
def test_a_control_moves_the_logits(precision):
    params, toks = make_params(), tokens(24)
    whole = ref.forward(params, toks, z=Z)
    low = ref.forward(params, toks, z=Z, precision=precision)
    assert float(jnp.abs(low - whole).max()) > 1e-5
    assert float(jnp.abs(low - whole).max()) < 0.5


def test_the_state_control_rounds_the_state_alone():
    """``s_bf16``: products in float32, so a model whose state-space
    mixers are switched off (ssm_out_multiplier 0) reads as float32."""
    params, toks = make_params(), tokens(16)
    off = Z._replace(ssm_out_multiplier=0.0)
    np.testing.assert_array_equal(
        ref.forward(params, toks, z=off, precision="s_bf16"),
        ref.forward(params, toks, z=off))
    with pytest.raises(ValueError, match="unknown precision"):
        ref.forward(params, toks, z=Z, precision="fp4")
