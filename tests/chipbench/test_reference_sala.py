"""chipbench/references/minicpm_sala.py against itself: the form that
takes a long stream a block of rows at a time against the
whole-sequence form, the pooled keys and the picks against a loop
written out by hand, the rule for a window that lies across two blocks,
and the lower precision that the control puts in its place."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.references import minicpm_sala as ref

Z = ref.Sizes(8, 2, 4, 2, 1, 16, 32, 12.0, 1.4 / 32 ** 0.5, 4.0)


def _params(seed=0, D=32, H=4, Hkv=2, Dh=8, F=48, V=64):
    rng = np.random.default_rng(seed)
    w = lambda *s: jnp.asarray(rng.standard_normal(s) / np.sqrt(s[0]),
                               jnp.float32)
    ffn = lambda: {"ln2_s": jnp.ones((D,)), "w_gate": w(D, F),
                   "w_up": w(D, F), "w_down": w(F, D)}
    attn = {"ln1_s": jnp.ones((D,)), "wq": w(D, H, Dh), "wk": w(D, Hkv, Dh),
            "wv": w(D, Hkv, Dh), "wog": w(D, H, Dh), "wo": w(H, Dh, D),
            "qn_s": jnp.ones((Dh,)), "kn_s": jnp.ones((Dh,)), **ffn()}
    la = lambda li: {
        "ln1_s": jnp.ones((D,)), "la_wq": w(D, H, Dh), "la_wk": w(D, H, Dh),
        "la_wv": w(D, H, Dh), "la_wz": w(D, H * Dh),
        "la_qn_s": jnp.ones((Dh,)), "la_kn_s": jnp.ones((Dh,)),
        "la_norm_s": jnp.ones((H * Dh,)), "la_wo": w(H * Dh, D),
        "la_slope": jnp.asarray(2.0 ** (-8.0 * (np.arange(H) + 1) / H)
                                * (1 - li / 31 + 1e-5), jnp.float32),
        **ffn()}
    return {"emb": w(V, D), "layers": [attn, la(1), la(2), la(3)],
            "lnf_s": jnp.ones((D,)), "head": w(V, D)}


def test_rows_at_a_time_is_the_whole_sequence():
    params = _params()
    toks = jnp.asarray(np.random.default_rng(1).integers(0, 64, (96,)),
                       jnp.int32)
    whole, picks = ref.forward(params, toks, z=Z)
    for rows in (96, 32, 8):
        x = params["emb"][toks].astype(jnp.float32) * Z.scale_emb
        for lp in params["layers"]:
            x = ref.layer_forward_rows(x, lp, rows=rows, z=Z)
        got = ref.head_logits(x, params["head"], params["lnf_s"], Z)
        np.testing.assert_allclose(got, whole, atol=2e-6)
    assert ref.row_block(96, 50) == 48 and ref.row_block(28672) == 2048
    got = ref.stream_logits(params, toks, 40, 16, z=Z)
    np.testing.assert_allclose(got, whole[40:56], atol=2e-6)
    assert picks[0].shape == (96, 2, 12)


def test_pooled_keys_and_picks_against_a_loop_written_out():
    rng = np.random.default_rng(2)
    T, H, Hkv, D = 70, 4, 2, 8
    q = jnp.asarray(rng.standard_normal((T, H, D)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((T, Hkv, D)), jnp.float32)
    c = np.asarray(ref.pooled_keys(k, Z))
    assert c.shape == (34, Hkv, D)
    for j in (0, 7, 33):
        np.testing.assert_allclose(c[j], np.asarray(k)[2 * j:2 * j + 4].mean(0),
                                   atol=1e-6)
    picks = np.asarray(ref.block_picks(q, k, 0, Z))
    for t in (10, 31, 32, 45, 69):
        n = t + 1
        for h in range(Hkv):
            sees = (n - 1) // 8 + 1
            if n <= 32:
                want = set(range(sees))
            else:
                nw = (n - 4) // 2 + 1
                w = np.einsum("gd,jd->gj",
                              np.asarray(q)[t, 2 * h:2 * h + 2],
                              c[:nw, h]) / np.sqrt(D)
                p = np.exp(w - w.max(-1, keepdims=True))
                s = (p / p.sum(-1, keepdims=True)).sum(0)
                held = {0} | set(range((n - 16) // 8, sees))
                score = {}
                for b in set(range(sees)) - held:
                    score[b] = max(s[j] for j in range(nw)
                                   if 2 * j < 8 * (b + 1) and 2 * j + 4 > 8 * b)
                best = sorted(score, key=lambda b: (-score[b], b))[:2]
                want = held | set(best)
            assert set(np.flatnonzero(picks[t, h])) == want, (t, h)


def test_a_window_across_two_blocks_counts_for_both():
    """One key far larger than the rest at row 8 (the first of block
    1): the window of rows 6..9 holds it and begins in block 0, so
    blocks 0 AND 1 score high; with the query along it block 1 stands
    though none of ITS own windows (rows 8..11 onward) scores higher."""
    T, D = 64, 8
    k = np.zeros((T, 1, D), np.float32)
    k[:, 0, 1] = 0.01
    k[8, 0, 0] = 100.0
    q = np.zeros((T, 1, D), np.float32)
    q[:, 0, 0] = 1.0
    z = Z._replace(topk=1)
    picks = np.asarray(ref.block_picks(jnp.asarray(q), jnp.asarray(k), 0, z))
    # the query at row 63 sees 64 rows: block 0 and blocks 6, 7 are held
    assert picks[63, 0].tolist() == [True, True, False, False, False, False,
                                     True, True]
    # equal scores: the earlier block (all keys alike, the query too)
    flat = np.ones((T, 1, D), np.float32)
    even = np.asarray(ref.block_picks(jnp.asarray(flat), jnp.asarray(flat),
                                      0, z))
    assert even[63, 0].tolist() == [True, True, False, False, False, False,
                                    True, True]


def test_lower_precision_moves_the_logits_and_the_picks():
    params = _params(seed=3)
    toks = jnp.asarray(np.random.default_rng(4).integers(0, 64, (96,)),
                       jnp.int32)
    whole, picks = ref.forward(params, toks, z=Z)
    low, low_picks = ref.forward(params, toks, z=Z, precision="fp8")
    assert float(jnp.abs(low - whole).max()) > 1e-2
    assert (np.asarray(picks[0]) != np.asarray(low_picks[0])).any()
    half, _ = ref.forward(params, toks, z=Z, precision="bfloat16")
    assert 1e-4 < float(jnp.abs(half - whole).max()) < float(
        jnp.abs(low - whole).max())
