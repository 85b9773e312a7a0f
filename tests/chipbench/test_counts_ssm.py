"""chipbench/counts_ssm.py against hand-worked cases, and against the
runner's shapes at the published widths, built by shape alone."""

from __future__ import annotations

import json
import math
from pathlib import Path

import pytest

from chipbench import counts_ssm

REPO = Path(__file__).resolve().parents[2]
SSM = dict(ssm_heads=32, ssm_head_dim=128, ssm_state=256, ssm_groups=2)
PUBLISHED = dict(d_model=5120, n_heads=20, kv_heads=4, head_dim=128,
                 d_ff=21504, n_layers=6, vocab=261120, ssm_conv=4, **SSM)


def test_a_hand_worked_mixer():
    # 2 heads of 4 with a state of 3 in 1 group over a width of 8:
    # [z | x | B | C | dt] = 8 | 8 | 3 | 3 | 2 columns, conv over 14
    tiny = dict(ssm_heads=2, ssm_head_dim=4, ssm_state=3, ssm_groups=1)
    assert counts_ssm.ssm_proj_width(**tiny) == 24
    assert counts_ssm.ssm_conv_channels(**tiny) == 14
    typed, f32 = counts_ssm.ssm_params(8, ssm_conv=4, **tiny)
    assert typed == 8 * 24 + 8 * 8 + 5 * 14 + 8 and f32 == 6
    assert counts_ssm.ssm_state_bytes(ssm_heads=2, ssm_head_dim=4,
                                      ssm_state=3) == 4 * 24
    assert counts_ssm.attention_params(8, 4, 2, 2) == 2 * 8 * 2 * 6


def test_the_published_mixers_spans():
    assert counts_ssm.ssm_proj_width(**SSM) == 4096 + 4096 + 512 + 512 + 32
    assert counts_ssm.ssm_conv_channels(**SSM) == 5120
    assert counts_ssm.ssm_state_bytes(
        ssm_heads=32, ssm_head_dim=128, ssm_state=256) == 4_194_304


@pytest.mark.parametrize("part,millions", [
    ("attention", 31.46), ("ssm", 68.35), ("ffn", 330.30), ("layer", 430.12),
    ("vocabulary", 1336.93), ("model", 5254.59)])
def test_the_issues_arithmetic(part, millions):
    from chipbench.counts_moe import gated_mlp_params

    d = PUBLISHED["d_model"]
    got = {
        "attention": counts_ssm.attention_params(d, 20, 4, 128),
        "ssm": sum(counts_ssm.ssm_params(d, ssm_conv=4, **SSM)),
        "ffn": gated_mlp_params(d, 21504),
        "layer": sum(counts_ssm.layer_params(**PUBLISHED)),
        "vocabulary": 261120 * d,
        "model": counts_ssm.model_params(**PUBLISHED),
    }[part]
    assert got / 1e6 == pytest.approx(millions, abs=0.006)


def test_a_steps_bytes():
    weights = counts_ssm.step_weight_bytes(**PUBLISHED)
    # all but the embedding, in bfloat16, and 96 float32 values a layer
    assert weights == 2 * (counts_ssm.model_params(**PUBLISHED)
                           - 261120 * 5120 - 6 * 96) + 4 * 6 * 96
    assert weights / 1e9 == pytest.approx(7.835, abs=0.001)
    state = counts_ssm.step_state_bytes(
        slots=16, n_layers=6, ssm_heads=32, ssm_head_dim=128, ssm_state=256)
    assert state == 2 * 16 * 6 * 4_194_304
    assert state / 1e9 == pytest.approx(0.805, abs=0.001)


def test_a_chunks_operations():
    f = counts_ssm.chunk_flops(256, ssm_chunk=128, **PUBLISHED)
    # two operations a weight and row over the layers' matrices
    matrices = 6 * (31_457_280 + 5120 * 9248 + 4096 * 5120
                    + 3 * 5120 * 21504)
    assert f["dense"] == 2 * 256 * matrices
    assert f["dense"] / 1e12 == pytest.approx(1.32, abs=0.005)
    # two sub-chunks of 128: C B^T a group, the masked product and the
    # two state products a head
    sub = 2 * (2 * 128 * 128 * 256 + 32 * 128 * 128 * 128
               + 2 * 32 * 128 * 256 * 128)
    assert f["ssm"] == 6 * 2 * sub
    # a ragged chunk rounds up to whole sub-chunks
    assert counts_ssm.chunk_flops(130, ssm_chunk=128, **PUBLISHED)["ssm"] == (
        f["ssm"])
    assert counts_ssm.chunk_flops(64, ssm_chunk=128, **PUBLISHED)["ssm"] < (
        f["ssm"] / 4)


def test_the_counts_are_the_runners_shapes_at_the_published_widths(checkout):
    import jax
    import jax.numpy as jnp

    from chipbench.runners import serve_ssm

    cfg = json.loads((checkout / "chipbench/configs/"
                      "falcon-h1-34b-serve.json").read_text())
    z = serve_ssm.sizes(cfg)
    assert z == PUBLISHED
    shapes = serve_ssm.param_shapes(cfg)
    leaves = jax.tree.leaves(shapes)
    assert sum(math.prod(s.shape) for s in leaves) == (
        counts_ssm.model_params(**z))
    nbytes = sum(math.prod(s.shape) * s.dtype.itemsize for s in leaves)
    assert nbytes / 1e9 == pytest.approx(10.51, abs=0.005)
    # what a step reads: everything but the embedding
    emb = math.prod(shapes["emb"].shape) * 2
    assert nbytes - emb == counts_ssm.step_weight_bytes(**z)
    layer = shapes["layers"][0]
    typed, f32 = counts_ssm.layer_params(**z)
    assert sum(math.prod(s.shape) for s in layer.values()
               if s.dtype == jnp.float32) == f32
    assert sum(math.prod(s.shape) for s in layer.values()
               if s.dtype != jnp.float32) == typed
    # the state a slot keeps in a layer is the program's own leaf
    from mpistragglers_jl_tpu.models.transformer import ssm_zero_state

    state = jax.eval_shape(
        lambda: ssm_zero_state(serve_ssm.transformer_config(cfg), 1))
    assert math.prod(state["S"].shape) * 4 == counts_ssm.ssm_state_bytes(
        ssm_heads=32, ssm_head_dim=128, ssm_state=256)
    assert state["conv"].shape == (1, 3, 5120)
