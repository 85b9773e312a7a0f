"""The model's block names its own device time: the ``jax.named_scope``s
that models/transformer.py's shared functions open are in the lowered
train step (forward and backward), decode tick, prefill chunk and
first-token programs, every older scope keeps its name, the trainer's
``train.step`` span carries the flash kernels' block plan, and that
plan is the count a brute-force mask gives. Nothing compiles here: a
program is traced and lowered, and its debug text searched."""

from __future__ import annotations

import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from mpistragglers_jl_tpu.models import serving
from mpistragglers_jl_tpu.models import transformer as tr
from mpistragglers_jl_tpu.models.serving import ServingScheduler
from mpistragglers_jl_tpu.models.transformer import (
    TransformerConfig,
    init_params,
    make_train_step,
)
from mpistragglers_jl_tpu.ops import flash_attention as fa
from mpistragglers_jl_tpu.ops.delta_rule import SUBCHUNK

DENSE = TransformerConfig(vocab=97, d_model=32, n_heads=4, n_kv_heads=2,
                          n_layers=2, d_ff=64, attn_window=32)
EXPERTS = dict(n_experts=8, experts_per_token=2, d_expert=16,
               shared_experts=1, norm="rmsnorm", norm_eps=1e-6,
               ffn="swiglu", tie_head=False, max_context=96)
# latent attention under four residual streams, one dense and one
# expert layer: the ``mla_*``, ``hc_mix`` and ``moe_*`` scopes
LATENT = TransformerConfig(
    vocab=97, d_model=32, n_heads=4, d_head=12, n_layers=2, d_ff=48,
    layer_mixers=("mla",) * 2, mla_q_rank=16, mla_kv_rank=16,
    mla_nope_dim=8, mla_rope_dim=4, mla_v_dim=8, hc_mult=4,
    layer_experts=(False, True), route_scale=2.0, **EXPERTS)
# a gated delta-rule layer beside a gated attention layer: ``gdn_*``
DELTA = TransformerConfig(
    vocab=97, d_model=32, n_heads=4, n_kv_heads=2, d_head=16, n_layers=2,
    d_ff=48, qk_norm=True, attn_gate=True, layer_mixers=("gdn", "attn"),
    gdn_key_heads=2, gdn_value_heads=4, gdn_key_dim=8, gdn_value_dim=8,
    gdn_conv=4, layer_experts=(True,) * 2, route_score="softmax",
    shared_gate=True, **EXPERTS)

BLOCK = ("embed", "attn_qkv", "attn_out", "ffn", "head")
OLD_TICK = ("decode_attn", "decode_mlp", "kv_page_gather",
            "kv_page_scatter")
OLD_LATENT = ("mla_q", "mla_kv", "mla_attn", "mla_out", "hc_mix",
              "moe_route", "moe_experts", "moe_shared")
OLD_DELTA = ("gdn_proj", "gdn_conv", "gdn_rule", "gdn_out")


def paths(lowered) -> list[list[str]]:
    """The name-stack paths of a lowered program's operations, each as
    its parts (``loc("jit(step)/jvp()/ffn/dot_general"(...))``; inside
    a scan's body a path starts at the body)."""
    text = lowered.as_text(debug_info=True)
    return [p.split("/") for p in set(re.findall(r'loc\("([^"]+)"', text))]


def scopes_of(lowered) -> set[str]:
    return {part for path in paths(lowered) for part in path}


@pytest.fixture(scope="module")
def train_step():
    cfg = TransformerConfig(
        vocab=97, d_model=32, n_heads=4, n_kv_heads=2, n_layers=2,
        d_ff=64, attn="ulysses", attn_impl="flash", attn_window=32)
    mesh = Mesh(np.asarray(jax.devices()[:1]).reshape(1, 1, 1),
                ("dp", "sp", "tp"))
    tokens = jnp.zeros((2, 64), jnp.int32)
    return make_train_step(cfg, mesh, lr=0.1), (
        init_params(cfg, seed=0), tokens, tokens)


@pytest.fixture(scope="module")
def train_paths(train_step):
    step, args = train_step
    return paths(step.lower(*args))


def _scheduler(cfg):
    return ServingScheduler(
        init_params(cfg, seed=1), cfg, slots=3, n_inner=4,
        quantize_kv=True, page_tokens=16, prompt_chunk=16, max_prompt=64)


@pytest.fixture(scope="module")
def programs():
    """{model: {program: scopes}} of the serving programs of the three
    tiny models, lowered against a scheduler's own state."""
    out = {}
    for name, cfg in (("dense", DENSE), ("latent", LATENT),
                      ("delta", DELTA)):
        sched = _scheduler(cfg)
        arena = serving._fresh_cache(cfg, 1, sched.Lmax, True)
        chunk = jnp.zeros((1, sched.C), jnp.int32)
        valid = (jnp.int32(sched.C),) if cfg.state_layers else ()
        hidden = jnp.zeros((1, sched.C, cfg.d_model), cfg.dtype)
        out[name] = {
            "tick": scopes_of(sched.lower_tick()),
            "chunk": scopes_of(sched._extend.lower(
                sched.params, chunk, arena, jnp.int32(16), *valid)),
            "first_token": scopes_of(sched._finish.lower(
                sched.params, arena, hidden, jnp.int32(20), jnp.int32(16),
                jax.random.key(0))),
        }
    return out


@pytest.mark.parametrize("backward", [False, True],
                         ids=["forward", "backward"])
@pytest.mark.parametrize("scope", BLOCK + ("loss",))
def test_the_train_step_has_each_scope_forward_and_backward(
        train_paths, scope, backward):
    held = [p for p in train_paths if scope in p]
    assert held, scope
    # value_and_grad wraps the name stack, the scope's name stays a
    # part of its own: ``jvp()`` forward, ``transpose(jvp())`` backward
    assert any(
        any(part.startswith("transpose(") for part in p) == backward
        for p in held)


def test_the_update_has_its_scope_outside_the_gradient(train_paths):
    held = [p for p in train_paths if "sgd_update" in p]
    assert held and not any(
        part.startswith(("jvp(", "transpose(")) for p in held for part in p)


@pytest.mark.parametrize("scope", BLOCK)
@pytest.mark.parametrize("model", ["dense", "latent", "delta"])
def test_the_tick_has_each_scope_of_the_block(programs, model, scope):
    if model == "latent" and scope in ("attn_qkv", "attn_out"):
        # latent layers only: their projections are ``mla_*``'s
        assert scope not in programs[model]["tick"]
    else:
        assert scope in programs[model]["tick"]


@pytest.mark.parametrize("scope", ("embed", "attn_qkv", "attn_out", "ffn",
                                   "chunk_attn"))
def test_the_dense_chunk_has_each_scope(programs, scope):
    assert scope in programs["dense"]["chunk"]
    assert "head" not in programs["dense"]["chunk"]  # one row a request


@pytest.mark.parametrize("model", ["dense", "latent", "delta"])
def test_the_first_token_program_has_the_head(programs, model):
    assert "head" in programs[model]["first_token"]


@pytest.mark.parametrize("model,scope", [
    *(("dense", s) for s in OLD_TICK),
    *(("latent", s) for s in OLD_LATENT + ("decode_mlp",)),
    *(("delta", s) for s in OLD_DELTA + ("decode_attn", "moe_experts")),
])
def test_every_older_scope_of_the_tick_keeps_its_name(programs, model,
                                                     scope):
    assert scope in programs[model]["tick"]


@pytest.mark.parametrize("model,scope", [
    ("latent", "mla_attn"), ("latent", "chunk_attn"), ("latent", "hc_mix"),
    ("latent", "moe_experts"), ("delta", "gdn_rule"),
    ("delta", "chunk_attn"),
])
def test_older_scopes_of_the_chunk_keep_their_names(programs, model, scope):
    assert scope in programs[model]["chunk"]


def test_the_delta_rule_kernel_is_called_under_gdn_rule():
    """At head sizes of whole lane tiles a chunk's rows go through the
    kernel (ops/delta_rule.py): in the lowered GROUPED prefill program
    the call of the jitted kernel function carries ``gdn_rule`` on its
    path (the compiler inlines the callee, whose own paths start at
    ``delta_rule/pallas_call``, under the call's), which is where
    ``gdn_prefill_share_pct`` finds the kernel's time."""
    cfg = dataclasses.replace(DELTA, gdn_key_heads=1, gdn_value_heads=2,
                              gdn_key_dim=128, gdn_value_dim=128,
                              max_context=2 * SUBCHUNK + 64)
    sched = ServingScheduler(
        init_params(cfg, seed=1), cfg, slots=3, n_inner=4, quantize_kv=True,
        page_tokens=16, prompt_chunk=SUBCHUNK, max_prompt=2 * SUBCHUNK)
    n = sched._group
    assert n > 1
    arenas = tuple(serving._fresh_cache(cfg, 1, sched.Lmax, True)
                   for _ in range(n))
    held = paths(sched._extend_group.lower(
        sched.params, jnp.zeros((n, sched.C), jnp.int32), arenas,
        jnp.zeros((n,), jnp.int32), jnp.full((n,), sched.C, jnp.int32)))
    calls = [p for p in held if p[-1] == "jit(delta_rule_call)"]
    assert calls and all("gdn_rule" in p for p in calls)
    assert ["delta_rule", "pallas_call"] in held


def test_the_step_kernel_is_called_under_gdn_rule():
    """And one token of the tick goes through the kernel that updates S
    where it lies: in the lowered tick the call of its jitted function
    carries ``gdn_rule`` on its path, which is where ``gdn_share_pct``
    and ``gdn_state_hbm_pct`` find its time."""
    cfg = dataclasses.replace(DELTA, gdn_key_heads=1, gdn_value_heads=2,
                              gdn_key_dim=128, gdn_value_dim=128)
    held = paths(_scheduler(cfg).lower_tick())
    calls = [p for p in held if p[-1] == "jit(delta_step_call)"]
    assert calls and all("gdn_rule" in p for p in calls)
    assert ["delta_rule_step", "pallas_call"] in held


def test_the_feed_forward_nests_under_the_ticks_older_scope():
    nested = [p for p in paths(_scheduler(DENSE).lower_tick())
              if "ffn" in p]
    assert nested and all(
        p.index("decode_mlp") < p.index("ffn") for p in nested)


# -- the trainer's host span -------------------------------------------------


def test_what_make_train_step_returns_still_lowers_as_jit_step(train_step):
    step, args = train_step
    text = step.lower(*args).as_text()
    assert "module @jit_step" in text


def test_train_step_span_carries_the_counts(train_step, monkeypatch):
    seen = []

    class Span:
        def __init__(self, name, **args):
            seen.append((name, args))

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return None

    monkeypatch.setattr(tr, "annotate", Span)
    step, (params, tokens, targets) = train_step
    for _ in range(2):
        params, loss = step(params, tokens, targets)
    assert np.isfinite(float(loss))
    (name, args), second = seen
    assert second == (name, args) and name == "train.step"
    plan = fa.block_plan(64, 64, causal=True, window=32)
    assert args == {
        "tokens": 128, "flash_block": "64x64", "flash_tile": "64x64",
        "flash_grid_steps": 1, "flash_tile_steps": 1, "flash_run_steps": 1,
        "flash_interior_steps": 0, "flash_pairs_run": 64 * 64,
        "flash_pairs_band": plan["pairs_band"],
    }


def test_a_step_without_flash_kernels_counts_its_tokens_only():
    ring = TransformerConfig(vocab=97, d_model=32, n_heads=4, n_layers=1,
                             d_ff=64, attn="ring")
    assert tr._train_step_counts(ring, (4, 32)) == {"tokens": 128}


# -- the flash kernels' block plan -------------------------------------------


def test_the_block_plan_at_the_training_cells_shape():
    """2048 x 2048 blocks computed in 512 x 512 tiles: of the grid's 16
    steps 10 fetch a block, and of a sweep's 256 tiles 108 run, 84 of
    them with no mask (in tiles of 1024 it would be 30 and 18, with
    31,457,280 pairs run)."""
    assert fa.block_plan(8192, 8192, causal=True, window=4096) == {
        "block": "2048x2048", "tile": "512x512", "grid_steps": 16,
        "tile_steps": 256, "run_steps": 108, "interior_steps": 84,
        "pairs_run": 28_311_552, "pairs_band": 25_167_872,
    }


@pytest.mark.parametrize("head_dim,itemsize", [(128, 4), (256, 2)])
def test_the_block_plan_follows_the_kernels_blocks(head_dim, itemsize):
    """Where the kernels take 1024-blocks (float32, a wider head: the
    2048-blocks were read in bfloat16 at head size 128 alone) the plan
    says so, from the same ``_blocks``; the tiles and what is counted
    in them stay."""
    at_cell = fa.block_plan(8192, 8192, causal=True, window=4096)
    plan = fa.block_plan(8192, 8192, causal=True, window=4096,
                         head_dim=head_dim, itemsize=itemsize)
    assert fa._blocks(8192, 8192, head_dim, itemsize, fa._BLOCK,
                      fa._BLOCK) == (1024, 1024, (512, 512))
    assert plan == {**at_cell, "block": "1024x1024", "grid_steps": 64}


def test_the_block_plan_in_the_tiles_of_the_whole_block_kernels(monkeypatch):
    """1024 x 1024 blocks computed whole, as the kernels were until
    PR 38: the cell's sweep runs 30 of its 64 blocks, 18 of them
    interior."""
    monkeypatch.setattr(fa, "_TILE", 1024)
    plan = fa.block_plan(8192, 8192, causal=True, window=4096,
                         block_q=1024, block_k=1024)
    assert plan == {
        "block": "1024x1024", "tile": "1024x1024", "grid_steps": 64,
        "tile_steps": 64, "run_steps": 30, "interior_steps": 18,
        "pairs_run": 31_457_280, "pairs_band": 25_167_872,
    }


@pytest.mark.parametrize("Lq,Lk,causal,window,bq,bk,tile", [
    (64, 64, True, 24, 16, 16, 16),
    (64, 64, True, 24, 16, 16, 8),
    (96, 96, True, None, 32, 16, 8),
    (48, 80, False, 20, 16, 16, 16),
    (48, 80, False, 20, 16, 16, 4),
    (32, 32, False, None, 16, 16, 8),
])
def test_the_block_plan_is_a_brute_force_count(Lq, Lk, causal, window,
                                               bq, bk, tile, monkeypatch):
    """Against the kernels' own functions evaluated tile by tile:
    ``_block_run`` decides the steps, ``_block_mask`` the pairs, and a
    tile counts as interior exactly where its mask hides nothing, pair
    by pair."""
    monkeypatch.setattr(fa, "_TILE", tile)
    plan = fa.block_plan(Lq, Lk, causal=causal, window=window,
                         block_q=bq, block_k=bk)
    tq, tk = fa._compute_tile(bq, bk)
    assert (tq, tk) == (min(tile, bq), min(tile, bk))
    run_steps = interior = pairs = 0
    for i in range(Lq // tq):
        for j in range(Lk // tk):
            mask = fa._block_mask(i, j, tq, tk, causal, window)
            seen = tq * tk if mask is None else int(mask.sum())
            if mask is not None:  # the dk/dv kernel's is the same, turned
                turned = fa._block_mask(i, j, tq, tk, causal, window,
                                        transposed=True)
                assert np.array_equal(np.asarray(turned).T,
                                      np.asarray(mask))
            full = bool(fa._block_interior(i, j, tq, tk, causal, window))
            assert full == (seen == tq * tk)
            if fa._block_run(i, j, tq, tk, causal, window):
                run_steps += 1
                interior += full
                pairs += seen
            else:  # a tile the kernels skip holds no visible pair
                assert seen == 0
    q, k = np.arange(Lq)[:, None], np.arange(Lk)[None, :]
    band = np.ones((Lq, Lk), bool)
    if causal:
        band &= k <= q
    if window is not None:
        band &= q - k < window
    assert pairs == int(band.sum())
    assert plan == {
        "block": f"{bq}x{bk}", "tile": f"{tq}x{tk}",
        "grid_steps": (Lq // bq) * (Lk // bk),
        "tile_steps": (Lq // tq) * (Lk // tk),
        "run_steps": run_steps, "interior_steps": interior,
        "pairs_run": run_steps * tq * tk, "pairs_band": pairs,
    }
