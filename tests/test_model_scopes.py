"""The model's block names its own device time: the ``jax.named_scope``s
that models/transformer.py's shared functions open are in the lowered
train step (forward and backward), decode tick, prefill chunk and
first-token programs, every older scope keeps its name, the trainer's
``train.step`` span carries the flash kernels' block plan, and that
plan is the count a brute-force mask gives. Nothing compiles here: a
program is traced and lowered, and its debug text searched."""

from __future__ import annotations

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
        "tokens": 128, "flash_block": "64x64", "flash_grid_steps": 1,
        "flash_run_steps": 1, "flash_pairs_run": 64 * 64,
        "flash_pairs_band": plan["pairs_band"],
    }


def test_a_step_without_flash_kernels_counts_its_tokens_only():
    ring = TransformerConfig(vocab=97, d_model=32, n_heads=4, n_layers=1,
                             d_ff=64, attn="ring")
    assert tr._train_step_counts(ring, (4, 32)) == {"tokens": 128}


# -- the flash kernels' block plan -------------------------------------------


def test_the_block_plan_at_the_training_cells_shape():
    assert fa.block_plan(8192, 8192, causal=True, window=4096) == {
        "block": "1024x1024", "grid_steps": 64, "run_steps": 30,
        "pairs_run": 31_457_280, "pairs_band": 25_167_872,
    }


@pytest.mark.parametrize("Lq,Lk,causal,window,bq,bk", [
    (64, 64, True, 24, 16, 16),
    (96, 96, True, None, 32, 16),
    (48, 80, False, 20, 16, 16),
])
def test_the_block_plan_is_a_brute_force_count(Lq, Lk, causal, window,
                                               bq, bk):
    """Against the kernels' own two functions evaluated block by block:
    ``_block_run`` decides the steps, ``_block_mask`` the pairs."""
    plan = fa.block_plan(Lq, Lk, causal=causal, window=window,
                         block_q=bq, block_k=bk)
    run_steps = pairs = 0
    for i in range(Lq // bq):
        for j in range(Lk // bk):
            mask = fa._block_mask(i, j, bq, bk, causal, window)
            seen = bq * bk if mask is None else int(mask.sum())
            if fa._block_run(i, j, bq, bk, causal, window):
                run_steps += 1
                pairs += seen
            else:  # a block the grid skips holds no visible pair
                assert seen == 0
    q, k = np.arange(Lq)[:, None], np.arange(Lk)[None, :]
    band = np.ones((Lq, Lk), bool)
    if causal:
        band &= k <= q
    if window is not None:
        band &= q - k < window
    assert pairs == int(band.sum())
    assert plan == {
        "block": f"{bq}x{bk}", "grid_steps": (Lq // bq) * (Lk // bk),
        "run_steps": run_steps, "pairs_run": run_steps * bq * bk,
        "pairs_band": pairs,
    }
