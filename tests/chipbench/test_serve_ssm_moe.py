"""The ``serve_ssm_moe`` kind: rehearsed on the CPU at tiny size from a
throw-away checkout (as test_serve_ssm.py does for its kind), its
shapes against the program's ``init_params``, its configuration file
against the catalog row it was drawn from, its counts against the
issue's arithmetic, a state lost at a chunk boundary and a slot's state
not reset against the comparison, and its new per-layer reader on
hand-made device events (tests/test_granite_block.py holds the block
itself to the reference)."""

from __future__ import annotations

import json
import math
import time
import types
from pathlib import Path

import pytest

from chipbench import control
from chipbench import run as bench

REPO = Path(__file__).resolve().parents[2]
CATALOG = Path("/opt/skills/guides/model-configs/architectures.jsonl")
NAME, CELL_NAME, MIX = ("granite-4.0-h-small-serve", "serve_granite4h_chat",
                        "chat_backlog")
NEW = "ssm_proj_hbm_pct"
# every list the issue names for the cell, the tail's five apart
LISTED = (
    "serve_tok_s", "decode_step_hbm_pct", "serve_device_idle_pct",
    "idle_in_admit_pct", "idle_in_decode_pct", "idle_in_harvest_pct",
    "idle_outside_step_pct", "slot_occupancy_pct", "admitting_slots_pct",
    "tick_gather_share_pct", "chunks_per_prefill_program",
    "tick_scoped_pct", "head_share_pct", "head_hbm_pct",
    "decode_attn_share_pct", "attn_rows_hbm_pct", "ssm_share_pct",
    "ssm_state_hbm_pct", "ssm_prefill_share_pct", "moe_share_pct",
    "moe_experts_hbm_pct", "experts_hit_pct", "experts_local_pct", NEW)
WITH_THE_TAIL = ("prefill_share_pct", "itl_p50_ms", "first_token_wait_ms",
                 "prefill_scoped_pct", "chunk_attn_share_pct")

# the published keys at a size the CPU runs in seconds: (mamba, mamba,
# attention, mamba), 4 state-space heads of 32 at a state of 8 (four a
# lane tile: the step kernel's packed layout, interpreted here), 8
# experts of which 4 are held, 3 a token. The residual multiplier
# nearer one than the published 0.22, so that at this width a lost
# state moves a served token.
TINY = {
    "kind": "serve_ssm_moe", "reference": "granitemoehybrid",
    "attention_bias": False, "attention_multiplier": 0.125,
    "embedding_multiplier": 0.5, "hidden_act": "silu", "hidden_size": 64,
    "intermediate_size": 32,
    "layer_types": ["mamba", "mamba", "attention", "mamba"],
    "logits_scaling": 2, "mamba_chunk_size": 8, "mamba_conv_bias": True,
    "mamba_d_conv": 4, "mamba_d_head": 32, "mamba_d_state": 8,
    "mamba_expand": 2, "mamba_n_groups": 1, "mamba_n_heads": 4,
    "mamba_proj_bias": False, "max_position_embeddings": 131072,
    "model_type": "granitemoehybrid", "normalization_function": "rmsnorm",
    "num_attention_heads": 4, "num_experts_per_tok": 3,
    "num_hidden_layers": 4, "num_key_value_heads": 2,
    "num_local_experts": 4, "position_embedding_type": "nope",
    "residual_multiplier": 2.0, "rms_norm_eps": 1e-5, "rope_scaling": None,
    "rope_theta": 10000, "shared_intermediate_size": 64,
    "tie_word_embeddings": True, "vocab_size": 128,
    "torch_dtype": "float32", "router_experts": 8, "experts_held": [0, 4],
    "moe_intermediate_size": 32, "num_experts": 4, "num_dense_layers": 0,
    "program": {"slots": 4, "n_inner": 4, "quantize_kv": True,
                "page_tokens": 8, "prompt_chunk": 16, "max_prompt": 64,
                "max_context": 96, "attn": "ulysses",
                "attn_impl": "reference"},
    # set from this size's own readings (float32 weights), see
    # test_control_in_lower_precision_fails_a_limit
    "limits": {"logit_gap_worst": 2e-3, "logit_gap_mean": 2e-5,
               "state_bfloat16_share": 0.5},
}
CELL = "tiny_serve_ssm_moe"


@pytest.fixture(scope="module")
def moe_root(tmp_path_factory):
    """_tiny.py's throw-away checkout with one more configuration and
    cell dropped in, of the new kind, reporting what the committed cell
    of this kind reports."""
    import _tiny

    root = _tiny.make_tiny_checkout(
        tmp_path_factory.mktemp("chipbench_ssm_moe"))
    (root / "chipbench/configs/tiny-serve-ssm-moe.json").write_text(
        json.dumps(TINY))
    manifest = json.loads((root / "BENCHMARK.json").read_text())
    manifest["configs"].append({
        "name": "tiny-serve-ssm-moe", "source": "tests/chipbench",
        "file": "chipbench/configs/tiny-serve-ssm-moe.json", "reduced": [],
        "why": "throw-away"})
    manifest["workloads"].append({
        "name": CELL, "config": "tiny-serve-ssm-moe",
        "traffic": "tiny_backlog", "chips": 1, "why": "throw-away"})
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        if CELL_NAME in m.get("workloads", ()):
            m["workloads"].append(CELL)
    (root / "BENCHMARK.json").write_text(json.dumps(manifest))
    return root


def one_run(root, trace, seed=2**31 + 11):
    return bench.run_cell(root, CELL, seed, 0.6, trace, require_chip=False,
                          t_start=time.perf_counter())


def test_result_line_of_the_new_kind(moe_root, capsys):
    result = one_run(moe_root, False)
    assert set(result) == {"correct", "attempted", "failed", "metrics",
                           "device"}
    assert result["correct"] is True
    assert result["attempted"] > 0 and result["failed"] == 0
    manifest = json.loads((moe_root / "BENCHMARK.json").read_text())
    listed = {m["name"] for m in manifest["end_to_end"]
              if CELL in m.get("workloads", [CELL])}
    assert set(result["metrics"]) == listed >= {"setup_s", "serve_tok_s"}
    json.dumps(result)
    out = capsys.readouterr().out
    # four heads of 32 share a lane tile: the step kernel is routed
    assert "note ssm_step_kernel_routed True" in out
    assert "note int8_decode_kernel_routed" in out
    assert "experts_hit_mean" in out and "note step_bytes " in out


def test_traced_run_on_the_cpu_reports_no_device_number(moe_root):
    result = one_run(moe_root, True)
    assert result["correct"] is True
    # no chip: the readers of the device trace and of the program's
    # spans in it find nothing and leave their metric out
    assert set(result["metrics"]) <= {"slot_occupancy_pct", "itl_p50_ms"}
    assert "slot_occupancy_pct" in result["metrics"]


def test_a_broken_timed_path_is_not_correct(moe_root, monkeypatch):
    from mpistragglers_jl_tpu.models.serving import ServingScheduler

    real = ServingScheduler._decode_scan_fetch
    monkeypatch.setattr(
        ServingScheduler, "_decode_scan_fetch",
        lambda self: (real(self) + 1) % self.cfg.vocab)
    assert one_run(moe_root, False)["correct"] is False


def _zeroing(real, leaves):
    """``real``'s programs with the named state leaves of the arena
    zeroed before every chunk."""
    import jax.numpy as jnp

    def factory(*a):
        chunk = real(*a)

        def run(params, tokens, cache, *rest, **kw):
            zero = lambda c: [
                {kk: jnp.zeros_like(v) if kk in leaves else v
                 for kk, v in cl.items()} for cl in c]
            cache = (zero(cache) if isinstance(cache[0], dict)
                     else tuple(zero(c) for c in cache))
            return chunk(params, tokens, cache, *rest, **kw)

        return run

    return factory


@pytest.mark.parametrize("leaves", [("S",), ("conv",)])
def test_a_state_lost_at_a_chunk_boundary_is_not_correct(moe_root,
                                                         monkeypatch,
                                                         leaves):
    """A program that starts every prefill chunk from a zero S, or
    from no conv rows, fails the comparison."""
    from mpistragglers_jl_tpu.models import serving

    monkeypatch.setattr(serving, "_extend_chunk_dense",
                        _zeroing(serving._extend_chunk_dense, leaves))
    monkeypatch.setattr(serving, "_extend_chunk_group",
                        _zeroing(serving._extend_chunk_group, leaves))
    assert one_run(moe_root, False)["correct"] is False


def test_a_slot_that_keeps_its_last_requests_state_is_not_correct(
        moe_root, monkeypatch):
    """Placement that writes the attention layer's rows into the slot's
    pages and leaves the slot's S and conv rows, in the three layers
    that have nothing else, as the last request left them."""
    from mpistragglers_jl_tpu.models import serving

    real = serving._place_paged

    def forgetful(*a):
        place = real(*a)

        def run(caches, ring, *rest):
            ring = [{kk: (caches[li][kk][rest[5]][None]
                          if kk in serving.STATE_LEAVES else v)
                     for kk, v in r.items()} for li, r in enumerate(ring)]
            return place(caches, ring, *rest)

        return run

    monkeypatch.setattr(serving, "_place_paged", forgetful)
    assert one_run(moe_root, False)["correct"] is False


def test_a_program_that_keeps_s_in_bfloat16_is_not_correct(
        moe_root, monkeypatch, capsys):
    """The step kernel's S rounded to bfloat16 after every step: every
    served token is still the reference's best or near it, and the
    one number that looks at S itself fails the run."""
    import jax.numpy as jnp

    from mpistragglers_jl_tpu.models import transformer

    real = transformer.ssm_step

    def rounding(*a):
        o, S = real(*a)
        return o, S.astype(jnp.bfloat16).astype(S.dtype)

    monkeypatch.setattr(transformer, "ssm_step", rounding)
    assert one_run(moe_root, False)["correct"] is False
    out = capsys.readouterr().out
    assert "check served_state_bfloat16_share: 1 limit 0.5 FAILED" in out
    assert out.count("FAILED") == 1


@pytest.mark.parametrize("precision", ["fp8", "int8", "s_bf16"])
def test_control_in_lower_precision_fails_a_limit(moe_root, precision):
    """Rounded products fail the limits on the logits and leave S in
    float32; a state kept in bfloat16 ranks every token as the float32
    reference does and fails the one limit that looks at S."""
    row = control.readings(moe_root, CELL, 7, 0.3, [precision],
                           require_chip=False)
    assert row["correct"] is True
    sound, low = row["sound"], row["control"][precision]
    limit = TINY["limits"]
    for name, most in limit.items():
        assert sound["served_" + name.replace("logit", "token_logit")] \
            <= most, name
    assert sound["served_state_bfloat16_share"] < 0.01
    failed = {name for name, most in limit.items() if low[name] > most}
    if precision == "s_bf16":
        assert failed == {"state_bfloat16_share"}
        assert low["state_bfloat16_share"] == 1.0
    else:
        assert failed and "state_bfloat16_share" not in failed
        assert low["state_gap"] > 10 * 1e-5


def test_shapes_are_the_programs_own():
    import jax
    import numpy as np

    from chipbench.runners import serve_ssm_moe
    from mpistragglers_jl_tpu.models.transformer import init_params

    model = serve_ssm_moe.transformer_config(TINY)
    params = init_params(model, seed=0)
    want = jax.tree.map(lambda a: (a.shape, a.dtype), params)
    got = jax.tree.map(lambda s: (s.shape, s.dtype),
                       serve_ssm_moe.param_shapes(TINY))
    assert got == want
    made = serve_ssm_moe.make_params(TINY, 2**31 + 5)
    assert jax.tree.map(lambda a: (a.shape, a.dtype), made) == want
    for lp, kind in zip(made["layers"], TINY["layer_types"]):
        assert ("ssm_win" in lp) == (kind == "mamba")
        assert ("wq" in lp) == (kind == "attention")
        for name in lp:
            if name.endswith("_s") or name == "ssm_D":
                assert float(abs(lp[name] - 1).max()) == 0.0
        if kind != "mamba":
            continue
        A = np.exp(np.asarray(lp["ssm_A_log"]))
        assert (A >= 1).all() and (A <= 16).all()
        dt = np.log1p(np.exp(np.asarray(lp["ssm_dt_bias"])))
        assert (dt >= 1e-3 * 0.999).all() and (dt <= 0.1 * 1.001).all()
        for name in ("ssm_conv_w", "ssm_conv_b"):
            w = np.asarray(lp[name])
            assert -0.5 <= w.min() < -0.2 and 0.2 < w.max() <= 0.5
    assert model.layer_mixers == ("ssm", "ssm", "attn", "ssm")
    assert [model.state(li) for li in range(4)] == [True, True, False, True]
    assert [model.rows(li) for li in range(4)] == [False, False, True, False]
    assert (model.ssm_heads, model.ssm_head_dim, model.ssm_state,
            model.ssm_groups, model.ssm_conv, model.ssm_chunk) == (
        4, 32, 8, 1, 4, 8)
    assert (model.n_experts, model.experts_held, model.experts_per_token,
            model.expert_width(), model.shared_experts,
            model.route_score) == (8, (0, 4), 3, 32, 2, "softmax")
    assert model.tie_head and not model.rope_at(2)


def test_weights_draw_gives_every_seed_one_draws_values():
    """With the key, as the committed file has it: two seeds' weights
    are one draw's, the tied embedding's rows in two orders; without
    it, each seed its own."""
    import numpy as np

    from chipbench.runners import serve_ssm_moe

    drawn = {**TINY, "weights_draw": {"seed": 5}}
    a, b = (serve_ssm_moe.make_params(drawn, s) for s in (11, 2**31 + 12))
    wa, wb = (np.asarray(p["layers"][0]["ssm_win"]) for p in (a, b))
    assert (wa == wb).all()
    ea, eb = (np.asarray(p["emb"]) for p in (a, b))
    assert not (ea == eb).all()
    key = lambda e: sorted(map(tuple, e))
    assert key(ea) == key(eb)
    c, d = (serve_ssm_moe.make_params(TINY, s) for s in (11, 12))
    assert not (np.asarray(c["layers"][0]["ssm_win"])
                == np.asarray(d["layers"][0]["ssm_win"])).all()


CONSTANTS = {
    "embedding_multiplier": ("emb_scale", lambda v: v),
    "attention_multiplier": ("attn_scale", lambda v: v),
    "residual_multiplier": ("residual_scale", lambda v: v),
    "logits_scaling": ("head_scale", lambda v: 1.0 / v),
}


@pytest.mark.parametrize("key", sorted(CONSTANTS))
def test_every_constant_reaches_program_and_reference(key):
    """Moved in the file, a constant moves in the program's
    configuration and in the reference's ``Sizes``."""
    from chipbench.references import granitemoehybrid as ref
    from chipbench.runners import serve_ssm_moe

    moved = TINY[key] * 4
    cfg = {**TINY, key: moved}
    model, z = (serve_ssm_moe.transformer_config(cfg),
                serve_ssm_moe.reference_sizes(ref, cfg))
    field, as_program = CONSTANTS[key]
    assert getattr(model, field) == as_program(moved)
    assert getattr(z, key) == moved


@pytest.mark.parametrize("key,value", [
    ("attention_bias", True), ("mamba_conv_bias", False),
    ("mamba_proj_bias", True), ("position_embedding_type", "rope"),
    ("tie_word_embeddings", False), ("normalization_function", "layernorm"),
    ("layer_types", ["mamba"]), ("mamba_expand", 3),
    ("experts_held", [0, 3]), ("shared_intermediate_size", 48)])
def test_a_block_the_runner_is_not_written_for_is_refused(key, value):
    from chipbench.runners import serve_ssm_moe

    with pytest.raises(ValueError):
        serve_ssm_moe.transformer_config({**TINY, key: value})


def test_published_widths_build_by_shape_alone(checkout):
    """4,962.7M parameters, 9.93 GB in bfloat16: the runner's shapes,
    the counts' arithmetic and ISSUE 51's agree."""
    import jax

    from chipbench import counts_ssm_moe
    from chipbench.runners import serve_ssm_moe

    cfg = json.loads((checkout / "chipbench/configs"
                      / f"{NAME}.json").read_text())
    shapes = serve_ssm_moe.param_shapes(cfg)
    n = sum(math.prod(s.shape) for s in jax.tree.leaves(shapes))
    z = serve_ssm_moe.sizes(cfg)
    parts = counts_ssm_moe.model_params(**z)
    assert n == parts["total"] == 4_962_732_672
    assert round(n / 1e5) == 49627           # the issue's 4,962.7M
    assert parts["embedding"] == 100_352 * 4096
    assert parts["experts"] == 10 * 36 * 3 * 4096 * 768
    ssm, attn = (sum(counts_ssm_moe.layer_params(m, **z))
                 for m in ("ssm", "attn"))
    assert (round(ssm / 1e4), round(attn / 1e4)) == (12146, 6112)
    model = serve_ssm_moe.transformer_config(cfg)
    assert model.layer_mixers == ("ssm",) * 5 + ("attn",) + ("ssm",) * 4
    assert sum(map(model.state, range(10))) == 9
    assert sum(map(model.rows, range(10))) == 1
    assert model.max_context == 768 and not model.rope_at(5)
    assert (model.n_heads, model.kv_heads, model.head_dim) == (32, 8, 128)
    assert (model.ssm_heads, model.ssm_head_dim, model.ssm_state,
            model.ssm_groups, model.ssm_chunk) == (128, 64, 128, 1, 256)
    assert (model.emb_scale, model.softmax_scale, model.residual_scale,
            model.head_scale) == (12.0, 0.0078125, 0.22, 0.0625)
    assert (model.n_experts, model.experts_held, model.experts_per_token,
            model.expert_width(), model.shared_experts) == (
        72, (0, 36), 10, 768, 2)
    # the routes the published widths take: the paged attention kernel
    # and the step kernel (two heads a lane tile), the chunked form as
    # products
    from mpistragglers_jl_tpu.models import decode, transformer

    assert decode._paged_kernel_possible(model, True, 64)
    assert transformer.ssm_rule_route(model, 1) == "kernel"
    assert transformer.ssm_rule_route(model, 256) == "xla"
    assert transformer.ssm_zero_state(model, 1)["S"].shape == (
        1, 64, 128, 128)


def test_a_steps_bytes_at_the_published_widths(checkout):
    """The issue's step: 2.31 GB of weights outside the experts, 6.2 GB
    of experts at 32.7 hit of 36, 0.82 GB of head, 1.21 GB of state
    read and written; 204.5 MB of projections a state-space layer."""
    from chipbench import counts_ssm_moe
    from chipbench.runners import serve_ssm_moe

    cfg = json.loads((checkout / "chipbench/configs"
                      / f"{NAME}.json").read_text())
    z = serve_ssm_moe.sizes(cfg)
    b = counts_ssm_moe.step_bytes(experts_hit=32.7, slots=16, **z)
    assert b["state"] == 2 * 16 * 9 * 4 * 128 * 64 * 128
    assert b["head"] == 2 * (100_352 + 1) * 4096
    assert b["experts"] == pytest.approx(10 * 32.7 * 3 * 4096 * 768 * 2)
    assert b["ssm_proj"] == 9 * 2 * (4096 * 16_768 + 8192 * 4096)
    assert round(b["ssm_proj"] / 9 / 1e5) == 2045
    gb = {k: round(v / 1e9, 2) for k, v in b.items()}
    assert gb == {"outside_experts": 2.31, "experts": 6.17, "state": 1.21,
                  "head": 0.82, "ssm_proj": 1.84}
    # a hand-worked tiny case: one layer of each kind
    tiny = dict(d_model=4, n_heads=2, kv_heads=1, head_dim=2, d_expert=3,
                d_shared=6, router_experts=5, experts_held=2, n_layers=2,
                ssm_layers=1, vocab=7, ssm_heads=2, ssm_head_dim=4,
                ssm_state=3, ssm_groups=1, ssm_conv=4)
    # mixer: in 4 x (16 + 6 + 2) = 96, out 8 x 4 = 32, conv 5 x 14 = 70,
    # norm 8; norms 8; shared 3 x 4 x 6 = 72; float32: 3 x 2 + router 20
    assert counts_ssm_moe.layer_params("ssm", **tiny) == (286, 26)
    # attention 2 x 4 x 2 x (2 + 1) = 48; norms 8; shared 72; router 20
    assert counts_ssm_moe.layer_params("attn", **tiny) == (128, 20)
    assert counts_ssm_moe.model_params(**tiny)["total"] == (
        312 + 148 + 2 * 2 * 36 + 28 + 4)
    assert counts_ssm_moe.ssm_proj_params(**tiny) == 128


# -- the configuration file against the catalog row ----------------------------


def test_configuration_keeps_every_published_key_but_the_reduced(checkout):
    if not CATALOG.is_file():
        pytest.skip("no catalog on this machine")
    row = next(r for r in map(json.loads, CATALOG.read_text().splitlines())
               if r["name"] == "granite-4.0-h-small")
    manifest = json.loads((checkout / "BENCHMARK.json").read_text())
    entry = next(c for c in manifest["configs"] if c["name"] == NAME)
    cfg = json.loads((checkout / entry["file"]).read_text())
    assert entry["source"] == row["source_url"] == cfg["source"]
    reduced = {"num_hidden_layers": 10, "num_local_experts": 36,
               "layer_types": row["config"]["layer_types"][:10]}
    assert set(entry["reduced"]) == set(reduced)
    for key, value in row["config"].items():
        if key in reduced:
            assert cfg[key] == reduced[key] != value, key
            assert cfg["published"][key] and cfg["reduced_why"][key], key
        else:
            assert cfg[key] == value, key
    assert (cfg["published"]["num_hidden_layers"],
            cfg["published"]["num_local_experts"]) == (40, 72)
    # what the shared readers take under another name, each said
    assert (cfg["router_experts"], cfg["experts_held"]) == (72, [0, 36])
    assert (cfg["moe_intermediate_size"], cfg["num_experts"],
            cfg["num_dense_layers"]) == (cfg["intermediate_size"], 36, 0)
    for key in ("moe_intermediate_size", "num_experts", "num_dense_layers",
                "router_experts", "experts_held", "torch_dtype"):
        assert key in cfg["shared_reader_keys"], key
    # the seed moved the work (PERF.md section 2): one draw for every seed
    assert cfg["weights_draw"]["seed"] == 51 and cfg["weights_draw"]["why"]
    assert "weights_draw" in cfg["assumed"]["weights"]
    for key in ("assumed", "departures", "deployment", "limits",
                "limits_from", "shared_reader_keys", "program_why"):
        assert cfg[key], key
    for key in ("block", "ssm", "attention", "experts", "time_step_limit",
                "initializer", "state", "torch_dtype", "kv_cache"):
        assert cfg["assumed"][key], key
    for words in ("2 chips share each layer", "one pipeline stage of four",
                  "8 chips", "is not run"):
        assert words in cfg["deployment"], words
    assert any("(heads / 2, d_state, 2 x head_dim)" in d
               for d in cfg["departures"])
    for key in ("logit_gap_worst", "logit_gap_mean"):
        assert 0 < cfg["limits"][key] < 0.3  # of logits divided by 16
        assert cfg["limits_from"][key], key
    # a share of S's values: between a float32 state's none and a
    # bfloat16 state's all
    assert 0 < cfg["limits"]["state_bfloat16_share"] < 1
    assert cfg["limits_from"]["state_bfloat16_share"]
    assert set(cfg["limits"]) == {"logit_gap_worst", "logit_gap_mean",
                                  "state_bfloat16_share"}
    assert cfg["limits_from"]["method"]
    # the same call as the chat cell of the dense block, its own lengths
    chat = json.loads((checkout / "chipbench/configs/"
                       "sc2-3b-serve.json").read_text())["program"]
    prog = cfg["program"]
    for key in ("slots", "n_inner", "quantize_kv", "page_tokens",
                "prompt_chunk"):
        assert prog[key] == chat[key], key
    assert (prog["max_prompt"], prog["max_context"]) == (512, 768)
    cell = next(w for w in manifest["workloads"] if w["name"] == CELL_NAME)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (NAME, MIX, 1)


def test_the_cells_metrics(checkout):
    import _tiny

    manifest = json.loads((checkout / "BENCHMARK.json").read_text())
    by_name = {m["name"]: m
               for m in manifest["end_to_end"] + manifest["per_layer"]}
    names = [m["name"] for m in manifest["per_layer"]]
    m = by_name[NEW]
    assert m["workloads"] == [CELL_NAME] or CELL_NAME in m["workloads"]
    assert (m["layer"], m["unit"], m["moves"], m["source"], m["better"]) == (
        "model step", "%", "serve_tok_s", "device_trace", "higher")
    assert (checkout / "chipbench/metrics" / f"{NEW}.py").is_file()
    assert _tiny.stands_after(names, NEW, "ssm_prefill_share_pct")
    assert "setup_s" in by_name and "workloads" not in by_name["setup_s"]
    for name in LISTED:
        assert CELL_NAME in by_name[name]["workloads"], name
    # a metric that moves the tail is listed only where the tail is
    tail = CELL_NAME in by_name["itl_p95_ms"]["workloads"]
    for name in WITH_THE_TAIL:
        assert (CELL_NAME in by_name[name]["workloads"]) == tail, name
    # and nothing the cell's programs do not feed
    for name in ("gdn_share_pct", "la_share_pct", "mla_attn_share_pct",
                 "hc_share_pct", "mtp_share_pct", "kv_full_pages_pct",
                 "sparse_blocks_pct", "mfu_pct"):
        assert CELL_NAME not in by_name[name]["workloads"], name
    cells = [w for w in manifest["workloads"] if w["chips"] != 1]
    assert not cells


# -- the new reader on hand-made device events ---------------------------------


def test_ssm_proj_reader_on_a_recorded_summary():
    """Two runs of a tick program whose scopes' times are given; ns."""
    from chipbench.metrics import _program_spans as ps
    from chipbench.metrics import _ssm_scopes, ssm_proj_hbm_pct

    tick = {"whole": 16000e-9, "runs": 2, "moves": 2000e-9,
            "ssm_proj": 2000e-9, "ssm_conv": 0.0, "ssm_rule": 4000e-9,
            "ssm_out": 1000e-9}
    moved, state = 9 * 2 * (4096 * 16_768 + 8192 * 4096) / 1e6, 81_900.0
    loaded = ps.ProgramSpans((0.0, 1e9), [], 0.0, {}, 0.0)
    summary = types.SimpleNamespace(ops=[types.SimpleNamespace(
        name="while.1", module="jit_serving_tick_paged_7", dur=1.0)])
    run = types.SimpleNamespace(
        summary=summary, config={}, trace_dir="",
        peaks={"hbm_bytes_per_s": 819e9},
        info={ps.CACHE_KEY: loaded, "slots": 16, "n_inner": 8,
              "ssm_proj_bytes": moved, "ssm_state_bytes": state,
              _ssm_scopes.CACHE_KEY + "_tick": tick})
    # nine layers' two projections and the state (scaled to these
    # microseconds), 8 steps, 2 ticks, over the 7 us under the four
    # scopes and the 2 us of the compiler's copies: measured time
    # alone, nothing taken off it
    assert ssm_proj_hbm_pct.read(run) == pytest.approx(
        100 * (moved + state) * 16 / (9000e-9 * 819e9))
    # it follows the step kernel too: the whole mixer's share
    slow = {**tick, "ssm_rule": 13000e-9}
    run.info[_ssm_scopes.CACHE_KEY + "_tick"] = slow
    assert ssm_proj_hbm_pct.read(run) == pytest.approx(
        100 * (moved + state) * 16 / (18000e-9 * 819e9))
    run.info[_ssm_scopes.CACHE_KEY + "_tick"] = tick
    # a runner that counts no such bytes (Falcon-H1's), a program
    # without the scopes (a parent commit, another model), no trace
    del run.info["ssm_proj_bytes"]
    assert ssm_proj_hbm_pct.read(run) is None
    run.info["ssm_proj_bytes"] = moved
    run.info[_ssm_scopes.CACHE_KEY + "_tick"] = {
        **tick, **{s: 0.0 for s in _ssm_scopes.SCOPES}}
    assert ssm_proj_hbm_pct.read(run) is None
    run.info[_ssm_scopes.CACHE_KEY + "_tick"] = None
    assert ssm_proj_hbm_pct.read(run) is None
    none = types.SimpleNamespace(summary=None, info={}, config={},
                                 peaks=None)
    assert ssm_proj_hbm_pct.read(none) is None
