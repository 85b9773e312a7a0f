"""The ``serve_ssm`` kind: rehearsed on the CPU at tiny size from a
throw-away checkout (as test_serve_sala.py does for its kind), its
shapes against the program's ``init_params``, its configuration file
against the catalog row it was drawn from, a state lost at a chunk
boundary and a slot's state not reset against the comparison, and its
per-layer readers on hand-made device events (tests/
test_falcon_h1_block.py holds the block itself to the reference)."""

from __future__ import annotations

import json
import time
import types
from pathlib import Path

import pytest

from chipbench import control
from chipbench import run as bench

REPO = Path(__file__).resolve().parents[2]
CATALOG = Path("/opt/skills/guides/model-configs/architectures.jsonl")
NAME, CELL_NAME, MIX = ("falcon-h1-34b-serve", "serve_falconh1_chat",
                        "chat_backlog")
NEW = ("ssm_share_pct", "ssm_state_hbm_pct", "ssm_prefill_share_pct")

# the published keys at a size the CPU runs in seconds; the mixers'
# multipliers nearer one than the 34B's, so that at this width a lost
# state moves a served token (at 0.0375 and 0.088 on a width of 64 both
# mixers together are a twentieth of the residual)
TINY = {
    "kind": "serve_ssm", "reference": "falcon_h1",
    "attention_bias": False, "attention_in_multiplier": 1,
    "attention_out_multiplier": 1.0, "attn_layer_indices": None,
    "embedding_multiplier": 2.0, "head_dim": 16,
    "hidden_act": "silu", "hidden_size": 64, "intermediate_size": 96,
    "key_multiplier": 0.5, "lm_head_multiplier": 0.5,
    "mamba_chunk_size": 8, "mamba_conv_bias": True, "mamba_d_conv": 4,
    "mamba_d_head": 16, "mamba_d_ssm": 64, "mamba_d_state": 8,
    "mamba_expand": 2, "mamba_n_groups": 2, "mamba_n_heads": 4,
    "mamba_norm_before_gate": False, "mamba_proj_bias": False,
    "mamba_rms_norm": True, "mamba_use_mlp": True,
    "max_position_embeddings": 262144, "mlp_bias": False,
    "mlp_multipliers": [0.5, 1.0],
    "num_attention_heads": 4, "num_hidden_layers": 2,
    "num_key_value_heads": 2, "projectors_bias": False,
    "rms_norm_eps": 1e-5, "rope_scaling": None, "rope_theta": 100000000000,
    # dt's multiplier large: steps of order one, so that S (and not the
    # skip D x) carries most of the mixer's result
    "ssm_in_multiplier": 1.0,
    "ssm_multipliers": [0.3535533905932738, 0.5, 1.0, 1.0, 8.0],
    "ssm_out_multiplier": 1.0,
    "tie_word_embeddings": False, "vocab_size": 128,
    "torch_dtype": "float32",
    "program": {"slots": 4, "n_inner": 4, "quantize_kv": True,
                "page_tokens": 8, "prompt_chunk": 16, "max_prompt": 64,
                "max_context": 96, "attn": "ulysses",
                "attn_impl": "reference"},
    # At this size (float32 weights) the sound runs read 0 and 0 over 3
    # seeds (one above 2**31); the fp8 control 0.08 to 0.13 and 0.0057
    # to 0.0067, the int8 one 0.010 to 0.038 and 0.00025 to 0.0012. The
    # control that keeps S in bfloat16 flips no token of 128 here (its
    # logits move, tests/chipbench/test_reference_falcon_h1.py).
    "limits": {"logit_gap_worst": 1e-3, "logit_gap_mean": 1e-5},
}
CELL = "tiny_serve_ssm"


@pytest.fixture(scope="module")
def ssm_root(tmp_path_factory):
    """_tiny.py's throw-away checkout with one more configuration and
    cell dropped in, of the new kind, reporting what the committed cell
    of this kind reports."""
    import _tiny

    root = _tiny.make_tiny_checkout(tmp_path_factory.mktemp("chipbench_ssm"))
    (root / "chipbench/configs/tiny-serve-ssm.json").write_text(
        json.dumps(TINY))
    manifest = json.loads((root / "BENCHMARK.json").read_text())
    manifest["configs"].append({
        "name": "tiny-serve-ssm", "source": "tests/chipbench",
        "file": "chipbench/configs/tiny-serve-ssm.json", "reduced": [],
        "why": "throw-away"})
    manifest["workloads"].append({
        "name": CELL, "config": "tiny-serve-ssm",
        "traffic": "tiny_backlog", "chips": 1, "why": "throw-away"})
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        if CELL_NAME in m.get("workloads", ()):
            m["workloads"].append(CELL)
    (root / "BENCHMARK.json").write_text(json.dumps(manifest))
    return root


def one_run(root, trace, seed=2**31 + 11):
    return bench.run_cell(root, CELL, seed, 0.6, trace, require_chip=False,
                          t_start=time.perf_counter())


def test_result_line_of_the_new_kind(ssm_root):
    result = one_run(ssm_root, False)
    assert set(result) == {"correct", "attempted", "failed", "metrics",
                           "device"}
    assert result["correct"] is True
    assert result["attempted"] > 0 and result["failed"] == 0
    manifest = json.loads((ssm_root / "BENCHMARK.json").read_text())
    listed = {m["name"] for m in manifest["end_to_end"]
              if CELL in m.get("workloads", [CELL])}
    assert set(result["metrics"]) == listed >= {"setup_s", "serve_tok_s"}
    json.dumps(result)


def test_traced_run_on_the_cpu_reports_no_device_number(ssm_root):
    result = one_run(ssm_root, True)
    assert result["correct"] is True
    # no chip: the readers of the device trace and of the program's
    # spans in it find nothing and leave their metric out
    assert set(result["metrics"]) <= {"slot_occupancy_pct", "itl_p50_ms"}
    assert "slot_occupancy_pct" in result["metrics"]


def test_a_broken_timed_path_is_not_correct(ssm_root, monkeypatch):
    from mpistragglers_jl_tpu.models.serving import ServingScheduler

    real = ServingScheduler._decode_scan_fetch
    monkeypatch.setattr(
        ServingScheduler, "_decode_scan_fetch",
        lambda self: (real(self) + 1) % self.cfg.vocab)
    assert one_run(ssm_root, False)["correct"] is False


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
def test_a_state_lost_at_a_chunk_boundary_is_not_correct(ssm_root,
                                                         monkeypatch,
                                                         leaves):
    """A program that starts every prefill chunk from a zero S, or
    from no conv rows, fails the comparison."""
    from mpistragglers_jl_tpu.models import serving

    monkeypatch.setattr(serving, "_extend_chunk_dense",
                        _zeroing(serving._extend_chunk_dense, leaves))
    monkeypatch.setattr(serving, "_extend_chunk_group",
                        _zeroing(serving._extend_chunk_group, leaves))
    assert one_run(ssm_root, False)["correct"] is False


def test_a_slot_that_keeps_its_last_requests_state_is_not_correct(
        ssm_root, monkeypatch):
    """Placement that writes the rows into the slot's pages and leaves
    the slot's S and conv rows as the last request left them."""
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
    assert one_run(ssm_root, False)["correct"] is False


@pytest.mark.parametrize("precision", ["fp8", "int8"])
def test_control_in_lower_precision_fails_a_limit(ssm_root, precision):
    row = control.readings(ssm_root, CELL, 7, 0.3, [precision],
                           require_chip=False)
    assert row["correct"] is True
    sound, low = row["sound"], row["control"][precision]
    limit = TINY["limits"]
    assert sound["served_token_logit_gap_worst"] <= limit["logit_gap_worst"]
    assert sound["served_token_logit_gap_mean"] <= limit["logit_gap_mean"]
    assert (low["logit_gap_worst"] > limit["logit_gap_worst"]
            or low["logit_gap_mean"] > limit["logit_gap_mean"])


def test_shapes_are_the_programs_own():
    import jax
    import numpy as np

    from chipbench.runners import serve_ssm
    from mpistragglers_jl_tpu.models.transformer import init_params

    model = serve_ssm.transformer_config(TINY)
    params = init_params(model, seed=0)
    want = jax.tree.map(lambda a: (a.shape, a.dtype), params)
    got = jax.tree.map(lambda s: (s.shape, s.dtype),
                       serve_ssm.param_shapes(TINY))
    assert got == want
    made = serve_ssm.make_params(TINY, 2**31 + 5)
    assert jax.tree.map(lambda a: (a.shape, a.dtype), made) == want
    for lp in made["layers"]:
        for name in lp:
            if name.endswith("_s") or name == "ssm_D":
                assert float(abs(lp[name] - 1).max()) == 0.0
        A = np.exp(np.asarray(lp["ssm_A_log"]))
        assert (A >= 1).all() and (A <= 16).all()
        dt = np.log1p(np.exp(np.asarray(lp["ssm_dt_bias"])))
        assert (dt >= 1e-3 * 0.999).all() and (dt <= 0.1 * 1.001).all()
        for name in ("ssm_conv_w", "ssm_conv_b"):
            w = np.asarray(lp[name])
            assert -0.5 <= w.min() < -0.2 and 0.2 < w.max() <= 0.5
    assert model.layer_mixers == ("attn_ssm",) * 2
    assert (model.ssm_heads, model.ssm_head_dim, model.ssm_state,
            model.ssm_groups, model.ssm_conv, model.ssm_chunk) == (
        4, 16, 8, 2, 4, 8)


MULTIPLIERS = {
    "embedding_multiplier": "emb_scale", "lm_head_multiplier": "head_scale",
    "attention_in_multiplier": "attn_in_scale",
    "attention_out_multiplier": "attn_out_scale",
    "key_multiplier": "key_scale", "ssm_in_multiplier": "ssm_in_scale",
    "ssm_out_multiplier": "ssm_out_scale", "ssm_multipliers": "ssm_scales",
}


@pytest.mark.parametrize("key", sorted(MULTIPLIERS) + ["mlp_multipliers"])
def test_every_multiplier_key_reaches_program_and_reference(key):
    """Moved in the file, a multiplier moves in the program's
    configuration and in the reference's ``Sizes``."""
    from chipbench.references import falcon_h1 as ref
    from chipbench.runners import serve_ssm

    value = TINY[key]
    moved = ([v * 3 for v in value] if isinstance(value, list)
             else value * 3)
    cfg = {**TINY, key: moved}
    model, z = (serve_ssm.transformer_config(cfg),
                serve_ssm.reference_sizes(ref, cfg))
    if key == "mlp_multipliers":
        assert (model.ffn_gate_scale, model.ffn_down_scale) == tuple(moved)
    else:
        got = getattr(model, MULTIPLIERS[key])
        assert got == (tuple(moved) if isinstance(moved, list) else moved)
    want = tuple(moved) if isinstance(moved, list) else moved
    assert getattr(z, key) == want


@pytest.mark.parametrize("key,value", [
    ("mamba_norm_before_gate", True), ("mamba_rms_norm", False),
    ("attention_bias", True), ("mamba_conv_bias", False),
    ("attn_layer_indices", [0]), ("tie_word_embeddings", True),
    ("mamba_d_ssm", 128)])
def test_a_block_the_runner_is_not_written_for_is_refused(key, value):
    from chipbench.runners import serve_ssm

    with pytest.raises(ValueError, match=key):
        serve_ssm.transformer_config({**TINY, key: value})


def test_published_widths_build_by_shape_alone(checkout):
    """5,254.6M parameters, 10.51 GB in bfloat16: the runner's shapes,
    the counts' arithmetic and ISSUE 47's agree."""
    import math

    import jax

    from chipbench import counts_ssm
    from chipbench.runners import serve_ssm

    cfg = json.loads((checkout / "chipbench/configs"
                      / f"{NAME}.json").read_text())
    shapes = serve_ssm.param_shapes(cfg)
    n = sum(math.prod(s.shape) for s in jax.tree.leaves(shapes))
    assert n == counts_ssm.model_params(**serve_ssm.sizes(cfg))
    assert round(n / 1e6) == 5255
    model = serve_ssm.transformer_config(cfg)
    assert model.layer_mixers == ("attn_ssm",) * 6
    assert model.max_context == 768 and model.rope_theta == 1e11
    assert (model.n_heads, model.kv_heads, model.head_dim) == (20, 4, 128)
    assert (model.ssm_heads, model.ssm_head_dim, model.ssm_state,
            model.ssm_groups, model.ssm_chunk) == (32, 128, 256, 2, 128)
    # the routes the published widths take: the paged attention kernel
    # and the step kernel, the chunked form as products
    from mpistragglers_jl_tpu.models import decode, transformer

    assert decode._paged_kernel_possible(model, True, 64)
    assert transformer.ssm_rule_route(model, 1) == "kernel"
    assert transformer.ssm_rule_route(model, 256) == "xla"


# -- the configuration file against the catalog row ----------------------------


def test_configuration_keeps_every_published_key_but_the_reduced(checkout):
    if not CATALOG.is_file():
        pytest.skip("no catalog on this machine")
    row = next(r for r in map(json.loads, CATALOG.read_text().splitlines())
               if r["name"] == "Falcon-H1-34B-Instruct")
    manifest = json.loads((checkout / "BENCHMARK.json").read_text())
    entry = next(c for c in manifest["configs"] if c["name"] == NAME)
    cfg = json.loads((checkout / entry["file"]).read_text())
    assert entry["source"] == row["source_url"] == cfg["source"]
    assert entry["reduced"] == ["num_hidden_layers"]
    for key, value in row["config"].items():
        if key == "num_hidden_layers":
            assert (cfg[key], cfg["published"][key]) == (6, value == 72 and 72)
            assert key in cfg["reduced_why"]
        else:
            assert cfg[key] == value, key
    for key in ("assumed", "departures", "deployment", "limits",
                "limits_from", "shared_reader_keys", "program_why"):
        assert cfg[key], key
    for key in ("block", "attention", "ssm", "norm_span", "time_step_limit",
                "initializer", "state", "torch_dtype", "kv_cache"):
        assert cfg["assumed"][key], key
    for key in ("logit_gap_worst", "logit_gap_mean"):
        assert 0 < cfg["limits"][key] < 0.05  # of logits scaled by 2**-7
        assert cfg["limits_from"][key], key
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
    for name in NEW:
        m = by_name[name]
        assert CELL_NAME in m["workloads"]
        assert (m["layer"], m["unit"], m["moves"], m["source"]) == (
            "model step", "%", "serve_tok_s", "device_trace")
        assert (checkout / "chipbench/metrics" / f"{name}.py").is_file()
        assert _tiny.stands_after(names, name, "sparse_rows_hbm_pct")
    assert by_name["ssm_state_hbm_pct"]["better"] == "higher"
    assert by_name["ssm_share_pct"]["better"] == "lower"
    for name in ("setup_s", "serve_tok_s", "slot_occupancy_pct",
                 "decode_step_hbm_pct", "serve_device_idle_pct",
                 "idle_in_admit_pct", "idle_in_decode_pct",
                 "idle_in_harvest_pct", "idle_outside_step_pct",
                 "admitting_slots_pct", "tick_gather_share_pct",
                 "chunks_per_prefill_program", "tick_scoped_pct",
                 "head_share_pct", "head_hbm_pct", "decode_attn_share_pct",
                 "attn_rows_hbm_pct"):
        assert CELL_NAME in by_name[name].get("workloads", [CELL_NAME]), name
    # a metric that moves the tail is listed only where the tail is
    tail = CELL_NAME in by_name["itl_p95_ms"]["workloads"]
    for name in ("prefill_share_pct", "itl_p50_ms", "first_token_wait_ms",
                 "prefill_scoped_pct", "chunk_attn_share_pct"):
        assert (CELL_NAME in by_name[name]["workloads"]) == tail, name
    cells = [w for w in manifest["workloads"] if w["chips"] != 1]
    assert not cells


# -- the readers on hand-made device events ------------------------------------


def test_scope_readers_on_hand_made_device_events(monkeypatch):
    """Two runs of a tick program and one of a chunk program; ns."""
    from chipbench import trace_reduce
    from chipbench.metrics import _program_spans as ps
    from chipbench.metrics import _ssm_scopes
    from chipbench.metrics import (
        ssm_prefill_share_pct,
        ssm_share_pct,
        ssm_state_hbm_pct,
    )

    ops = []
    for t0 in (1000, 11000):
        ops += [("%while.1", t0, 8000),            # the scan, 1000 of its own
                ("%fusion.2", t0 + 100, 1000),     # the in-projection
                ("%ssm_step.3", t0 + 1200, 2000),  # the step kernel
                ("%fusion.4", t0 + 3300, 500),     # the gated norm
                ("%select.5", t0 + 3900, 1500),    # the attention kernel
                ("%fusion.6", t0 + 5500, 1000),    # the feed-forward
                ("%copy-done.9", t0 + 6600, 1000)]  # the compiler's, unscoped
    ops += [("%fusion.7", 21000, 5000), ("%fusion.8", 26000, 2000),
            ("%fusion.9", 28000, 1000)]
    device = {0: {"ops": ops,
                  "modules": [("jit_serving_tick_paged(7)", 1000, 8000),
                              ("jit_serving_tick_paged(7)", 11000, 8000),
                              ("jit_serving_prefill_chunk(9)", 21000, 8000)]}}
    scopes = {(7, "%fusion.2"): "jit(f)/while/body/ssm_proj/dot",
              (7, "%ssm_step.3"): "jit(f)/while/body/ssm_rule/pallas_call",
              (7, "%fusion.4"): "jit(f)/while/body/ssm_out/mul",
              (7, "%select.5"): "jit(f)/while/body/decode_attn/pallas_call",
              (7, "%fusion.6"): "jit(f)/while/body/decode_mlp/ffn/dot",
              (7, "%while.1"): "jit(f)/while",
              (9, "%fusion.7"): "jit(f)/ffn/dot",
              (9, "%fusion.8"): "jit(f)/ssm_rule/while/body/dot",
              (9, "%fusion.9"): "jit(f)/ssm_conv/mul"}
    monkeypatch.setattr(trace_reduce, "load_xplane",
                        lambda path: {"device": device, "host": []})
    monkeypatch.setattr(ps, "op_scopes", lambda path: scopes)
    tick = _ssm_scopes.reduce_scopes(
        "unused", lambda n: n == "jit_serving_tick_paged_7", (0, 40000))
    assert tick["runs"] == 2
    assert tick["whole"] == pytest.approx(16000e-9)
    assert tick["ssm_proj"] == pytest.approx(2000e-9)
    assert tick["ssm_rule"] == pytest.approx(4000e-9)
    assert tick["ssm_out"] == pytest.approx(1000e-9)
    assert tick["moves"] == pytest.approx(2000e-9)
    chunk = _ssm_scopes.reduce_scopes(
        "unused", lambda n: n.startswith("jit_serving_prefill_chunk"),
        (0, 40000))
    assert chunk["ssm_rule"] == pytest.approx(2000e-9)
    assert chunk["ssm_conv"] == pytest.approx(1000e-9)

    state = 2 * 16 * 6 * 4 * 32 * 128 * 256
    loaded = ps.ProgramSpans((0.0, 1e9), [], 0.0, {}, 0.0)
    run = types.SimpleNamespace(
        summary=object(), config={}, trace_dir="",
        peaks={"hbm_bytes_per_s": 819e9},
        info={ps.CACHE_KEY: loaded, "slots": 16, "n_inner": 8,
              "ssm_state_bytes": state,
              _ssm_scopes.CACHE_KEY + "_tick": tick,
              _ssm_scopes.CACHE_KEY + "_chunk": chunk})
    assert ssm_share_pct.read(run) == pytest.approx(100 * 7000 / 16000)
    assert ssm_prefill_share_pct.read(run) == pytest.approx(
        100 * 3000 / 8000)
    # S of 16 slots in 6 layers, read and written, 8 steps, 2 ticks, over
    # 4 us under ssm_rule and 2 us of the compiler's copies
    assert ssm_state_hbm_pct.read(run) == pytest.approx(
        100 * state * 8 * 2 / (6000e-9 * 819e9))
    # a program without the new scopes (a parent commit, another model),
    # and a run whose runner wrote no ``ssm_state_bytes``
    del run.info["ssm_state_bytes"]
    assert ssm_state_hbm_pct.read(run) is None
    run.info[_ssm_scopes.CACHE_KEY + "_tick"] = {
        **tick, **{s: 0.0 for s in _ssm_scopes.SCOPES}}
    run.info[_ssm_scopes.CACHE_KEY + "_chunk"] = None
    for reader in (ssm_share_pct, ssm_prefill_share_pct, ssm_state_hbm_pct):
        assert reader.read(run) is None
    none = types.SimpleNamespace(summary=None, info={}, config={},
                                 peaks=None)
    for reader in (ssm_share_pct, ssm_prefill_share_pct, ssm_state_hbm_pct):
        assert reader.read(none) is None
