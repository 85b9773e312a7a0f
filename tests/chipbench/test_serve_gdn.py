"""The ``serve_gdn`` kind: rehearsed on the CPU at tiny size from a
throw-away checkout (as test_serve_moe.py does for its kind), its
shapes against the program's ``init_params``, its configuration file
against the catalog row it was drawn from, a state lost at a chunk
boundary against the comparison, and its per-layer readers on
hand-made spans and device events."""

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

# the published keys at a size the CPU runs in seconds
TINY = {
    "kind": "serve_gdn", "reference": "qwen3_next",
    "hidden_size": 32, "head_dim": 16, "num_attention_heads": 4,
    "num_key_value_heads": 2, "intermediate_size": 48,
    "full_attention_interval": 4, "num_hidden_layers": 4,
    "linear_num_key_heads": 2, "linear_num_value_heads": 4,
    "linear_key_head_dim": 8, "linear_value_head_dim": 8,
    "linear_conv_kernel_dim": 4, "partial_rotary_factor": 0.25,
    "rope_theta": 10000000, "rms_norm_eps": 1e-6,
    "moe_intermediate_size": 16, "shared_expert_intermediate_size": 16,
    "num_experts": 8, "router_experts": 16, "experts_held": [0, 8],
    "num_experts_per_tok": 4, "norm_topk_prob": True,
    "mlp_only_layers": [], "decoder_sparse_step": 1, "num_dense_layers": 0,
    "vocab_size": 128, "tie_word_embeddings": False,
    "torch_dtype": "float32",
    "program": {"slots": 4, "n_inner": 4, "quantize_kv": True,
                "page_tokens": 8, "prompt_chunk": 16, "max_prompt": 64,
                "max_context": 96, "attn": "ulysses",
                "attn_impl": "reference"},
    # Over 8 seeds at this size the sound runs read a worst gap of at
    # most 0.0016 and a mean of at most 0.000016 (float32 weights; the
    # int8 K/V noise of one attention layer); the fp8 control 1.7 and
    # 0.19 or more; a run whose state is lost at every chunk boundary
    # 3.2 and 0.38 or more.
    "limits": {"logit_gap_worst": 1.0, "logit_gap_mean": 0.015},
}
CELL = "tiny_serve_gdn"


@pytest.fixture(scope="module")
def gdn_root(tmp_path_factory):
    """_tiny.py's throw-away checkout with one more configuration and
    cell dropped in, of the new kind, reporting what the committed cell
    of this kind reports."""
    import _tiny

    root = _tiny.make_tiny_checkout(tmp_path_factory.mktemp("chipbench_gdn"))
    (root / "chipbench/configs/tiny-serve-gdn.json").write_text(
        json.dumps(TINY))
    manifest = json.loads((root / "BENCHMARK.json").read_text())
    manifest["configs"].append({
        "name": "tiny-serve-gdn", "source": "tests/chipbench",
        "file": "chipbench/configs/tiny-serve-gdn.json", "reduced": [],
        "why": "throw-away"})
    manifest["workloads"].append({
        "name": CELL, "config": "tiny-serve-gdn",
        "traffic": "tiny_backlog", "chips": 1, "why": "throw-away"})
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        if "serve_q3next_mixed" in m.get("workloads", ()):
            m["workloads"].append(CELL)
    (root / "BENCHMARK.json").write_text(json.dumps(manifest))
    return root


def one_run(root, trace, seed=2**31 + 11):
    return bench.run_cell(root, CELL, seed, 0.6, trace, require_chip=False,
                          t_start=time.perf_counter())


def test_result_line_of_the_new_kind(gdn_root):
    result = one_run(gdn_root, False)
    assert set(result) == {"correct", "attempted", "failed", "metrics",
                           "device"}
    assert result["correct"] is True
    assert result["attempted"] > 0 and result["failed"] == 0
    assert set(result["metrics"]) == {"setup_s", "serve_tok_s", "itl_p95_ms"}
    json.dumps(result)


def test_traced_run_on_the_cpu_reports_no_device_number(gdn_root):
    result = one_run(gdn_root, True)
    assert result["correct"] is True
    # no chip: the readers of the device trace and of the program's
    # spans in it find nothing and leave their metric out
    assert set(result["metrics"]) == {"slot_occupancy_pct", "itl_p50_ms"}


def test_a_broken_timed_path_is_not_correct(gdn_root, monkeypatch):
    from mpistragglers_jl_tpu.models.serving import ServingScheduler

    real = ServingScheduler._decode_scan_fetch
    monkeypatch.setattr(
        ServingScheduler, "_decode_scan_fetch",
        lambda self: (real(self) + 1) % self.cfg.vocab)
    assert one_run(gdn_root, False)["correct"] is False


def test_a_state_lost_at_a_chunk_boundary_is_not_correct(gdn_root,
                                                         monkeypatch):
    """At the runner's draw of ``A_log`` and ``dt_bias`` the state
    remembers hundreds of tokens, so a program that starts every
    prefill chunk from a zero state fails the comparison. (At a plain
    normal draw the state halves every token and such a program would
    pass.)"""
    import jax
    import jax.numpy as jnp

    from mpistragglers_jl_tpu.models import serving

    real = serving._extend_chunk_dense

    def forgetful(cfg, C, Lmax):
        chunk = real(cfg, C, Lmax)

        def run(params, tokens, cache, offset, *valid):
            cache = [jax.tree.map(jnp.zeros_like, cl) if "S" in cl else cl
                     for cl in cache]
            return chunk(params, tokens, cache, offset, *valid)

        return run

    monkeypatch.setattr(serving, "_extend_chunk_dense", forgetful)
    assert one_run(gdn_root, False)["correct"] is False


def test_the_runners_draw_keeps_the_state_for_hundreds_of_tokens():
    import numpy as np

    from chipbench.runners import serve_gdn

    made = serve_gdn.make_params(TINY, 2**31 + 5)
    lp = made["layers"][0]
    # g at a = 0: -A * softplus(dt_bias) = -A * dt
    A = np.exp(np.asarray(lp["gdn_A_log"]))
    dt = np.log1p(np.exp(np.asarray(lp["gdn_dt_bias"])))
    assert 0 < A.min() and A.max() <= 16.0
    assert 1e-3 * 0.99 <= dt.min() and dt.max() <= 1e-1 * 1.01
    assert np.exp(-A * dt).min() > 0.2
    taps = np.asarray(lp["gdn_conv_w"])
    assert abs(taps).max() <= 0.5 and abs(taps).mean() > 0.2
    # two layers draw apart, and a seed draws the same twice
    assert not np.allclose(lp["gdn_A_log"], made["layers"][1]["gdn_A_log"])
    again = serve_gdn.make_params(TINY, 2**31 + 5)
    np.testing.assert_array_equal(lp["gdn_A_log"],
                                  again["layers"][0]["gdn_A_log"])


def test_control_in_lower_precision_fails_a_limit(gdn_root):
    row = control.readings(gdn_root, CELL, 7, 0.3, ["fp8"],
                           require_chip=False)
    assert row["correct"] is True
    sound, low = row["sound"], row["control"]["fp8"]
    limit = TINY["limits"]
    assert sound["served_token_logit_gap_worst"] <= limit["logit_gap_worst"]
    assert sound["served_token_logit_gap_mean"] <= limit["logit_gap_mean"]
    assert (low["logit_gap_worst"] > limit["logit_gap_worst"]
            or low["logit_gap_mean"] > limit["logit_gap_mean"])


def test_shapes_are_the_programs_own():
    import jax

    from chipbench.runners import serve_gdn
    from mpistragglers_jl_tpu.models.transformer import init_params

    model = serve_gdn.transformer_config(TINY)
    params = init_params(model, seed=0)
    want = jax.tree.map(lambda a: (a.shape, a.dtype), params)
    got = jax.tree.map(lambda s: (s.shape, s.dtype),
                       serve_gdn.param_shapes(TINY))
    assert got == want
    made = serve_gdn.make_params(TINY, 2**31 + 5)
    assert jax.tree.map(lambda a: (a.shape, a.dtype), made) == want
    for lp in made["layers"]:
        for name in lp:
            if name.endswith("_s"):
                assert float(abs(lp[name] - 1).max()) == 0.0
    assert model.layer_mixers == ("gdn", "gdn", "gdn", "attn")
    assert model.experts_held == (0, 8) and model.n_experts == 16
    assert model.rope_dims == 4 and model.rope_theta == 1e7


# -- the configuration file against the catalog row ----------------------------


@pytest.mark.parametrize("name", ["q3next-80b-a3b-serve",
                                  "q3next-80b-a3b-serve-long"])
def test_configuration_keeps_every_published_key_but_the_reduced(checkout,
                                                                 name):
    if not CATALOG.is_file():
        pytest.skip("no catalog on this machine")
    REPO = checkout
    row = next(r for r in map(json.loads, CATALOG.read_text().splitlines())
               if r["name"] == "Qwen3-Next-80B-A3B-Instruct")
    manifest = json.loads((REPO / "BENCHMARK.json").read_text())
    entry = next(c for c in manifest["configs"] if c["name"] == name)
    cfg = json.loads((REPO / entry["file"]).read_text())
    assert entry["source"] == row["source_url"] == cfg["source"]
    reduced = set(entry["reduced"])
    assert reduced == {"num_hidden_layers", "num_experts"}
    for key, value in row["config"].items():
        if key in reduced:
            assert cfg[key] != value, key
            assert cfg["published"][key] == value, key
            assert key in cfg["reduced_why"]
        else:
            assert cfg[key] == value, key
    # no width among the reduced keys: depth, and the experts held here
    for key in reduced:
        assert not key.endswith(("_dim", "_rank", "_size"))
    assert cfg["num_hidden_layers"] == cfg["full_attention_interval"] == 4
    lo, hi = cfg["experts_held"]
    assert hi - lo == cfg["num_experts"] == 256
    assert cfg["router_experts"] == cfg["published"]["num_experts"] == 512
    assert cfg["num_experts_per_tok"] == 10 and cfg["num_dense_layers"] == 0
    assert "2 chips share each layer" in cfg["deployment"]
    for key in ("assumed", "departures", "limits", "limits_from"):
        assert cfg[key]
    # the widths by name
    assert (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["head_dim"]) == (2048, 16, 2, 256)
    assert (cfg["linear_num_key_heads"], cfg["linear_num_value_heads"],
            cfg["linear_key_head_dim"], cfg["linear_value_head_dim"],
            cfg["linear_conv_kernel_dim"]) == (16, 32, 128, 128, 4)
    assert (cfg["moe_intermediate_size"],
            cfg["shared_expert_intermediate_size"]) == (512, 512)
    assert (cfg["vocab_size"], cfg["tie_word_embeddings"]) == (151936, False)
    prog = cfg["program"]
    assert prog["max_context"] % prog["page_tokens"] == 0
    trinity = json.loads(
        (REPO / "chipbench/configs/trinity-mini-serve.json").read_text())
    if name == "q3next-80b-a3b-serve":
        assert prog["max_context"] >= prog["max_prompt"] + 256
        assert prog == trinity["program"]  # one schedule for both
        return
    # the long configuration: the same block and the same scheduler,
    # contexts to 32,768 + the long mix's largest answer
    short = json.loads(
        (REPO / "chipbench/configs/q3next-80b-a3b-serve.json").read_text())
    apart = {"program", "program_why", "departures", "limits", "limits_from"}
    assert {k for k in set(cfg) | set(short)
            if cfg.get(k) != short.get(k)} <= apart
    assert cfg["departures"][:-1] == short["departures"][:-1]
    assert (prog["max_prompt"], prog["max_context"]) == (32768, 33792)
    assert {k: v for k, v in prog.items()
            if k not in ("max_prompt", "max_context", "prompt_chunk")} == {
        k: v for k, v in short["program"].items()
        if k not in ("max_prompt", "max_context", "prompt_chunk")}
    assert prog["max_context"] <= cfg["max_position_embeddings"]
    cell = next(w for w in manifest["workloads"]
                if w["name"] == "serve_q3next_long")
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        name, "long_backlog", 1)


# -- the readers on hand-made spans and device events --------------------------


def _run_with_spans(spans, info=None):
    from chipbench.metrics import _program_spans as ps

    loaded = ps.ProgramSpans((0.0, 1e9), spans, 0.0, {}, 0.0)
    return types.SimpleNamespace(
        summary=object(),
        info={ps.CACHE_KEY: loaded, "slots": 16, "n_inner": 8,
              **(info or {})},
        config=json.loads((REPO / "chipbench/configs/"
                           "q3next-80b-a3b-serve.json").read_text()),
        peaks={"hbm_bytes_per_s": 819e9}, trace_dir="",
    )


def test_counter_reader_on_hand_made_spans():
    from chipbench.metrics import _program_spans as ps
    from chipbench.metrics import experts_hit_pct, experts_local_pct

    spans = [
        ps.HostSpan("serving.harvest", 5, 9,
                    {"experts_hit": 60.0, "pairs_local": 76.0}),
        ps.HostSpan("serving.harvest", 15, 19,
                    {"experts_hit": 68.0, "pairs_local": 84.0}),
    ]
    run = _run_with_spans(spans)
    # 16 slots x 10 experts a token = 160 pairs a layer and step
    assert experts_local_pct.read(run) == pytest.approx(100 * 80 / 160)
    # of the 256 held experts
    assert experts_hit_pct.read(run) == pytest.approx(100 * 64 / 256)
    # a program that wrote no such argument (a parent commit): nothing
    bare = _run_with_spans([ps.HostSpan("serving.harvest", 5, 9, {})])
    assert experts_local_pct.read(bare) is None
    assert experts_local_pct.read(types.SimpleNamespace(
        summary=None, info={}, config={})) is None


def test_scope_readers_on_hand_made_device_events(monkeypatch):
    """Two runs of a tick program and one of a chunk program; ns."""
    from chipbench import counts_gdn, trace_reduce
    from chipbench.metrics import _gdn_scopes, _program_spans as ps
    from chipbench.metrics import (
        gdn_prefill_share_pct,
        gdn_share_pct,
        gdn_state_hbm_pct,
    )

    ops = []
    for t0 in (1000, 11000):
        ops += [("%while.1", t0, 8000),            # the scan, 2000 of its own
                ("%fusion.2", t0 + 100, 1000),     # projections
                ("%fusion.3", t0 + 1200, 3000),    # the recurrence
                ("%gmm.4", t0 + 4300, 2000)]       # experts, another scope
        ops += [("%copy-done.9", t0 + 6400, 600),    # the compiler's, unscoped
                ("%copy-start.5", t0 + 7000, 400)]   # and under another scope
    ops += [("%fusion.7", 21000, 6000), ("%fusion.8", 27000, 2000)]
    device = {0: {"ops": ops,
                  "modules": [("jit_serving_tick_paged(7)", 1000, 8000),
                              ("jit_serving_tick_paged(7)", 11000, 8000),
                              ("jit_serving_prefill_chunk(9)", 21000, 8000)]}}
    scopes = {(7, "%fusion.2"): "jit(f)/while/body/gdn_proj/dot",
              (7, "%fusion.3"): "jit(f)/while/body/gdn_rule/mul",
              (7, "%gmm.4"): "jit(f)/decode_mlp/moe_experts/gmm",
              (7, "%while.1"): "jit(f)/while",
              (7, "%copy-start.5"): "jit(f)/while/body/decode_mlp/copy",
              (9, "%fusion.7"): "jit(f)/moe_experts/gmm",
              (9, "%fusion.8"): "jit(f)/gdn_rule/while/body/dot"}
    monkeypatch.setattr(trace_reduce, "load_xplane",
                        lambda path: {"device": device, "host": []})
    monkeypatch.setattr(ps, "op_scopes", lambda path: scopes)
    tick = _gdn_scopes.reduce_scopes(
        "unused", lambda n: n == "jit_serving_tick_paged_7", (0, 40000))
    assert tick["runs"] == 2
    assert tick["whole"] == pytest.approx(16000e-9)  # the scan's own: 1000
    assert tick["gdn_proj"] == pytest.approx(2000e-9)
    assert tick["gdn_rule"] == pytest.approx(6000e-9)
    assert tick["gdn_conv"] == tick["gdn_out"] == 0.0
    assert tick["moves"] == pytest.approx(2000e-9)
    chunk = _gdn_scopes.reduce_scopes(
        "unused", lambda n: n.startswith(_gdn_scopes.CHUNK_PROGRAM),
        (0, 40000))
    assert chunk["runs"] == 1
    assert chunk["gdn_rule"] == pytest.approx(2000e-9)
    assert chunk["moves"] == 0.0

    s_bytes = 4 * 32 * 128 * 128
    run = _run_with_spans([], {
        "state_S_bytes": 2 * 16 * 3 * s_bytes,
        _gdn_scopes.CACHE_KEY + "_tick": tick,
        _gdn_scopes.CACHE_KEY + "_chunk": chunk})
    assert gdn_share_pct.read(run) == pytest.approx(100 * 8000 / 16000)
    assert gdn_prefill_share_pct.read(run) == pytest.approx(100 * 2000 / 8000)
    # S of 16 slots in 3 layers, read and written, 8 steps, 2 ticks, over
    # 6 us under gdn_rule and 2 us of the compiler's copies
    assert counts_gdn.gdn_state_bytes(
        key_heads=16, value_heads=32, key_dim=128, value_dim=128,
        conv=4)[0] == s_bytes
    assert gdn_state_hbm_pct.read(run) == pytest.approx(
        100 * 2 * s_bytes * 16 * 3 * 8 * 2 / (8000e-9 * 819e9))
    # no operation under a gdn scope (a parent commit, another model)
    monkeypatch.setattr(ps, "op_scopes", lambda path: {
        k: "jit(f)/decode_attn/dot" for k in scopes})
    assert _gdn_scopes.reduce_scopes(
        "unused", lambda n: n == "jit_serving_tick_paged_7",
        (0, 40000)) is None
    none = types.SimpleNamespace(summary=None, info={}, config={},
                                 peaks=None)
    for reader in (gdn_share_pct, gdn_prefill_share_pct, gdn_state_hbm_pct):
        assert reader.read(none) is None
