"""The ``serve_moe`` kind: rehearsed on the CPU at tiny size from a
throw-away checkout (as test_runners.py does for the other kinds), its
shapes against the program's ``init_params``, its configuration file
against the catalog row it was drawn from, and its per-layer readers on
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

# the published keys at a size the CPU runs in seconds; the reference's
# published top-8 and route_scale stay (it has them as constants)
TINY = {
    "kind": "serve_moe", "reference": "afmoe",
    "hidden_size": 32, "head_dim": 16, "num_attention_heads": 4,
    "num_key_value_heads": 2, "intermediate_size": 48,
    "moe_intermediate_size": 16, "num_experts": 16,
    "num_experts_per_tok": 8, "num_shared_experts": 1,
    "num_hidden_layers": 5, "num_dense_layers": 1,
    "layer_types": ["sliding_attention"] * 4 + ["full_attention"],
    "sliding_window": 16, "vocab_size": 128, "mup_enabled": True,
    "route_scale": 2.826, "rms_norm_eps": 1e-5,
    "tie_word_embeddings": False, "torch_dtype": "float32",
    "program": {"slots": 4, "n_inner": 4, "quantize_kv": True,
                "page_tokens": 8, "prompt_chunk": 16, "max_prompt": 64,
                "max_context": 96, "attn": "ulysses",
                "attn_impl": "reference"},
    # At this size int8 K/V noise flips the choice between near-tied
    # experts (8 of 16 are chosen), so single tokens stray: over 8 seeds
    # the sound runs read a worst gap of 0.004 to 0.46 and a mean of
    # 0.00006 to 0.0093, the fp8 control 0.6 to 2.0 and 0.038 to 0.116.
    # The mean tells them apart, with a factor of two on each side.
    "limits": {"logit_gap_worst": 1.0, "logit_gap_mean": 0.02},
}
CELL = "tiny_serve_moe"


@pytest.fixture(scope="module")
def moe_root(tmp_path_factory):
    """_tiny.py's throw-away checkout with one more configuration and
    cell dropped in, of the new kind."""
    import _tiny

    root = _tiny.make_tiny_checkout(tmp_path_factory.mktemp("chipbench_moe"))
    (root / "chipbench/configs/tiny-serve-moe.json").write_text(
        json.dumps(TINY))
    manifest = json.loads((root / "BENCHMARK.json").read_text())
    manifest["configs"].append({
        "name": "tiny-serve-moe", "source": "tests/chipbench",
        "file": "chipbench/configs/tiny-serve-moe.json", "reduced": [],
        "why": "throw-away"})
    manifest["workloads"].append({
        "name": CELL, "config": "tiny-serve-moe",
        "traffic": "tiny_backlog", "chips": 1, "why": "throw-away"})
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        if "serve_trinity_mixed" in m.get("workloads", ()):
            m["workloads"].append(CELL)
    (root / "BENCHMARK.json").write_text(json.dumps(manifest))
    return root


def one_run(root, trace, seed=2**31 + 11):
    return bench.run_cell(root, CELL, seed, 0.6, trace, require_chip=False,
                          t_start=time.perf_counter())


def test_result_line_of_the_new_kind(moe_root):
    result = one_run(moe_root, False)
    assert set(result) == {"correct", "attempted", "failed", "metrics",
                           "device"}
    assert result["correct"] is True
    assert result["attempted"] > 0 and result["failed"] == 0
    assert set(result["metrics"]) == {"setup_s", "serve_tok_s", "itl_p95_ms"}
    json.dumps(result)


def test_traced_run_on_the_cpu_reports_no_device_number(moe_root):
    result = one_run(moe_root, True)
    assert result["correct"] is True
    # no chip: the readers of the device trace and of the program's
    # spans in it find nothing and leave their metric out
    assert set(result["metrics"]) == {"slot_occupancy_pct", "itl_p50_ms"}


def test_a_broken_timed_path_is_not_correct(moe_root, monkeypatch):
    from mpistragglers_jl_tpu.models.serving import ServingScheduler

    real = ServingScheduler._decode_scan_fetch
    monkeypatch.setattr(
        ServingScheduler, "_decode_scan_fetch",
        lambda self: (real(self) + 1) % self.cfg.vocab)
    assert one_run(moe_root, False)["correct"] is False


def test_control_in_lower_precision_fails_a_limit(moe_root):
    row = control.readings(moe_root, CELL, 7, 0.3, ["fp8"],
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

    from chipbench.runners import serve_moe
    from mpistragglers_jl_tpu.models.transformer import init_params

    model = serve_moe.transformer_config(TINY)
    params = init_params(model, seed=0)
    want = jax.tree.map(lambda a: (a.shape, a.dtype), params)
    got = jax.tree.map(lambda s: (s.shape, s.dtype),
                       serve_moe.param_shapes(TINY))
    assert got == want
    made = serve_moe.make_params(TINY, 2**31 + 5)
    lp = made["layers"][1]
    for name in ("ln1_s", "ln1p_s", "ln2_s", "ln2p_s", "qn_s", "kn_s"):
        assert float(abs(lp[name] - 1).max()) == 0.0
    assert float(abs(lp["router_bias"]).max()) > 0.0
    assert model.windows == (16, 16, 16, 16, None)
    assert model.layer_experts == (False, True, True, True, True)


# -- the configuration file against the catalog row ----------------------------


def test_configuration_keeps_every_published_key_but_the_reduced(checkout):
    if not CATALOG.is_file():
        pytest.skip("no catalog on this machine")
    REPO = checkout
    row = next(r for r in map(json.loads, CATALOG.read_text().splitlines())
               if r["name"] == "Trinity-Mini")
    manifest = json.loads((REPO / "BENCHMARK.json").read_text())
    entry = next(c for c in manifest["configs"]
                 if c["name"] == "trinity-mini-serve")
    cfg = json.loads((REPO / entry["file"]).read_text())
    assert entry["source"] == row["source_url"] == cfg["source"]
    reduced = set(entry["reduced"])
    assert reduced == {"num_hidden_layers", "num_dense_layers", "layer_types"}
    for key, value in row["config"].items():
        if key in reduced:
            assert cfg[key] != value, key
        else:
            assert cfg[key] == value, key
    # no width among the reduced keys; the cut is depth and nothing else
    for key in reduced:
        assert not key.endswith(("_dim", "_rank", "_size"))
    assert cfg["published"]["num_hidden_layers"] == 32
    assert cfg["published"]["num_dense_layers"] == 2
    assert cfg["layer_types"] == ["sliding_attention"] * 4 + ["full_attention"]
    assert len(cfg["layer_types"]) == cfg["num_hidden_layers"] == 5
    # the widths by name
    assert (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["head_dim"]) == (2048, 32, 4, 128)
    assert (cfg["intermediate_size"], cfg["moe_intermediate_size"],
            cfg["num_experts"], cfg["num_experts_per_tok"],
            cfg["num_shared_experts"]) == (6144, 1024, 128, 8, 1)
    assert (cfg["sliding_window"], cfg["vocab_size"],
            cfg["tie_word_embeddings"]) == (2048, 200192, False)
    prog = cfg["program"]
    assert prog["max_context"] % prog["page_tokens"] == 0
    assert prog["max_context"] >= prog["max_prompt"] + 256


# -- the readers on hand-made spans and device events --------------------------


def _run_with_spans(spans, info=None):
    from chipbench.metrics import _program_spans as ps

    loaded = ps.ProgramSpans((0.0, 1e9), spans, 0.0, {}, 0.0)
    run = types.SimpleNamespace(
        summary=object(), info={ps.CACHE_KEY: loaded, **(info or {})},
        config={"num_experts": 128, "hidden_size": 2048,
                "moe_intermediate_size": 1024, "num_hidden_layers": 5,
                "num_dense_layers": 1},
        peaks={"hbm_bytes_per_s": 819e9}, trace_dir="",
    )
    return run


def test_counter_readers_on_hand_made_spans():
    from chipbench.metrics import _program_spans as ps
    from chipbench.metrics import experts_hit_pct, kv_full_pages_pct

    spans = [
        ps.HostSpan("serving.tick", 0, 10, {"pages_window": 300,
                                            "pages_full": 100}),
        ps.HostSpan("serving.harvest", 5, 9, {"experts_hit": 80.0}),
        ps.HostSpan("serving.tick", 10, 20, {"pages_window": 100,
                                             "pages_full": 100}),
        ps.HostSpan("serving.harvest", 15, 19, {"experts_hit": 84.0}),
    ]
    run = _run_with_spans(spans)
    assert experts_hit_pct.read(run) == pytest.approx(100 * 82 / 128)
    assert kv_full_pages_pct.read(run) == pytest.approx(100 * 100 / 300)
    # a program that wrote no such argument (a parent commit): nothing
    bare = _run_with_spans([ps.HostSpan("serving.tick", 0, 10, {}),
                            ps.HostSpan("serving.harvest", 5, 9, {})])
    assert experts_hit_pct.read(bare) is None
    assert kv_full_pages_pct.read(bare) is None
    assert experts_hit_pct.read(types.SimpleNamespace(
        summary=None, info={}, config={})) is None


def test_scope_readers_on_hand_made_device_events(monkeypatch):
    """Two runs of a tick program of four operations; times in ns."""
    from chipbench import counts_moe, trace_reduce
    from chipbench.metrics import _moe_scopes, _program_spans as ps
    from chipbench.metrics import moe_experts_hbm_pct, moe_share_pct

    ops = []
    for t0 in (1000, 11000):
        ops += [("%while.1", t0, 8000),            # the scan, 2000 of its own
                ("%fusion.2", t0 + 100, 1000),     # route
                ("%gmm.3", t0 + 1200, 4000),       # experts
                ("%fusion.4", t0 + 5300, 1000)]    # attention, another scope
    device = {0: {"ops": ops,
                  "modules": [("jit_serving_tick_paged(7)", 1000, 8000),
                              ("jit_serving_tick_paged(7)", 11000, 8000)]}}
    scopes = {(7, "%fusion.2"): "jit(f)/decode_mlp/moe_route/top_k",
              (7, "%gmm.3"): "jit(f)/decode_mlp/moe_experts/gmm",
              (7, "%fusion.4"): "jit(f)/decode_attn/dot",
              (7, "%while.1"): "jit(f)/while"}
    monkeypatch.setattr(trace_reduce, "load_xplane",
                        lambda path: {"device": device, "host": []})
    monkeypatch.setattr(ps, "op_scopes", lambda path: scopes)
    got = _moe_scopes._reduce("unused", "jit_serving_tick_paged_7", (0, 20000))
    assert got["runs"] == 2
    assert got["whole"] == pytest.approx(16000e-9)
    assert got["moe_route"] == pytest.approx(2000e-9)
    assert got["moe_experts"] == pytest.approx(8000e-9)
    assert got["moe_shared"] == 0.0

    spans = [ps.HostSpan("serving.harvest", 0, 1, {"experts_hit": 64.0})]
    run = _run_with_spans(spans, {_moe_scopes.CACHE_KEY: got, "n_inner": 8})
    assert moe_share_pct.read(run) == pytest.approx(100 * 10000 / 16000)
    # 64 experts x 12.6 MB, 4 expert layers, 8 steps, 2 ticks, in 8 us
    byts = counts_moe.experts_hit_bytes(64.0, d_model=2048, d_expert=1024)
    assert moe_experts_hbm_pct.read(run) == pytest.approx(
        100 * byts * 4 * 8 * 2 / (8000e-9 * 819e9))
    # no operation under a moe scope (a parent commit's tick): nothing
    monkeypatch.setattr(ps, "op_scopes", lambda path: {
        k: "jit(f)/decode_attn/dot" for k in scopes})
    assert _moe_scopes._reduce(
        "unused", "jit_serving_tick_paged_7", (0, 20000)) is None
