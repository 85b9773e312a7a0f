"""The ``serve_mla`` kind: rehearsed on the CPU at tiny size from a
throw-away checkout (as test_serve_moe.py does for its kind), its
shapes against the program's ``init_params``, its configuration file
against the catalog row it was drawn from, faults injected into the
residual mixing, the rotated part of a row and the softmax scale
against the comparison, and its per-layer readers on hand-made device
events."""

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
    "kind": "serve_mla", "reference": "xing4_0",
    "hidden_size": 32, "num_attention_heads": 4, "num_key_value_heads": 4,
    "q_lora_rank": 16, "kv_lora_rank": 24, "qk_nope_head_dim": 8,
    "qk_rope_head_dim": 4, "v_head_dim": 8, "attention_bias": False,
    "intermediate_size": 48, "moe_intermediate_size": 16,
    "n_routed_experts": 8, "n_shared_experts": 1, "num_experts_per_tok": 2,
    "norm_topk_prob": True, "routed_scaling_factor": 2,
    "scoring_func": "sigmoid", "topk_method": "noaux_tc", "n_group": 1,
    "topk_group": 1, "moe_layer_freq": 1, "first_k_dense_replace": 1,
    "num_hidden_layers": 3, "num_dense_layers": 1, "num_experts": 8,
    "hc_mult": 4, "hc_sinkhorn_iters": 20, "hc_eps": 1e-6,
    "mhc_h_res_clamp_min": -30, "mhc_h_res_clamp_max": 30,
    "rms_norm_eps": 1e-6, "rope_theta": 10000,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 64,
                     "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 16,
                     "type": "yarn"},
    "vocab_size": 128, "tie_word_embeddings": False,
    "torch_dtype": "float32",
    "program": {"slots": 4, "n_inner": 4, "quantize_kv": True,
                "page_tokens": 8, "prompt_chunk": 16, "max_prompt": 64,
                "max_context": 96, "attn": "ulysses",
                "attn_impl": "reference"},
    # Over 8 seeds at this size the sound runs (float32 weights; what is
    # left is the int8 rows' noise) read a worst gap of at most 0.1 and
    # a mean of at most 0.0015 in seven and, in the one where that noise
    # flips one of 8 experts for a token, 0.78 and 0.0085.
    "limits": {"logit_gap_worst": 2.5, "logit_gap_mean": 0.03},
}
CELL = "tiny_serve_mla"


@pytest.fixture(scope="module")
def mla_root(tmp_path_factory):
    """_tiny.py's throw-away checkout with one more configuration and
    cell dropped in, of the new kind, reporting what the committed cell
    of this kind reports."""
    import _tiny

    root = _tiny.make_tiny_checkout(tmp_path_factory.mktemp("chipbench_mla"))
    (root / "chipbench/configs/tiny-serve-mla.json").write_text(
        json.dumps(TINY))
    manifest = json.loads((root / "BENCHMARK.json").read_text())
    manifest["configs"].append({
        "name": "tiny-serve-mla", "source": "tests/chipbench",
        "file": "chipbench/configs/tiny-serve-mla.json", "reduced": [],
        "why": "throw-away"})
    manifest["workloads"].append({
        "name": CELL, "config": "tiny-serve-mla",
        "traffic": "tiny_backlog", "chips": 1, "why": "throw-away"})
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        if "serve_xing4_mixed" in m.get("workloads", ()):
            m["workloads"].append(CELL)
    (root / "BENCHMARK.json").write_text(json.dumps(manifest))
    return root


def one_run(root, trace, seed=2**31 + 11):
    return bench.run_cell(root, CELL, seed, 0.6, trace, require_chip=False,
                          t_start=time.perf_counter())


def test_result_line_of_the_new_kind(mla_root):
    result = one_run(mla_root, False)
    assert set(result) == {"correct", "attempted", "failed", "metrics",
                           "device"}
    assert result["correct"] is True
    assert result["attempted"] > 0 and result["failed"] == 0
    # the cell is not on ``itl_p95_ms``'s list (PERF.md section 2: the
    # tail's spread in this cell is over what a new cell is admitted at)
    assert set(result["metrics"]) == {"setup_s", "serve_tok_s"}
    json.dumps(result)


def test_traced_run_on_the_cpu_reports_no_device_number(mla_root):
    result = one_run(mla_root, True)
    assert result["correct"] is True
    # no chip: the readers of the device trace and of the program's
    # spans in it find nothing and leave their metric out
    assert set(result["metrics"]) == {"slot_occupancy_pct"}


def test_a_broken_timed_path_is_not_correct(mla_root, monkeypatch):
    from mpistragglers_jl_tpu.models.serving import ServingScheduler

    real = ServingScheduler._decode_scan_fetch
    monkeypatch.setattr(
        ServingScheduler, "_decode_scan_fetch",
        lambda self: (real(self) + 1) % self.cfg.vocab)
    assert one_run(mla_root, False)["correct"] is False


@pytest.mark.parametrize("fault", ["phi_res", "rotated_key", "scale"])
def test_an_injected_fault_is_not_correct(mla_root, monkeypatch, fault):
    """A program whose stream-to-stream matrices ignore the token, whose
    cached rows lose their rotated part, or whose softmax scale is the
    plain ``head_dim ** -0.5`` serves tokens the reference does not
    rank first. The fault is in the PROGRAM alone (the reference gets
    the runner's sound weights and sizes). That the first shows is the
    initialiser's doing: ``alpha`` is one, and each half reads mostly a
    stream of its own (``transformer.hc_bias``): with every half reading
    all streams alike they are interchangeable and ``Hres`` moves
    nothing (read: a worst gap of 0.02 where this reads over 1)."""
    import dataclasses

    from mpistragglers_jl_tpu.models import decode, serving, transformer

    if fault == "phi_res":
        real_pre = transformer.hc_pre

        def pre(x, lp, cfg, half):
            phi = lp[half + "_phi"]
            res = 2 * cfg.hc_mult  # [pre | post | res]
            return real_pre(
                x, {**lp, half + "_phi": phi.at[:, res:].set(0.0)}, cfg, half)

        for mod in (transformer, decode, serving):
            monkeypatch.setattr(mod, "hc_pre", pre)
    elif fault == "rotated_key":
        real_leaves = decode._latent_leaves

        def leaves(row, R, quantized):
            return real_leaves(row.at[..., R:].set(0.0), R, quantized)

        for mod in (decode, serving):
            monkeypatch.setattr(mod, "_latent_leaves", leaves)
    else:
        # the run loads the runner's file afresh: wrap what it loads
        real_load = bench.load_from

        def load(root, folder, name):
            mod = real_load(root, folder, name)
            if (folder, name) == ("runners", "serve_mla"):
                real_config = mod.transformer_config
                mod.transformer_config = lambda config: dataclasses.replace(
                    real_config(config), attn_scale=None)
            return mod

        monkeypatch.setattr(bench, "load_from", load)
    assert one_run(mla_root, False)["correct"] is False


def test_control_in_lower_precision_fails_a_limit(mla_root):
    row = control.readings(mla_root, CELL, 7, 0.3, ["fp8"],
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
    import numpy as np

    from chipbench.runners import serve_mla
    from mpistragglers_jl_tpu.models.transformer import init_params

    model = serve_mla.transformer_config(TINY)
    params = init_params(model, seed=0)
    want = jax.tree.map(lambda a: (a.shape, a.dtype), params)
    got = jax.tree.map(lambda s: (s.shape, s.dtype),
                       serve_mla.param_shapes(TINY))
    assert got == want
    made = serve_mla.make_params(TINY, 2**31 + 5)
    assert jax.tree.map(lambda a: (a.shape, a.dtype), made) == want
    lp, ours = made["layers"][1], params["layers"][1]
    for name in lp:
        if name.endswith("_s") or name.endswith("_alpha"):
            assert float(abs(lp[name] - 1).max()) == 0.0
    # the mixing starts where the program's own initialiser starts it
    # (the runner calls the program's ``hc_bias``), pinned here so that
    # the benchmark's weights cannot follow a change of it unseen:
    # layer 1's first half is the model's half 2
    np.testing.assert_array_equal(lp["hc1_b"], ours["hc1_b"])
    np.testing.assert_array_equal(lp["hc1_b"], np.concatenate(
        [[-2.0, -2.0, 2.0, -2.0], np.zeros(4), 2.0 * np.eye(4).ravel()]))
    assert float(np.std(lp["hc2_phi"])) == pytest.approx(
        (4 * 32) ** -0.5, rel=0.1)
    assert model.layer_mixers == ("mla",) * 3
    assert model.layer_experts == (False, True, True)
    assert model.hc_mult == 4 and model.head_dim == 12
    assert model.attn_scale == pytest.approx(
        12 ** -0.5 * (0.1 * np.log(64) + 1) ** 2)
    assert len(model.rope_table) == 2


# -- the configuration file against the catalog row ----------------------------


def test_configuration_keeps_every_published_key_but_the_reduced(checkout):
    if not CATALOG.is_file():
        pytest.skip("no catalog on this machine")
    REPO = checkout
    row = next(r for r in map(json.loads, CATALOG.read_text().splitlines())
               if r["name"] == "Xing4.0-29B-A4B")
    manifest = json.loads((REPO / "BENCHMARK.json").read_text())
    entry = next(c for c in manifest["configs"]
                 if c["name"] == "xing4-29b-a4b-serve")
    cfg = json.loads((REPO / entry["file"]).read_text())
    assert entry["source"] == row["source_url"] == cfg["source"]
    reduced = set(entry["reduced"])
    assert reduced == {"num_hidden_layers", "first_k_dense_replace"}
    for key, value in row["config"].items():
        if key in reduced:
            assert cfg[key] != value, key
            assert cfg["published"][key] == value, key
            assert key in cfg["reduced_why"]
        else:
            assert cfg[key] == value, key
    # no width among the reduced keys: depth alone
    for key in reduced:
        assert not key.endswith(("_dim", "_rank", "_size"))
    assert cfg["num_hidden_layers"] == 5 and cfg["first_k_dense_replace"] == 1
    # the names the shared readers read repeat two published keys
    assert cfg["num_dense_layers"] == cfg["first_k_dense_replace"]
    assert cfg["num_experts"] == cfg["n_routed_experts"] == 64
    for key in ("assumed", "departures", "limits", "limits_from",
                "deployment"):
        assert cfg[key]
    # the widths by name
    assert (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["q_lora_rank"], cfg["kv_lora_rank"]) == (3584, 32, 768, 512)
    assert (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
            cfg["v_head_dim"]) == (128, 64, 128)
    assert (cfg["intermediate_size"], cfg["moe_intermediate_size"],
            cfg["num_experts_per_tok"], cfg["n_shared_experts"]) == (
                9216, 1024, 4, 1)
    assert (cfg["vocab_size"], cfg["tie_word_embeddings"]) == (131072, False)
    assert (cfg["hc_mult"], cfg["hc_sinkhorn_iters"]) == (4, 20)
    prog = cfg["program"]
    assert prog["max_context"] % prog["page_tokens"] == 0
    assert prog["max_context"] >= prog["max_prompt"] + 256
    trinity = json.loads(
        (REPO / "chipbench/configs/trinity-mini-serve.json").read_text())
    assert prog == trinity["program"]  # one schedule for all three
    cell = next(w for w in manifest["workloads"]
                if w["name"] == "serve_xing4_mixed")
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "xing4-29b-a4b-serve", "mixed_backlog", 1)


# The cell and its metrics are looked up by name: later cells are
# appended behind it on every list, as the guard's copy does (_tiny.py).
@pytest.mark.parametrize("holds", ["shared_lists", "own_metrics",
                                   "left_out"])
def test_the_manifest_lists_the_cell_by_name(checkout, holds):
    import _tiny

    manifest = json.loads((checkout / "BENCHMARK.json").read_text())
    by_name = {m["name"]: m
               for m in manifest["end_to_end"] + manifest["per_layer"]}
    cell = "serve_xing4_mixed"
    if holds == "shared_lists":
        # on each, behind the cell that was accepted before it
        for name in ("serve_tok_s", "slot_occupancy_pct",
                     "decode_step_hbm_pct", "serve_device_idle_pct",
                     "idle_in_admit_pct", "idle_in_decode_pct",
                     "idle_in_harvest_pct", "idle_outside_step_pct",
                     "admitting_slots_pct", "tick_gather_share_pct",
                     "chunks_per_prefill_program", "moe_share_pct",
                     "moe_experts_hbm_pct", "experts_hit_pct"):
            assert _tiny.stands_after(by_name[name]["workloads"], cell,
                                      "serve_q3next_mixed"), name
    elif holds == "own_metrics":
        names = [m["name"] for m in manifest["per_layer"]]
        for name in ("mla_attn_share_pct", "mla_cache_hbm_pct",
                     "mla_prefill_share_pct", "hc_share_pct"):
            m = by_name[name]
            # the first of its cells: a later latent cell comes behind
            assert m["workloads"][0] == cell
            assert (m["moves"], m["layer"], m["unit"]) == (
                "serve_tok_s", "model step", "%")
            assert (checkout / "chipbench/metrics" / f"{name}.py").is_file()
            assert _tiny.stands_after(names, name,
                                      "chunks_per_prefill_program")
    else:
        # not the tail, nor the three metrics that move it: its quartile
        # distance read 3.1 to 6.8% in four sets of six on the chip, over
        # the 3% a new cell is admitted at (PERF.md section 2)
        for name in ("kv_full_pages_pct", "gdn_share_pct",
                     "experts_local_pct", "train_tok_s", "itl_p95_ms",
                     "prefill_share_pct", "itl_p50_ms",
                     "first_token_wait_ms"):
            assert cell not in by_name[name]["workloads"]


def test_the_published_configuration_is_the_programs_block():
    """The runner's ``TransformerConfig`` at the published sizes, and
    YaRN's table in it against the reference's direct evaluation."""
    import numpy as np

    from chipbench.references import xing4_0
    from chipbench.runners import serve_mla

    cfg = json.loads(
        (REPO / "chipbench/configs/xing4-29b-a4b-serve.json").read_text())
    model = serve_mla.transformer_config(cfg)
    assert (model.d_model, model.n_heads, model.head_dim) == (3584, 32, 192)
    assert (model.mla_q_rank, model.mla_kv_rank, model.mla_nope_dim,
            model.mla_rope_dim, model.mla_v_dim) == (768, 512, 128, 64, 128)
    assert model.latent_width == 576 and model.hc_mult == 4
    assert model.attn_scale == pytest.approx(192 ** -0.5 * 1.4159 ** 2,
                                             rel=1e-4)
    assert model.layer_experts == (False, True, True, True, True)
    assert (model.n_experts, model.experts_per_token, model.d_expert,
            model.shared_experts, model.route_scale) == (64, 4, 1024, 1, 2.0)
    np.testing.assert_allclose(
        model.rope_table, xing4_0.yarn_frequencies(64, serve_mla.yarn(cfg)),
        rtol=1e-12)
    # the fast pairs keep their frequency, the slow ones a 64th of it
    base = 10000.0 ** (-np.arange(32) / 32)
    assert model.rope_table[0] == pytest.approx(base[0])
    assert model.rope_table[-1] == pytest.approx(base[-1] / 64)
    assert xing4_0.yarn_mscale(1.0, 64.0) == pytest.approx(1.4159, rel=1e-4)
    # the reference's constants are the published keys
    assert (xing4_0.TOP_K, xing4_0.ROUTE_SCALE, xing4_0.KV_RANK,
            xing4_0.NOPE, xing4_0.ROPE, xing4_0.HC_ITERS) == (
        cfg["num_experts_per_tok"], cfg["routed_scaling_factor"],
        cfg["kv_lora_rank"], cfg["qk_nope_head_dim"],
        cfg["qk_rope_head_dim"], cfg["hc_sinkhorn_iters"])
    assert xing4_0.YARN == serve_mla.yarn(cfg)
    assert xing4_0.HC_CLAMP == (cfg["mhc_h_res_clamp_min"],
                                cfg["mhc_h_res_clamp_max"])
    assert xing4_0.HC_EPS == cfg["hc_eps"]
    assert xing4_0.RMS_EPS == cfg["rms_norm_eps"]


def test_the_reference_imports_nothing_of_the_program():
    src = (REPO / "chipbench/references/xing4_0.py").read_text()
    assert "mpistragglers_jl_tpu" not in src.split('"""', 2)[2]


# -- the readers on hand-made device events ------------------------------------


def _run_with(info):
    from chipbench.metrics import _program_spans as ps

    loaded = ps.ProgramSpans((0.0, 1e9), [], 0.0, {}, 0.0)
    return types.SimpleNamespace(
        summary=object(),
        info={ps.CACHE_KEY: loaded, "slots": 16, "n_inner": 8, **info},
        config={}, peaks={"hbm_bytes_per_s": 819e9}, trace_dir="",
    )


def test_scope_readers_on_hand_made_device_events(monkeypatch):
    """Two runs of a tick program, one each of the lone chunk's and the
    grouped chunk's program; ns."""
    from chipbench import trace_reduce
    from chipbench.metrics import _mla_scopes, _program_spans as ps
    from chipbench.metrics import (
        hc_share_pct,
        mla_attn_share_pct,
        mla_cache_hbm_pct,
        mla_prefill_share_pct,
    )

    ops = []
    for t0 in (1000, 11000):
        ops += [("%while.1", t0, 8000),            # the scan, 1000 of its own
                ("%fusion.2", t0 + 100, 1000),     # q projections
                ("%fusion.3", t0 + 1200, 2000),    # scores, softmax, values
                ("%fusion.4", t0 + 3300, 1500),    # the mixing
                ("%gmm.5", t0 + 4900, 2500)]       # experts, another scope
    ops += [("%fusion.7", 21000, 3000), ("%fusion.8", 24000, 1000),
            ("%fusion.7", 31000, 5000), ("%fusion.8", 36000, 3000)]
    device = {0: {"ops": ops, "modules": [
        ("jit_serving_tick_paged(7)", 1000, 8000),
        ("jit_serving_tick_paged(7)", 11000, 8000),
        ("jit_serving_prefill_chunk(9)", 21000, 4000),
        ("jit_serving_prefill_chunk_x4(10)", 31000, 8000)]}}
    scopes = {(7, "%fusion.2"): "jit(f)/while/body/mla_q/dot",
              (7, "%fusion.3"): "jit(f)/while/body/mla_attn/mul",
              (7, "%fusion.4"): "jit(f)/while/body/decode_mlp/hc_mix/div",
              (7, "%gmm.5"): "jit(f)/decode_mlp/moe_experts/gmm",
              (7, "%while.1"): "jit(f)/while",
              (9, "%fusion.7"): "jit(f)/moe_experts/gmm",
              (9, "%fusion.8"): "jit(f)/mla_attn/while/body/dot",
              (10, "%fusion.7"): "jit(f)/moe_experts/gmm",
              (10, "%fusion.8"): "jit(f)/jit(grouped_layer)/mla_attn/dot"}
    monkeypatch.setattr(trace_reduce, "load_xplane",
                        lambda path: {"device": device, "host": []})
    monkeypatch.setattr(ps, "op_scopes", lambda path: scopes)
    tick = _mla_scopes.reduce_scopes(
        "unused", lambda n: n == "jit_serving_tick_paged_7", (0, 50000))
    assert tick["runs"] == 2
    assert tick["whole"] == pytest.approx(16000e-9)
    assert tick["mla_q"] == pytest.approx(2000e-9)
    assert tick["mla_attn"] == pytest.approx(4000e-9)
    assert tick["hc_mix"] == pytest.approx(3000e-9)
    assert tick["mla_kv"] == tick["mla_out"] == 0.0
    chunk = _mla_scopes.reduce_scopes(
        "unused", lambda n: n.startswith(_mla_scopes.CHUNK_PROGRAM),
        (0, 50000))
    assert chunk["runs"] == 2
    assert chunk["whole"] == pytest.approx(12000e-9)
    assert chunk["mla_attn"] == pytest.approx(4000e-9)

    run = _run_with({
        "mean_kv_rows_per_tick": 5 * 9000.0, "kv_row_bytes": 584,
        _mla_scopes.CACHE_KEY + "_tick": tick,
        _mla_scopes.CACHE_KEY + "_chunk": chunk})
    assert mla_attn_share_pct.read(run) == pytest.approx(100 * 4000 / 16000)
    assert hc_share_pct.read(run) == pytest.approx(100 * 3000 / 16000)
    assert mla_prefill_share_pct.read(run) == pytest.approx(
        100 * 4000 / 12000)
    # 9000 rows in each of 5 layers at 584 B, 8 steps, 2 ticks, over 4 us
    assert mla_cache_hbm_pct.read(run) == pytest.approx(
        100 * 45000 * 584 * 8 * 2 / (4000e-9 * 819e9))
    # no operation under such a scope (a parent commit, another model)
    monkeypatch.setattr(ps, "op_scopes", lambda path: {
        k: "jit(f)/decode_attn/dot" for k in scopes})
    assert _mla_scopes.reduce_scopes(
        "unused", lambda n: n == "jit_serving_tick_paged_7",
        (0, 50000)) is None
    none = types.SimpleNamespace(summary=None, info={}, config={},
                                 peaks=None)
    for reader in (mla_attn_share_pct, mla_cache_hbm_pct,
                   mla_prefill_share_pct, hc_share_pct):
        assert reader.read(none) is None
