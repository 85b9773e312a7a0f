"""The ``serve_sala`` kind: rehearsed on the CPU at tiny size from a
throw-away checkout (as test_serve_gdn.py does for its kind), its
shapes against the program's ``init_params``, its configuration file
against the catalog row it was drawn from, a state lost at a chunk
boundary against the comparison, and its per-layer readers on hand-made
spans and device events (tests/test_sala_block.py holds the selection
itself to the reference, in logits and in the blocks picked)."""

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
NAME, CELL_NAME, MIX = ("minicpm-sala-9b-serve", "serve_sala_long",
                        "long_1in16_backlog")

# the published keys at a size the CPU runs in seconds; the longest
# request of tiny_backlog (60 + 8 rows) sees more than dense_len rows
# in its prefill, the short class's longest (20 + 16) while decoding
TINY = {
    "kind": "serve_sala", "reference": "minicpm_sala",
    "hidden_size": 64, "head_dim": 16, "num_attention_heads": 4,
    "num_key_value_heads": 2, "intermediate_size": 96,
    "lightning_nh": 4, "lightning_nkv": 4, "lightning_head_dim": 16,
    "lightning_use_rope": True, "attn_use_rope": False,
    "num_hidden_layers": 4,
    "mixer_types": ["minicpm4", "lightning-attn", "lightning-attn",
                    "lightning-attn"],
    "published": {"num_hidden_layers": 32},
    "qk_norm": True, "attn_use_output_gate": True, "rope_theta": 10000,
    "rms_norm_eps": 1e-6, "scale_emb": 12, "scale_depth": 1.4,
    "dim_model_base": 16, "vocab_size": 128, "tie_word_embeddings": False,
    "torch_dtype": "float32",
    "assumed": {"sparse_config": {
        "block_size": 8, "topk": 2, "kernel_size": 4, "kernel_stride": 2,
        "init_blocks": 1, "window_size": 16, "dense_len": 32}},
    "program": {"slots": 4, "n_inner": 4, "quantize_kv": True,
                "page_tokens": 8, "prompt_chunk": 16, "max_prompt": 64,
                "max_context": 96, "attn": "ulysses",
                "attn_impl": "reference"},
    # Over 8 seeds at this size (three above 2**31) the sound runs read
    # a worst gap and a mean of 0 (float32 weights; the logits are a
    # sixteenth of a unit-scale head's, and the int8 K/V noise of the one
    # attention layer moves no served token off the reference's best);
    # the fp8 control 0.0023 to 0.045 and 0.000024 to 0.00096.
    "limits": {"logit_gap_worst": 0.001, "logit_gap_mean": 1e-5},
}
CELL = "tiny_serve_sala"


@pytest.fixture(scope="module")
def sala_root(tmp_path_factory):
    """_tiny.py's throw-away checkout with one more configuration and
    cell dropped in, of the new kind, reporting what the committed cell
    of this kind reports."""
    import _tiny

    root = _tiny.make_tiny_checkout(tmp_path_factory.mktemp("chipbench_sala"))
    (root / "chipbench/configs/tiny-serve-sala.json").write_text(
        json.dumps(TINY))
    manifest = json.loads((root / "BENCHMARK.json").read_text())
    manifest["configs"].append({
        "name": "tiny-serve-sala", "source": "tests/chipbench",
        "file": "chipbench/configs/tiny-serve-sala.json", "reduced": [],
        "why": "throw-away"})
    manifest["workloads"].append({
        "name": CELL, "config": "tiny-serve-sala",
        "traffic": "tiny_backlog", "chips": 1, "why": "throw-away"})
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        if CELL_NAME in m.get("workloads", ()):
            m["workloads"].append(CELL)
    (root / "BENCHMARK.json").write_text(json.dumps(manifest))
    return root


def one_run(root, trace, seed=2**31 + 11):
    return bench.run_cell(root, CELL, seed, 0.6, trace, require_chip=False,
                          t_start=time.perf_counter())


def test_result_line_of_the_new_kind(sala_root):
    result = one_run(sala_root, False)
    assert set(result) == {"correct", "attempted", "failed", "metrics",
                           "device"}
    assert result["correct"] is True
    assert result["attempted"] > 0 and result["failed"] == 0
    assert set(result["metrics"]) == {"setup_s", "serve_tok_s", "itl_p95_ms"}
    json.dumps(result)


def test_traced_run_on_the_cpu_reports_no_device_number(sala_root):
    result = one_run(sala_root, True)
    assert result["correct"] is True
    # no chip: the readers of the device trace and of the program's
    # spans in it find nothing and leave their metric out
    assert set(result["metrics"]) == {"slot_occupancy_pct", "itl_p50_ms"}


def test_a_broken_timed_path_is_not_correct(sala_root, monkeypatch):
    from mpistragglers_jl_tpu.models.serving import ServingScheduler

    real = ServingScheduler._decode_scan_fetch
    monkeypatch.setattr(
        ServingScheduler, "_decode_scan_fetch",
        lambda self: (real(self) + 1) % self.cfg.vocab)
    assert one_run(sala_root, False)["correct"] is False


def test_a_state_lost_at_a_chunk_boundary_is_not_correct(sala_root,
                                                         monkeypatch):
    """At the configuration's decay constants the slowest heads
    remember hundreds of tokens, so a program that starts every prefill
    chunk from a zero state fails the comparison."""
    import jax
    import jax.numpy as jnp

    from mpistragglers_jl_tpu.models import serving

    def forgetful(real):
        def factory(*a):
            chunk = real(*a)

            def run(params, tokens, cache, *rest, **kw):
                zero = lambda c: [
                    jax.tree.map(jnp.zeros_like, cl) if "S" in cl else cl
                    for cl in c]
                cache = (zero(cache) if isinstance(cache[0], dict)
                         else tuple(zero(c) for c in cache))
                return chunk(params, tokens, cache, *rest, **kw)

            return run

        return factory

    monkeypatch.setattr(serving, "_extend_chunk_dense",
                        forgetful(serving._extend_chunk_dense))
    monkeypatch.setattr(serving, "_extend_chunk_group",
                        forgetful(serving._extend_chunk_group))
    assert one_run(sala_root, False)["correct"] is False


def test_the_runners_decay_is_the_published_layers(sala_root):
    import numpy as np

    from chipbench.runners import serve_sala
    from mpistragglers_jl_tpu.models.transformer import la_slopes

    made = serve_sala.make_params(TINY, 2**31 + 5)
    for li in (1, 2, 3):
        slope = np.asarray(made["layers"][li]["la_slope"])
        want = 2.0 ** (-8.0 * (np.arange(4) + 1) / 4) * (
            1 - li / 31 + 1e-5)
        np.testing.assert_allclose(slope, want, rtol=1e-6)
        # the program's own constants at the published depth
        np.testing.assert_allclose(slope, la_slopes(4, li, 32), rtol=1e-6)
    # at the published 32 heads the slowest head keeps 0.9965 a token
    # in layer 3 and the fastest 0.44 in layer 1
    lam = np.exp(-serve_sala.la_slopes(32, 3, 32))
    assert lam[-1] == pytest.approx(0.99648, abs=1e-4)
    assert np.exp(-serve_sala.la_slopes(32, 1, 32))[0] == pytest.approx(
        0.4434, abs=1e-3)


def test_control_in_lower_precision_fails_a_limit(sala_root):
    row = control.readings(sala_root, CELL, 7, 0.3, ["fp8"],
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

    from chipbench.runners import serve_sala
    from mpistragglers_jl_tpu.models.transformer import init_params

    model = serve_sala.transformer_config(TINY)
    params = init_params(model, seed=0)
    want = jax.tree.map(lambda a: (a.shape, a.dtype), params)
    got = jax.tree.map(lambda s: (s.shape, s.dtype),
                       serve_sala.param_shapes(TINY))
    assert got == want
    made = serve_sala.make_params(TINY, 2**31 + 5)
    assert jax.tree.map(lambda a: (a.shape, a.dtype), made) == want
    for lp in made["layers"]:
        for name in lp:
            if name.endswith("_s"):
                assert float(abs(lp[name] - 1).max()) == 0.0
    assert model.layer_mixers == ("attn", "la", "la", "la")
    assert (model.sparse_block, model.sparse_topk, model.sparse_kernel,
            model.sparse_stride, model.sparse_init_blocks,
            model.sparse_window, model.sparse_dense_len) == (
        8, 2, 4, 2, 1, 16, 32)
    assert model.residual_scale == pytest.approx(1.4 / 32 ** 0.5)
    assert model.head_scale == 0.25 and model.emb_scale == 12.0
    assert not model.rope_at(0)


def test_published_widths_build_by_shape_alone(checkout):
    """1,711M parameters, 3.42 GB in bfloat16: the runner's shapes, the
    counts' arithmetic and ISSUE 42's agree."""
    import math

    from chipbench import counts_sala
    from chipbench.runners import serve_sala

    cfg = json.loads((checkout / "chipbench/configs"
                      / f"{NAME}.json").read_text())
    shapes = serve_sala.param_shapes(cfg)
    import jax

    n = sum(math.prod(s.shape) for s in jax.tree.leaves(shapes))
    assert n == counts_sala.model_params(**serve_sala.sizes(cfg))
    assert round(n / 1e6) == 1711
    model = serve_sala.transformer_config(cfg)
    assert model.layer_mixers == ("attn", "la", "la", "la")
    assert model.max_context == 33792


# -- the configuration file against the catalog row ----------------------------


def test_configuration_keeps_every_published_key_but_the_reduced(checkout):
    if not CATALOG.is_file():
        pytest.skip("no catalog on this machine")
    row = next(r for r in map(json.loads, CATALOG.read_text().splitlines())
               if r["name"] == "MiniCPM-SALA")
    manifest = json.loads((checkout / "BENCHMARK.json").read_text())
    entry = next(c for c in manifest["configs"] if c["name"] == NAME)
    cfg = json.loads((checkout / entry["file"]).read_text())
    assert entry["source"] == row["source_url"] == cfg["source"]
    reduced = set(entry["reduced"])
    assert reduced == {"num_hidden_layers", "mixer_types"}
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
    assert cfg["num_hidden_layers"] == 4
    assert cfg["mixer_types"] == row["config"]["mixer_types"][:4] == [
        "minicpm4", "lightning-attn", "lightning-attn", "lightning-attn"]
    for key in ("assumed", "departures", "deployment", "limits",
                "limits_from"):
        assert cfg[key]
    for key in ("sparse_config", "sparse_config_source", "pooling_rule",
                "tie_rule", "decay", "output_norm_span", "common"):
        assert cfg["assumed"][key], key
    assert cfg["assumed"]["sparse_config"] == {
        "block_size": 64, "topk": 64, "kernel_size": 32,
        "kernel_stride": 16, "init_blocks": 1, "window_size": 2048,
        "dense_len": 8192}
    # the widths by name
    assert (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["head_dim"],
            cfg["intermediate_size"]) == (4096, 32, 2, 128, 16384)
    assert (cfg["lightning_nh"], cfg["lightning_nkv"],
            cfg["lightning_head_dim"]) == (32, 32, 128)
    assert (cfg["vocab_size"], cfg["tie_word_embeddings"]) == (73448, False)
    # the same call and program keys as the long Qwen3-Next cell's
    long = json.loads((checkout / "chipbench/configs/"
                       "q3next-80b-a3b-serve-long.json").read_text())
    assert cfg["program"] == long["program"]
    prog = cfg["program"]
    assert prog["page_tokens"] == cfg["assumed"]["sparse_config"][
        "block_size"]
    assert prog["max_context"] <= cfg["max_position_embeddings"]
    cell = next(w for w in manifest["workloads"] if w["name"] == CELL_NAME)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (NAME, MIX, 1)


def test_the_cells_mix_and_metrics(checkout):
    import _tiny

    from chipbench import traffic_gen

    manifest = json.loads((checkout / "BENCHMARK.json").read_text())
    mix = json.loads((checkout / "chipbench/traffic"
                      / f"{MIX}.json").read_text())
    assert (mix["round"], mix["rounds"], mix["warm_rounds"],
            mix["window_rounds"], mix["check_requests"],
            mix["trace_seconds"]) == (32, 12, 4, 4, 5, 4)
    shares = {c["name"]: c["share"] for c in mix["classes"]}
    assert shares == {"chat": 0.9375, "long_document": 0.0625}
    one = traffic_gen.ordered_requests(mix)[:32]
    long = sorted((p, o) for _, p, o in one if p > 8192)
    assert long == [(19484, 362), (27554, 724)]
    assert len([1 for _, p, _ in one if p <= 512]) == 30
    by_name = {m["name"]: m
               for m in manifest["end_to_end"] + manifest["per_layer"]}
    new = ("la_share_pct", "la_state_hbm_pct", "la_prefill_share_pct",
           "sparse_select_share_pct", "sparse_blocks_pct",
           "sparse_rows_hbm_pct")
    names = [m["name"] for m in manifest["per_layer"]]
    for name in new:
        m = by_name[name]
        assert m["workloads"] == [CELL_NAME] or CELL_NAME in m["workloads"]
        assert (m["layer"], m["unit"], m["moves"]) == (
            "model step", "%", "serve_tok_s")
        assert (checkout / "chipbench/metrics" / f"{name}.py").is_file()
        assert _tiny.stands_after(names, name, "gdn_rule_roofline_pct")
    for name in ("setup_s", "serve_tok_s", "slot_occupancy_pct",
                 "decode_step_hbm_pct", "serve_device_idle_pct",
                 "decode_attn_share_pct", "chunk_attn_share_pct",
                 "head_hbm_pct"):
        assert CELL_NAME in by_name[name].get("workloads", [CELL_NAME])
    # its yardstick is every row a request HAS, which a selection beats
    assert CELL_NAME not in by_name["attn_rows_hbm_pct"]["workloads"]


# -- the readers on hand-made spans and device events --------------------------


def _run_with_spans(spans, info=None):
    from chipbench.metrics import _program_spans as ps

    loaded = ps.ProgramSpans((0.0, 1e9), spans, 0.0, {}, 0.0)
    return types.SimpleNamespace(
        summary=object(),
        info={ps.CACHE_KEY: loaded, "slots": 16, "n_inner": 8,
              **(info or {})},
        config=json.loads((REPO / "chipbench/configs"
                           / f"{NAME}.json").read_text()),
        peaks={"hbm_bytes_per_s": 819e9}, trace_dir="",
    )


def test_counter_reader_on_hand_made_spans():
    from chipbench.metrics import _program_spans as ps
    from chipbench.metrics import sparse_blocks_pct

    spans = [
        ps.HostSpan("serving.tick", 0, 9, {
            "sparse_slots": 2, "blocks_attended": 392, "blocks_visible": 1500}),
        ps.HostSpan("serving.tick", 10, 19, {
            "sparse_slots": 0, "blocks_attended": 0, "blocks_visible": 0}),
        ps.HostSpan("serving.prefill_chunk", 3, 5, {
            "chunks": 1, "blocks_attended": 50000, "blocks_visible": 100000}),
        ps.HostSpan("serving.prefill_chunk", 13, 15, {"chunks": 1}),
    ]
    assert sparse_blocks_pct.read(_run_with_spans(spans)) == pytest.approx(
        100 * 50392 / 101500)
    # no query of the window saw more than dense_len rows, or a program
    # that wrote no such argument (a parent commit): nothing
    assert sparse_blocks_pct.read(_run_with_spans(spans[1:2])) is None
    assert sparse_blocks_pct.read(_run_with_spans(spans[3:])) is None
    assert sparse_blocks_pct.read(types.SimpleNamespace(
        summary=None, info={}, config={})) is None


def test_scope_readers_on_hand_made_device_events(monkeypatch):
    """Two runs of a tick program and one of a chunk program; ns."""
    from chipbench import trace_reduce
    from chipbench.metrics import _program_spans as ps
    from chipbench.metrics import _sala_scopes
    from chipbench.metrics import (
        la_prefill_share_pct,
        la_share_pct,
        la_state_hbm_pct,
        sparse_rows_hbm_pct,
        sparse_select_share_pct,
    )

    ops = []
    for t0 in (1000, 11000):
        ops += [("%while.1", t0, 8000),            # the scan, 1000 of its own
                ("%fusion.2", t0 + 100, 1000),     # la projections
                ("%fusion.3", t0 + 1200, 2000),    # the recurrence
                ("%fusion.4", t0 + 3300, 500),     # the pick
                ("%select.5", t0 + 3900, 1500),    # the kernel
                ("%fusion.6", t0 + 5500, 1000),    # the feed-forward
                ("%copy-done.9", t0 + 6600, 1000)]  # the compiler's, unscoped
    ops += [("%fusion.7", 21000, 5000), ("%fusion.8", 26000, 2000),
            ("%fusion.9", 28000, 1000)]
    device = {0: {"ops": ops,
                  "modules": [("jit_serving_tick_paged(7)", 1000, 8000),
                              ("jit_serving_tick_paged(7)", 11000, 8000),
                              ("jit_serving_prefill_chunk(9)", 21000, 8000)]}}
    scopes = {(7, "%fusion.2"): "jit(f)/while/body/la_proj/dot",
              (7, "%fusion.3"): "jit(f)/while/body/la_rule/mul",
              (7, "%fusion.4"): "jit(f)/while/body/sparse_select/vmap()/max",
              (7, "%select.5"): "jit(f)/while/body/decode_attn/pallas_call",
              (7, "%fusion.6"): "jit(f)/while/body/decode_mlp/ffn/dot",
              (7, "%while.1"): "jit(f)/while",
              (9, "%fusion.7"): "jit(f)/ffn/dot",
              (9, "%fusion.8"): "jit(f)/la_rule/while/body/dot",
              (9, "%fusion.9"): "jit(f)/sparse_select/vmap()/dot"}
    monkeypatch.setattr(trace_reduce, "load_xplane",
                        lambda path: {"device": device, "host": []})
    monkeypatch.setattr(ps, "op_scopes", lambda path: scopes)
    tick = _sala_scopes.reduce_scopes(
        "unused", lambda n: n == "jit_serving_tick_paged_7", (0, 40000))
    assert tick["runs"] == 2
    assert tick["whole"] == pytest.approx(16000e-9)
    assert tick["la_proj"] == pytest.approx(2000e-9)
    assert tick["la_rule"] == pytest.approx(4000e-9)
    assert tick["sparse_select"] == pytest.approx(1000e-9)
    assert tick["decode_attn"] == pytest.approx(3000e-9)
    assert tick["moves"] == pytest.approx(2000e-9)
    chunk = _sala_scopes.reduce_scopes(
        "unused", lambda n: n.startswith("jit_serving_prefill_chunk"),
        (0, 40000))
    assert chunk["la_rule"] == pytest.approx(2000e-9)
    assert chunk["sparse_select"] == pytest.approx(1000e-9)

    state = 2 * 16 * 3 * 4 * 32 * 128 * 128
    run = _run_with_spans([], {
        "la_state_bytes": state, "kv_row_bytes": 528,
        "kv_rows_by_tick": [10000.0, 12000.0, 99999.0],
        _sala_scopes.CACHE_KEY + "_tick": tick,
        _sala_scopes.CACHE_KEY + "_chunk": chunk})
    assert la_share_pct.read(run) == pytest.approx(100 * 6000 / 16000)
    assert la_prefill_share_pct.read(run) == pytest.approx(100 * 2000 / 8000)
    assert sparse_select_share_pct.read(run) == pytest.approx(
        100 * 2000 / 24000)
    # S of 16 slots in 3 layers, read and written, 8 steps, 2 ticks, over
    # 4 us under la_rule and 2 us of the compiler's copies
    assert la_state_hbm_pct.read(run) == pytest.approx(
        100 * state * 8 * 2 / (6000e-9 * 819e9))
    # the two traced ticks' must-read rows at 528 bytes, 8 steps each,
    # over the kernel's 3 us and the pick's 1
    assert sparse_rows_hbm_pct.read(run) == pytest.approx(
        100 * 22000 * 528 * 8 / (4000e-9 * 819e9))
    # a program without the new scopes (a parent commit, another model)
    run.info[_sala_scopes.CACHE_KEY + "_tick"] = {
        **tick, **{s: 0.0 for s in _sala_scopes.LA_SCOPES
                   + _sala_scopes.SELECT_SCOPES}}
    run.info[_sala_scopes.CACHE_KEY + "_chunk"] = None
    for reader in (la_share_pct, la_prefill_share_pct, la_state_hbm_pct,
                   sparse_select_share_pct, sparse_rows_hbm_pct):
        assert reader.read(run) is None
    none = types.SimpleNamespace(summary=None, info={}, config={},
                                 peaks=None)
    for reader in (la_share_pct, la_prefill_share_pct, la_state_hbm_pct,
                   sparse_select_share_pct, sparse_rows_hbm_pct):
        assert reader.read(none) is None
