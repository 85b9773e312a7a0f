"""The ``serve_dsv3`` kind: rehearsed on the CPU at tiny size from a
throw-away checkout (as test_serve_mla.py does for its kind), its shapes
against the program's ``init_params``, its configuration file against
the catalog row it was drawn from, faults injected into the drafter
against the three comparisons that decide ``correct``, and its
per-layer readers on hand-made spans and device events."""

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
CELL_NAME = "serve_dsv3_chat"

# the published keys at a size the CPU runs in seconds
TINY = {
    "kind": "serve_dsv3", "reference": "deepseek_v3",
    "hidden_size": 32, "num_attention_heads": 4, "num_key_value_heads": 4,
    "q_lora_rank": 16, "kv_lora_rank": 24, "qk_nope_head_dim": 8,
    "qk_rope_head_dim": 4, "v_head_dim": 8, "attention_bias": False,
    "intermediate_size": 48, "moe_intermediate_size": 16,
    "n_routed_experts": 8, "router_experts": 16, "experts_held": [0, 8],
    "num_experts": 8, "n_shared_experts": 1, "num_experts_per_tok": 4,
    "norm_topk_prob": True, "routed_scaling_factor": 2.5,
    "scoring_func": "sigmoid", "topk_method": "noaux_tc", "n_group": 4,
    "topk_group": 2, "moe_layer_freq": 1, "first_k_dense_replace": 1,
    "num_dense_layers": 1, "num_hidden_layers": 3,
    "num_nextn_predict_layers": 1,
    "rms_norm_eps": 1e-6, "rope_theta": 10000,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40,
                     "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 16,
                     "type": "yarn"},
    "vocab_size": 128, "tie_word_embeddings": False,
    "torch_dtype": "float32",
    # as in the cell: the head is drawn at 1/sqrt(d), so logits have
    # deviation about 1 and at temperature 1 the shared noise makes
    # about half of the drafts agree: both paths of the step run
    "program": {"slots": 4, "n_inner": 4, "quantize_kv": True,
                "page_tokens": 8, "prompt_chunk": 16, "max_prompt": 64,
                "max_context": 96, "temperature": 1.0, "draft": "mtp",
                "attn": "ulysses", "attn_impl": "reference"},
    # float32 weights: what is left is the int8 rows' noise. Over 5
    # seeds at this size the sound runs read a served mean of at most
    # 0.0002 and a drafted mean of at most 0.0065 (worst 0.39), the fp8
    # control 0.023 to 0.064 and 0.014 to 0.034
    "limits": {"logit_gap_worst": 1.0, "logit_gap_mean": 0.01,
               "draft_gap_worst": 1.0, "draft_gap_mean": 0.01,
               "accept_rate_gap": 0.08},
}
CELL = "tiny_serve_dsv3"


@pytest.fixture(scope="module")
def dsv3_root(tmp_path_factory):
    """_tiny.py's throw-away checkout with one more configuration and
    cell dropped in, of the new kind, reporting what the committed cell
    of this kind reports."""
    import _tiny

    root = _tiny.make_tiny_checkout(tmp_path_factory.mktemp("chipbench_dsv3"))
    (root / "chipbench/configs/tiny-serve-dsv3.json").write_text(
        json.dumps(TINY))
    manifest = json.loads((root / "BENCHMARK.json").read_text())
    manifest["configs"].append({
        "name": "tiny-serve-dsv3", "source": "tests/chipbench",
        "file": "chipbench/configs/tiny-serve-dsv3.json", "reduced": [],
        "why": "throw-away"})
    manifest["workloads"].append({
        "name": CELL, "config": "tiny-serve-dsv3",
        "traffic": "tiny_backlog", "chips": 1, "why": "throw-away"})
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        if CELL_NAME in m.get("workloads", ()):
            m["workloads"].append(CELL)
    (root / "BENCHMARK.json").write_text(json.dumps(manifest))
    return root


def one_run(root, trace, seed=2**31 + 17):
    return bench.run_cell(root, CELL, seed, 0.6, trace, require_chip=False,
                          t_start=time.perf_counter())


def test_result_line_of_the_new_kind(dsv3_root, capsys):
    result = one_run(dsv3_root, False)
    assert set(result) == {"correct", "attempted", "failed", "metrics",
                           "device"}
    assert result["correct"] is True
    assert result["attempted"] > 0 and result["failed"] == 0
    assert set(result["metrics"]) == {"setup_s", "serve_tok_s"}
    json.dumps(result)
    out = capsys.readouterr().out
    # all three comparisons were read, from the timed path's streams
    for name in ("served_token_logit_gap_worst", "served_token_logit_gap_mean",
                 "drafted_token_logit_gap_worst",
                 "drafted_token_logit_gap_mean",
                 "accept_rate_gap_to_reference", "drafts_were_verified"):
        assert f"check {name}:" in out
    note = next(ln for ln in out.splitlines()
                if ln.startswith("note drafts "))
    drafted, accepted = int(note.split()[3]), int(note.split()[5])
    assert 0.2 * drafted < accepted < 0.9 * drafted  # both paths ran


def test_traced_run_on_the_cpu_reports_no_device_number(dsv3_root):
    result = one_run(dsv3_root, True)
    assert result["correct"] is True
    assert set(result["metrics"]) == {"slot_occupancy_pct"}


def test_the_drafter_off_copy_of_the_file_runs(dsv3_root):
    """``program.draft`` removed, as the builder's drafter-off reading
    removes it: the same cell serves one token a step and is judged on
    the served tokens alone."""
    path = dsv3_root / "chipbench/configs/tiny-serve-dsv3.json"
    off = json.loads(path.read_text())
    del off["program"]["draft"]
    path.write_text(json.dumps(off))
    try:
        result = one_run(dsv3_root, False)
    finally:
        path.write_text(json.dumps(TINY))
    assert result["correct"] is True and result["failed"] == 0


@pytest.mark.parametrize("fault", [
    "served_tokens", "stops_drafting", "cheaper_draft", "module_input",
    "group_limit"])
def test_an_injected_fault_is_not_correct(dsv3_root, monkeypatch, fault):
    """A timed path that serves other tokens; a scheduler that no
    longer drafts; a draft from something cheaper than the module (the
    last token again); a module that is handed another token than the
    one that follows its position; a router without the group limit.
    The fault is in the PROGRAM alone."""
    from mpistragglers_jl_tpu.models import decode, moe, serving
    from mpistragglers_jl_tpu.models.serving import ServingScheduler

    if fault == "served_tokens":
        real = ServingScheduler._fetch_drafted

        def fetch(self, toks):
            host = real(self, toks).copy()
            host[..., :2] = (host[..., :2] + 1) % self.cfg.vocab
            return host

        monkeypatch.setattr(ServingScheduler, "_fetch_drafted", fetch)
    elif fault == "stops_drafting":
        real_init = ServingScheduler.__init__

        def init(self, *a, draft=None, **kw):
            real_init(self, *a, draft=None, **kw)

        monkeypatch.setattr(ServingScheduler, "__init__", init)
    elif fault == "cheaper_draft":
        real_step = serving._draft_step

        def step(params, tok, *a, **kw):
            carry, out = real_step(params, tok, *a, **kw)
            new_tok = carry[0].at[:, 1].set(carry[0][:, 0])
            return (new_tok,) + tuple(carry[1:]), out

        monkeypatch.setattr(serving, "_draft_step", step)
    elif fault == "module_input":
        real_input = serving.mtp_input
        monkeypatch.setattr(
            serving, "mtp_input",
            lambda params, h, nxt, cfg: real_input(
                params, h, (nxt + 1) % cfg.vocab, cfg))
    else:
        real_route = moe.topk_route
        monkeypatch.setattr(
            moe, "topk_route",
            lambda *a, **kw: real_route(*a[:6]))
    # the programs are cached by configuration: a fault patched in must
    # be traced anew, and the sound programs after it
    for cached in (serving._serving_scan_paged, serving._extend_chunk_dense,
                   serving._extend_chunk_group, serving._finish_admit_dense,
                   decode._grouped_layer):
        cached.cache_clear()
    try:
        assert one_run(dsv3_root, False)["correct"] is False
    finally:
        for cached in (serving._serving_scan_paged,
                       serving._extend_chunk_dense,
                       serving._extend_chunk_group,
                       serving._finish_admit_dense, decode._grouped_layer):
            cached.cache_clear()


def test_control_in_lower_precision_fails_a_limit(dsv3_root):
    row = control.readings(dsv3_root, CELL, 7, 0.3, ["fp8"],
                           require_chip=False)
    assert row["correct"] is True
    sound, low = row["sound"], row["control"]["fp8"]
    limit = TINY["limits"]
    assert sound["served_token_logit_gap_mean"] <= limit["logit_gap_mean"]
    assert sound["drafted_token_logit_gap_mean"] <= limit["draft_gap_mean"]
    assert (low["logit_gap_worst"] > limit["logit_gap_worst"]
            or low["logit_gap_mean"] > limit["logit_gap_mean"])
    assert (low["draft_gap_worst"] > limit["draft_gap_worst"]
            or low["draft_gap_mean"] > limit["draft_gap_mean"])


def test_shapes_are_the_programs_own():
    import jax

    from chipbench.runners import serve_dsv3
    from mpistragglers_jl_tpu.models.transformer import init_params

    model = serve_dsv3.transformer_config(TINY)
    params = init_params(model, seed=0)
    want = jax.tree.map(lambda a: (a.shape, a.dtype), params)
    got = jax.tree.map(lambda s: (s.shape, s.dtype),
                       serve_dsv3.param_shapes(TINY))
    assert got == want
    made = serve_dsv3.make_params(TINY, 2**31 + 5)
    assert jax.tree.map(lambda a: (a.shape, a.dtype), made) == want
    for name in ("hn_s", "en_s", "lnf_s"):
        assert float(abs(made["mtp"][name] - 1).max()) == 0.0
    assert float(abs(made["mtp"]["block"]["router_bias"]).max()) > 0.0
    assert made["mtp"]["block"]["we_gate"].shape[0] == 8
    assert made["mtp"]["block"]["router"].shape == (32, 16)
    assert model.layer_mixers == ("mla",) * 3 and model.mtp_depth == 1
    assert model.layer_experts == (False, True, True)
    assert (model.route_groups, model.route_topk_groups) == (4, 2)
    assert (model.n_experts, model.experts_held) == (16, (0, 8))
    assert model.hc_mult == 1 and model.head_dim == 12
    # two requests' keys differ, and a seed above 2**31 has keys
    a, b = (serve_dsv3.request_key(2**31 + 5, i) for i in (0, 1))
    assert (jax.random.key_data(a) != jax.random.key_data(b)).any()


def test_a_fixed_draw_gives_every_seed_the_same_values_in_another_order():
    """``weights_draw``: the values are that draw's for every seed; the
    seed orders the vocabulary's rows, embedding and head alike, and
    without the key every seed draws its own values as before."""
    import jax
    import numpy as np

    from chipbench.runners import serve_dsv3

    fixed = {**TINY, "weights_draw": {"seed": 7}}
    a, b = (serve_dsv3.make_params(fixed, seed) for seed in (11, 2**31 + 12))
    again = serve_dsv3.make_params(fixed, 11)
    own = serve_dsv3.make_params(TINY, 7)
    rest = lambda p: {k: v for k, v in p.items() if k not in ("emb", "head")}
    same = lambda x, y: all(jax.tree.leaves(jax.tree.map(
        lambda u, v: bool((u == v).all()), x, y)))
    assert same(rest(a), rest(b)) and same(rest(a), rest(own))
    assert same(a, again)
    emb_a, emb_b, emb_own = (np.asarray(p["emb"]) for p in (a, b, own))
    assert not (emb_a == emb_b).all()
    # one order for embedding and head: row i of a is row order[i] of
    # the draw, in both
    order = [int(np.flatnonzero((emb_own == row).all(axis=1))[0])
             for row in emb_a]
    assert sorted(order) == list(range(TINY["vocab_size"]))
    assert order != list(range(TINY["vocab_size"]))
    assert (np.asarray(a["head"]) == np.asarray(own["head"])[order]).all()
    # no key: the seed's own values
    assert not same(rest(serve_dsv3.make_params(TINY, 11)), rest(own))


# -- the configuration file against the catalog row ----------------------------


def test_configuration_keeps_every_published_key_but_the_reduced(checkout):
    if not CATALOG.is_file():
        pytest.skip("no catalog on this machine")
    REPO = checkout
    row = next(r for r in map(json.loads, CATALOG.read_text().splitlines())
               if r["name"] == "DeepSeek-V3")
    manifest = json.loads((REPO / "BENCHMARK.json").read_text())
    entry = next(c for c in manifest["configs"]
                 if c["name"] == "dsv3-671b-a37b-serve")
    cfg = json.loads((REPO / entry["file"]).read_text())
    assert entry["source"] == row["source_url"] == cfg["source"]
    reduced = set(entry["reduced"])
    assert reduced == {"num_hidden_layers", "first_k_dense_replace",
                       "n_routed_experts", "vocab_size"}
    for key, value in row["config"].items():
        if key in reduced:
            assert cfg[key] != value, key
            assert cfg["published"][key] == value, key
            assert key in cfg["reduced_why"]
        else:
            assert cfg[key] == value, key
    # no width among the reduced keys
    for key in reduced:
        assert not key.endswith(("_dim", "_rank"))
        assert key not in ("hidden_size", "intermediate_size",
                           "moe_intermediate_size", "num_experts_per_tok")
    assert (cfg["num_hidden_layers"], cfg["first_k_dense_replace"],
            cfg["n_routed_experts"], cfg["vocab_size"]) == (5, 1, 16, 16160)
    assert cfg["vocab_size"] * 8 == cfg["published"]["vocab_size"]
    assert cfg["num_nextn_predict_layers"] == 1
    # the held experts as q3next-80b-a3b-serve.json states its own
    assert cfg["router_experts"] == cfg["published"]["n_routed_experts"]
    assert cfg["experts_held"] == [0, 16]
    assert cfg["num_experts"] == cfg["n_routed_experts"]
    assert cfg["num_dense_layers"] == cfg["first_k_dense_replace"]
    for key in ("assumed", "departures", "limits", "limits_from",
                "deployment"):
        assert cfg[key]
    for key in ("mtp_module", "drafting_step", "experts",
                "latent_attention"):
        assert cfg["assumed"][key]
    assert (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["q_lora_rank"], cfg["kv_lora_rank"]) == (7168, 128, 1536, 512)
    assert (cfg["intermediate_size"], cfg["moe_intermediate_size"],
            cfg["num_experts_per_tok"], cfg["n_group"],
            cfg["topk_group"]) == (18432, 2048, 8, 8, 4)
    prog = cfg["program"]
    assert prog["max_context"] % prog["page_tokens"] == 0
    # the largest prompt, the largest answer and a drafting tick's rows
    assert prog["max_context"] >= (prog["max_prompt"] + 256
                                   + 2 * prog["n_inner"])
    assert (prog["temperature"], prog["draft"]) == (1.0, "mtp")
    # one draw's values for every seed (PERF.md section 2)
    assert cfg["weights_draw"]["seed"] == 39 and cfg["weights_draw"]["why"]
    assert set(cfg["limits"]) == {
        "logit_gap_worst", "logit_gap_mean", "draft_gap_worst",
        "draft_gap_mean", "accept_rate_gap"}
    cell = next(w for w in manifest["workloads"] if w["name"] == CELL_NAME)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "dsv3-671b-a37b-serve", "chat_backlog", 1)
    # behind the cell and the configuration accepted before them
    import _tiny

    assert _tiny.stands_after([w["name"] for w in manifest["workloads"]],
                              CELL_NAME, "serve_xing4_mixed")
    assert _tiny.stands_after([c["name"] for c in manifest["configs"]],
                              entry["name"], "xing4-29b-a4b-serve")


def test_the_manifest_lists_the_cell_where_the_issue_names_it(checkout):
    import _tiny

    REPO = checkout
    manifest = json.loads((REPO / "BENCHMARK.json").read_text())
    by_name = {m["name"]: m
               for m in manifest["end_to_end"] + manifest["per_layer"]}
    joined = ["serve_tok_s", "slot_occupancy_pct", "decode_step_hbm_pct",
              "serve_device_idle_pct", "idle_in_admit_pct",
              "idle_in_decode_pct", "idle_in_harvest_pct",
              "idle_outside_step_pct", "admitting_slots_pct",
              "tick_gather_share_pct", "moe_share_pct", "experts_hit_pct",
              "experts_local_pct", "chunks_per_prefill_program",
              "mla_attn_share_pct", "mla_cache_hbm_pct",
              "mla_prefill_share_pct", "tick_scoped_pct", "head_share_pct",
              "head_hbm_pct"]
    for name in joined:
        # on the list, behind the cell that was accepted before it
        cells = by_name[name]["workloads"]
        assert CELL_NAME in cells, name
        if "serve_xing4_mixed" in cells:
            assert _tiny.stands_after(cells, CELL_NAME,
                                      "serve_xing4_mixed"), name
    own = {"mtp_accept_pct": ("scheduler (host)", "%"),
           "tokens_per_step": ("server", "tokens"),
           "mtp_share_pct": ("model step", "%"),
           "mtp_hbm_pct": ("model step", "%")}
    # its own four: together, in this order, behind PR 36's metrics
    names = [m["name"] for m in manifest["per_layer"]]
    at = names.index("mtp_accept_pct")
    assert names[at:at + 4] == list(own)
    assert _tiny.stands_after(names, "mtp_accept_pct",
                              "chunk_attn_share_pct")
    for name, (layer, unit) in own.items():
        m = by_name[name]
        assert (m["workloads"], m["moves"], m["layer"], m["unit"]) == (
            [CELL_NAME], "serve_tok_s", layer, unit)
        assert (REPO / "chipbench/metrics" / f"{name}.py").is_file()
    # left out: the tail and what moves it (the cell does not report
    # it), the readers of other mechanisms, and the expert products'
    # roofline share, whose reader counts the model's four expert
    # layers' bytes over a time that holds the module's fifth
    for name in ("itl_p95_ms", "prefill_share_pct", "itl_p50_ms",
                 "first_token_wait_ms", "kv_full_pages_pct", "gdn_share_pct",
                 "hc_share_pct", "moe_experts_hbm_pct", "train_tok_s"):
        assert CELL_NAME not in by_name[name]["workloads"]


def test_the_published_configuration_is_the_programs_block():
    import numpy as np

    from chipbench.references import deepseek_v3, xing4_0
    from chipbench.runners import serve_dsv3

    cfg = json.loads(
        (REPO / "chipbench/configs/dsv3-671b-a37b-serve.json").read_text())
    model = serve_dsv3.transformer_config(cfg)
    assert (model.d_model, model.n_heads, model.head_dim) == (7168, 128, 192)
    assert (model.mla_q_rank, model.mla_kv_rank, model.mla_nope_dim,
            model.mla_rope_dim, model.mla_v_dim) == (1536, 512, 128, 64, 128)
    assert model.latent_width == 576 and model.hc_mult == 1
    assert model.attn_scale == pytest.approx(192 ** -0.5 * 1.3689 ** 2,
                                             rel=1e-4)
    assert model.layer_experts == (False, True, True, True, True)
    assert (model.n_experts, model.experts_held, model.experts_per_token,
            model.d_expert, model.shared_experts, model.route_scale) == (
                256, (0, 16), 8, 2048, 1, 2.5)
    assert (model.route_groups, model.route_topk_groups) == (8, 4)
    assert (model.vocab, model.max_context, model.mtp_depth,
            model.cache_layers) == (16160, 832, 1, 6)
    np.testing.assert_allclose(
        model.rope_table,
        xing4_0.yarn_frequencies(64, serve_dsv3.yarn(cfg)), rtol=1e-12)
    assert (deepseek_v3.TOP_K, deepseek_v3.ROUTE_SCALE, deepseek_v3.N_GROUP,
            deepseek_v3.TOPK_GROUP, deepseek_v3.KV_RANK, deepseek_v3.NOPE,
            deepseek_v3.ROPE) == (
        cfg["num_experts_per_tok"], cfg["routed_scaling_factor"],
        cfg["n_group"], cfg["topk_group"], cfg["kv_lora_rank"],
        cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"])
    assert deepseek_v3.YARN == serve_dsv3.yarn(cfg)
    assert serve_dsv3.reference_sizes(cfg)["held_lo"] == 0


def test_the_reference_imports_nothing_of_the_program():
    src = (REPO / "chipbench/references/deepseek_v3.py").read_text()
    assert "mpistragglers_jl_tpu" not in src.split('"""', 2)[2]


def test_the_references_noise_is_the_programs_sampling():
    """``argmax(logits / T + gumbel_rows)`` is ``decode._pick_token``."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from chipbench.references import deepseek_v3
    from mpistragglers_jl_tpu.models.decode import _pick_token

    key = jax.random.key(11)
    logits = jax.random.normal(jax.random.key(3), (6, 500))
    noise = deepseek_v3.gumbel_rows(key, 40, 6, 500)
    for j in range(6):
        got = _pick_token(logits[j][None], jnp.int32(40 + j), key, 0.7, None,
                          jnp.int32)[0]
        assert int(got) == int(np.argmax(logits[j] / 0.7 + noise[j]))


# -- the readers on hand-made spans and device events --------------------------


def _spans_run(spans, info=None):
    from chipbench.metrics import _program_spans as ps

    loaded = ps.ProgramSpans((0.0, 1e9), spans, 0.0, {}, 0.0)
    return types.SimpleNamespace(
        summary=object(), info={ps.CACHE_KEY: loaded, **(info or {})},
        config={}, peaks={"hbm_bytes_per_s": 819e9}, trace_dir="")


def test_counter_readers_on_hand_made_spans():
    from chipbench.metrics import _program_spans as ps
    from chipbench.metrics import mtp_accept_pct, tokens_per_step

    tick = lambda **a: ps.HostSpan("serving.tick", 0.0, 1.0, a)
    harvest = lambda **a: ps.HostSpan("serving.harvest", 0.0, 1.0, a)
    run = _spans_run([
        tick(drafted=100, accepted=40), harvest(tokens=139),
        tick(drafted=60, accepted=20), harvest(tokens=80),
        tick(), harvest(tokens=0)])  # a tick without a decoding slot
    assert mtp_accept_pct.read(run) == pytest.approx(100 * 60 / 160)
    assert tokens_per_step.read(run) == pytest.approx(219 / 160)
    # a scheduler that does not draft (a parent commit, the drafter off)
    plain = _spans_run([tick(), harvest(tokens=128)])
    assert mtp_accept_pct.read(plain) is None
    assert tokens_per_step.read(plain) is None
    none = types.SimpleNamespace(summary=None, info={}, config={},
                                 peaks=None)
    for reader in (mtp_accept_pct, tokens_per_step):
        assert reader.read(none) is None


def test_scope_readers_on_hand_made_device_events(monkeypatch):
    """Two runs of a tick program; ns."""
    from chipbench import trace_reduce
    from chipbench.metrics import _program_spans as ps
    from chipbench.metrics import _scope_time, mtp_hbm_pct, mtp_share_pct

    ops = []
    for t0 in (1000, 11000):
        ops += [("%while.1", t0, 8000),            # the scan, 500 of its own
                ("%fusion.2", t0 + 100, 4000),     # the model's layers
                ("%fusion.3", t0 + 4200, 1000),    # the model's head
                ("%fusion.4", t0 + 5300, 300),     # the module's projection
                ("%gmm.5", t0 + 5700, 1200),       # its block's experts
                ("%fusion.6", t0 + 7000, 500)]     # its head
    device = {0: {"ops": ops, "modules": [
        ("jit_serving_tick_paged(7)", 1000, 8000),
        ("jit_serving_tick_paged(7)", 11000, 8000)]}}
    scopes = {(7, "%fusion.2"): "jit(f)/while/body/decode_mlp/ffn/dot",
              (7, "%fusion.3"): "jit(f)/while/body/head/dot",
              (7, "%fusion.4"): "jit(f)/while/body/mtp/mtp_proj/dot",
              (7, "%gmm.5"):
                  "jit(f)/while/body/mtp/decode_mlp/ffn/moe_experts/gmm",
              (7, "%fusion.6"): "jit(f)/while/body/mtp/mtp_head/dot",
              (7, "%while.1"): "jit(f)/while"}
    raw = {"device": device, "host": []}
    is_tick = lambda n: n == "jit_serving_tick_paged_7"
    t = _scope_time.reduce_scopes(raw, scopes, is_tick, (0, 50000),
                                  ("mtp", "mtp_proj", "mtp_head"), ("mtp",))
    assert t["whole"] == pytest.approx(16000e-9)
    assert t["scope"]["mtp"] == pytest.approx(4000e-9)
    assert t["scope"]["mtp_head"] == pytest.approx(1000e-9)
    # the model's head is not the module's: head_hbm_pct reads one
    # product a step
    head = _scope_time.reduce_scopes(raw, scopes, is_tick, (0, 50000),
                                     ("head",), ("head",))
    assert head["scope"]["head"] == pytest.approx(2000e-9)
    summary = types.SimpleNamespace(
        ops=[types.SimpleNamespace(name="while.1",
                                   module="jit_serving_tick_paged_7",
                                   dur=8000)],
        modules={"jit_serving_tick_paged_7": [(0, 0, 8000), (0, 0, 8000)]})
    run = types.SimpleNamespace(
        summary=summary, config={}, trace_dir="",
        peaks={"hbm_bytes_per_s": 819e9},
        info={"scope_time_tick_mtp": t, "n_inner": 8,
              "mtp_step_bytes": 2.0e5})
    assert mtp_share_pct.read(run) == pytest.approx(100 * 4000 / 16000)
    assert mtp_hbm_pct.read(run) == pytest.approx(
        100 * 2.0e5 * 8 * 2 / (4000e-9 * 819e9))
    # a program without the scope: nothing to read, and no raise
    none = _scope_time.reduce_scopes(
        raw, {k: v.replace("mtp", "xyz") for k, v in scopes.items()},
        is_tick, (0, 50000), ("mtp",), ("mtp",))
    assert none is None
    blank = types.SimpleNamespace(summary=None, info={}, config={},
                                  peaks=None)
    for reader in (mtp_share_pct, mtp_hbm_pct):
        assert reader.read(blank) is None
    run.info["scope_time_tick_mtp"] = None
    for reader in (mtp_share_pct, mtp_hbm_pct):
        assert reader.read(run) is None
    del monkeypatch, trace_reduce, ps
