"""chipbench/metrics/_scope_time.py on hand-made events: how a ``tf_op``
is taken apart, where an operation's self time goes, and what the
readers make of it; chipbench/counts_train_scopes.py against a
hand-worked case; the eleven metrics' entries in BENCHMARK.json."""

from __future__ import annotations

import json
import types
from pathlib import Path

import pytest

from chipbench import counts, counts_train_scopes
from chipbench import run as bench
from chipbench.metrics import _scope_time as st

REPO = Path(__file__).resolve().parents[2]
MS = 1_000_000  # ns


@pytest.mark.parametrize("tf_op,parts,backward", [
    ("jit(step)/jvp()/ffn/dot_general:", {"jit(step)", "", "ffn",
                                          "dot_general"}, False),
    ("jit(step)/transpose(jvp())/ffn/bld,df->blf/dot_general",
     {"jit(step)", "", "ffn", "bld,df->blf", "dot_general"}, True),
    # a name stack that wraps the scope itself
    ("jit(step)/transpose(jvp(ffn))/mul", {"jit(step)", "ffn", "mul"}, True),
    ("jit(step)/jvp(head)/reduce_max", {"jit(step)", "head", "reduce_max"},
     False),
    ("jit(serving_tick_paged)/while/body/decode_mlp/ffn/add",
     {"jit(serving_tick_paged)", "while", "body", "decode_mlp", "ffn",
      "add"}, False),
    # other wrappers are left as they are: a program named like a scope
    # is no scope
    ("jit(head)/add", {"jit(head)", "add"}, False),
])
def test_a_path_is_unwrapped_into_parts_and_a_direction(tf_op, parts,
                                                        backward):
    assert st.path_facts(tf_op) == (frozenset(parts), backward)


@pytest.mark.parametrize("tf_op, at_loop", [
    ("jit(serving_tick_paged)/while:", True),
    ("jit(serving_tick_paged)/while/body/closed_call/while", True),
    ("jit(serving_tick_paged)/while/body/dynamic_update_slice:", False),
    ("jit(serving_tick_paged)/while/body/decode_mlp/ffn/add", False),
    ("caches[0]['k']:", False),
])
def test_a_path_that_ends_at_a_loop_is_the_loops_own(tf_op, at_loop):
    assert st.names_the_loop(tf_op) is at_loop


def _trace():
    """One chip, two executions of ``jit_step(7)`` of 10 ms each and one
    of another program; the window cuts nothing. Per step: 3 ms under
    ``ffn`` forward, 2 backward, 1 ms of a flash kernel, half a
    millisecond with a ``tf_op`` and no scope, half of a slice that
    carries a loop instruction's own path, 1 ms without a ``tf_op``,
    and a ``while`` of 2 ms whose body (1.5 ms under ``head``) leaves
    it 0.5 ms of its own, under ``loss``."""
    ops, t = [], 0
    for step in range(2):
        t = step * 20 * MS
        for name, dur in (("%fusion.1", 3), ("%fusion.2", 2),
                          ("%jvp__.3", 1), ("%copy.4", 0.5),
                          ("%slice-done.8", 0.5), ("%copy-done.5", 1)):
            ops.append((name, t, int(dur * MS)))
            t += int(dur * MS)
        ops.append(("%while.6", t, 2 * MS))
        ops.append(("%fusion.7", t + MS // 4, 3 * MS // 2))
    ops.append(("%fusion.1", 50 * MS, 4 * MS))  # the other program's
    modules = [("jit_step(7)", 0, 10 * MS), ("jit_step(7)", 20 * MS, 10 * MS),
               ("jit_other(9)", 50 * MS, 5 * MS)]
    tf_ops = {
        (7, "%fusion.1"): "jit(step)/jvp()/ffn/dot_general",
        (7, "%fusion.2"): "jit(step)/transpose(jvp())/ffn/dot_general",
        (7, "%jvp__.3"): "jit(step)/jvp()/pallas_call",
        (7, "%copy.4"): "jit(step)/jvp()/transpose",
        (7, "%slice-done.8"): "jit(step)/while:",
        (7, "%while.6"): "jit(step)/jvp()/loss/while",
        (7, "%fusion.7"): "jit(step)/jvp()/loss/while/body/head/mul",
        (9, "%fusion.1"): "jit(other)/ffn/dot_general",
    }
    raw = {"device": {0: {"ops": ops, "modules": modules}}, "host": []}
    return raw, tf_ops


def test_self_time_goes_to_scopes_kernels_and_the_rest():
    raw, tf_ops = _trace()
    t = st.reduce_scopes(
        raw, tf_ops, lambda name: name.startswith("jit_step"),
        (0, 60 * MS), st.TRAIN_SCOPES, st.TRAIN_SCOPES,
        lambda op: op.startswith("jvp"))
    assert t["runs"] == 2
    assert t["whole"] == pytest.approx(20e-3)
    assert t["scope"]["ffn"] == pytest.approx(10e-3)
    assert t["backward"]["ffn"] == pytest.approx(4e-3)
    # the body counts under both scopes on its path, the loop's own
    # half millisecond under ``loss`` alone; once under ``any``
    assert t["scope"]["head"] == pytest.approx(3e-3)
    assert t["scope"]["loss"] == pytest.approx(4e-3)
    assert t["any"] == pytest.approx(14e-3)
    assert t["kernel"] == pytest.approx(2e-3)
    # a path that ends at the loop instruction is the compiler's work
    # for the loop, not an operation the program wrote without a scope
    assert t["unscoped"] == pytest.approx(1e-3)
    assert t["loop"] == pytest.approx(1e-3)
    assert t["no_tf_op"] == pytest.approx(2e-3)
    assert t["outside"] == pytest.approx(
        {"copy": 1e-3, "slice-done": 1e-3, "copy-done": 2e-3})
    assert sum(t[k] for k in st.KINDS) == pytest.approx(t["whole"])
    line = st.note_line("train_step", t)
    assert line.startswith("note train_step_time_by_scope_ms whole=20.000 ")
    assert " ffn=6.000+4.000 " in line and " copy-done=2.000" in line
    assert " loop_instruction=1.000 tf_op_but_no_scope=1.000 " in line


def test_the_window_cuts_and_the_program_is_a_parameter():
    raw, tf_ops = _trace()
    is_step = lambda name: name.startswith("jit_step")
    t = st.reduce_scopes(raw, tf_ops, is_step, (15 * MS, 60 * MS),
                         ("ffn",), ("ffn",))
    assert t["runs"] == 1 and t["scope"]["ffn"] == pytest.approx(5e-3)
    other = st.reduce_scopes(raw, tf_ops, lambda name: name == "jit_other_9",
                             (0, 60 * MS), ("ffn",), ("ffn",))
    assert other["whole"] == other["scope"]["ffn"] == pytest.approx(4e-3)


def test_a_program_without_the_new_scopes_reads_none():
    raw, tf_ops = _trace()
    old = {k: v.replace("ffn", "decode_mlp").replace("head", "decode_attn")
           .replace("loss", "moe_route") for k, v in tf_ops.items()}
    is_step = lambda name: name.startswith("jit_step")
    assert st.reduce_scopes(raw, old, is_step, (0, 60 * MS),
                            st.SERVE_SCOPES, st.BLOCK_SCOPES) is None
    assert st.reduce_scopes(raw, {}, is_step, (0, 60 * MS),
                            st.TRAIN_SCOPES, st.TRAIN_SCOPES) is None


NEW = {
    "train_scoped_pct": ("trainer", "train_tok_s", "train"),
    "train_ffn_share_pct": ("trainer", "train_tok_s", "train"),
    "train_proj_share_pct": ("trainer", "train_tok_s", "train"),
    "train_head_loss_share_pct": ("trainer", "train_tok_s", "train"),
    "train_matmul_mxu_pct": ("trainer", "train_tok_s", "train"),
    "flash_pairs_useful_pct": ("train kernels", "train_tok_s", "train"),
    "tick_scoped_pct": ("model step", "serve_tok_s", "serve"),
    "head_share_pct": ("model step", "serve_tok_s", "serve"),
    "head_hbm_pct": ("model step", "serve_tok_s", "serve"),
    "prefill_scoped_pct": ("model step", "itl_p95_ms", "tail"),
    "chunk_attn_share_pct": ("model step", "itl_p95_ms", "tail"),
}


@pytest.mark.parametrize("name", NEW)
def test_every_reader_finds_nothing_without_a_device_trace(name):
    """A CPU run has no reduced trace: the harness leaves the metric
    out (and a parent's trace has none of the scopes: the case above)."""
    run = types.SimpleNamespace(summary=None, info={}, peaks=None)
    assert bench.load_from(REPO, "metrics", name).read(run) is None


@pytest.mark.parametrize("name", NEW)
def test_the_manifest_lists_the_metric_after_the_accepted_ones(name,
                                                               checkout):
    import _tiny

    manifest = json.loads((checkout / "BENCHMARK.json").read_text())
    names = [m["name"] for m in manifest["per_layer"]]
    assert _tiny.stands_after(names, name, "hc_share_pct")
    layer, moves, cells = NEW[name]
    entry = manifest["per_layer"][names.index(name)]
    moved = next(m for m in manifest["end_to_end"] if m["name"] == moves)
    want = {
        "train": [w["name"] for w in manifest["workloads"]
                  if w["name"] not in _tiny.serving_cells(checkout, manifest)],
        "serve": _tiny.serving_cells(checkout, manifest),
        "tail": moved["workloads"],
    }[cells]
    assert entry["workloads"] == want
    assert (entry["layer"], entry["moves"], entry["unit"]) == (
        layer, moves, "%")


def test_the_products_operations_by_scope_on_a_hand_worked_case():
    sz = dict(d_model=8, n_heads=2, kv_heads=1, d_ff=16, n_layers=3,
              vocab=32)
    got = counts_train_scopes.matmul_train_flops(batch=2, seq=5, **sz)
    # a token: q 8x8, k and v 8x4 each, out 8x8: 192 weights; the MLP
    # 2 x 8 x 16 = 256; the head 8 x 32 = 256; 2 operations a weight,
    # 10 tokens, 3 layers, forward and twice that
    assert got == {"projections": 3 * 3 * 2 * 10 * 192,
                   "feed_forward": 3 * 3 * 2 * 10 * 256,
                   "head": 3 * 2 * 10 * 256}
    # with the attention kernels' operations it is the whole step's count
    assert sum(got.values()) + counts.flash_train_flops(
        batch=2, seq=5, n_heads=2, head_dim=4, n_layers=3, window=3
    ) == counts.transformer_train_flops(batch=2, seq=5, window=3, **sz)


def test_the_training_cells_products_are_ninety_teraflop_a_step():
    got = counts_train_scopes.matmul_train_flops(
        batch=2, seq=8192, d_model=3072, n_heads=24, kv_heads=2,
        d_ff=12288, n_layers=8, vocab=49152)
    assert {k: round(v / 1e12, 1) for k, v in got.items()} == {
        "projections": 16.1, "feed_forward": 59.4, "head": 14.8}
