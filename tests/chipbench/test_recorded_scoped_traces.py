"""The readers of the model's own scopes and of the trainer's span on
real trace files, recorded on one v5e chip from programs that open the
scopes: chipbench/testdata/small_train_scoped_tpu.xplane.pb.gz (by
record_train_scoped_trace.py: five steps of a tiny ``make_train_step``)
and small_serving_scoped_tpu.xplane.pb.gz (by
record_serving_scoped_trace.py: the tiny paged scheduler of
small_serving_tpu.xplane.pb.gz, whose file stays as it was recorded,
from a program without them). The numbers are the ones the two scripts
printed."""

from __future__ import annotations

import gzip
import shutil
import sys
import types
from pathlib import Path

import pytest

from chipbench import common
from chipbench import run as bench
from chipbench import trace_reduce as tr
from chipbench.metrics import _program_spans as ps
from chipbench.metrics import _scope_time as st

REPO = Path(__file__).resolve().parents[2]
TESTDATA = REPO / "chipbench/testdata"
sys.path.insert(0, str(TESTDATA))

import record_serving_scoped_trace as serving_recorder  # noqa: E402
import record_train_scoped_trace as train_recorder  # noqa: E402


def reader(name):
    return bench.load_from(REPO, "metrics", name)


def unpacked(tmp_path_factory, name: str, **more):
    """What the harness hands a reader: the reduced trace, the directory
    that still holds the file, the chip's published peaks."""
    trace_dir = tmp_path_factory.mktemp(name.split(".")[0])
    with gzip.open(TESTDATA / (name + ".gz"), "rb") as f, open(
            trace_dir / name, "wb") as g:
        shutil.copyfileobj(f, g)
    summary = tr.reduce_events(tr.load_xplane(tr.find_xplane(str(trace_dir))))
    return types.SimpleNamespace(
        summary=summary, trace_dir=str(trace_dir), info={},
        peaks=common.peaks_for("TPU v5 lite"), **more)


@pytest.fixture(scope="module")
def train(tmp_path_factory):
    return unpacked(
        tmp_path_factory, train_recorder.NAME, config=train_recorder.CONFIG,
        traffic={"batch": train_recorder.BATCH, "seq": train_recorder.SEQ})


@pytest.fixture(scope="module")
def serving(tmp_path_factory):
    run = unpacked(tmp_path_factory, serving_recorder.NAME,
                   config=serving_recorder.CONFIG)
    run.info.update(slots=4, n_inner=serving_recorder.N_INNER)
    return run


# -- the train step -----------------------------------------------------------


def test_the_train_steps_time_by_scope(train):
    t = st.train_step_time(train)
    assert t["runs"] == train_recorder.STEPS
    assert t["whole"] == pytest.approx(2.3468e-3, rel=1e-4)
    assert t["whole"] == pytest.approx(train.summary.busy_s)
    assert sum(t[k] for k in st.KINDS) == pytest.approx(t["whole"])
    assert {k: round(1e6 * v, 1) for k, v in t["scope"].items()} == {
        "embed": 159.0, "attn_qkv": 390.4, "attn_out": 55.1, "ffn": 303.1,
        "head": 112.2, "loss": 173.7, "sgd_update": 22.3}
    # forward and backward are told apart, and the update is neither
    assert {k: round(1e6 * v, 1) for k, v in t["backward"].items()} == {
        "embed": 110.4, "attn_qkv": 203.6, "attn_out": 38.9, "ffn": 224.9,
        "head": 77.6, "loss": 56.9, "sgd_update": 0.0}
    # the flash kernels keep the names ``flash_share_pct`` finds
    assert 100 * t["kernel"] / t["whole"] == pytest.approx(
        reader("flash_share_pct").read(train))
    assert max(t["outside"], key=t["outside"].get) == "fusion"


@pytest.mark.parametrize("name,value", [
    ("train_scoped_pct", 92.766), ("train_ffn_share_pct", 12.916),
    ("train_proj_share_pct", 18.982), ("train_head_loss_share_pct", 12.184),
    ("train_matmul_mxu_pct", 35.560), ("flash_pairs_useful_pct", 50.016),
    ("flash_share_pct", 40.955),
])
def test_the_trainers_readers_on_the_recorded_steps(train, name, value):
    assert reader(name).read(train) == pytest.approx(value, abs=1e-3)


def test_train_step_is_there_once_a_step_with_its_six_arguments(train):
    spans = reader("flash_pairs_useful_pct").step_spans(
        tr.find_xplane(train.trace_dir))
    assert len(spans) == train_recorder.STEPS
    # one (batch x head) sweep of 2 x 2 blocks of 1024 under a window of
    # 1024: the block left of the diagonal runs, half masked
    assert all({k: str(v) for k, v in a.items()} == {
        "tokens": "2048", "flash_block": "1024x1024",
        "flash_grid_steps": "4", "flash_run_steps": "3",
        "flash_pairs_run": "3145728", "flash_pairs_band": "1573376",
    } for a in spans)


# -- the serving programs -----------------------------------------------------


def test_the_ticks_time_by_scope(serving):
    t = st.tick_time(serving)
    assert t["runs"] == 8
    assert t["whole"] == pytest.approx(448.33e-6, rel=1e-4)
    got = {k: round(1e6 * v, 1) for k, v in t["scope"].items() if v > 0}
    assert got == {
        "embed": 11.4, "attn_qkv": 53.2, "attn_out": 12.8, "ffn": 26.9,
        "head": 37.8, "decode_attn": 137.5, "decode_mlp": 26.9,
        "kv_page_gather": 57.0, "kv_page_scatter": 49.9}
    # the feed-forward nests in the older scope and counts once
    assert t["scope"]["ffn"] == t["scope"]["decode_mlp"]
    assert sum(t["backward"].values()) == 0
    assert t["unscoped"] / t["whole"] < 0.03
    assert max(t["outside"], key=t["outside"].get) == "copy"


def test_the_prefill_programs_are_the_lone_chunks_and_the_groups(serving):
    t = st.prefill_time(serving)
    runs = {name.rsplit("_", 1)[0]: len(v)
            for name, v in serving.summary.modules.items()
            if name.startswith(st.PREFILL_PROGRAMS)}
    assert runs == {"jit_serving_prefill_chunk": 5,
                    "jit_serving_prefill_chunk_x4": 2}
    assert t["runs"] == 7
    assert t["whole"] == pytest.approx(94.30e-6, rel=1e-3)
    assert t["scope"]["chunk_attn"] == pytest.approx(21.69e-6, rel=1e-3)
    assert t["scope"]["head"] == 0  # the first token's program has it


@pytest.mark.parametrize("name,value", [
    ("tick_scoped_pct", 86.229), ("head_share_pct", 8.430),
    ("head_hbm_pct", 27.115), ("prefill_scoped_pct", 55.584),
    ("chunk_attn_share_pct", 22.998),
])
def test_the_model_steps_readers_on_the_recorded_ticks(serving, name, value):
    assert reader(name).read(serving) == pytest.approx(value, abs=1e-3)


def test_an_accepted_scope_reader_reads_the_new_file_too(serving):
    assert reader("tick_gather_share_pct").read(serving) == pytest.approx(
        36.203, abs=1e-2)
    scopes = ps.op_scopes(tr.find_xplane(serving.trace_dir))
    found = {p for v in scopes.values() for p in ps.scope_parts(v)}
    assert set(ps.TICK_SCOPES) | set(st.BLOCK_SCOPES) | {"chunk_attn"} <= found


# -- a program without the scopes ---------------------------------------------


NEW_SERVING = ("tick_scoped_pct", "head_share_pct", "head_hbm_pct",
               "prefill_scoped_pct", "chunk_attn_share_pct")


@pytest.fixture(scope="module")
def parent(tmp_path_factory):
    """The trace the tree had, recorded from a program without the new
    scopes: what a parent commit gives these readers."""
    run = unpacked(tmp_path_factory, "small_serving_tpu.xplane.pb",
                   config=serving_recorder.CONFIG)
    run.info.update(slots=4, n_inner=4)
    return run


@pytest.mark.parametrize("name", NEW_SERVING + ("flash_pairs_useful_pct",))
def test_a_program_without_the_scopes_reads_nothing(parent, name, capsys):
    assert reader(name).read(parent) is None
    assert "time_by_scope" not in capsys.readouterr().out
