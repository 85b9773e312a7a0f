"""Each kind of cell, rehearsed on the CPU at tiny size from a throw-
away checkout into which a configuration, a mix, a cell and a per-layer
metric were dropped as new files and manifest entries (_tiny.py)."""

from __future__ import annotations

import json
import time

import pytest

from chipbench import common, control
from chipbench import run as bench

TOP_KEYS = {"correct", "attempted", "failed", "metrics", "device"}
CELLS = ["tiny_train", "tiny_serve"]
END_TO_END = {
    "tiny_train": {"setup_s", "train_tok_s"},
    "tiny_serve": {"setup_s", "serve_tok_s", "itl_p95_ms"},
}
# per-layer metrics that need no device trace, so a CPU run reports them
HOST_PER_LAYER = {
    "tiny_train": {"step_p50_ms", "tiny_attempted"},
    "tiny_serve": {"slot_occupancy_pct", "itl_p50_ms"},
}


def one_run(root, cell, trace, seed=2**31 + 11):
    return bench.run_cell(root, cell, seed, 0.6, trace, require_chip=False,
                          t_start=time.perf_counter())


@pytest.mark.parametrize("cell", CELLS)
def test_result_line_has_exactly_the_contract_keys(tiny_root, cell):
    result = one_run(tiny_root, cell, False)
    assert set(result) == TOP_KEYS
    assert result["correct"] is True
    assert result["attempted"] > 0 and result["failed"] == 0
    assert set(result["metrics"]) == END_TO_END[cell]
    for m in result["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0
    assert set(result["device"]) == {
        "platform", "kind", "count", "memory_peak_bytes"}
    json.dumps(result)


@pytest.mark.parametrize("cell", CELLS)
def test_traced_run_reports_per_layer_metrics_and_no_device_number(
        tiny_root, cell):
    result = one_run(tiny_root, cell, True)
    assert set(result) - {"breakdown"} == TOP_KEYS
    assert result["correct"] is True
    # no chip: nothing read from a device trace may appear
    assert set(result["metrics"]) == HOST_PER_LAYER[cell]
    assert "busy_s" not in result["device"]
    assert result["device"]["platform"] == "cpu"


def test_dropped_in_metric_is_found_by_name(tiny_root):
    result = one_run(tiny_root, "tiny_train", True)
    assert result["metrics"]["tiny_attempted"]["value"] == result["attempted"]
    assert result["metrics"]["tiny_attempted"]["unit"] == "1"


def test_a_cpu_device_makes_the_command_fail(capsys):
    rc = bench.main(["--workload", "train_sc2_8k", "--seed", "1",
                     "--seconds", "1", "--trace", "0"])
    out = capsys.readouterr()
    assert rc != 0
    assert "needs a TPU" in out.err
    assert "correct" not in out.out


def test_more_chips_than_present_is_refused():
    with pytest.raises(common.NoChip):
        common.find_devices(4096, require_chip=True)


def test_an_unknown_device_kind_has_no_peaks():
    with pytest.raises(KeyError):
        common.peaks_for("TPU v99")
    assert common.peaks_for("TPU v5 lite")["bf16_flops_per_s"] == 197e12


# -- the timed path broken underneath: correct must come out false --------


def break_train(monkeypatch):
    """A step that returns its state unchanged."""
    import jax

    from mpistragglers_jl_tpu.models import transformer

    real = transformer.make_train_step

    def broken(cfg, mesh, **kw):
        kw["donate"] = False
        step = real(cfg, mesh, **kw)
        return lambda params, inp, tgt: (params, step(params, inp, tgt)[1])

    monkeypatch.setattr(transformer, "make_train_step", broken)


def break_serve(monkeypatch):
    """Every token altered where the tick hands it to the host."""
    from mpistragglers_jl_tpu.models.serving import ServingScheduler

    real = ServingScheduler._decode_scan_fetch

    def broken(self):
        return (real(self) + 1) % self.cfg.vocab

    monkeypatch.setattr(ServingScheduler, "_decode_scan_fetch", broken)


@pytest.mark.parametrize("cell,breaker", [
    ("tiny_train", break_train),
    ("tiny_serve", break_serve),
])
def test_a_broken_timed_path_is_not_correct(tiny_root, monkeypatch, cell,
                                            breaker):
    breaker(monkeypatch)
    result = one_run(tiny_root, cell, False)
    assert result["correct"] is False
    assert set(result) == TOP_KEYS


def test_train_with_part_of_the_batch_left_out_is_not_correct(
        tiny_root, monkeypatch):
    from mpistragglers_jl_tpu.models import transformer

    real = transformer.make_train_step

    def broken(cfg, mesh, **kw):
        step = real(cfg, mesh, **kw)
        half = lambda a: a.at[1:].set(a[:1])  # row 1 repeats row 0
        return lambda p, inp, tgt: step(p, half(inp), half(tgt))

    monkeypatch.setattr(transformer, "make_train_step", broken)
    assert one_run(tiny_root, "tiny_train", False)["correct"] is False


# -- the control: a lower precision in the program's place must fail ------


def test_train_control_in_lower_precision_fails_a_limit(tiny_root):
    row = control.readings(tiny_root, "tiny_train", 7, 0.3, ["fp8"],
                           require_chip=False)
    limits = json.loads(
        (tiny_root / "chipbench/configs/tiny-train.json").read_text()
    )["limits"]
    assert row["correct"] is True
    low = row["control"]["fp8"]
    assert (max(low["loss"]) > limits["loss_rel_gap"]
            or low["grad"] > limits["grad_norm_gap"])
    assert low["grad"] > 3 * row["sound"][
        "first_gradient_norm_worst_leaf_gap"]


@pytest.mark.parametrize("seed", [7, 2**31 + 8])
def test_serve_control_in_lower_precision_fails_a_limit(tiny_root, seed):
    # the window is a stretch of the schedule (two rounds at this
    # --seconds), so a seed's readings repeat: the sound ones have to
    # pass both limits and the control to fail one, as on the chip
    row = control.readings(tiny_root, "tiny_serve", seed, 0.3, ["fp8"],
                           require_chip=False)
    limit = json.loads(
        (tiny_root / "chipbench/configs/tiny-serve.json").read_text()
    )["limits"]
    assert row["correct"] is True
    sound, low = row["sound"], row["control"]["fp8"]
    assert sound["served_token_logit_gap_worst"] <= limit["logit_gap_worst"]
    assert sound["served_token_logit_gap_mean"] <= limit["logit_gap_mean"]
    assert (low["logit_gap_worst"] > limit["logit_gap_worst"]
            or low["logit_gap_mean"] > limit["logit_gap_mean"])
