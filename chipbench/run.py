"""Entry point of the chip benchmark.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one cell is found by name from
``BENCHMARK.json``: the configuration's file, the traffic mix
(``chipbench/traffic/<traffic>.json``), the runner for the
configuration's ``kind`` (``chipbench/runners/<kind>.py``) and, in a
traced run, one reader per per-layer metric
(``chipbench/metrics/<metric>.py``). Nothing here lists them.

The last line of standard output is the result; earlier lines are the
run's raw series, the numbers that decide ``correct`` beside their
limits, and notes. See chipbench/README.md.
"""

from __future__ import annotations

import argparse
import dataclasses
import importlib.util
import json
import shutil
import sys
import time
from pathlib import Path

T_START = time.perf_counter()

ROOT = Path(__file__).resolve().parent.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from chipbench import common  # noqa: E402


@dataclasses.dataclass
class Run:
    """What a runner is given, and what the per-layer readers read."""

    root: Path
    cell: dict
    config: dict
    traffic: dict
    seed: int
    seconds: float
    run_seconds: float  # the manifest's: the length --seconds has in a check
    trace: bool
    devices: list
    spans: common.Spans
    t_start: float
    trace_dir: str
    peaks: dict | None = None
    # filled by the runner
    info: dict = dataclasses.field(default_factory=dict)
    end_to_end: dict = dataclasses.field(default_factory=dict)
    window: tuple[float, float] = (0.0, 0.0)
    attempted: int = 0
    failed: int = 0
    check: common.Check = dataclasses.field(default_factory=common.Check)
    memory_peak_bytes: int = 0
    summary: object | None = None  # trace_reduce.TraceSummary
    meter: common.CompileMeter | None = None


def load_manifest(root: Path) -> dict:
    return common.load_json(root / "BENCHMARK.json")


def find_cell(manifest: dict, name: str) -> tuple[dict, dict]:
    cells = {w["name"]: w for w in manifest["workloads"]}
    if name not in cells:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json")
    cell = cells[name]
    configs = {c["name"]: c for c in manifest["configs"]}
    return cell, configs[cell["config"]]


def metrics_of(manifest: dict, group: str, cell: str) -> list[dict]:
    return [
        m for m in manifest[group]
        if "workloads" not in m or cell in m["workloads"]
    ]


def load_from(root: Path, folder: str, name: str):
    """The module ``chipbench/<folder>/<name>.py`` of the checkout at
    ``root``, found by name alone."""
    path = root / "chipbench" / folder / f"{name}.py"
    if not path.is_file():
        raise SystemExit(f"no {folder[:-1]} file {path}")
    spec = importlib.util.spec_from_file_location(
        f"chipbench_{folder}_{name}", path
    )
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def execute(root: Path, workload: str, seed: int, seconds: float,
            trace: bool, *, require_chip: bool = True,
            t_start: float | None = None):
    """Find the cell's files by name and run its runner once. Returns
    the finished ``Run`` and the manifest."""
    t_start = T_START if t_start is None else t_start
    root = Path(root)
    manifest = load_manifest(root)
    cell, config_entry = find_cell(manifest, workload)
    config = common.load_json(root / config_entry["file"])
    traffic = common.load_json(
        root / "chipbench" / "traffic" / f"{cell['traffic']}.json"
    )
    devices = common.find_devices(cell["chips"], require_chip)
    cache_dir = common.wire_compile_cache()
    print(f"device {json.dumps(common.device_record(devices))} "
          f"compile_cache {cache_dir}", flush=True)
    peaks = None
    if devices[0].platform == "tpu":
        peaks = common.peaks_for(devices[0].device_kind)
    trace_dir = str(root / ".chipbench" / "trace" / workload)
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
    run = Run(
        root=root, cell=cell, config=config, traffic=traffic,
        seed=int(seed), seconds=float(seconds),
        run_seconds=float(manifest["run_seconds"]), trace=bool(trace),
        devices=devices, spans=common.Spans(annotate=bool(trace)),
        t_start=t_start, trace_dir=trace_dir, peaks=peaks,
    )
    runner = load_from(root, "runners", config["kind"])
    run.meter = common.CompileMeter()
    run.spans.spans["setup_process_start"] = [(t_start, common.now())]
    runner.run(run)
    run.check.print()
    return run, manifest


def run_cell(root: Path, workload: str, seed: int, seconds: float,
             trace: bool, *, require_chip: bool = True,
             t_start: float | None = None) -> dict:
    """One run of one cell; returns the result object. ``require_chip``
    is False only in the benchmark's own tests, which rehearse the rest
    of a run on the CPU at tiny size; the command line cannot set it."""
    run, manifest = execute(
        root, workload, seed, seconds, trace, require_chip=require_chip,
        t_start=t_start,
    )
    device = common.device_record(run.devices)
    device["memory_peak_bytes"] = int(run.memory_peak_bytes)
    metrics: dict[str, dict] = {}
    result = {
        "correct": run.check.correct,
        "attempted": int(run.attempted),
        "failed": int(run.failed),
        "metrics": metrics,
        "device": device,
    }
    if not trace:
        for m in metrics_of(manifest, "end_to_end", workload):
            if m["name"] in run.end_to_end:
                metrics[m["name"]] = {
                    "value": float(run.end_to_end[m["name"]]),
                    "unit": m["unit"],
                }
    else:
        if run.summary is not None:
            device["busy_s"] = float(run.summary.busy_s)
            device["window_s"] = float(run.summary.window_s)
            result["breakdown"] = run.summary.breakdown()
        for m in metrics_of(manifest, "per_layer", workload):
            value = load_from(root, "metrics", m["name"]).read(run)
            if value is not None:
                metrics[m["name"]] = {
                    "value": float(value), "unit": m["unit"],
                }
        shutil.rmtree(run.trace_dir, ignore_errors=True)
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result = run_cell(
            ROOT, args.workload, args.seed, args.seconds, bool(args.trace),
        )
    except common.NoChip as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 3
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
