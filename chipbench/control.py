"""Readings that the limits of ``correct`` are set from (not part of a
benchmark run; see PERF.md section 2 and tests/chipbench).

    python3 chipbench/control.py --workload <cell> --seeds 1,2,3 \\
        --seconds 4 --precisions fp8,int8

For each seed, in one process: the cell's program is run with a short
window and the numbers it is judged on are read (the sound readings);
then the configuration's control, the reference or the program's own
path in a lower precision, is put in its place and the same numbers are
read again. One JSON line per seed; the last line gathers the largest
sound and the smallest control reading of each number.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from chipbench import common  # noqa: E402
from chipbench.run import execute, load_from  # noqa: E402


def readings(root, workload, seed, seconds, precisions, require_chip=True):
    run, _ = execute(root, workload, seed, seconds, False,
                     require_chip=require_chip,
                     t_start=time.perf_counter())
    sound = {name: value for name, value, _, _ in run.check.rows}
    runner = load_from(Path(root), "runners", run.config["kind"])
    controls = {p: runner.control(run, p) for p in precisions}
    return {"seed": seed, "correct": run.check.correct, "sound": sound,
            "control": controls}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=4.0)
    ap.add_argument("--precisions", default="")
    args = ap.parse_args(argv)
    precisions = [p for p in args.precisions.split(",") if p]
    rows = []
    try:
        for seed in (int(s) for s in args.seeds.split(",")):
            row = readings(ROOT, args.workload, seed, args.seconds,
                           precisions)
            rows.append(row)
            print("control_reading " + json.dumps(row), flush=True)
    except common.NoChip as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 3
    sound_max: dict[str, float] = {}
    for r in rows:
        for k, v in r["sound"].items():
            sound_max[k] = max(sound_max.get(k, 0.0), v)
    print("control_summary " + json.dumps({
        "workload": args.workload, "seeds": len(rows),
        "all_correct": all(r["correct"] for r in rows),
        "sound_largest": sound_max,
        "control": [r["control"] for r in rows],
    }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
