"""Small helpers the readers share."""

from __future__ import annotations


def idle_pct(run):
    if run.summary is None:
        return None
    return 100.0 * run.summary.idle_share


def peak(run, key: str):
    """A published peak of the device the run is on; None on a CPU."""
    return None if run.peaks is None else run.peaks[key]


def decode_tick_module(summary):
    """The program of the serving tick: of the programs that hold a
    ``while`` (the scan over the tick's decode steps), the one with the
    most device time."""
    by_module: dict[str, float] = {}
    for op in summary.ops:
        if op.name.startswith("while"):
            by_module[op.module] = by_module.get(op.module, 0.0) + op.dur
    if not by_module:
        return None
    return max(by_module, key=by_module.get)
