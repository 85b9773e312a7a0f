"""What the readers of the expert layers' metrics share: the device time
of the tick program by ``moe_*`` scope, and the program's own counters
on ``serving.tick`` and ``serving.harvest``.

The scopes are ``jax.named_scope`` names in models/moe.py
(``moe_route``: scores, top-k, the sort by expert; ``moe_experts``: the
gather of rows by expert, the three grouped products and the weighted
way back; ``moe_shared``: the shared expert). The counters are
arguments the scheduler puts on its spans: ``experts_hit`` on
``serving.harvest`` and ``pages_<kind>`` on ``serving.tick``. A program
without them (a parent commit) gives None everywhere here.
"""

from __future__ import annotations

import bisect
import re
import statistics

from chipbench import trace_reduce
from chipbench.metrics import _program_spans as ps
from chipbench.metrics._util import decode_tick_module

MOE_SCOPES = ("moe_route", "moe_experts", "moe_shared")
HARVEST = "serving.harvest"
CACHE_KEY = "moe_scopes"


def tick_time_by_scope(run) -> dict | None:
    """{'whole': s, 'runs': n, 'moe_route': s, ...}: self time of the
    tick program's operations inside the traced window, in all and
    under each ``moe_*`` scope, with the number of its executions. None
    without a device trace or where no operation carries such a
    scope."""
    if run.summary is None:
        return None
    if CACHE_KEY in run.info:
        return run.info[CACHE_KEY]
    out = None
    tick = decode_tick_module(run.summary)
    window, _ = ps._host(run)
    if tick is not None and window is not None:
        out = _reduce(trace_reduce.find_xplane(run.trace_dir), tick, window)
    run.info[CACHE_KEY] = out
    if out is not None:
        print("note tick_time_by_moe_scope_ms " + " ".join(
            f"{k}={1e3 * out[k]:.3f}" for k in ("whole",) + MOE_SCOPES
        ) + f" runs={out['runs']}", flush=True)
    return out


def _reduce(path: str, tick: str, window) -> dict | None:
    w0, w1 = window
    parts_of = {k: frozenset(ps.scope_parts(v))
                for k, v in ps.op_scopes(path).items()}
    total = {"whole": 0.0, **{s: 0.0 for s in MOE_SCOPES}}
    n_runs = 0
    chips = trace_reduce.load_xplane(path)["device"]
    for chip in chips.values():
        runs = sorted(
            (s, s + d, int(m.group(1)))
            for n, s, d in chip["modules"]
            if trace_reduce.clean_module(n) == tick
            and (m := re.search(r"\((\d+)\)$", n))
        )
        starts = [r[0] for r in runs]
        n_runs += sum(1 for s, e, _ in runs if e > w0 and s < w1)
        evs = [(n, float(s), float(d)) for n, s, d in chip["ops"]
               if s + d > w0 and s < w1]
        for (name, s, _), self_ns in zip(evs, trace_reduce._self_times(evs)):
            i = bisect.bisect_right(starts, s) - 1
            if i < 0 or s >= runs[i][1]:
                continue
            total["whole"] += self_ns
            parts = parts_of.get((runs[i][2], name), frozenset())
            for scope in MOE_SCOPES:
                if scope in parts:
                    total[scope] += self_ns
    if total["whole"] <= 0 or not any(total[s] > 0 for s in MOE_SCOPES):
        return None
    n = max(1, len(chips))
    out = {k: v * 1e-9 / n for k, v in total.items()}
    out["runs"] = n_runs // n
    return out


def mean_span_argument(run, span: str, key: str) -> float | None:
    """Mean of one argument over the window's spans of one name."""
    spans = ps.load(run)
    if spans is None:
        return None
    values = [float(s.args[key]) for s in spans.named(span) if key in s.args]
    return statistics.fmean(values) if values else None


def mean_experts_hit(run) -> float | None:
    """The scheduler's own count, tick by tick: per expert layer and
    step, the experts that got at least one token."""
    return mean_span_argument(run, HARVEST, "experts_hit")
