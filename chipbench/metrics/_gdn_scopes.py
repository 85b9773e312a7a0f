"""What the readers of the gated delta-rule layers' metrics share: the
device time of a serving program (the decode tick, or the prefill
chunk) by ``gdn_*`` scope.

The scopes are ``jax.named_scope`` names in models/transformer.py
(``gdn_half``): ``gdn_proj`` (the q/k/v/z and b/a projections),
``gdn_conv`` (the depthwise causal conv, its silu, the rows kept for
the next call), ``gdn_rule`` (the l2 norms, the gates, the recurrence
itself: one step a token in the tick, the chunked form in a prefill
chunk; reads and writes the state ``S``) and ``gdn_out`` (the gated
norm over a value head and the out-projection). A program without them
(a parent commit, or a model without such a layer) gives None
everywhere here.

The reduction itself is not the delta rule's: ``time_by_scope`` and
``reduce_scopes`` take the scopes to sum under as a parameter, and
``_mla_scopes.py`` calls them with the latent layers' names.
"""

from __future__ import annotations

import bisect
import re

from chipbench import trace_reduce
from chipbench.metrics import _program_spans as ps
from chipbench.metrics._util import decode_tick_module

GDN_SCOPES = ("gdn_proj", "gdn_conv", "gdn_rule", "gdn_out")
# The compiler keeps the state in the chip's fast memory where it fits
# and moves it to and from HBM with asynchronous copies of its own
# (``copy-start`` / ``copy-done``), which are not under ``gdn_rule``.
# ``moves`` is the self time of every such copy in the program that no
# ``gdn_*`` scope already counts, whatever it moves: a share that puts
# the state's bytes over ``gdn_rule`` alone leaves that time out and
# read 125% (my chip run, PR 32).
MOVE_OPS = ("copy-start", "copy-done")
CHUNK_PROGRAM = "jit_serving_prefill_chunk"
CACHE_KEY = "gdn_scopes"


def time_by_scope(run, program: str, scopes=GDN_SCOPES,
                  cache_key: str = CACHE_KEY, label: str = "gdn"):
    """{'whole': s, 'runs': n, 'moves': s, 'gdn_proj': s, ...} for
    ``program`` ("tick", or "chunk": every prefill chunk program, the
    lone chunk's and the grouped one): self time of that program's
    operations inside the traced window, in all, under each of
    ``scopes`` and in the compiler's own asynchronous copies
    (``MOVE_OPS`` outside every one of them), with the number of its
    executions. None without a device trace or where no operation of
    the program carries such a scope. Read once a run and
    ``cache_key``; prints ``note <program>_time_by_<label>_scope_ms``."""
    if run.summary is None:
        return None
    key = f"{cache_key}_{program}"
    if key in run.info:
        return run.info[key]
    out = None
    if program == "tick":
        tick = decode_tick_module(run.summary)
        is_program = lambda name: name == tick
    else:
        tick = CHUNK_PROGRAM
        is_program = lambda name: name.startswith(CHUNK_PROGRAM)
    window, _ = ps._host(run)
    if tick is not None and window is not None:
        out = reduce_scopes(trace_reduce.find_xplane(run.trace_dir),
                            is_program, window, scopes)
    run.info[key] = out
    if out is not None:
        listed = ("whole", "moves") if label == "gdn" else ("whole",)
        print(f"note {program}_time_by_{label}_scope_ms " + " ".join(
            f"{k}={1e3 * out[k]:.3f}" for k in listed + tuple(scopes)
        ) + f" runs={out['runs']}", flush=True)
    return out


def reduce_scopes(path: str, is_program, window,
                  scopes=GDN_SCOPES) -> dict | None:
    """Over the chips of the trace at ``path``: the operations that run
    inside an execution of a program whose cleaned name
    ``is_program`` accepts, their self time summed in all, under each
    of ``scopes`` and as ``moves``, a mean over the chips."""
    w0, w1 = window
    parts_of = {k: frozenset(ps.scope_parts(v))
                for k, v in ps.op_scopes(path).items()}
    total = {"whole": 0.0, "moves": 0.0, **{s: 0.0 for s in scopes}}
    n_runs = 0
    chips = trace_reduce.load_xplane(path)["device"]
    for chip in chips.values():
        runs = sorted(
            (s, s + d, int(m.group(1)))
            for n, s, d in chip["modules"]
            if is_program(trace_reduce.clean_module(n))
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
            under = [scope for scope in scopes if scope in parts]
            for scope in under:
                total[scope] += self_ns
            if not under and trace_reduce.clean_name(name) in MOVE_OPS:
                total["moves"] += self_ns
    if total["whole"] <= 0 or not any(total[s] > 0 for s in scopes):
        return None
    n = max(1, len(chips))
    out = {k: v * 1e-9 / n for k, v in total.items()}
    out["runs"] = n_runs // n
    return out
