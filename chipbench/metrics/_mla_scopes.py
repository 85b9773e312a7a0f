"""What the readers of the latent-attention layers' and of the residual
mixing's metrics share: the device time of a serving program (the
decode tick, or the prefill programs) by ``mla_*`` / ``hc_mix`` scope.

The scopes are ``jax.named_scope`` names in models/transformer.py and
models/decode.py: ``mla_q`` (the query's down- and up-projection, its
norm, rotary, the absorption of the key's up-projection), ``mla_kv``
(the latent's down-projection, its norm, the shared key's rotary),
``mla_attn`` (the row's write into the cache, scores, softmax, the
probabilities' product with the rows), ``mla_out`` (the value's
up-projection of the result and the out-projection) and ``hc_mix``
(the streams' norm, the product with ``phi``, the Sinkhorn rounds, the
mix a half reads and the way its result goes back). A program without
them (a parent commit, or a model without such a layer) gives None
everywhere here.
"""

from __future__ import annotations

import bisect
import re

from chipbench import trace_reduce
from chipbench.metrics import _program_spans as ps
from chipbench.metrics._util import decode_tick_module

SCOPES = ("mla_q", "mla_kv", "mla_attn", "mla_out", "hc_mix")
CHUNK_PROGRAM = "jit_serving_prefill_chunk"
CACHE_KEY = "mla_scopes"


def time_by_scope(run, program: str) -> dict | None:
    """{'whole': s, 'runs': n, 'mla_q': s, ...} for ``program`` ("tick",
    or "chunk": every prefill chunk program, the lone chunk's and the
    grouped one): self time of that program's operations inside the
    traced window, in all and under each scope, with the number of its
    executions. None without a device trace or where no operation of
    the program carries such a scope."""
    if run.summary is None:
        return None
    key = f"{CACHE_KEY}_{program}"
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
                            is_program, window)
    run.info[key] = out
    if out is not None:
        print(f"note {program}_time_by_mla_scope_ms " + " ".join(
            f"{k}={1e3 * out[k]:.3f}" for k in ("whole",) + SCOPES
        ) + f" runs={out['runs']}", flush=True)
    return out


def reduce_scopes(path: str, is_program, window) -> dict | None:
    """Over the chips of the trace at ``path``: the operations that run
    inside an execution of a program whose cleaned name ``is_program``
    accepts, their self time summed in all and by scope, a mean over
    the chips."""
    w0, w1 = window
    parts_of = {k: frozenset(ps.scope_parts(v))
                for k, v in ps.op_scopes(path).items()}
    total = {"whole": 0.0, **{s: 0.0 for s in SCOPES}}
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
            for scope in SCOPES:
                if scope in parts:
                    total[scope] += self_ns
    if total["whole"] <= 0 or not any(total[s] > 0 for s in SCOPES):
        return None
    n = max(1, len(chips))
    out = {k: v * 1e-9 / n for k, v in total.items()}
    out["runs"] = n_runs // n
    return out
