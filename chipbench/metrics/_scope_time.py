"""Device time of ONE program of a traced run by ``jax.named_scope``:
the one reducer the readers of the model step's and the trainer's own
scopes share. The program (a predicate on a module's cleaned name) and
the scope names are parameters, so the train step, the decode tick and
the prefill programs are three calls of :func:`time_by_scope`, not
three copies of it.

The scopes are the program's (docs/API.md, "Scopes in the device
trace, and the trainer's span"): the block's six functions in models/transformer.py
open ``embed``, ``attn_qkv``, ``attn_out``, ``ffn``, ``head`` and
``loss``, the update ``sgd_update``, a prefill chunk's block walk
``chunk_attn``; the older ones (``decode_attn``, ``decode_mlp``,
``kv_page_*``, ``moe_*``, ``gdn_*``, ``mla_*``, ``hc_mix``) keep their
names. The profiler stores an operation's scope path as the ``tf_op``
of its metadata (``_program_spans.op_scopes``): 'jit(step)/jvp()/ffn/
dot_general' for a forward operation of the train step,
'jit(step)/transpose(jvp())/ffn/...' for its backward; a name stack
that wraps the scope itself ('transpose(jvp(ffn))') is unwrapped to the
same two facts. A fused operation carries ONE instruction's path.

An operation's self time counts under every listed scope on its path
(``ffn`` holds the ``moe_*`` scopes nested in it), once under ``any``;
an operation outside every scope counts as ``kernel`` where its name
says what it is (the flash kernels: ``jvp*`` / ``transpose_jvp*``), as
``loop`` where its ``tf_op`` is a loop instruction's own path
('jit(serving_tick_paged)/while': no function under the loop wrote the
operation; the compiler made it for the loop's buffers, the copies and
slices it starts ahead of a step), as ``unscoped`` where it has any
other ``tf_op`` and as ``no_tf_op`` where the compiler gave it none
(its own copies). A program without the NEW scopes (a parent commit)
gives None, and so does a CPU run.
"""

from __future__ import annotations

import bisect
import re
import statistics
import types

from chipbench import trace_reduce
from chipbench.metrics import _program_spans as ps
from chipbench.metrics._util import decode_tick_module

# what this PR's program opens; a reader finds nothing to read where an
# operation of its program carries none of them
BLOCK_SCOPES = ("embed", "attn_qkv", "attn_out", "ffn", "head")
TRAIN_SCOPES = BLOCK_SCOPES + ("loss", "sgd_update")
# every scope a serving program can carry, old and new
SERVE_SCOPES = BLOCK_SCOPES + (
    "chunk_attn", "decode_attn", "decode_mlp", "kv_page_gather",
    "kv_page_scatter", "moe_route", "moe_experts", "moe_shared",
    "gdn_proj", "gdn_conv", "gdn_rule", "gdn_out", "mla_q", "mla_kv",
    "mla_attn", "mla_out", "hc_mix",
)
PREFILL_PROGRAMS = "jit_serving_prefill_chunk"
_WRAPPED = re.compile(r"^(jvp|transpose)\((.*)\)$")
OUTSIDE_LISTED = 6  # operations outside every scope the note line names
# an operation's self time counts under exactly one of these
KINDS = ("any", "kernel", "loop", "unscoped", "no_tf_op")


def path_facts(tf_op: str) -> tuple[frozenset, bool]:
    """(the path's parts with ``jvp(...)`` / ``transpose(...)`` taken
    off, whether any part was under ``transpose``: the backward pass)
    of one ``tf_op``."""
    parts, backward = set(), False
    for part in ps.scope_parts(tf_op):
        while (m := _WRAPPED.match(part)):
            backward = backward or m.group(1) == "transpose"
            part = m.group(2)
        parts.add(part)
    return frozenset(parts), backward


def names_the_loop(tf_op: str) -> bool:
    """Whether a ``tf_op`` ends at a loop instruction ('.../while:'):
    the path of the ``while`` itself, which the compiler gives the
    operations it makes for the loop's buffers."""
    return ps.scope_parts(tf_op)[-1] == "while"


def reduce_scopes(raw: dict, tf_ops: dict, is_program, window, scopes,
                  need, is_kernel=None) -> dict | None:
    """The arithmetic, on ``trace_reduce.load_xplane``'s tuples and
    ``_program_spans.op_scopes``'s map. Seconds, a mean over the chips:
    ``whole`` (the program's operations inside ``window``), ``runs``
    (its executions), ``scope`` and ``backward`` ({name: seconds}; the
    second is the part of the first under ``transpose``), ``any``,
    ``kernel``, ``loop``, ``unscoped``, ``no_tf_op`` and ``outside``
    ({operation: seconds} of the last three). None where no operation
    of the program carries a scope of ``need``."""
    w0, w1 = window
    facts = {k: path_facts(v) + (names_the_loop(v),)
             for k, v in tf_ops.items()}
    scope = {s: 0.0 for s in scopes}
    backward = {s: 0.0 for s in scopes}
    flat = dict.fromkeys(("whole",) + KINDS, 0.0)
    outside: dict[str, float] = {}
    n_runs, seen_needed = 0, False
    chips = raw["device"]
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
            flat["whole"] += self_ns
            parts, is_backward, at_loop = facts.get(
                (runs[i][2], name), (None, False, False))
            under = [sc for sc in scopes if sc in parts] if parts else []
            for sc in under:
                scope[sc] += self_ns
                if is_backward:
                    backward[sc] += self_ns
            if under:
                flat["any"] += self_ns
                seen_needed = seen_needed or any(sc in need for sc in under)
                continue
            op = trace_reduce.clean_name(name)
            if is_kernel is not None and is_kernel(op):
                flat["kernel"] += self_ns
                continue
            kind = ("no_tf_op" if parts is None
                    else "loop" if at_loop else "unscoped")
            flat[kind] += self_ns
            outside[op] = outside.get(op, 0.0) + self_ns
    if flat["whole"] <= 0 or not seen_needed:
        return None
    k = 1e-9 / max(1, len(chips))
    out = {key: v * k for key, v in flat.items()}
    out["scope"] = {s: v * k for s, v in scope.items()}
    out["backward"] = {s: v * k for s, v in backward.items()}
    out["outside"] = {op: v * k for op, v in outside.items()}
    out["runs"] = n_runs // max(1, len(chips))
    return out


def _trace(run):
    """The traced window, the file's tuples and its ``tf_op`` map, read
    once a run for every program's reduction."""
    if "scope_time_trace" not in run.info:
        path = trace_reduce.find_xplane(run.trace_dir)
        window, _ = ps._host(run)
        run.info["scope_time_trace"] = (
            window, trace_reduce.load_xplane(path), ps.op_scopes(path))
    return run.info["scope_time_trace"]


def time_by_scope(run, label: str, is_program, scopes, need,
                  is_kernel=None) -> dict | None:
    """:func:`reduce_scopes` over this run's trace for the program(s)
    ``is_program`` accepts, once a run and ``label`` (then found in
    ``run.info``); prints ``note <label>_time_by_scope_ms``. None
    without a device trace."""
    if run.summary is None:
        return None
    key = f"scope_time_{label}"
    if key in run.info:
        return run.info[key]
    window, raw, tf_ops = _trace(run)
    out = None
    if window is not None:
        out = reduce_scopes(raw, tf_ops, is_program, window, scopes, need,
                            is_kernel)
    run.info[key] = out
    if out is not None:
        print(note_line(label, out), flush=True)
    return out


def note_line(label: str, t: dict) -> str:
    """One line a program: the whole, the five kinds of time outside
    and inside scopes, every scope that has time as forward+backward,
    and the largest operations outside every scope by name."""
    ms = lambda x: f"{1e3 * x:.3f}"
    by_scope = " ".join(
        f"{s}={ms(v - t['backward'][s])}+{ms(t['backward'][s])}"
        for s, v in t["scope"].items() if v > 0)
    top = sorted(t["outside"].items(), key=lambda kv: -kv[1])
    rest = sum(v for _, v in top[OUTSIDE_LISTED:])
    listed = " ".join(f"{op}={ms(v)}" for op, v in top[:OUTSIDE_LISTED])
    return (
        f"note {label}_time_by_scope_ms whole={ms(t['whole'])} "
        f"runs={t['runs']} under_any_scope={ms(t['any'])} "
        f"kernels_by_name={ms(t['kernel'])} "
        f"loop_instruction={ms(t['loop'])} "
        f"tf_op_but_no_scope={ms(t['unscoped'])} "
        f"no_tf_op={ms(t['no_tf_op'])} forward+backward: {by_scope} "
        f"outside_every_scope: {listed} others={ms(rest)}")


# -- the three programs the readers ask for ---------------------------------


def train_step_time(run) -> dict | None:
    """``jit_step``'s time by scope; the flash kernels, which no scope
    wraps (a scope around the call could rename them), are told by
    their names as ``flash_share_pct`` tells them."""
    from chipbench.metrics.flash_share_pct import is_flash

    return time_by_scope(
        run, "train_step", lambda name: name.startswith("jit_step"),
        TRAIN_SCOPES, TRAIN_SCOPES,
        lambda op: is_flash(types.SimpleNamespace(name=op)))


def tick_time(run) -> dict | None:
    """The decode tick program's time by scope, old scopes and new."""
    if run.summary is None:
        return None
    tick = decode_tick_module(run.summary)
    if tick is None:
        return None
    return time_by_scope(run, "tick", lambda name: name == tick,
                         SERVE_SCOPES, BLOCK_SCOPES)


def prefill_time(run) -> dict | None:
    """The same over every program whose name starts
    ``jit_serving_prefill_chunk`` (the lone chunk's and the grouped)."""
    return time_by_scope(
        run, "prefill", lambda name: name.startswith(PREFILL_PROGRAMS),
        SERVE_SCOPES, BLOCK_SCOPES)


def pct(part: float, t: dict | None) -> float | None:
    return None if t is None else 100.0 * part / t["whole"]


def program_steps(run, is_program) -> float:
    """Executions of a program inside the traced window, the two at its
    edges in part: its device time over its median execution."""
    runs = [d for name, rs in run.summary.modules.items()
            if is_program(name) for _, _, d in rs]
    return sum(runs) / statistics.median(runs) if runs else 0.0
