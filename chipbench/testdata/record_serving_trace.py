"""Records chipbench/testdata/small_serving_tpu.xplane.pb.gz on a chip
(run once, from the repository's root, when the trace format or the
scheduler's spans have to be refreshed; copy the ``.gz`` it leaves in
the output directory over the one in testdata):

    python3 chipbench/testdata/record_serving_trace.py chiprun_out/small_serving

A tiny paged ``ServingScheduler`` (1 layer of width 256, 4 slots, int8
pages of 64 tokens, ticks of 4 steps; a head size of 64 keeps the int8
kernel out, so the tick gathers and scatters its pages as the serving
cells' does) serves six requests from a backlog, prompts of one to
three chunks. The same requests run once before the profiler starts, so
that nothing compiles under it. Each tick is inside a host span
``chipbench:tick`` as in the serve runner, and the whole run inside the
window's mark. What the readers must find in it is in
tests/chipbench/test_recorded_serving_trace.py; the numbers this script
prints are where that test's were taken from. The file is kept
compressed (1.3 MB of operation texts, 0.3 MB as gzip); the test unpacks
it into a temporary directory.
"""

from __future__ import annotations

import gzip
import json
import shutil
import sys
import types
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from chipbench import trace_reduce  # noqa: E402
from chipbench.metrics import _program_spans  # noqa: E402

SLOTS = 4
REQUESTS = [(40, 12), (150, 8), (20, 16), (90, 12), (64, 8),
            (130, 12)]  # (prompt tokens, answer tokens)


def serve(sched, vocab: int, annotate: bool) -> int:
    rng = np.random.default_rng(7)
    for n_prompt, n_new in REQUESTS:
        sched.submit(rng.integers(1, vocab, size=n_prompt), max_new=n_new)
    ticks = 0
    while sched.pending or sched.active:
        if annotate:
            with jax.profiler.TraceAnnotation("chipbench:tick"):
                sched.step()
        else:
            sched.step()
        ticks += 1
    return ticks


def main(out_dir: str) -> int:
    if jax.devices()[0].platform != "tpu":
        print("needs a TPU", file=sys.stderr)
        return 3
    from mpistragglers_jl_tpu.models.serving import ServingScheduler
    from mpistragglers_jl_tpu.models.transformer import (
        TransformerConfig,
        init_params,
    )

    cfg = TransformerConfig(
        vocab=512, d_model=256, n_heads=4, n_kv_heads=2, n_layers=1,
        d_ff=512, attn_window=256, dtype=jnp.bfloat16,
    )
    params = jax.device_put(init_params(cfg, seed=3))
    sched = ServingScheduler(
        params, cfg, slots=SLOTS, n_inner=4, quantize_kv=True,
        page_tokens=64, prompt_chunk=64, max_prompt=256,
    )
    assert not sched.use_kernel
    serve(sched, cfg.vocab, annotate=False)  # compiles every program
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    shutil.rmtree(out_dir, ignore_errors=True)
    jax.profiler.start_trace(out_dir, profiler_options=opts)
    with jax.profiler.TraceAnnotation(trace_reduce.WINDOW_SPAN):
        ticks = serve(sched, cfg.vocab, annotate=True)
    jax.profiler.stop_trace()
    src = trace_reduce.find_xplane(out_dir)
    dst = Path(out_dir) / "small_serving_tpu.xplane.pb"
    shutil.copy(src, dst)
    shutil.rmtree(Path(out_dir) / "plugins", ignore_errors=True)

    summary = trace_reduce.reduce_events(trace_reduce.load_xplane(str(dst)))
    run = types.SimpleNamespace(summary=summary, trace_dir=out_dir,
                                info={"slots": SLOTS})
    ps = _program_spans.load(run)
    packed = dst.with_name(dst.name + ".gz")
    with open(dst, "rb") as f, gzip.GzipFile(packed, "wb", 9, mtime=0) as g:
        shutil.copyfileobj(f, g)
    print(packed, packed.stat().st_size, "bytes of", dst.stat().st_size,
          "; ticks", ticks)
    print("window_s", summary.window_s, "busy_s", summary.busy_s,
          "idle_pct", 100 * summary.idle_share)
    print("modules", {k: (len(v), sum(d for _, _, d in v))
                      for k, v in summary.modules.items()})
    print("gaps", json.dumps(summary.gaps))
    print("spans", {n: len(ps.named(n)) for n in
                    sorted({s.name for s in ps.spans})})
    print("idle_pct_by_key", {
        k: _program_spans.idle_pct(run, k)
        for k in _program_spans.PHASES + ("outside", "tick_self")
    })
    print("first_token_wait_ms", _program_spans.median_span_ms(
        run, _program_spans.FIRST_TOKEN_WAIT))
    print("admitting_mean", _program_spans.mean_tick_argument(
        run, "admitting"))
    print("tick_gather_share_pct",
          _program_spans.gather_share_of_tick(run))
    scopes = _program_spans.op_scopes(str(dst))
    print("scopes", len(scopes), sorted({
        p for v in scopes.values() for p in _program_spans.scope_parts(v)
        if p in _program_spans.TICK_SCOPES
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
