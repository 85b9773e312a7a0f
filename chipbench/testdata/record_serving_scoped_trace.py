"""Records chipbench/testdata/small_serving_scoped_tpu.xplane.pb.gz on a
chip (run once, from the repository's root, when the trace format or
the serving programs' scopes have to be refreshed; copy the ``.gz`` it
leaves in the output directory over the one in testdata):

    python3 chipbench/testdata/record_serving_scoped_trace.py chiprun_out/small_serving_scoped

The scheduler, the requests and the loop are
chipbench/testdata/record_serving_trace.py's (a tiny paged
``ServingScheduler``, six requests over four slots, the gather route),
recorded from a program that opens the block's own scopes (``embed``,
``attn_qkv``, ``attn_out``, ``ffn``, ``head``, ``chunk_attn``) beside
the older ones; ``small_serving_tpu.xplane.pb.gz`` stays as it was
recorded, from a program without them. What the readers must find in
the new file is in tests/chipbench/test_recorded_scoped_traces.py; the
numbers this script prints are where that test's were taken from.
"""

from __future__ import annotations

import shutil
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import record_serving_trace as base  # noqa: E402
import record_train_scoped_trace as packing  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from chipbench import trace_reduce  # noqa: E402
from chipbench.metrics import _scope_time  # noqa: E402
from chipbench.run import load_from  # noqa: E402

CONFIG = {"vocab_size": 512, "hidden_size": 256}
N_INNER = 4
NAME = "small_serving_scoped_tpu.xplane.pb"
READERS = ("tick_scoped_pct", "head_share_pct", "head_hbm_pct",
           "prefill_scoped_pct", "chunk_attn_share_pct",
           "tick_gather_share_pct")


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
        vocab=CONFIG["vocab_size"], d_model=CONFIG["hidden_size"],
        n_heads=4, n_kv_heads=2, n_layers=1, d_ff=512, attn_window=256,
        dtype=jnp.bfloat16,
    )
    params = jax.device_put(init_params(cfg, seed=3))
    sched = ServingScheduler(
        params, cfg, slots=base.SLOTS, n_inner=N_INNER, quantize_kv=True,
        page_tokens=64, prompt_chunk=64, max_prompt=256,
    )
    assert not sched.use_kernel
    base.serve(sched, cfg.vocab, annotate=False)  # compiles every program
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    shutil.rmtree(out_dir, ignore_errors=True)
    jax.profiler.start_trace(out_dir, profiler_options=opts)
    with jax.profiler.TraceAnnotation(trace_reduce.WINDOW_SPAN):
        ticks = base.serve(sched, cfg.vocab, annotate=True)
    jax.profiler.stop_trace()
    dst = packing.pack(out_dir, NAME)
    run = packing.reader_run(out_dir, dst, config=CONFIG)
    run.info.update(slots=base.SLOTS, n_inner=N_INNER)
    print("ticks", ticks, "window_s", run.summary.window_s,
          "busy_s", run.summary.busy_s)
    print("modules", {k: (len(v), sum(d for _, _, d in v))
                      for k, v in run.summary.modules.items()})
    for label, t in (("tick", _scope_time.tick_time(run)),
                     ("prefill", _scope_time.prefill_time(run))):
        print(label, {k: v for k, v in t.items() if k != "outside"})
        print(label, "outside", t["outside"])
    for name in READERS:
        print(name, load_from(base.ROOT, "metrics", name).read(run))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
