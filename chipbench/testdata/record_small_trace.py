"""Records chipbench/testdata/small_tpu.xplane.pb on a chip (run once,
from the repository's root, when the trace format has to be refreshed):

    python3 chipbench/testdata/record_small_trace.py chiprun_out/small_tpu

Six executions of one small program ``small_step`` (a matrix product,
then a loop of four more), each inside a host span ``chipbench:step``,
with a 20 ms ``chipbench:sleep`` after every one. What the reduction
must find in it is in tests/chipbench/test_recorded_trace.py.
"""

from __future__ import annotations

import shutil
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from chipbench import trace_reduce  # noqa: E402


def main(out_dir: str) -> int:
    if jax.devices()[0].platform != "tpu":
        print("needs a TPU", file=sys.stderr)
        return 3

    @jax.jit
    def small_step(x):
        y = x @ x
        return jax.lax.fori_loop(0, 4, lambda i, a: jnp.tanh(a @ x), y)

    x = jnp.full((1024, 1024), 1e-3, jnp.bfloat16)
    small_step(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    shutil.rmtree(out_dir, ignore_errors=True)
    jax.profiler.start_trace(out_dir, profiler_options=opts)
    with jax.profiler.TraceAnnotation(trace_reduce.WINDOW_SPAN):
        for _ in range(6):
            with jax.profiler.TraceAnnotation("chipbench:step"):
                small_step(x).block_until_ready()
            with jax.profiler.TraceAnnotation("chipbench:sleep"):
                time.sleep(0.02)
    jax.profiler.stop_trace()
    src = trace_reduce.find_xplane(out_dir)
    dst = Path(out_dir) / "small_tpu.xplane.pb"
    shutil.copy(src, dst)
    s = trace_reduce.reduce_events(trace_reduce.load_xplane(str(dst)))
    print(dst, dst.stat().st_size, "bytes")
    print("window_s", s.window_s, "busy_s", s.busy_s)
    print("ops", s.op_seconds())
    print("modules", {k: (len(v), sum(d for _, _, d in v))
                      for k, v in s.modules.items()})
    print("gaps", s.gaps)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
