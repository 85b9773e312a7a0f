"""Records chipbench/testdata/small_train_scoped_tpu.xplane.pb.gz on a
chip (run once, from the repository's root, when the trace format, the
trainer's scopes or its ``train.step`` span have to be refreshed; copy
the ``.gz`` it leaves in the output directory over the one in
testdata):

    python3 chipbench/testdata/record_train_scoped_trace.py chiprun_out/small_train

A tiny ``make_train_step`` (2 layers of width 256, two heads of 128
over one K/V head, batch 1 x 2048 tokens under a window of 1024, so the
flash kernels' grid is 2 x 2 blocks of 1024 of which 3 run; bfloat16,
mesh (1, 1, 1)) runs five steps inside the window's mark, each inside
a host span ``chipbench:step``, after two outside the profiler that
compile it. What the readers must find in it is in
tests/chipbench/test_recorded_scoped_traces.py; the numbers this script
prints are where that test's were taken from.
"""

from __future__ import annotations

import gzip
import shutil
import sys
import types
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from chipbench import common, trace_reduce  # noqa: E402
from chipbench.metrics import _scope_time  # noqa: E402
from chipbench.run import load_from  # noqa: E402

BATCH, SEQ, STEPS = 1, 2048, 5
CONFIG = {  # the published key names, as chipbench/runners/_model.py reads
    "hidden_size": 256, "num_attention_heads": 2, "num_key_value_heads": 1,
    "intermediate_size": 512, "num_hidden_layers": 2, "vocab_size": 1024,
    "sliding_window": 1024,
}
NAME = "small_train_scoped_tpu.xplane.pb"
READERS = ("train_scoped_pct", "train_ffn_share_pct", "train_proj_share_pct",
           "train_head_loss_share_pct", "train_matmul_mxu_pct",
           "flash_pairs_useful_pct", "flash_share_pct")


def pack(out_dir: str, name: str) -> Path:
    """The newest trace under ``out_dir`` as ``<out_dir>/<name>`` and
    its gzip beside it (mtime 0: the same bytes give the same file)."""
    dst = Path(out_dir) / name
    shutil.copy(trace_reduce.find_xplane(out_dir), dst)
    shutil.rmtree(Path(out_dir) / "plugins", ignore_errors=True)
    packed = dst.with_name(dst.name + ".gz")
    with open(dst, "rb") as f, gzip.GzipFile(packed, "wb", 9, mtime=0) as g:
        shutil.copyfileobj(f, g)
    print(packed, packed.stat().st_size, "bytes of", dst.stat().st_size)
    return dst


def reader_run(out_dir: str, dst: Path, **more):
    """What the harness hands a reader, from a recorded file."""
    summary = trace_reduce.reduce_events(trace_reduce.load_xplane(str(dst)))
    return types.SimpleNamespace(
        summary=summary, trace_dir=out_dir, info={},
        peaks=common.peaks_for("TPU v5 lite"), **more)


def main(out_dir: str) -> int:
    if jax.devices()[0].platform != "tpu":
        print("needs a TPU", file=sys.stderr)
        return 3
    from jax.sharding import Mesh

    from mpistragglers_jl_tpu.models.transformer import (
        TransformerConfig,
        init_params,
        make_train_step,
    )

    cfg = TransformerConfig(
        vocab=CONFIG["vocab_size"], d_model=CONFIG["hidden_size"],
        n_heads=CONFIG["num_attention_heads"],
        n_kv_heads=CONFIG["num_key_value_heads"],
        n_layers=CONFIG["num_hidden_layers"],
        d_ff=CONFIG["intermediate_size"], attn="ulysses",
        attn_impl="flash", attn_window=CONFIG["sliding_window"],
        dtype=jnp.bfloat16,
    )
    mesh = Mesh(np.asarray(jax.devices()[:1]).reshape(1, 1, 1),
                ("dp", "sp", "tp"))
    params = jax.device_put(init_params(cfg, seed=3))
    tokens = jnp.asarray(np.random.default_rng(7).integers(
        0, cfg.vocab, (BATCH, SEQ + 1)), jnp.int32)
    inp, tgt = tokens[:, :-1], tokens[:, 1:]
    step = make_train_step(cfg, mesh, lr=0.01)
    for _ in range(2):  # compiles outside the profiler
        params, loss = step(params, inp, tgt)
    loss.block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    shutil.rmtree(out_dir, ignore_errors=True)
    jax.profiler.start_trace(out_dir, profiler_options=opts)
    with jax.profiler.TraceAnnotation(trace_reduce.WINDOW_SPAN):
        for _ in range(STEPS):
            with jax.profiler.TraceAnnotation("chipbench:step"):
                params, loss = step(params, inp, tgt)
                loss.block_until_ready()
    jax.profiler.stop_trace()
    dst = pack(out_dir, NAME)
    run = reader_run(out_dir, dst, config=CONFIG,
                     traffic={"batch": BATCH, "seq": SEQ})
    print("window_s", run.summary.window_s, "busy_s", run.summary.busy_s)
    print("modules", {k: (len(v), sum(d for _, _, d in v))
                      for k, v in run.summary.modules.items()})
    t = _scope_time.train_step_time(run)
    print("train_step_time", {k: v for k, v in t.items() if k != "outside"})
    print("outside", t["outside"])
    for name in READERS:
        print(name, load_from(ROOT, "metrics", name).read(run))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
