"""Runner of the serving cells of the dense block: the model and its
weights from the configuration's file, what a decode step reads
(chipbench/counts.py), and the plain reference with its control. The
run itself (backlog, warm phase, the window on the schedule, the
bookkeeping after each tick, the metrics and the lines a run prints,
the sample of finished streams and the checks) is
chipbench/runners/_serve_loop.py, which every serving kind shares.
"""

from __future__ import annotations

import numpy as np

from chipbench import counts, weights
from chipbench.runners import _model, _serve_loop


def _bucket(n: int, step: int = 256) -> int:
    return -(-n // step) * step


def reference_gaps(ref, params, streams, window, precision="float32"):
    """For each (prompt, served tokens): how far each served token's
    logit lies below the reference's best, and the reference's logits
    themselves row by row (for the control)."""
    import jax.numpy as jnp

    out = []
    for prompt, served in streams:
        tp, n = len(prompt), len(served)
        pad = _bucket(tp + n)
        seq = np.zeros((pad,), np.int32)
        seq[:tp] = prompt
        seq[tp:tp + n] = served
        rows = _bucket(n, 64)
        first = min(tp - 1, pad - rows)
        lg = np.asarray(ref.stream_logits(
            params, jnp.asarray(seq), first, rows, window=window,
            precision=precision,
        ))[tp - 1 - first: tp - 1 - first + n]
        out.append(lg)
    return out


def control(run, precision: str) -> dict:
    """The reference in a lower precision, put in the program's place
    without decoding: at each position of the same prompts and served
    tokens, how far the token that the lower precision puts first lies
    below the float32 reference's best."""
    params, streams, ref_logits = run.info["reference"]
    ref = _model.reference_module(run)
    low = reference_gaps(ref, params, streams,
                         run.config["sliding_window"], precision)
    worst, mean = _serve_loop.gap_numbers(
        ref_logits, [lo.argmax(axis=-1) for lo in low])
    return {"logit_gap_worst": worst, "logit_gap_mean": mean}


def run(run) -> None:
    import jax

    cfg, program = run.config, run.config["program"]
    model = _model.transformer_config(cfg)
    sz = _model.sizes(cfg)
    W = cfg["sliding_window"]
    with run.spans.span("setup_weights"):
        params = weights.make_params(
            _model.param_shapes(cfg), run.seed, d_model=sz["d_model"],
            n_layers=sz["n_layers"],
        )
        jax.block_until_ready(params)
    sched, reqs = _serve_loop.submit_backlog(run, params, model, sz["vocab"])
    served = _serve_loop.serve(run, sched, reqs,
                               kv_rows=lambda length: min(W, length))
    del sched, reqs
    run.info.update(
        weight_bytes=counts.serving_weight_bytes(**sz),
        kv_row_bytes=counts.kv_row_bytes(
            kv_heads=sz["kv_heads"], head_dim=sz["d_model"] // sz["n_heads"],
            n_layers=sz["n_layers"], quantized=bool(program["quantize_kv"]),
        ),
    )
    ref = _model.reference_module(run)
    _serve_loop.judge(
        run, params, served.streams,
        lambda streams: reference_gaps(ref, params, streams, W),
    )
