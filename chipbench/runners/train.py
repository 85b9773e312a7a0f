"""Runner of the training cells: the program's ``make_train_step`` on
seeded weights and tokens, one step kept in flight.

Set-up builds one object, the compiled step with its parameters, drives
it from the seed through its first steps (whose losses, first update
and change of parameters are what ``correct`` is decided on) and hands
that same object to the window. The window dispatches step i+1 and then
waits for step i's loss, so the device is never drained to take a
reading; every completion is stamped. After the window the program's
state is freed and the plain reference follows the first steps.
"""

from __future__ import annotations

import gc
import statistics

import numpy as np

from chipbench import common, counts, weights
from chipbench.runners import _model


def _worst_gap(prog: dict, ref: dict, names=None) -> tuple[float, str]:
    """Worst leaf of |norm_prog - norm_ref| over the larger of that
    leaf's reference norm and the median reference norm."""
    keys = [k for k in ref if names is None or k.split("/")[-1] in names]
    med = statistics.median(ref[k] for k in keys)
    worst, where = 0.0, ""
    for k in keys:
        gap = abs(prog[k] - ref[k]) / max(ref[k], med, 1e-30)
        if gap >= worst:
            worst, where = gap, k
    return worst, where


def _flat(tree) -> dict:
    import jax

    leaves, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {
        "/".join(weights.leaf_name((p,)) for p in path): float(v)
        for path, v in leaves
    }


def gaps(got, ref) -> dict:
    """The numbers compared: each step's loss (relative), the norm of
    the first gradient over the leaves whose update a bfloat16 store
    keeps whole (those that start at zero), and the norm of every
    leaf's change over the steps; the latter two by the worst leaf."""
    (losses, grad, change), (r_losses, r_grad, r_change) = got, ref
    out = {"loss": [abs(a - b) / abs(b) for a, b in zip(losses, r_losses)]}
    out["grad"], where = _worst_gap(grad, r_grad, weights.ZERO_LEAVES)
    print(f"note first_gradient worst leaf {where}", flush=True)
    out["change"], where = _worst_gap(change, r_change)
    print(f"note parameter_change worst leaf {where}", flush=True)
    return out


def control(run, precision: str) -> dict:
    """The reference put in the program's place, computed in a lower
    precision: the same numbers, against the float32 reference."""
    make, batches, lr = run.info["reference_args"]
    ref = _model.reference_module(run)
    losses, grad, change = ref.train_steps(
        make(), batches, lr=lr, window=run.config["sliding_window"],
        precision=precision,
    )
    return gaps((losses, _flat(grad), _flat(change)), run.info["reference"])


def run(run) -> None:
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding

    from mpistragglers_jl_tpu.models.transformer import (
        data_spec,
        make_train_step,
        param_specs,
    )

    cfg, traffic, program = run.config, run.traffic, run.config["program"]
    model = _model.transformer_config(cfg)
    sz = _model.sizes(cfg)
    B, L = traffic["batch"], traffic["seq"]
    lr = float(program["lr"])
    mesh_shape = tuple(program["mesh"])
    n_dev = int(np.prod(mesh_shape))
    mesh = Mesh(
        np.asarray(run.devices[:n_dev]).reshape(mesh_shape),
        ("dp", "sp", "tp"),
    )
    shapes = _model.param_shapes(cfg)
    shardings = jax.tree.map(
        lambda s: NamedSharding(mesh, s), param_specs(model, mesh)
    )
    make = lambda: weights.make_params(
        shapes, run.seed, d_model=sz["d_model"], n_layers=sz["n_layers"],
        out_shardings=shardings,
    )
    with run.spans.span("setup_weights"):
        params = make()
        nb = int(traffic["distinct_batches"])
        tokens = weights.make_tokens(run.seed, (nb, B, L + 1), sz["vocab"])
        tok_sharding = NamedSharding(mesh, data_spec(model))
        feed = [
            (jax.device_put(tokens[i, :, :-1], tok_sharding),
             jax.device_put(tokens[i, :, 1:], tok_sharding))
            for i in range(nb)
        ]
        jax.block_until_ready(feed)
    step = make_train_step(model, mesh, lr=lr, donate=bool(program["donate"]))

    @jax.jit
    def moved(params, start):
        return jax.tree.map(
            lambda a, b: jnp.sqrt(jnp.sum(jnp.square(
                a.astype(jnp.float32) - b.astype(jnp.float32)))),
            params, start,
        )

    # -- first steps: the window's own call and feed ----------------------
    n_ref = int(traffic["reference_steps"])
    first_losses, first_update, change = [], None, None
    with run.spans.span("setup_first_steps"):
        for i in range(n_ref):
            with run.spans.span("setup_compile" if i == 0 else "first_step"):
                params, loss = step(params, *feed[i % nb])
                first_losses.append(float(loss))
            if i == 0:
                first_update = _flat(moved(params, make()))
        change = _flat(moved(params, make()))
        k = n_ref
        for _ in range(int(traffic["warm_steps"])):
            params, loss = step(params, *feed[k % nb])
            k += 1
        loss.block_until_ready()

    # -- the window -------------------------------------------------------
    tracer = common.WindowTrace(run, traffic.get("trace_seconds", 8))
    stamps, losses = [], []
    params, in_flight = step(params, *feed[k % nb])
    k += 1
    in_flight.block_until_ready()
    run.end_to_end["setup_s"] = common.now() - run.t_start
    common.print_setup(run)
    tracer.start()
    t_open = common.now()
    stamps.append(t_open)
    params, in_flight = step(params, *feed[k % nb])
    k += 1
    while True:
        with run.spans.span("step"):
            params, nxt = step(params, *feed[k % nb])
            k += 1
            in_flight.block_until_ready()
        stamps.append(common.now())
        losses.append(in_flight)
        in_flight = nxt
        tracer.stop_if_due()
        if stamps[-1] - t_open >= run.seconds:
            break
    in_flight.block_until_ready()
    run.window = (stamps[0], stamps[-1])
    tracer.reduce()
    run.memory_peak_bytes = common.memory_peak_bytes(run.devices)
    common.print_memory(run.devices)
    common.window_compiled_nothing(run)

    intervals = [b - a for a, b in zip(stamps, stamps[1:])]
    n_steps = len(intervals)
    tokens_per_step = B * L
    losses = [float(x) for x in losses]
    finite = [bool(np.isfinite(x)) for x in losses]
    run.attempted = n_steps
    run.failed = n_steps - sum(finite)
    run.end_to_end["train_tok_s"] = (
        n_steps * tokens_per_step / (stamps[-1] - stamps[0])
    )
    run.info.update(
        intervals=intervals,
        flops_per_step=counts.transformer_train_flops(
            batch=B, seq=L, window=cfg["sliding_window"], **sz),
        flash_flops_per_step=counts.flash_train_flops(
            batch=B, seq=L, n_heads=sz["n_heads"],
            head_dim=sz["d_model"] // sz["n_heads"],
            n_layers=sz["n_layers"], window=cfg["sliding_window"]),
    )
    print("series step_interval_ms " + common.compact(
        [1e3 * x for x in intervals], 2), flush=True)
    print("series window_loss " + common.compact(losses, 4), flush=True)
    print(
        f"note steps {n_steps} mean_ms "
        f"{1e3 * statistics.fmean(intervals):.3f} median_ms "
        f"{1e3 * statistics.median(intervals):.3f} tok_s_from_median "
        f"{tokens_per_step / statistics.median(intervals):.1f}",
        flush=True,
    )

    # -- the plain reference, once the program's state is freed -----------
    del params, in_flight, nxt, step
    gc.collect()
    jax.clear_caches()
    limits = cfg["limits"]
    ref = _model.reference_module(run)
    with run.spans.span("reference"):
        ref_params = make()
        batches = [feed[i % nb] for i in range(n_ref)]
        ref_losses, ref_grad, ref_change = ref.train_steps(
            ref_params, batches, lr=lr, window=cfg["sliding_window"],
        )
    ref_grad, ref_change = _flat(ref_grad), _flat(ref_change)
    grad_prog = {k: v / lr for k, v in first_update.items()}
    run.info["reference"] = (ref_losses, ref_grad, ref_change)
    run.info["reference_args"] = (make, batches, lr)
    got = gaps((first_losses, grad_prog, change),
               (ref_losses, ref_grad, ref_change))
    for i, g in enumerate(got["loss"]):
        run.check.at_most(f"loss_step{i + 1}_rel_gap", g,
                          limits["loss_rel_gap"])
    run.check.at_most("first_gradient_norm_worst_leaf_gap", got["grad"],
                      limits["grad_norm_gap"])
    run.check.at_most("parameter_change_norm_worst_leaf_gap",
                      got["change"], limits["change_norm_gap"])
    run.check.require("window_losses_finite", all(finite) and n_steps > 0)
    print(
        f"note reference_s {run.spans.durations('reference')[0]:.2f} "
        f"first_losses {common.compact(first_losses, 5)} reference "
        f"{common.compact(ref_losses, 5)}", flush=True,
    )
