"""Pipeline parallelism: SPMD microbatch pipeline over a ``"pp"`` axis.

The reference has no pipeline parallelism ("no tensor parallelism,
pipeline parallelism, ... anywhere in the repo" — SURVEY §2); this is a
north-star mechanism so the framework covers every axis of a modern TPU
mesh. The design is the TPU-native formulation (collective-permute
pipelining, as in praxis/scaling-book) rather than the GPU
point-to-point one:

* The L layers are **stacked** along a leading axis and sharded over
  ``pp`` — each device holds L/pp contiguous layers (one *stage*).
* The batch is split into M **microbatches**. A single ``lax.scan``
  runs M + pp - 1 ticks; each tick every stage applies its layers to
  its current microbatch and hands the activation to the next stage
  with one ``jax.lax.ppermute`` hop (stage handoffs ride ICI
  neighbor links — the mesh's last axis is physically adjacent chips).
* Stage 0 injects microbatch t at tick t; the last stage emits
  microbatch t at tick t + pp - 1 into a preallocated output buffer
  (``dynamic_update_slice`` guarded by a validity mask — everything is
  static shapes, XLA unrolls nothing).
* The whole schedule is **differentiable**: ``jax.grad`` through the
  scan reverses the ticks and transposes each ``ppermute`` into the
  reverse hop, which *is* the backward pipeline (GPipe schedule) — no
  hand-written 1F1B machinery, the bubble fraction is the standard
  (pp-1)/(M+pp-1) each way.

``pipeline_spmd`` is the generic per-shard engine (call inside
``shard_map``; composes with a ``dp`` batch axis outside and ``tp``
psums inside ``stage_fn``). ``make_pipeline_train_step`` wires it into
the flagship transformer over a (dp, pp) mesh.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = [
    "pipeline_spmd",
    "pipeline_1f1b",
    "pipeline_circular",
    "pipeline_param_specs_circular",
    "bubble_fraction",
    "measure_bubble",
    "stack_layers",
    "make_pipeline_train_step",
    "make_optax_pipeline_train_step",
    "pipeline_param_specs",
    "shard_params_pipeline",
]


def pipeline_spmd(stage_fn, stage_params, x, *, axis: str = "pp",
                  n_microbatch: int, return_busy: bool = False):
    """Run ``x`` through pp stages of ``stage_fn``; call inside shard_map.

    ``stage_fn(stage_params, micro) -> micro`` applies this device's
    layer stack to one microbatch; ``stage_params`` is the pp-local
    shard (leading axis = layers-per-stage). ``x`` is the full local
    batch (identical on every stage of a pp group — shard it over dp,
    not pp); the batch axis must divide into ``n_microbatch``.

    Returns the full-batch output, replicated across the ``pp`` axis
    (one psum at the end — the output buffer is only populated on the
    last stage). ``return_busy=True`` additionally returns this device's
    per-tick busy mask (T,) — True where the tick's stage application
    consumed a real microbatch — the measured-bubble evidence
    (:func:`measure_bubble`).
    """
    p = jax.lax.axis_size(axis)
    idx = jax.lax.axis_index(axis)
    B = x.shape[0]
    if B % n_microbatch != 0:
        raise ValueError(
            f"batch {B} not divisible by n_microbatch {n_microbatch}"
        )
    micro = x.reshape(n_microbatch, B // n_microbatch, *x.shape[1:])
    perm = [(j, (j + 1) % p) for j in range(p)]
    # the carry becomes pp-varying inside the loop (stage-dependent
    # injection/emission), so its initial value must be typed varying
    out0 = jax.lax.pcast(jnp.zeros_like(micro), (axis,), to="varying")
    buf0 = jax.lax.pcast(jnp.zeros_like(micro[0]), (axis,), to="varying")
    # payload-validity flag RIDES THE RING with the buffer: set at
    # injection, permuted alongside the activation, and the last
    # stage's emission is gated on it — so the per-tick busy trace
    # (measure_bubble) is the same state that decides which outputs are
    # real, not re-derived index arithmetic
    live0 = jax.lax.pcast(jnp.zeros((), jnp.bool_), (axis,), to="varying")

    def tick(carry, t):
        buf, out, live = carry
        # stage 0 ingests microbatch t (clamped: injections past M-1
        # would surface only after the last tick, so they are inert)
        inject = micro[jnp.minimum(t, n_microbatch - 1)]
        buf = jnp.where(idx == 0, inject, buf)
        live = jnp.where(idx == 0, t < n_microbatch, live)
        y = stage_fn(stage_params, buf)
        # last stage emits microbatch ot = t - (p - 1), once its LIVE
        # payload arrives (the flag injected p-1 ticks ago at stage 0)
        ot = t - (p - 1)
        valid = jnp.logical_and(idx == p - 1, jnp.logical_and(ot >= 0, live))
        oc = jnp.clip(ot, 0, n_microbatch - 1)
        cur = jax.lax.dynamic_slice_in_dim(out, oc, 1, axis=0)
        upd = jnp.where(valid, y[None].astype(out.dtype), cur)
        out = jax.lax.dynamic_update_slice_in_dim(out, upd, oc, axis=0)
        # hand the activation to the next stage (wrap hop p-1 -> 0 is
        # overwritten by the next injection)
        buf = jax.lax.ppermute(y, axis, perm)
        busy = live  # what this stage computed on this tick
        live = jax.lax.ppermute(live, axis, perm)
        return (buf, out, live), busy

    (_, out, _), busy = jax.lax.scan(
        tick, (buf0, out0, live0), jnp.arange(n_microbatch + p - 1)
    )
    # out is nonzero only on the last stage; replicate it everywhere
    out = jax.lax.psum(out, axis)
    out = out.reshape(B, *x.shape[1:])
    return (out, busy) if return_busy else out


def stack_layers(layers: list[dict]) -> dict:
    """list-of-pytrees -> pytree-of-stacked-arrays (leading = layer)."""
    return jax.tree.map(lambda *xs: jnp.stack(xs), *layers)


def bubble_fraction(pp: int, n_microbatch: int,
                    schedule: str = "1f1b") -> float:
    """Fraction of pipeline ticks that are bubble (no useful work).

    * ``"1f1b"`` — the interleaved fwd/bwd scan of
      :func:`pipeline_1f1b`: each device does M forward and M backward
      microbatch steps over ``M + 2(pp-1)`` ticks, so the bubble is
      ``2(pp-1) / (M + 2(pp-1))``.
    * ``"gpipe"`` — the fill/drain :func:`pipeline_spmd` schedule
      differentiated by ``jax.grad``: ``(pp-1) / (M + pp - 1)`` each
      way (the same ratio forward and backward).
    """
    p, M = int(pp), int(n_microbatch)
    if schedule == "1f1b":
        return 2 * (p - 1) / (M + 2 * (p - 1))
    if schedule == "gpipe":
        return (p - 1) / (M + p - 1)
    if schedule == "circular" or (
        schedule.startswith("circular:")
        and schedule.split(":", 1)[1].isdigit()
    ):
        # "circular:v" — v virtual chunks per device; ticks are 1/v the
        # work of a gpipe tick, so the fill/drain bubble shrinks by v:
        # wall = (v*M + p - 1) ticks * (L / (v*p)) = (M + (p-1)/v) * L/p
        v = int(schedule.split(":", 1)[1]) if ":" in schedule else 2
        return (p - 1) / (v * M + p - 1)
    raise ValueError(f"unknown schedule {schedule!r}")


def measure_bubble(mesh: Mesh, n_microbatch: int, schedule: str = "1f1b",
                   *, v: int = 2, axis: str = "pp") -> dict:
    """Run a schedule with per-tick tracing and MEASURE its idle
    fraction, vs the :func:`bubble_fraction` formula.

    Each engine's scan emits a per-device busy mask while executing the
    real schedule (for the circular engine the mask is the live-payload
    state carried around the ring — injection/emission bookkeeping, not
    arithmetic). Returns ``{"measured", "formula", "ticks", "busy"}``
    where ``busy`` is the (pp, T[, 2]) mask; ``measured`` is
    ``1 - mean(busy)`` over all stage-slots.

    The measured value can legitimately exceed the formula: the
    formulas count ideal schedule ticks, while an implementation may
    spend extra ticks on bookkeeping (the circular engine's final
    emission hop costs one tick beyond the analytic ``v*M + p - 1``) —
    exactly the gap this function exists to expose.
    """
    import numpy as np

    p = mesh.shape[axis]
    M = int(n_microbatch)
    B = M  # one row per microbatch; payload is a tiny (B, 2) activation
    x = jnp.arange(B * 2, dtype=jnp.float32).reshape(B, 2)

    if schedule == "1f1b":
        def local(x, tgt):
            *_, slots = pipeline_1f1b(
                lambda sp, pl: (pl[0] * sp["w"], pl[1]),
                lambda hp, pl, t: (pl[0] * hp["w"]).sum(),
                {"w": jnp.float32(1.001)}, {"w": jnp.float32(1.0)},
                x, tgt, axis=axis, n_microbatch=M, return_busy=True,
            )
            return slots[None]

        f = jax.shard_map(
            local, mesh=mesh, in_specs=(P(), P()),
            out_specs=P(axis, None, None),
        )
        busy = np.asarray(f(x, x))  # (pp, T, 2)
        sched_name = "1f1b"
    elif schedule == "gpipe":
        def local(x):
            _, b = pipeline_spmd(
                lambda sp, m: m * sp["w"], {"w": jnp.float32(1.001)},
                x, axis=axis, n_microbatch=M, return_busy=True,
            )
            return b[None]

        f = jax.shard_map(
            local, mesh=mesh, in_specs=(P(),), out_specs=P(axis, None)
        )
        busy = np.asarray(f(x))  # (pp, T)
        sched_name = "gpipe"
    elif schedule == "circular":
        def local(x):
            _, b = pipeline_circular(
                lambda cp, j, m: m * cp["w"], {"w": jnp.float32(1.001)},
                x, axis=axis, n_microbatch=M, v=v, return_busy=True,
            )
            return b[None]

        f = jax.shard_map(
            local, mesh=mesh, in_specs=(P(),), out_specs=P(axis, None)
        )
        busy = np.asarray(f(x))  # (pp, T)
        sched_name = f"circular:{v}"
    else:
        raise ValueError(f"unknown schedule {schedule!r}")
    return {
        "schedule": sched_name,
        "pp": p,
        "n_microbatch": M,
        "ticks": int(busy.shape[1]),
        "measured": float(1.0 - busy.mean()),
        "formula": bubble_fraction(p, M, sched_name),
        "busy": busy,
    }


def pipeline_circular(chunk_fn, chunk_params, x, *, axis: str = "pp",
                      n_microbatch: int, v: int = 2, return_busy: bool = False):
    """Interleaved virtual stages: each device holds ``v`` NON-contiguous
    layer chunks and microbatches lap the device ring ``v`` times —
    call inside shard_map.

    The fill/drain schedule (:func:`pipeline_spmd`) idles ``pp - 1``
    FULL-stage ticks each way. Here a tick applies one CHUNK (1/v of a
    device's layers), and the ring is collision-free by construction:
    chunk ``c`` lives on device ``c mod pp`` (device-major interleaving
    — device d's local chunk ``j`` is global chunk ``j*pp + d``), and a
    payload's stage counter rides with it, so at any tick each device
    hosts exactly one microbatch, at a stage congruent to the device
    index mod pp. Injection is seamless: the wrap-around arrival at
    device 0 is either a FINISHED microbatch (stage == v*pp — emitted
    and replaced by the next injection) or a lap-in-progress (passed
    through to its next chunk). Bubble: ``(pp-1)/(v*M + pp - 1)`` —
    the gpipe ratio divided by ~v (``bubble_fraction("circular:v")``).

    ``chunk_fn(local_chunks, j, micro) -> micro`` applies this device's
    ``j``-th local chunk (``j`` is a traced index into the leading
    ``v``-axis of ``local_chunks``). ``x``: the full local batch,
    ``n_microbatch`` must divide it and be a multiple of the ``pp``
    size (seamless waves need full ring occupancy). Differentiable:
    ``jax.grad`` through the scan reverses the ring, giving the
    backward wave the same 1/v bubble (activation memory is O(scan
    length), like the gpipe path; use :func:`pipeline_1f1b` when memory
    is the binding constraint instead).
    """
    p = jax.lax.axis_size(axis)
    idx = jax.lax.axis_index(axis)
    B = x.shape[0]
    M = int(n_microbatch)
    if B % M != 0:
        raise ValueError(f"batch {B} not divisible by n_microbatch {M}")
    if M % p != 0:
        raise ValueError(
            f"n_microbatch {M} must be a multiple of the pipeline size "
            f"{p} (seamless circular waves need full ring occupancy)"
        )
    C = v * p  # total chunks = virtual stages
    micro = x.reshape(M, B // M, *x.shape[1:])
    perm = [(j, (j + 1) % p) for j in range(p)]

    def _varying(a):
        if axis in getattr(jax.typeof(a), "vma", ()):
            return a
        return jax.lax.pcast(a, (axis,), to="varying")

    buf0 = _varying(jnp.zeros_like(micro[0]))
    # stage counter rides with the payload: s < C live (next chunk = s),
    # s == C finished (emit on arrival at device 0), s == C+1 empty slot
    s0 = _varying(jnp.full((), C + 1, jnp.int32))
    out0 = _varying(jnp.zeros_like(micro))
    inj0 = _varying(jnp.zeros((), jnp.int32))   # injections so far
    emit0 = _varying(jnp.zeros((), jnp.int32))  # emissions so far

    def tick(carry, t):
        buf, s, out, inj, emit = carry
        # --- device 0: emit a finished arrival, refill the freed slot --
        # (FIFO: injection order == ring order == emission order, so
        # per-device counters — only device 0's ever advance — give the
        # microbatch ids; tick arithmetic would break across waves)
        arr_done = jnp.logical_and(idx == 0, s == C)
        arr_free = jnp.logical_and(idx == 0, s >= C)
        o_valid = jnp.logical_and(arr_done, emit < M)
        oc = jnp.clip(emit, 0, M - 1)
        cur = jax.lax.dynamic_slice_in_dim(out, oc, 1, axis=0)
        upd = jnp.where(o_valid, buf[None].astype(out.dtype), cur)
        out = jax.lax.dynamic_update_slice_in_dim(out, upd, oc, axis=0)
        emit = emit + o_valid.astype(jnp.int32)
        can_inject = jnp.logical_and(arr_free, inj < M)
        ic = jnp.clip(inj, 0, M - 1)
        buf = jnp.where(can_inject, micro[ic], buf)
        # a consumed finished slot parks as empty so it cannot re-emit
        s = jnp.where(
            can_inject, 0, jnp.where(arr_done, C + 1, s)
        )
        inj = inj + can_inject.astype(jnp.int32)
        # --- apply this device's local chunk j = s // p ---------------
        # (every live payload here has s ≡ idx (mod p), by construction)
        j = jnp.clip(s // p, 0, v - 1)
        live = s < C
        y = chunk_fn(chunk_params, j, buf)
        buf = jnp.where(live, y, buf)
        s = jnp.where(live, s + 1, s)
        # --- rotate payload + its stage counter to the next device ----
        buf = jax.lax.ppermute(buf, axis, perm)
        s = jax.lax.ppermute(s, axis, perm)
        # ``live`` is genuine carried state (stage counters + injection
        # and emission bookkeeping riding the ring), so this per-tick
        # busy mask measures the schedule as executed, not a formula
        return (buf, s, out, inj, emit), live

    # wave w (p microbatches) injects during ticks [w*C, w*C + p); the
    # last microbatch (inj = M-1) enters at (M/p - 1)*C + p - 1 and its
    # finished payload arrives back at device 0 C ticks later
    T = v * M + p
    (_, _, out, _, _), busy = jax.lax.scan(
        tick, (buf0, s0, out0, inj0, emit0), jnp.arange(T)
    )
    out = jax.lax.psum(out, axis)  # populated on device 0 only
    out = out.reshape(B, *x.shape[1:])
    return (out, busy) if return_busy else out


def pipeline_1f1b(stage_fn, head_fn, stage_params, head_params, x, targets,
                  *, axis: str = "pp", n_microbatch: int,
                  return_busy: bool = False):
    """One-forward-one-backward pipeline step; call inside shard_map.

    The GPipe formulation above leans on ``jax.grad`` through the scan,
    which checkpoints every tick's carry — activation memory grows with
    ``M``. This schedule interleaves each microbatch's backward with
    later microbatches' forwards in a SINGLE scan, which needs only a
    ring of ``2·pp - 1`` residual slots (the in-flight window), the
    1F1B memory property. The enabler is folding the *loss head* into
    the last stage: per-token LM loss is independent across
    microbatches, so ``dL/dy`` for microbatch m is available the tick
    its forward exits — the backward wavefront starts immediately
    instead of after a full forward pass.

    Schedule (device d, tick t, ``T = M + 2(pp-1)`` ticks):

    * forward slot: microbatch ``f = t - d`` (valid while ``0 <= f < M``);
      stage 0 injects ``micro[f]``, stage pp-1 feeds its output straight
      into ``head_fn`` and the same tick's backward slot.
    * backward slot: microbatch ``b = t - (2·pp - 2 - d)`` — the reverse
      wavefront. The stage vjp *recomputes* the forward from the saved
      ring input (rematerialization: storing linearizations in a scan
      carry is impossible, and remat is the standard TPU trade of FLOPs
      for HBM anyway).
    * two collective permutes per tick: activations to ``d+1``, grads to
      ``d-1``. Wrap-around values are overwritten by injections, so the
      ring permutes are schedule-exact.

    ``stage_fn(stage_params, payload) -> payload`` where ``payload`` is
    any pytree (the transformer stages use ``(activation, aux_loss)`` so
    MoE load-balance aux rides the pipeline to the head — that is what
    makes expert layers pipeline-legal).
    ``head_fn(head_params, payload, tgt_micro) -> scalar loss`` (summed,
    not meaned, over the microbatch; normalize outside).

    Returns ``(loss_sum, stage_grads, head_grads, dx)`` — all *local*
    sums: psum ``loss/head_grads/dx`` over the pipeline axis (each is
    nonzero on one stage) and everything over the data axes, caller-side.
    ``dx`` is (M, ...) microbatch-input grads for the embedding update.
    """
    p = jax.lax.axis_size(axis)
    idx = jax.lax.axis_index(axis)
    B = x.shape[0]
    M = int(n_microbatch)
    if B % M != 0:
        raise ValueError(f"batch {B} not divisible by n_microbatch {M}")
    micro = x.reshape(M, B // M, *x.shape[1:])
    tgt = targets.reshape(M, B // M, *targets.shape[1:])
    R = 2 * p - 1  # residual ring: covers the 2(pp-1)-tick in-flight window
    fwd_perm = [(j, (j + 1) % p) for j in range(p)]
    bwd_perm = [(j, (j - 1) % p) for j in range(p)]

    # the scan carry becomes varying over every manual axis the loop body
    # touches: the pipeline axis (stage-dependent masking) plus whatever
    # the data and params are already varying over (e.g. "dp"-sharded
    # batches). Type the initial carry to that union up front.
    target_vma = {axis}
    for leaf in jax.tree.leaves((x, targets, stage_params, head_params)):
        target_vma |= set(getattr(jax.typeof(leaf), "vma", ()))

    def _varying(v):
        def f(a):
            need = tuple(
                target_vma - set(getattr(jax.typeof(a), "vma", ()))
            )
            return jax.lax.pcast(a, need, to="varying") if need else a

        return jax.tree.map(f, v)

    # CRITICAL: the params must be fully varying before any vjp runs.
    # A replicated (unvarying) operand used by a varying computation is
    # an implicit broadcast, and the TRANSPOSE of that broadcast is a
    # psum — jax.vjp/value_and_grad would hand every device the
    # cross-device SUM of param grads (polluted by the masked-out
    # warmup/cooldown evals of other stages) instead of its own
    # partial. Caller-side psums then double-count. Varying params keep
    # every grad a per-device partial; the caller owns the collectives.
    stage_params = _varying(stage_params)
    head_params = _varying(head_params)

    def _pperm(v, perm):
        return jax.tree.map(lambda a: jax.lax.ppermute(a, axis, perm), v)

    def _where(c, a, b):
        return jax.tree.map(lambda u, v: jnp.where(c, u, v), a, b)

    zero_payload = (jnp.zeros_like(micro[0]), jnp.float32(0.0))
    carry0 = dict(
        buf_f=_varying(zero_payload),            # activation entering here
        buf_b=_varying(zero_payload),            # grad entering here
        ring=_varying(jax.tree.map(
            lambda a: jnp.zeros((R,) + a.shape, a.dtype), zero_payload
        )),
        g_stage=_varying(jax.tree.map(jnp.zeros_like, stage_params)),
        g_head=_varying(jax.tree.map(jnp.zeros_like, head_params)),
        loss=_varying(jnp.float32(0.0)),
        dx=_varying(jnp.zeros((M,) + micro.shape[1:], micro.dtype)),
    )

    def tick(c, t):
        # ---- forward slot: microbatch f = t - idx -----------------------
        f = t - idx
        f_valid = jnp.logical_and(f >= 0, f < M)
        fc = jnp.clip(f, 0, M - 1)
        inject = (micro[fc], jnp.float32(0.0))
        p_in = _where(idx == 0, inject, c["buf_f"])
        # save the stage input for the backward recompute (ring slot)
        ring = jax.tree.map(
            lambda r, v: jnp.where(
                f_valid,
                jax.lax.dynamic_update_index_in_dim(r, v, fc % R, 0),
                r,
            ),
            c["ring"], p_in,
        )
        y = stage_fn(stage_params, p_in)
        # ---- head on the last stage: loss + dL/dy, same tick ------------
        def head_loss(hp, payload):
            return head_fn(hp, payload, tgt[fc])

        (loss_f, (g_head_f, dy)) = jax.value_and_grad(
            head_loss, argnums=(0, 1)
        )(head_params, y)
        head_valid = jnp.logical_and(idx == p - 1, f_valid)
        loss = c["loss"] + jnp.where(head_valid, loss_f, 0.0)
        g_head = jax.tree.map(
            lambda acc, g: acc + jnp.where(head_valid, g, 0),
            c["g_head"], g_head_f,
        )
        # ---- backward slot: microbatch b = t - (2p - 2 - idx) -----------
        b = t - (2 * p - 2 - idx)
        b_valid = jnp.logical_and(b >= 0, b < M)
        bc = jnp.clip(b, 0, M - 1)
        x_saved = jax.tree.map(
            lambda r: jax.lax.dynamic_index_in_dim(
                r, bc % R, 0, keepdims=False
            ),
            ring,
        )
        # on the last stage the backward microbatch IS this tick's
        # forward microbatch (b == f there): dy feeds straight in
        g_in = _where(idx == p - 1, dy, c["buf_b"])
        _, vjp_fn = jax.vjp(stage_fn, stage_params, x_saved)
        g_stage_b, g_x = vjp_fn(g_in)
        g_stage = jax.tree.map(
            lambda acc, g: acc + jnp.where(b_valid, g, 0),
            c["g_stage"], g_stage_b,
        )
        # stage 0's input grad is the embedding grad for microbatch b
        dx = jnp.where(
            jnp.logical_and(idx == 0, b_valid),
            jax.lax.dynamic_update_index_in_dim(
                c["dx"], g_x[0], bc, 0
            ),
            c["dx"],
        )
        # ---- handoffs ---------------------------------------------------
        buf_f = _pperm(y, fwd_perm)      # activations ride to d+1
        buf_b = _pperm(g_x, bwd_perm)    # grads ride to d-1
        return dict(
            buf_f=buf_f, buf_b=buf_b, ring=ring, g_stage=g_stage,
            g_head=g_head, loss=loss, dx=dx,
        ), jnp.stack([f_valid, b_valid])

    T = M + 2 * (p - 1)
    c, slots = jax.lax.scan(tick, carry0, jnp.arange(T))
    out = c["loss"], c["g_stage"], c["g_head"], c["dx"]
    # each tick runs a forward AND a backward slot; the (T, 2) mask says
    # which consumed a real microbatch — 1F1B's bubble denominator is
    # slot-time, 2T
    return out + (slots,) if return_busy else out


# ---------------------------------------------------------------- model


def _stage_apply(stacked_local, x, pos, cfg):
    """Apply this stage's layers-per-stage stack to one microbatch
    (activation-only view of :func:`_stage_apply_payload`, so the two
    schedules share one layer recipe)."""
    return _stage_apply_payload(
        stacked_local, (x, jnp.float32(0.0)), pos, cfg
    )[0]


def _stage_apply_payload(stacked_local, payload, pos, cfg):
    """Payload-form stage for the 1F1B schedule: ``(activation, aux)``.

    MoE layers are pipeline-legal here: experts live dense inside their
    stage (a (dp, pp) mesh has no ``ep`` axis — expert parallelism
    composes with the flat dp/sp/tp/ep program in models/transformer.py,
    pipeline composes depth), and each layer's Switch load-balance aux
    loss accumulates into the payload scalar that rides the pipeline to
    the head."""
    from ..models.moe import moe_ffn_dense
    from ..models.transformer import (
        _ln, _local_attention, _mlp, _rope, attn_merge, attn_qkv,
        require_plain_block,
    )

    require_plain_block(cfg, "a pipeline stage")
    attn_fn = _local_attention(cfg)
    x, aux = payload

    def one_layer(carry, lp):
        h, a = carry
        q, k, v, gate = attn_qkv(h, lp, cfg, 0, partial(_rope, pos=pos))
        h = attn_merge(h, attn_fn(q, k, v), gate, lp, cfg)
        h2 = _ln(h, lp["ln2_s"], lp["ln2_b"])
        if cfg.n_experts:
            y, la = moe_ffn_dense(h2, lp, cfg.capacity_factor)
            return (h + y, a + la), None
        return (h + _mlp(h2, lp) + lp["b2"], a), None

    (x, aux), _ = jax.lax.scan(one_layer, (x, aux), stacked_local)
    return x, aux


def _head_loss_sum(head_params, payload, tgt, cfg):
    """Per-microbatch loss head: final LN + tied logits + SUMMED token
    NLL (normalization happens once, outside the pipeline), plus the
    MoE aux term carried in by the payload."""
    from ..models.transformer import _ln

    y, aux = payload
    h = _ln(y, head_params["lnf_s"], head_params["lnf_b"])
    logits = jnp.einsum(
        "bld,vd->blv", h, head_params["emb"]
    ).astype(jnp.float32)
    # logsumexp form: no materialized f32 log_softmax (see nll_loss)
    lse = jax.nn.logsumexp(logits, axis=-1)
    tl = jnp.take_along_axis(logits, tgt[..., None], axis=-1)[..., 0]
    nll = lse - tl
    loss = nll.sum()
    if cfg.n_experts and cfg.moe_aux_coef:
        # aux is a per-microbatch mean-style quantity; scale by the
        # microbatch token count so it normalizes like the NLL sum
        loss = loss + cfg.moe_aux_coef * aux * nll.size
    return loss


def pipeline_param_specs(cfg) -> dict:
    """Specs for pipeline params: stacked layers sharded over ``pp`` on
    the leading (layer) axis, embedding/final-LN replicated. Stages run
    their layers dense within the stage (pipeline composes depth; tp/ep
    compose in the flat program), so only the layer axis is sharded —
    including the expert tables when ``cfg.n_experts``."""
    layer_keys = [
        "ln1_s", "ln1_b", "wq", "wk", "wv", "wo", "ln2_s", "ln2_b",
    ]
    if cfg.n_experts:
        layer_keys += ["wg", "we1", "be1", "we2", "be2"]
    else:
        layer_keys += ["w1", "b1", "w2", "b2"]
    return {
        "emb": P(),
        "layers": {k: P("pp") for k in layer_keys},
        "lnf_s": P(),
        "lnf_b": P(),
    }


def _check_dense(cfg):
    if cfg.n_experts:
        raise NotImplementedError(
            'the fill/drain "gpipe" schedule runs dense stages only; '
            'MoE stages are pipeline-legal under schedule="1f1b" '
            "(expert aux loss rides the 1F1B payload to the head)"
        )


def _chunk_apply(local_chunks, j, x, pos, cfg, v):
    """Circular-schedule chunk: dynamic-index the local ``v`` axis, then
    run that chunk's layers (the shard keeps a singleton device axis in
    front: local leaves are (1, v, layers_per_chunk, ...))."""
    leaf = jax.tree.leaves(local_chunks)[0]
    if leaf.shape[1] != v:
        # dynamic_index CLAMPS out-of-range j, so a layout/schedule v
        # mismatch (params sharded for one v, step built for another)
        # would silently apply only a prefix of each device's chunks
        raise ValueError(
            f"params are laid out with {leaf.shape[1]} virtual stages "
            f"per device but the schedule runs v={v}; pass the same "
            "virtual_stages to shard_params_pipeline and "
            "make_pipeline_train_step"
        )
    lp = jax.tree.map(
        lambda a: jax.lax.dynamic_index_in_dim(
            a[0], j, 0, keepdims=False
        ),
        local_chunks,
    )
    return _stage_apply(lp, x, pos, cfg)


def pipeline_param_specs_circular(cfg) -> dict:
    """Specs for the circular layout: stacked layers reorganized
    device-major to ``(pp, v, layers_per_chunk, ...)`` and sharded on
    the leading device axis (device d holds chunks d, pp+d, 2pp+d, ...).
    Dense stages only (MoE rides the 1F1B schedule); the key set and
    specs are the stage layout's — only the array layout differs."""
    _check_dense(cfg)
    return pipeline_param_specs(cfg)


def _circular_loss_local(params, tokens, targets, cfg, n_microbatch, v):
    return _pipeline_loss_local(
        params, tokens, targets, cfg, n_microbatch,
        engine=lambda pos, layers, x: pipeline_circular(
            partial(_chunk_apply, pos=pos, cfg=cfg, v=v),
            layers, x, axis="pp", n_microbatch=n_microbatch, v=v,
        ),
    )


def _pipeline_loss_local(params, tokens, targets, cfg, n_microbatch,
                         engine=None):
    """Shared per-shard loss: embed -> pipeline engine -> LN -> tied
    logits -> dp-mean NLL. ``engine(pos, layers, x)`` defaults to the
    fill/drain gpipe schedule; the circular schedule passes its own."""
    from ..models.transformer import _ln, nll_loss

    pos = jnp.arange(tokens.shape[1])
    x = params["emb"][tokens]
    if engine is None:
        x = pipeline_spmd(
            partial(_stage_apply, pos=pos, cfg=cfg),
            params["layers"],
            x,
            axis="pp",
            n_microbatch=n_microbatch,
        )
    else:
        x = engine(pos, params["layers"], x)
    x = _ln(x, params["lnf_s"], params["lnf_b"])
    logits = jnp.einsum("bld,vd->blv", x, params["emb"])
    return nll_loss(logits, targets, ("dp",))


def _1f1b_loss_grads_local(params, tokens, targets, cfg, n_microbatch):
    """Per-shard 1F1B step: returns the (replicated) mean loss and the
    full parameter-gradient pytree, stage grads pp-local."""
    pos = jnp.arange(tokens.shape[1])
    x = params["emb"][tokens]
    head_params = {
        "emb": params["emb"],
        "lnf_s": params["lnf_s"],
        "lnf_b": params["lnf_b"],
    }
    loss_sum, g_stage, g_head, dx = pipeline_1f1b(
        partial(_stage_apply_payload, pos=pos, cfg=cfg),
        partial(_head_loss_sum, cfg=cfg),
        params["layers"],
        head_params,
        x,
        targets,
        axis="pp",
        n_microbatch=n_microbatch,
    )
    # loss/head grads live on the last stage, dx on stage 0: the pp psum
    # both replicates and selects; dp psum sums the data shards. tokens
    # are pp-replicated, so the count psums over dp only.
    count = jax.lax.psum(jnp.float32(targets.size), "dp")
    loss = jax.lax.psum(loss_sum, ("dp", "pp")) / count
    g_head = jax.tree.map(
        lambda g: jax.lax.psum(g, ("dp", "pp")) / count, g_head
    )
    # embedding grad: head contribution + the lookup vjp of dx
    dxf = dx.reshape(tokens.shape[0], tokens.shape[1], -1)
    demb = jnp.zeros_like(params["emb"]).at[tokens].add(
        dxf.astype(params["emb"].dtype)
    )
    demb = jax.lax.psum(demb, ("dp", "pp")) / count
    g_stage = jax.tree.map(
        lambda g: jax.lax.psum(g, "dp") / count, g_stage
    )
    grads = {
        "emb": g_head["emb"] + demb,
        "layers": g_stage,
        "lnf_s": g_head["lnf_s"],
        "lnf_b": g_head["lnf_b"],
    }
    return loss, grads


def make_pipeline_train_step(cfg, mesh: Mesh, *, n_microbatch: int,
                             lr: float = 1e-2, schedule: str = "1f1b",
                             virtual_stages: int = 2):
    """Jitted (params, tokens, targets) -> (params, loss) SGD step over a
    (dp, pp) mesh: batch over ``dp``, the layer stack over ``pp``.

    ``schedule="1f1b"`` (default) runs the interleaved fwd/bwd scan of
    :func:`pipeline_1f1b` — O(pp) activation memory, MoE stages legal.
    ``schedule="circular"`` runs :func:`pipeline_circular` with
    ``virtual_stages`` chunks per device — the interleaved-virtual-stage
    schedule whose fill/drain bubble is 1/v of gpipe's (dense stages;
    autodiff backward; ``n_microbatch`` must be a multiple of pp and
    ``cfg.n_layers`` of ``v*pp``). ``schedule="gpipe"`` keeps the
    fill/drain forward differentiated by ``jax.grad`` (dense stages
    only) for comparison. Bubble fractions: :func:`bubble_fraction`.

    ``cfg.n_layers`` must divide by the pp size; params come from
    :func:`shard_params_pipeline`. Attention runs per-device full
    sequence inside each stage (compose with tp/sp via the flat
    shard_map program in models/transformer.py when sequence sharding is
    needed; pipeline targets the deep-model regime).
    """
    from ..models.transformer import sgd_step_from_grads

    pp = mesh.shape["pp"]
    if cfg.n_layers % pp != 0:
        raise ValueError(
            f"n_layers {cfg.n_layers} not divisible by pp size {pp}"
        )
    grad_fn = _pipeline_grad_fn(
        cfg, mesh, n_microbatch, schedule, virtual_stages
    )
    return sgd_step_from_grads(grad_fn, lr=lr)


def _pipeline_grad_fn(cfg, mesh: Mesh, n_microbatch: int, schedule: str,
                      virtual_stages: int):
    """(params, tokens, targets) -> (loss, grads) over the (dp, pp)
    mesh for any schedule — the shared gradient half of the SGD and
    optax pipeline steps. 1F1B computes grads inside its own scan; the
    autodiff schedules differentiate the shard_map loss."""
    if schedule == "1f1b":
        return jax.shard_map(
            partial(
                _1f1b_loss_grads_local, cfg=cfg, n_microbatch=n_microbatch
            ),
            mesh=mesh,
            in_specs=(pipeline_param_specs(cfg), P("dp"), P("dp")),
            out_specs=(P(), pipeline_param_specs(cfg)),
        )
    if schedule == "gpipe":
        _check_dense(cfg)
        loss_fn = jax.shard_map(
            partial(
                _pipeline_loss_local, cfg=cfg, n_microbatch=n_microbatch
            ),
            mesh=mesh,
            in_specs=(pipeline_param_specs(cfg), P("dp"), P("dp")),
            out_specs=P(),
        )
    elif schedule == "circular":
        _check_dense(cfg)
        v = int(virtual_stages)
        if cfg.n_layers % (v * mesh.shape["pp"]) != 0:
            raise ValueError(
                f"n_layers {cfg.n_layers} not divisible by v*pp = "
                f"{v * mesh.shape['pp']}"
            )
        loss_fn = jax.shard_map(
            partial(
                _circular_loss_local, cfg=cfg,
                n_microbatch=n_microbatch, v=v,
            ),
            mesh=mesh,
            in_specs=(pipeline_param_specs_circular(cfg), P("dp"), P("dp")),
            out_specs=P(),
        )
    else:
        raise ValueError(f"unknown schedule {schedule!r}")

    def grad_fn(params, tokens, targets):
        return jax.value_and_grad(loss_fn)(params, tokens, targets)

    return grad_fn


def make_optax_pipeline_train_step(
    cfg, mesh: Mesh, tx, *, n_microbatch: int, schedule: str = "1f1b",
    virtual_stages: int = 2, donate: bool = False,
):
    """Pipeline train step driving any optax optimizer (VERDICT r3
    missing #3 — pipeline training was SGD-only). Returns ``(step,
    init_state)`` like :func:`~..models.transformer.make_optax_train_step`:

    >>> step, init_state = make_optax_pipeline_train_step(
    ...     cfg, mesh, optax.adamw(3e-4), n_microbatch=8)
    >>> opt_state = init_state(params)   # moments shard like the params
    >>> params, opt_state, loss = step(params, opt_state, inp, tgt)

    ``init_state`` builds the optimizer state under jit so every moment
    leaf inherits its parameter's NamedSharding — pp-sharded stage
    params get pp-sharded AdamW moments (the layer-stacked leaves are
    sharded on their leading axis, so first/second moments land on the
    owning stage, no replicated optimizer copies in HBM).
    ``donate=True`` donates params AND opt_state for in-place updates.
    """
    from ..models.transformer import make_opt_init, optax_step_from_grads

    pp = mesh.shape["pp"]
    if cfg.n_layers % pp != 0:
        raise ValueError(
            f"n_layers {cfg.n_layers} not divisible by pp size {pp}"
        )
    grad_fn = _pipeline_grad_fn(
        cfg, mesh, n_microbatch, schedule, virtual_stages
    )
    step = optax_step_from_grads(grad_fn, tx, donate=donate)
    return step, make_opt_init(tx)


def shard_params_pipeline(params: dict, cfg, mesh: Mesh,
                          *, virtual_stages: int | None = None) -> dict:
    """Stack the per-layer params and place them on the mesh.

    Default (``virtual_stages=None``): contiguous stage layout — layer
    axis over ``pp`` (gpipe / 1F1B schedules). With ``virtual_stages=v``
    (circular schedule): device-major interleaved layout — stacked
    layers reorganized to ``(pp, v, layers_per_chunk, ...)`` so device d
    holds chunks ``d, pp+d, ..., (v-1)pp+d``."""
    stacked = dict(params)
    stacked["layers"] = stack_layers(params["layers"])
    if virtual_stages is None:
        return jax.tree.map(
            lambda x, s: jax.device_put(x, NamedSharding(mesh, s)),
            stacked,
            pipeline_param_specs(cfg),
        )
    v = int(virtual_stages)
    pp = mesh.shape["pp"]
    L = cfg.n_layers
    if L % (v * pp) != 0:
        raise ValueError(
            f"n_layers {L} not divisible by v*pp = {v * pp}"
        )
    lpc = L // (v * pp)

    def devmajor(a):
        # (L, ...) -> (C=v*pp, lpc, ...) -> (v, pp, lpc, ...) ->
        # (pp, v, lpc, ...): chunk j*pp + d lands at [d, j]
        a = a.reshape(v * pp, lpc, *a.shape[1:])
        a = a.reshape(v, pp, lpc, *a.shape[2:])
        return jnp.swapaxes(a, 0, 1)

    stacked["layers"] = jax.tree.map(devmajor, stacked["layers"])
    return jax.tree.map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)),
        stacked,
        pipeline_param_specs_circular(cfg),
    )
