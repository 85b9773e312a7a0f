"""chip_smoke.py: the pool, the 134M trainer and the serving scheduler,
once each, on the chip, in one process.

Run it from the repository root on a machine with a TPU::

    python3 chip_smoke.py

It drives the three paths the README leads with through the entry points
a user calls, at the full width of the one model the repository has
(step and request counts are small, weights are random from a seed), and
checks each result by the repository's own means:

* **Leg A, the pool**: (8, 6) MDS-coded GEMM at 8192^3 float32 through
  ``AsyncPool`` / ``asyncmap`` / ``waitall`` with one injected straggler,
  decoded from the six fastest and compared with an on-device matmul.
  With four chips it adds a (4, 3) code with one worker per chip and one
  ``PoolMeshCodedGemm`` epoch whose masked ``psum_scatter`` crosses the
  interconnect.
* **Leg B, the trainer**: the flagship ``TransformerConfig`` (d=1024,
  8 layers, vocabulary 32768, Ulysses + flash attention, bfloat16),
  batch 8 x sequence 2048, three donated SGD steps. With four chips it
  also runs on a ``("dp", "sp", "tp")`` mesh and compares first losses.
* **Leg C, the server**: the serving configuration (4 query heads per
  K/V head, window 1024) through ``ServingScheduler`` with the paged int8
  cache: twelve requests through eight slots, judged against
  ``generate_ring_dense`` and a float32 reference forward. One replica on
  one chip whatever the device count (several replicas behind the router
  is ROADMAP R5).

There is no fallback: the first thing it does is refuse any platform but
``tpu``, no leg is wrapped in ``try/except``, and any failed check is a
non-zero exit. It starts no child process (a chip belongs to one
process). The last line of standard output is the result::

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

The legs are plain functions with their sizes as keyword arguments so
that ``tests/test_chip_smoke.py`` can rehearse the control flow at tiny
sizes on the CPU mesh; ``main`` takes no size and reads no environment
variable that could let it pass without a chip.
"""

from __future__ import annotations

import dataclasses
import gc
import json
import os
import sys
import threading
import time

import numpy as np

# -- tolerances, each with its reason --------------------------------------

# Leg A: max|C - C_ref| / max|C_ref|, decoded product against an
# on-device float32 matmul, both at Precision.HIGHEST, per unit of the
# decode's amplification. A systematic shard is the reference's own rows
# (measured error 0.0 on the v5e); a parity worker multiplies a coded
# block, so its shard differs from the coded sum of exact blocks by a
# float32 product's rounding over K=8192 terms, and the k x k decode
# scales that by the largest absolute row sum of inv(G_S), which depends
# on which k arrived: 1 for the six systematic shards, 1.7 for
# [0,1,2,3,4,6], 8.6 for [0,1,2,3,6,7], 246 for the worst of the 28
# subsets. Measured on the v5e: 1.3e-6 per unit. A worker or a decode
# that fell back to single-pass bfloat16 shows 3e-3 at amplification 1.
POOL_UNIT_TOL = 5e-6

# Leg B: flash kernel against the materializing reference attention on
# bfloat16 inputs, max abs error over max|reference|. Both round their
# output to bfloat16 (8 significant bits, 2^-8 = 4e-3 relative) and
# accumulate in float32 in different orders: measured 4.5e-3 forward and
# 5.2e-3 backward on the v5e at (2, 2048, 8, 128). A wrong mask or a
# dropped block is an error of order 1.
FLASH_FWD_TOL = 2e-2
FLASH_BWD_TOL = 2e-2

# Leg B: first-step loss of the sharded flash program against the dense
# oracle (``forward_dense`` with the reference attention), relative. The
# loss is a float32 mean over batch x sequence tokens of a bfloat16
# forward; the two programs differ only in the rounding of the attention
# output, which the mean averages down: measured 3.6e-7 on the v5e, a
# few float32 steps at 10.6. Targets are random, so even a wrong
# attention moves this loss only by about 5e-4 (a logit's spread over
# the root of the token count); the kernel check above is the sharp one.
LOSS_ORACLE_REL_TOL = 1e-5

# Leg B, four chips: first loss on the (dp, sp, tp) mesh against the
# one-chip loss, relative. Same parameters and batch; tp splits each
# projection's contraction in two and sums the partial products, sp
# splits the token mean: measured 1.9e-6 on four v5e chips, a couple of
# float32 steps at 10.6.
LOSS_MESH_REL_TOL = 2e-5

# Leg C: how far below the float32 reference's largest logit a token the
# bfloat16/int8 programs emitted may sit. Logits here have a standard
# deviation near 0.64 (unit-variance activations against a tied
# embedding of scale 0.02), so the winner of 32768 sits near 2.6, where
# bfloat16 resolves 2^-6 = 0.016; the int8 cache adds about 1/127 of
# each attended value. Two tokens closer than a few hundredths are a
# toss-up between an S=8 and a B=1 program (measured on the v5e: worst
# gap 0.013 over 288 tokens, one stream of twelve parting from the
# oracle at a gap of 0.013); a wrong program picks a token a couple of
# units down.
LOGIT_GAP_TOL = 0.1


# -- helpers ---------------------------------------------------------------


class _CompileMeter:
    """Seconds spent in the backend compiler (cache look-ups included)
    and persistent-cache requests and hits, read from JAX's own
    monitoring events."""

    _BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
    _CACHE_REQUEST = "/jax/compilation_cache/compile_requests_use_cache"
    _CACHE_HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        from jax import monitoring

        self._lock = threading.Lock()  # pool worker threads compile too
        self.compile_s = 0.0
        self.requests = 0
        self.hits = 0
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **_kw) -> None:
        if event == self._BACKEND_COMPILE:
            with self._lock:
                self.compile_s += secs

    def _event(self, event: str, **_kw) -> None:
        with self._lock:
            if event == self._CACHE_REQUEST:
                self.requests += 1
            elif event == self._CACHE_HIT:
                self.hits += 1

    def read(self) -> tuple[float, int, int]:
        with self._lock:
            return self.compile_s, self.requests, self.hits


def _check(ok, detail=None) -> None:
    """A failed check is a failed smoke. Not ``assert``, which ``-O``
    removes."""
    if not ok:
        raise AssertionError(detail)


def _mosaic_calls(lowered_text: str, what: str) -> int:
    """Mosaic custom calls in a lowered program. On the chip there must
    be at least one (the Pallas kernel was compiled, not interpreted and
    not replaced by another path); on the CPU mesh the kernels are
    interpreted and there is none."""
    import jax

    n = lowered_text.count("tpu_custom_call")
    if jax.devices()[0].platform == "tpu":
        _check(
            n > 0,
            f"{what}: no Mosaic tpu_custom_call in the lowered program; "
            "the Pallas kernel did not run compiled",
        )
    return n


def _on(x, device) -> bool:
    return x.devices() == {device}


def release_device_memory() -> None:
    """Drop compiled programs and the buffers they pin between legs."""
    import jax

    from mpistragglers_jl_tpu.models import clear_cached_programs

    clear_cached_programs()
    jax.clear_caches()
    gc.collect()


def _cache_entries(cache_dir: str) -> int:
    return len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0


# -- Leg A: the pool -------------------------------------------------------


def _pad_rows(A: np.ndarray, k: int) -> np.ndarray:
    """Zero-pad the rows to a multiple of k (8192 -> 8196 for k=6); the
    decoded product is sliced back."""
    m_pad = -(-A.shape[0] // k) * k
    if m_pad == A.shape[0]:
        return A
    out = np.zeros((m_pad,) + A.shape[1:], A.dtype)
    out[: A.shape[0]] = A
    return out


def _decode_tol(code, pool, k: int) -> float:
    """Tolerance for a decode from the pool's first k fresh shards (the
    ones ``result_device`` / ``decode_from_pool`` take)."""
    idx = pool.fresh_indices()[:k]
    inv = np.linalg.inv(np.asarray(code.G, np.float64)[idx])
    return POOL_UNIT_TOL * float(np.abs(inv).sum(axis=1).max())


def _straggler_delay(straggler: int, delay_s: float):
    return lambda i, epoch: delay_s if i == straggler else 0.0


def _coded_gemm_epochs(A, B_dev, C_ref, ref_scale, devices, *, n, k,
                       epochs, straggler, delay_s) -> dict:
    """``epochs`` checked epochs (after one that compiles) of an (n, k)
    ``CodedGemm`` with the constructor's default dispatch: one program
    per worker, arrival when the device has finished."""
    import jax.numpy as jnp

    from mpistragglers_jl_tpu import AsyncPool, asyncmap, waitall
    from mpistragglers_jl_tpu.ops import CodedGemm

    m = A.shape[0]
    cg = CodedGemm(
        _pad_rows(A, k), n, k, devices=devices,
        delay_fn=_straggler_delay(straggler, delay_s),
    )
    pool = AsyncPool(n)
    nd = len(devices)
    fresh_at_return, errs = [], []
    try:
        # the first epoch compiles in the worker threads, so arrival
        # order there says nothing about the straggler
        asyncmap(pool, B_dev, cg.backend, nwait=k)
        cg.result_device(pool).block_until_ready()
        waitall(pool, cg.backend)
        for _ in range(epochs):
            repochs = asyncmap(pool, B_dev, cg.backend, nwait=k)
            fresh = int((repochs == pool.epoch).sum())
            _check(fresh >= k, (fresh, repochs))
            _check(
                repochs[straggler] < pool.epoch,
                f"worker {straggler} sleeps {delay_s} s before every "
                f"dispatch and still arrived among the first {k}",
            )
            C = cg.result_device(pool)[:m]
            err = float(jnp.max(jnp.abs(C - C_ref))) / ref_scale
            tol = _decode_tol(cg.code, pool, k)
            _check(err <= tol, (err, tol))
            waitall(pool, cg.backend)
            _check(not pool.active.any())
            fresh_at_return.append(fresh)
            errs.append((err, tol))
        for i in range(n):
            want = devices[i % nd]
            _check(_on(cg.blocks[i], want), (i, cg.blocks[i].devices()))
            _check(
                _on(pool.results[i], want), (i, pool.results[i].devices())
            )
        _check(_on(C, devices[0]))
    finally:
        cg.backend.shutdown()
    return {
        "code": [n, k],
        "epochs": epochs,
        "fresh_at_return": fresh_at_return,
        "straggler": straggler,
        "decode_rel_err_and_tol": max(errs),
        "worker_devices": len({devices[i % nd] for i in range(n)}),
    }


def _pool_mesh_epoch(A, B_dev, C_ref_host, ref_scale, devices, *, k,
                     straggler, delay_s) -> dict:
    """One ``PoolMeshCodedGemm`` epoch, one worker per device: the pool's
    device-resident results are adopted where they sit and decoded by
    the masked ``psum_scatter`` over the mesh."""
    from mpistragglers_jl_tpu import AsyncPool, waitall
    from mpistragglers_jl_tpu.parallel import PoolMeshCodedGemm, make_mesh

    n = len(devices)
    m = A.shape[0]
    fg = PoolMeshCodedGemm(
        _pad_rows(A, k), make_mesh(n, devices=devices), k,
        delay_fn=_straggler_delay(straggler, delay_s),
    )
    pool = AsyncPool(n)
    try:
        fg.epoch(pool, B_dev).block_until_ready()  # compiles
        waitall(pool, fg.backend)
        decoded = fg.epoch(pool, B_dev)
        fresh = int((pool.repochs == pool.epoch).sum())
        _check(fresh >= k, (fresh, pool.repochs))
        _check(pool.repochs[straggler] < pool.epoch)
        shard_devices = {s.device for s in decoded.addressable_shards}
        _check(shard_devices == set(devices), shard_devices)
        C = fg.full(decoded)[:m]
        err = float(np.max(np.abs(C - C_ref_host))) / ref_scale
        tol = _decode_tol(fg.code, pool, k)
        _check(err <= tol, (err, tol))
        waitall(pool, fg.backend)
    finally:
        fg.shutdown()
    return {
        "code": [n, k],
        "fresh_at_return": fresh,
        "decode_rel_err_and_tol": (err, tol),
        "decoded_shard_devices": len(shard_devices),
    }


def leg_pool(devices, *, m=8192, kdim=8192, ncols=8192, n=8, k=6,
             epochs=3, delay_s=0.75) -> dict:
    import jax
    import jax.numpy as jnp

    rng = np.random.default_rng(0)
    A = rng.standard_normal((m, kdim)).astype(np.float32)
    B = rng.standard_normal((kdim, ncols)).astype(np.float32)
    B_dev = jax.device_put(B, devices[0])
    C_ref = jax.jit(
        lambda a, b: jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)
    )(jax.device_put(A, devices[0]), B_dev)
    ref_scale = float(jnp.max(jnp.abs(C_ref)))
    run = dict(epochs=epochs, delay_s=delay_s)

    out = {
        "shape": [m, kdim, ncols],
        # by design the k winning shards are gathered onto devices[0]
        # and solved there (ops/coded_gemm.py result_device)
        "decode_device": str(devices[0]),
        "coded_gemm": _coded_gemm_epochs(
            A, B_dev, C_ref, ref_scale, devices,
            n=n, k=k, straggler=n - 3, **run,
        ),
    }
    if len(devices) >= 4:
        four = list(devices[:4])
        _check(
            out["coded_gemm"]["worker_devices"] == min(n, len(devices))
        )
        out["one_worker_per_chip"] = _coded_gemm_epochs(
            A, B_dev, C_ref, ref_scale, four,
            n=4, k=3, straggler=2, **run,
        )
        _check(out["one_worker_per_chip"]["worker_devices"] == 4)
        out["pool_mesh"] = _pool_mesh_epoch(
            A, B_dev, np.asarray(C_ref), ref_scale, four,
            k=3, straggler=2, delay_s=delay_s,
        )
    return out


# -- Leg B: the trainer ----------------------------------------------------


def _flash_vs_reference(device, *, heads, head_dim, seq, batch=2) -> dict:
    """Both flash kernels (forward, and the backward pair through the
    custom VJP) against the materializing reference, at the flagship's
    head shape."""
    import jax
    import jax.numpy as jnp

    from mpistragglers_jl_tpu.ops.flash_attention import flash_attention
    from mpistragglers_jl_tpu.parallel.ring_attention import (
        reference_attention,
    )

    shape = (batch, seq, heads, head_dim)
    keys = jax.random.split(jax.random.key(0), 4)
    q, k, v, w = (
        jax.device_put(jax.random.normal(kk, shape, jnp.float32), device)
        for kk in keys
    )
    q, k, v = (x.astype(jnp.bfloat16) for x in (q, k, v))

    def loss(attn):
        return lambda q, k, v: jnp.sum(
            attn(q, k, v, causal=True).astype(jnp.float32) * w
        )

    def rel_err(a, b):
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        return float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b)))

    fwd = rel_err(
        jax.jit(lambda q, k, v: flash_attention(q, k, v, causal=True))(
            q, k, v),
        jax.jit(lambda q, k, v: reference_attention(q, k, v, causal=True))(
            q, k, v),
    )
    _check(fwd <= FLASH_FWD_TOL, (fwd, FLASH_FWD_TOL))
    g_flash = jax.jit(jax.grad(loss(flash_attention), argnums=(0, 1, 2)))
    g_ref = jax.jit(jax.grad(loss(reference_attention), argnums=(0, 1, 2)))
    bwd = max(
        rel_err(a, b) for a, b in zip(g_flash(q, k, v), g_ref(q, k, v))
    )
    _check(bwd <= FLASH_BWD_TOL, (bwd, FLASH_BWD_TOL))
    return {"shape": list(shape), "fwd_rel_err": fwd, "bwd_rel_err": bwd}


def _train(cfg, devices, mesh_shape, *, batch, seq, steps, lr,
           oracle: bool) -> dict:
    """``steps`` donated SGD steps of the sharded flash program on a
    ("dp", "sp", "tp") mesh of ``mesh_shape`` over ``devices``."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding

    from mpistragglers_jl_tpu.models.transformer import (
        data_spec,
        forward_dense,
        init_params,
        make_train_step,
        shard_params,
    )

    mesh = Mesh(
        np.asarray(devices).reshape(mesh_shape), ("dp", "sp", "tp")
    )
    params = shard_params(init_params(cfg, seed=0), cfg, mesh)
    tokens = np.random.default_rng(0).integers(
        0, cfg.vocab, (batch, seq + 1), dtype=np.int32
    )
    sharding = NamedSharding(mesh, data_spec(cfg))
    inp = jax.device_put(tokens[:, :-1], sharding)
    tgt = jax.device_put(tokens[:, 1:], sharding)
    step = make_train_step(cfg, mesh, lr=lr, donate=True)

    out = {"mesh": dict(zip(mesh.axis_names, mesh_shape))}
    out["mosaic_calls"] = _mosaic_calls(
        step.lower(params, inp, tgt).as_text(), "train step"
    )
    if oracle:
        # before the first step donates the initial parameters
        cfg_ref = dataclasses.replace(cfg, attn_impl="reference")

        @jax.jit
        def oracle_loss(params, inp, tgt):
            logits = forward_dense(params, inp, cfg_ref)
            logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
            return -jnp.take_along_axis(
                logp, tgt[..., None], axis=-1
            ).mean()

        out["oracle_loss"] = float(oracle_loss(params, inp, tgt))

    losses = []
    for _ in range(steps):
        params, loss = step(params, inp, tgt)
        losses.append(float(loss))
    _check(all(np.isfinite(losses)), losses)
    _check(
        all(b < a for a, b in zip(losses, losses[1:])),
        f"losses not falling on a repeated batch: {losses}",
    )
    leaf = jax.tree.leaves(params)[0]
    _check(leaf.sharding.device_set == set(devices), leaf.sharding)
    out["losses"] = losses
    if oracle:
        rel = abs(losses[0] - out["oracle_loss"]) / abs(out["oracle_loss"])
        _check(rel <= LOSS_ORACLE_REL_TOL, (rel, LOSS_ORACLE_REL_TOL))
        out["loss_vs_oracle_rel_err"] = rel
    return out


def leg_trainer(devices, *, vocab=32768, d_model=1024, n_heads=8,
                n_layers=8, d_ff=4096, batch=8, seq=2048, steps=3,
                lr=1e-2, mesh_shape=(1, 2, 2)) -> dict:
    import jax.numpy as jnp

    from mpistragglers_jl_tpu.models.transformer import TransformerConfig

    cfg = TransformerConfig(
        vocab=vocab, d_model=d_model, n_heads=n_heads, n_layers=n_layers,
        d_ff=d_ff, attn="ulysses", attn_impl="flash", dtype=jnp.bfloat16,
    )
    run = dict(batch=batch, seq=seq, steps=steps, lr=lr)
    out = {
        "flash_kernel": _flash_vs_reference(
            devices[0], heads=n_heads, head_dim=cfg.head_dim, seq=seq
        ),
        "one_chip": _train(
            cfg, [devices[0]], (1, 1, 1), oracle=True, **run
        ),
    }
    if len(devices) >= 4:
        release_device_memory()
        out["four_chips"] = _train(
            cfg, list(devices[:4]), mesh_shape, oracle=False, **run
        )
        a = out["one_chip"]["losses"][0]
        b = out["four_chips"]["losses"][0]
        rel = abs(a - b) / abs(a)
        _check(rel <= LOSS_MESH_REL_TOL, (rel, LOSS_MESH_REL_TOL))
        out["four_vs_one_chip_loss_rel_err"] = rel
    return out


# -- Leg C: the server -----------------------------------------------------


def leg_server(*, vocab=32768, d_model=1024, n_heads=8, n_kv_heads=2,
               n_layers=8, d_ff=4096, window=1024, slots=8,
               page_tokens=64, n_inner=8, prompt_chunk=256,
               max_prompt=1024, prompt_lens=(40, 150, 300, 600),
               n_requests=12, max_new=24) -> dict:
    """One replica on the default device (``jax.devices()[0]``)."""
    import jax
    import jax.numpy as jnp

    from mpistragglers_jl_tpu.models.decode import generate_ring_dense
    from mpistragglers_jl_tpu.models.serving import ServingScheduler
    from mpistragglers_jl_tpu.models.transformer import (
        TransformerConfig,
        forward_dense,
        init_params,
    )

    _check(n_requests > slots and max(prompt_lens) > prompt_chunk)
    cfg = TransformerConfig(
        vocab=vocab, d_model=d_model, n_heads=n_heads,
        n_kv_heads=n_kv_heads, n_layers=n_layers, d_ff=d_ff,
        attn="ulysses", attn_impl="flash", dtype=jnp.bfloat16,
        attn_window=window,
    )
    params = init_params(cfg, seed=0)
    sched = ServingScheduler(
        params, cfg, slots=slots, n_inner=n_inner, quantize_kv=True,
        page_tokens=page_tokens, prompt_chunk=prompt_chunk,
        max_prompt=max_prompt,
    )
    _check(sched.use_kernel, "the int8 tick did not route the kernel")
    mosaic = _mosaic_calls(sched.lower_tick().as_text(), "serving tick")

    rng = np.random.default_rng(0)
    prompts = [
        rng.integers(
            0, vocab, prompt_lens[i % len(prompt_lens)], dtype=np.int32
        )
        for i in range(n_requests)
    ]
    reqs = [sched.submit(p, max_new) for p in prompts]
    sched.run(max_ticks=50 * n_requests)
    for r in reqs:
        _check(r.finished and r.reason == "length", (r.id, r.reason))
        _check(len(r.tokens) == max_new, (r.id, len(r.tokens)))
        _check(all(0 <= t < vocab for t in r.tokens))
    # more requests than slots all finished, and one was admitted after
    # another had retired: a slot served two requests
    _check(
        max(r.admitted_tick for r in reqs)
        > min(r.retired_tick for r in reqs)
    )

    # float32 reference: the training forward with the materializing
    # attention, teacher-forced on each emitted stream. One padded
    # length, one compile; causal attention makes the padding inert.
    cfg32 = dataclasses.replace(
        cfg, dtype=jnp.float32, attn_impl="reference"
    )
    params32 = jax.tree.map(lambda x: x.astype(jnp.float32), params)
    Lpad = max(prompt_lens) + max_new
    ref_logits = jax.jit(lambda p, t: forward_dense(p, t, cfg32)[0])

    equal, worst_gap, parted = 0, 0.0, []
    for prompt, r in zip(prompts, reqs):
        Tp = prompt.size
        oracle = np.asarray(
            generate_ring_dense(
                params, jnp.asarray(prompt)[None], max_new, cfg,
                quantize_kv=True,
            )
        )[0]
        mine = np.asarray(r.tokens)
        seq = np.zeros((1, Lpad), np.int32)
        seq[0, :Tp] = prompt
        seq[0, Tp:Tp + max_new] = mine
        # row j predicts emitted token j from prompt + tokens[:j]
        lg = np.asarray(
            ref_logits(params32, seq)[Tp - 1:Tp - 1 + max_new]
        )
        gaps = lg.max(axis=-1) - lg[np.arange(max_new), mine]
        worst_gap = max(worst_gap, float(gaps.max()))
        _check(gaps.max() <= LOGIT_GAP_TOL, (r.id, gaps.max()))
        if (mine == oracle).all():
            equal += 1
            continue
        # the streams part at j; up to there the prefixes agree, so the
        # same reference row judges the oracle's choice too
        j = int(np.argmax(mine != oracle))
        gap_oracle = float(lg[j].max() - lg[j, oracle[j]])
        _check(gap_oracle <= LOGIT_GAP_TOL, (r.id, j, gap_oracle))
        parted.append({
            "request": r.id, "at": j, "gap_scheduler": float(gaps[j]),
            "gap_oracle": gap_oracle,
        })
    return {
        "requests": n_requests,
        "slots": slots,
        "ticks": sched.tick_count,
        "tokens_each": max_new,
        "mosaic_calls": mosaic,
        "streams_equal_to_oracle": equal,
        "streams_parted": parted,
        "worst_logit_gap": worst_gap,
        "replicas": 1,
    }


# -- entry -----------------------------------------------------------------


def main() -> int:
    t_start = time.perf_counter()
    import importlib.metadata as metadata

    import jax

    devices = jax.devices()
    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }
    versions = {
        d: metadata.version(d) for d in ("jax", "jaxlib", "libtpu")
    }
    print(f"platform: {device['platform']}  device_kind: {device['kind']}"
          f"  devices: {device['count']}  {versions}", flush=True)
    if device["platform"] != "tpu":
        print(
            "chip_smoke: needs a TPU, but jax.devices()[0].platform is "
            f"{device['platform']!r}", file=sys.stderr,
        )
        return 1

    from mpistragglers_jl_tpu.utils.compile_cache import wire_compile_cache

    cache_dir = wire_compile_cache(min_compile_secs=0.0)
    entries_at_start = _cache_entries(cache_dir)
    print(f"compile cache: {cache_dir}  entries at start: "
          f"{entries_at_start}", flush=True)
    meter = _CompileMeter()

    legs = (
        ("A pool", lambda: leg_pool(devices)),
        ("B trainer", lambda: leg_trainer(devices)),
        ("C server", leg_server),
    )
    for name, leg in legs:
        t0 = time.perf_counter()
        c0, r0, h0 = meter.read()
        result = leg()
        c1, r1, h1 = meter.read()
        result.update(
            wall_s=round(time.perf_counter() - t0, 1),
            compile_s=round(c1 - c0, 1),
            cache_requests=r1 - r0,
            cache_hits=h1 - h0,
        )
        print(f"leg {name}: {json.dumps(result)}", flush=True)
        release_device_memory()

    print(f"compile cache: {cache_dir}  entries at end: "
          f"{_cache_entries(cache_dir)} (start {entries_at_start})  "
          f"total wall {time.perf_counter() - t_start:.1f} s", flush=True)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
