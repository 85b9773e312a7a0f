"""In-kernel attribution of the int8 decode-attention kernel.

VERDICT r4 item 2: the contiguous-layout kernel reads the theoretical
minimum bytes yet loses to the bf16 einsum path — prove where the
residual lives. Each variant strips one phase while keeping the SAME
grid, block specs, and DMA pattern, so differences attribute cleanly:

  dma      load K/V blocks, single f32 row-sum — the pure streaming
           floor of this grid/blocking (no dots, no softmax)
  dot      + the per-head MXU score dot (no scales, no softmax: max)
  dequant  + the rank-1 scale corrections
  full     the shipped kernel (online softmax + PV accumulate)

Against them: the bf16-einsum decode step cost and the int8-einsum
(XLA-materialized dequant) cost at the same shape, plus the byte model.

All timings CHAIN ``inner`` data-dependent calls inside one jit (the
output feeds the next call's query) — the in-scan shape, so the
per-call number carries the same launch/carry boundary cost the
generation scan pays, amortized over the batch rows exactly as the
decode scan amortizes it.

Run: ``PYTHONPATH=. python benchmarks/decode_kernel_attrib.py``
— prints the B=1 flagship attribution, then the BATCHED sweep
(B in {1, 4, 8}, the serving regime: the r6 routing work makes batch
the regime where the kernel must land >= 1.0x bf16 in-scan; the AUTO
gate in models/decode.py routes kernel-at-batch from exactly these
numbers).
"""

from __future__ import annotations

import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def main(B=1, L=16384, H=8, Hkv=2, D=128, reps=60, bk=8192,
         variants=True):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from mpistragglers_jl_tpu.ops.decode_attention import (
        _LANE,
        _NEG,
        _SUB,
        quantized_decode_attention,
    )
    from mpistragglers_jl_tpu.ops.flash_attention import _sds

    dev = jax.devices()[0]
    rng = np.random.default_rng(0)
    g = H // Hkv
    q = jax.device_put(
        jnp.asarray(rng.standard_normal((B, 1, H, D)), jnp.bfloat16), dev
    )
    cache = {
        "k": jax.device_put(jnp.asarray(
            rng.integers(-127, 128, (B, L, Hkv, D)), jnp.int8), dev),
        "v": jax.device_put(jnp.asarray(
            rng.integers(-127, 128, (B, L, Hkv, D)), jnp.int8), dev),
        "k_s": jax.device_put(jnp.asarray(
            rng.random((B, L, Hkv)) * 0.01, jnp.float32), dev),
        "v_s": jax.device_put(jnp.asarray(
            rng.random((B, L, Hkv)) * 0.01, jnp.float32), dev),
    }
    cache_bf = {
        "k": (cache["k"].astype(jnp.bfloat16)
              * cache["k_s"][..., None].astype(jnp.bfloat16)),
        "v": (cache["v"].astype(jnp.bfloat16)
              * cache["v_s"][..., None].astype(jnp.bfloat16)),
    }
    pos = jnp.int32(L - 1)
    scale = D ** -0.5

    tiny = jax.device_put(np.ones((8,), np.float32), dev)
    fence = jax.jit(jnp.sum)
    float(fence(tiny))
    rtt = min(
        (lambda t0: (float(fence(tiny)), time.perf_counter() - t0)[1])(
            time.perf_counter()
        )
        for _ in range(5)
    )

    # CHAINED timing: `inner` data-dependent invocations inside ONE
    # jitted program (the output feeds the next call's query), so the
    # per-call number is device time — a per-call dispatch loop would
    # measure the host's enqueue cost instead (the r4 slope lesson; a
    # first draft of this file measured exactly that).
    inner = 24

    def timed(fn_one, q0, *args):
        @jax.jit
        def chain(q0, *args):
            o = q0
            for _ in range(inner):
                o = fn_one(o, *args).astype(q0.dtype).reshape(q0.shape)
            return o

        out = chain(q0, *args)
        float(jnp.sum(out.astype(jnp.float32)))
        best = None
        for _ in range(3):
            t0 = time.perf_counter()
            out = chain(q0, *args)
            float(jnp.sum(out.astype(jnp.float32)))
            dt = (time.perf_counter() - t0 - rtt) / inner
            best = dt if best is None else min(best, dt)
        return best * 1e3

    # ---- einsum references ------------------------------------------
    from mpistragglers_jl_tpu.models.decode import _cached_attention

    ein_bf16 = timed(
        lambda q, c: _cached_attention(q, c, pos[None], scale,
                                       use_kernel=False),
        q, cache_bf,
    )
    ein_int8 = timed(
        lambda q, c: _cached_attention(q, c, pos[None], scale,
                                       use_kernel=False),
        q, cache,
    )
    full = timed(
        lambda q, c: quantized_decode_attention(q, c, pos, scale,
                                                block_k=bk),
        q, cache,
    )

    # ---- stripped variants (same grid/specs/DMA, same block pick as
    # the shipped kernel's VMEM model) ---------------------------------
    from mpistragglers_jl_tpu.ops.decode_attention import _pick_block_128

    bk_eff = _pick_block_128(L, bk, Hkv, D)
    nk = L // bk_eff

    def variant(mode):
        def kern(pos_ref, q_ref, k_ref, ks_ref, v_ref, vs_ref, o_ref,
                 acc, m_sc, l_sc):
            j = pl.program_id(1)

            @pl.when(j == 0)
            def _init():
                acc[:] = jnp.zeros_like(acc)
                m_sc[:] = jnp.full_like(m_sc, _NEG)
                l_sc[:] = jnp.zeros_like(l_sc)

            kblk = k_ref[0]
            vblk = v_ref[0]
            if mode == "dma":
                # touch every byte, minimal compute: one f32 accumulate
                acc[:1, :1] += (
                    kblk[:1, :1].astype(jnp.float32)
                    + vblk[:1, :1].astype(jnp.float32)
                )
            else:
                ksb = ks_ref[0].astype(jnp.float32)
                vsb = vs_ref[0].astype(jnp.float32)
                for h in range(Hkv):
                    rows = slice(h * _SUB, (h + 1) * _SUB)
                    qh = q_ref[0][rows]
                    kb = kblk[:, h * D:(h + 1) * D].astype(qh.dtype)
                    s = jax.lax.dot_general(
                        qh, kb, (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32,
                    ) * scale
                    if mode != "dot":
                        s = s * ksb[:, h][None, :]
                    if mode == "full_nosm":
                        vb = vblk[:, h * D:(h + 1) * D].astype(
                            jnp.float32)
                        pv = s * vsb[:, h][None, :]
                        acc[rows] += jax.lax.dot_general(
                            pv, vb, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32,
                        )
                    else:
                        # dot / dequant: reduce scores only
                        acc[rows, :1] += s.max(axis=-1, keepdims=True)

            @pl.when(j == nk - 1)
            def _fin():
                o_ref[0] = acc[:].astype(o_ref.dtype)

        rows = Hkv * _SUB
        q3 = jnp.pad(
            q.reshape(B, Hkv, g, D), ((0, 0), (0, 0), (0, _SUB - g),
                                      (0, 0))
        ).reshape(B, rows, D)
        kf = cache["k"].reshape(B, L, Hkv * D)
        vf = cache["v"].reshape(B, L, Hkv * D)

        def run(q3, kf, ks, vf, vs):
            return pl.pallas_call(
                kern,
                grid=(B, nk),
                in_specs=[
                    pl.BlockSpec(memory_space=pltpu.SMEM),
                    pl.BlockSpec((1, rows, D), lambda b, j: (b, 0, 0)),
                    pl.BlockSpec((1, bk_eff, Hkv * D),
                                 lambda b, j: (b, j, 0)),
                    pl.BlockSpec((1, bk_eff, Hkv),
                                 lambda b, j: (b, j, 0)),
                    pl.BlockSpec((1, bk_eff, Hkv * D),
                                 lambda b, j: (b, j, 0)),
                    pl.BlockSpec((1, bk_eff, Hkv),
                                 lambda b, j: (b, j, 0)),
                ],
                out_specs=pl.BlockSpec((1, rows, D),
                                       lambda b, j: (b, 0, 0)),
                out_shape=_sds((B, rows, D), jnp.float32, q3),
                scratch_shapes=[
                    pltpu.VMEM((rows, D), jnp.float32),
                    pltpu.VMEM((rows, _LANE), jnp.float32),
                    pltpu.VMEM((rows, _LANE), jnp.float32),
                ],
                compiler_params=pltpu.CompilerParams(
                    dimension_semantics=("parallel", "arbitrary")
                ),
            )(jnp.full((B,), L - 1, jnp.int32), q3, kf, cache["k_s"],
              vf, cache["v_s"])

        def one(q3c, kf, ks, vf, vs):
            return run(q3c, kf, ks, vf, vs)

        return timed(one, q3, kf, cache["k_s"], vf, cache["v_s"])

    out = {
        "shape": f"B={B} L={L} H={H} Hkv={Hkv} D={D} bk={bk_eff} nk={nk}",
        "fence_rtt_ms": round(rtt * 1e3, 2),
        "int8_bytes_mib": round(B * 2 * L * Hkv * D / 2**20, 1),
        "bf16_bytes_mib": round(B * 2 * L * Hkv * D * 2 / 2**20, 1),
        "einsum_bf16_ms": round(ein_bf16, 4),
        "einsum_int8_ms": round(ein_int8, 4),
        "kernel_full_ms": round(full, 4),
        # the acceptance ratio: batched in-scan int8 kernel vs the
        # bf16 einsum step, same chained-call discipline
        "kernel_vs_bf16": round(ein_bf16 / full, 3),
        "einsum_int8_vs_bf16": round(ein_bf16 / ein_int8, 3),
    }
    if variants:
        out.update({
            "kernel_dma_ms": round(variant("dma"), 4),
            "kernel_dot_ms": round(variant("dot"), 4),
            "kernel_dequant_ms": round(variant("dequant"), 4),
            "kernel_nosoftmax_ms": round(variant("full_nosm"), 4),
        })
    print(json.dumps(out))
    return out


if __name__ == "__main__":
    # flagship B=1 attribution (stripped variants included), then the
    # batched sweep — the serving regime the AUTO routing gate serves
    main()
    for B in (4, 8):
        main(B=B, variants=False)
