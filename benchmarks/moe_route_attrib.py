"""Attribute the MoE routing overhead per phase (VERDICT r4 item 6).

`routing_overhead_share` (moe_bench) lumps everything that is not the
expert FFN matmuls. This script times each routing phase of one layer
at the rung shape on the real chip — fwd and fwd+bwd — so the 27%% r4
share is attributed before it is attacked:

  route        _route: f32 router matmul + softmax/argmax + cumsum slots
  table        the (E, C) scatter building the slot table
  dispatch     _gather_dispatch: (T, D) -> (E, C, D)
  ffn          _expert_ffn on dispatched slots (the useful work)
  combine      gate-weight + _scatter_combine back to (T, D)

Run: ``PYTHONPATH=. python benchmarks/moe_route_attrib.py``
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def main(T=8 * 2048, D=1024, F=4096, E=4, cf=1.25, reps=30):
    import jax
    import jax.numpy as jnp

    from mpistragglers_jl_tpu.models import moe as M

    dev = jax.devices()[0]
    rng = np.random.default_rng(0)
    x = jax.device_put(
        jnp.asarray(rng.standard_normal((T, D)), jnp.bfloat16), dev
    )
    mp = jax.device_put(
        M.init_moe_layer(rng, D, F, E, 8, jnp.bfloat16), dev
    )
    C = M._capacity(T, E, cf)

    tiny = jax.device_put(np.ones((8,), np.float32), dev)
    fence = jax.jit(jnp.sum)
    float(fence(tiny))
    rtt = min(
        (lambda t0: (float(fence(tiny)), time.perf_counter() - t0)[1])(
            time.perf_counter()
        )
        for _ in range(5)
    )

    def timed(f, *args, grad=False):
        # the fence is a scalar D2H fetch that data-depends on the
        # output; its rtt is subtracted once per chain
        if grad:
            g = jax.jit(jax.grad(lambda *a: jnp.sum(
                jax.tree.leaves(f(*a))[0].astype(jnp.float32))))
        else:
            g = jax.jit(f)

        def scalar(o):
            return float(
                jnp.sum(jax.tree.leaves(o)[0].astype(jnp.float32))
            )

        out = g(*args)
        scalar(out)
        best = None
        for _ in range(3):
            t0 = time.perf_counter()
            for _ in range(reps):
                out = g(*args)
            scalar(out)
            dt = (time.perf_counter() - t0 - rtt) / reps
            best = dt if best is None else min(best, dt)
        return best * 1e3

    phases = {}

    phases["route_fwd"] = timed(lambda x: M._route(x, mp["wg"]), x)

    def table_fn(x):
        return M.switch_route_indices(x, mp["wg"], C)

    phases["route+table_fwd"] = timed(table_fn, x)

    table, expert, gate, aux = jax.jit(table_fn)(x)

    phases["dispatch_fwd"] = timed(
        lambda x: M._gather_dispatch(x, table), x
    )
    xe = jax.jit(lambda x: M._gather_dispatch(x, table))(x)
    phases["ffn_fwd"] = timed(lambda xe: M._expert_ffn(xe, mp), xe)
    ye = jax.jit(lambda xe: M._expert_ffn(xe, mp))(xe)

    gate_pad = jnp.concatenate([gate, jnp.zeros((1,), gate.dtype)])
    g = gate_pad[table].astype(x.dtype)

    phases["combine_fwd"] = timed(
        lambda ye: M._scatter_combine(ye * g[..., None], table, T), ye
    )

    def whole(x):
        y, aux = M.moe_ffn_dense(x.reshape(1, T, D), mp, cf)
        return y

    phases["layer_fwd"] = timed(whole, x)
    phases["layer_fwd_bwd"] = timed(whole, x, grad=True)

    def dense_mlp(x):
        lp = {
            "w1": mp["we1"][0], "b1": mp["be1"][0],
            "w2": mp["we2"][0], "b2": mp["be2"][0],
        }
        from mpistragglers_jl_tpu.models.transformer import _mlp

        return _mlp(x.reshape(1, T, D), lp)

    phases["dense_mlp_fwd"] = timed(dense_mlp, x)
    phases["dense_mlp_fwd_bwd"] = timed(dense_mlp, x, grad=True)

    out = {
        "shape": f"T={T} D={D} F={F} E={E} C={C}",
        "fence_rtt_ms": round(rtt * 1e3, 2),
        **{k: round(v, 3) for k, v in phases.items()},
    }
    print(json.dumps(out, indent=1))
    return out


if __name__ == "__main__":
    main()
