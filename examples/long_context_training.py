"""Long-context training walkthrough: flash attention + remat + AdamW.

The round-3 long-context stack in one script (the reference has no
model layer at all — SURVEY §2 — so this is framework surface, not
parity): a decoder-only transformer whose attention streams K/V blocks
through VMEM (ops/flash_attention.py), per-layer rematerialization
trading recompute for activation HBM (``TransformerConfig(remat=True)``),
and an optax AdamW step whose optimizer state is sharded exactly like
the params (models/transformer.py ``make_optax_train_step``). The mesh
is (dp, sp, tp): batch over dp, the SEQUENCE over sp (Ulysses
all-to-all — per-device activations are O(L/sp)), heads/FFN over tp.

Run it anywhere:

.. code-block:: console

    # 8-device virtual CPU mesh (what CI uses; tiny shapes)
    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/long_context_training.py

    # one real TPU chip (bigger shapes; pass --seq 16384 for the real thing)
    python examples/long_context_training.py --seq 2048 --d-model 512

On the bench chip the same program trains 32 k-token sequences at
~36 k tokens/s (earlier installation, not repeated on this one) — lengths
where materializing attention cannot even allocate its score matrices.
"""

from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from mpistragglers_jl_tpu.models import (
    TransformerConfig,
    init_params,
    make_optax_train_step,
    shard_params,
)
from mpistragglers_jl_tpu.parallel import make_mesh


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--d-model", type=int, default=64)
    ap.add_argument("--kv-heads", type=int, default=None,
                    help="grouped-query attention: K/V head count "
                    "(default MHA; e.g. 2 shrinks K/V projections and "
                    "the ring/Ulysses K/V traffic by n_heads/kv_heads)")
    ap.add_argument("--layers", type=int, default=2)
    ap.add_argument("--steps", type=int, default=8)
    args = ap.parse_args(argv)
    if args.steps < 2:
        ap.error("--steps must be >= 2 (the loss-decrease check needs "
                 "two points)")

    import optax

    n = len(jax.devices())
    # widest sp the device count and head count allow: sequence
    # parallelism is the long-context axis
    heads = max(4, args.d_model // 64)
    sp = 1
    for cand in (8, 4, 2):
        if n % cand == 0 and heads % cand == 0 and args.seq % cand == 0:
            sp = cand
            break
    dp = 2 if (n // sp) % 2 == 0 and args.batch % 2 == 0 else 1
    tp = n // sp // dp
    mesh = make_mesh((dp, sp, tp), ("dp", "sp", "tp"))
    print(f"mesh: dp={dp} sp={sp} tp={tp} over {n} devices")

    cfg = TransformerConfig(
        vocab=512,
        d_model=args.d_model,
        n_heads=heads,
        n_kv_heads=args.kv_heads,
        n_layers=args.layers,
        d_ff=args.d_model * 4,
        attn="ulysses",
        # compiled flash on TPU, interpret elsewhere — same program
        attn_impl="flash",
        remat=True,  # activation-free backward: HBM ~ O(layers) less
        dtype=jnp.float32 if jax.default_backend() == "cpu"
        else jnp.bfloat16,
    )
    params = shard_params(init_params(cfg, seed=0), cfg, mesh)
    tx = optax.adamw(3e-3)
    step, init_state = make_optax_train_step(cfg, mesh, tx, donate=True)
    opt_state = init_state(params)

    rng = np.random.default_rng(0)
    toks = rng.integers(
        0, cfg.vocab, (args.batch, args.seq + 1), dtype=np.int32
    )
    # slice host-side FIRST: seq+1 is never sp-divisible (sp divides
    # seq by construction), so the (B, seq+1) array cannot be placed
    # with P("dp", "sp") — only the seq-column slices can
    sh = NamedSharding(mesh, P("dp", "sp"))
    inp = jax.device_put(toks[:, :-1], sh)
    tgt = jax.device_put(toks[:, 1:], sh)

    losses = []
    for s in range(args.steps):
        params, opt_state, loss = step(params, opt_state, inp, tgt)
        losses.append(float(loss))
        print(f"step {s}: loss {losses[-1]:.4f}")
    assert losses[-1] < losses[0], losses
    print(
        f"done: seq={args.seq} sp={sp} remat=on adamw "
        f"loss {losses[0]:.3f} -> {losses[-1]:.3f}"
    )


if __name__ == "__main__":
    main()
