"""Seeded weights and tokens, made on the device in one jitted call.

The program's ``init_params`` draws every weight with numpy on the host
and uploads it (a minute for 3B parameters, in every run, and even
under ``jax.eval_shape``). The benchmark makes its own over the same
pytree of shapes, so that set-up pays for no host random numbers; the
plain reference is handed the same arrays.

Initialisation (listed under ``assumed`` in the configurations): every
matrix and the embedding are normal with standard deviation
``1/sqrt(d_model)`` (StarCoder2's ``initializer_range`` 0.018 at 3072);
the two projections that write into the residual stream (``wo``,
``w2``) are divided by ``sqrt(2 * n_layers)``; LayerNorm scales are
one; every bias is zero.
"""

from __future__ import annotations

import math

ZERO_LEAVES = ("ln1_b", "ln2_b", "lnf_b", "b1", "b2")
ONE_LEAVES = ("ln1_s", "ln2_s", "lnf_s")
RESIDUAL_OUT = ("wo", "w2")


def transformer_shapes(*, d_model: int, n_heads: int, kv_heads: int,
                       d_ff: int, n_layers: int, vocab: int, dtype):
    """The pytree of shapes that the program's ``init_params`` returns
    for a dense configuration, written out here because calling it, even
    under ``jax.eval_shape``, draws every weight with numpy on the host
    (a minute for 3B parameters). tests/chipbench/test_reference.py
    holds the two against each other."""
    import jax

    dh = d_model // n_heads
    s = lambda *shape: jax.ShapeDtypeStruct(shape, dtype)
    layer = lambda: {
        "ln1_s": s(d_model), "ln1_b": s(d_model),
        "wq": s(d_model, n_heads, dh), "wk": s(d_model, kv_heads, dh),
        "wv": s(d_model, kv_heads, dh), "wo": s(n_heads, dh, d_model),
        "ln2_s": s(d_model), "ln2_b": s(d_model),
        "w1": s(d_model, d_ff), "b1": s(d_ff),
        "w2": s(d_ff, d_model), "b2": s(d_model),
    }
    return {
        "emb": s(vocab, d_model),
        "layers": [layer() for _ in range(n_layers)],
        "lnf_s": s(d_model), "lnf_b": s(d_model),
    }


def seed_key(seed: int, salt: int = 0):
    """A key from any whole-number seed, also above 2**31."""
    import jax

    seed = int(seed)
    key = jax.random.key(seed & 0x7FFFFFFF)
    key = jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)
    return jax.random.fold_in(key, salt)


def leaf_name(path) -> str:
    last = path[-1]
    for attr in ("key", "idx", "name"):
        if hasattr(last, attr):
            return str(getattr(last, attr))
    return str(last)


def make_params(shapes, seed: int, *, d_model: int, n_layers: int,
                out_shardings=None):
    """``shapes``: the pytree of ``ShapeDtypeStruct`` that the program's
    ``init_params`` would return. One jitted call, on the device."""
    import jax
    import jax.numpy as jnp

    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    sd = 1.0 / math.sqrt(d_model)

    def build(key):
        out = []
        for i, (path, s) in enumerate(leaves):
            name = leaf_name(path)
            if name in ZERO_LEAVES:
                out.append(jnp.zeros(s.shape, s.dtype))
            elif name in ONE_LEAVES:
                out.append(jnp.ones(s.shape, s.dtype))
            else:
                scale = sd
                if name in RESIDUAL_OUT:
                    scale = sd / math.sqrt(2.0 * n_layers)
                x = jax.random.normal(
                    jax.random.fold_in(key, i), s.shape, jnp.float32
                )
                out.append((x * scale).astype(s.dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    fn = jax.jit(build, out_shardings=out_shardings)
    return fn(seed_key(seed, 1))


def make_tokens(seed: int, shape, vocab: int, salt: int = 2):
    import jax
    import jax.numpy as jnp

    return jax.jit(
        lambda k: jax.random.randint(k, shape, 0, vocab, jnp.int32)
    )(seed_key(seed, salt))
