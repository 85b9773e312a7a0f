"""GC003 good fixture: every allowance the rule grants — static
shape/dtype/`is None` branching inside traced code, and free use of
host clocks OUTSIDE it."""

import time

import jax
import jax.numpy as jnp


@jax.jit
def clean(x, eos_id=None):
    if eos_id is None:  # static config test: allowed
        eos_id = 0
    if x.shape[0] > 4:  # shape is a trace-time constant: allowed
        x = x[:4]
    n = len(x.shape)  # len(): allowed
    return jnp.where(x > 0, x, eos_id) * n


def host_step(xs):
    t0 = time.perf_counter()  # not traced: allowed

    def body(carry, x):
        return carry + jnp.square(x), x.dtype.type(0)

    out = jax.lax.scan(body, jnp.zeros(()), xs)
    return out, time.perf_counter() - t0


def fused_window(xs, mesh, payload=None):
    # shard_map-wrapped scan using only the static allowances: shape
    # tests, `is None` config branching, and clocks OUTSIDE the traced
    # region
    t0 = time.perf_counter()  # not traced: allowed

    def window(x):
        if payload is None:  # static config test: allowed
            scale = 1
        else:
            scale = 2
        if x.shape[0] > 4:  # shape is a trace-time constant: allowed
            x = x[:4]

        def body(carry, t):
            return carry + jnp.square(t) * scale, t

        return jax.lax.scan(body, jnp.zeros(()), x)

    f = jax.shard_map(
        window, mesh=mesh, in_specs=None, out_specs=None
    )
    return f(xs), time.perf_counter() - t0


@jax.jit
def closure_static(xs, ref):
    # closed-over enclosing tracer used only behind static accessors
    # inside the nested scan body: allowed
    def body(carry, t):
        if ref.shape[0] > 4:  # shape is a trace-time constant: allowed
            return carry + t, t
        return carry, t

    return jax.lax.scan(body, jnp.zeros(()), xs)
