"""GC003 bad fixture: host effects and tracer leaks inside traced
code. Violation lines pinned by the fixture test; one site carries a
suppression to pin the round-trip."""

import functools
import time

import numpy as np

import jax
import jax.numpy as jnp


@jax.jit
def leaky(x):
    t0 = time.perf_counter()  # GC003 line 16: host clock
    noise = np.random.normal(size=3)  # GC003 line 17: host RNG
    if x > 0:  # GC003 line 18: Python branch on traced arg
        x = x + jnp.asarray(noise)
    return x, t0


@functools.partial(jax.jit, donate_argnums=(0,))
def casty(x):
    return float(x)  # GC003 line 25: concretizes the tracer


def scanner(xs):
    def body(carry, x):
        stamp = time.time()  # GC003 line 30: host clock in scan body
        return carry + x, stamp

    return jax.lax.scan(body, jnp.zeros(()), xs)


@jax.jit
def suppressed(x):
    t = time.time()  # graftcheck: disable=GC003  (pinned round-trip)
    return x, t


def fused_window(xs, mesh):
    # the round-17 device-coordination shape: the whole epoch scan
    # nests inside ONE shard_map-wrapped callable, so leaks both in
    # the wrapped fn and in the scan body it contains must resolve
    # through the shard_map boundary
    def window(x):
        w0 = time.time()  # GC003 line 48: host clock in shard_map'd fn

        def body(carry, t):
            return carry + t.item(), t  # GC003 line 51: .item() in body

        out = jax.lax.scan(body, jnp.zeros(()), x)
        return out, w0

    f = jax.shard_map(
        window, mesh=mesh, in_specs=None, out_specs=None
    )
    return f(xs)


@jax.jit
def closure_branch(xs, lo):
    # the scan body is its own traced region under the _walk_own dedup,
    # but `lo` is the ENCLOSING jit fn's tracer — the branch on it must
    # still be attributed (to the body, once)
    def body(carry, t):
        if lo > 0:  # GC003 line 68: branch on closed-over tracer
            carry = carry + t
        return carry, t

    return jax.lax.scan(body, jnp.zeros(()), xs)
