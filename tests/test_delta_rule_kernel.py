"""The chunked gated delta rule as one Pallas kernel
(ops/delta_rule.py), interpreted here: against the token-by-token
recurrence and the plain chunked form at head sizes of 128, through
``gdn_half`` with padding and across calls, and the route that sends
everything else to the plain form. What Mosaic says of it is
tests/test_decode_attention_tpu_compile.py's; what the chip says,
PERF.md's (section 6, PR 40).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mpistragglers_jl_tpu.models import transformer as tr
from mpistragglers_jl_tpu.models.transformer import (
    TransformerConfig,
    init_params,
)
from mpistragglers_jl_tpu.ops.delta_rule import (
    SUBCHUNK as C,
    _heads_per_step,
    chunked_delta_rule,
    delta_rule_viable,
)

D = 128  # the published head size, key and value
HK, HV = 1, 2  # two value heads a key head

# one delta-rule layer at the kernel's widths, everything else tiny
CFG = TransformerConfig(
    vocab=97, d_model=32, n_heads=4, n_kv_heads=2, d_head=16, n_layers=1,
    d_ff=48, norm="rmsnorm", norm_eps=1e-6, ffn="swiglu", tie_head=False,
    layer_mixers=("gdn",), gdn_key_heads=HK, gdn_value_heads=HV,
    gdn_key_dim=D, gdn_value_dim=D, gdn_conv=4, max_context=512,
)
LP = init_params(CFG, seed=3)["layers"][0]


def _operands(T, seed, B=2, Hk=HK, Hv=HV):
    """The conv's rows ``[q | k | v]``, the gates and a state that is not
    zero; decays from nearly none to nearly all."""
    rng = np.random.default_rng(seed)
    f = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)
    qkv, S0 = f(B, T, (2 * Hk + Hv) * D), f(B, Hv, D, D)
    g = -jnp.asarray(rng.random((B, T, Hv)) ** 4 * 3.0, jnp.float32)
    beta = jnp.asarray(rng.random((B, T, Hv)), jnp.float32)
    return qkv, g, beta, S0


def _heads(qkv, Hk=HK, Hv=HV):
    """What ``gdn_half`` hands the plain forms: q and k normed, q
    scaled, both repeated to the value heads."""
    B, T, _ = qkv.shape
    kw = Hk * D
    l2 = lambda a: a * jax.lax.rsqrt((a * a).sum(-1, keepdims=True) + 1e-6)
    q = l2(qkv[..., :kw].reshape(B, T, Hk, D)) * D ** -0.5
    k = l2(qkv[..., kw:2 * kw].reshape(B, T, Hk, D))
    v = qkv[..., 2 * kw:].reshape(B, T, Hv, D)
    return jnp.repeat(q, Hv // Hk, 2), jnp.repeat(k, Hv // Hk, 2), v


def _kernel(qkv, g, beta, S0, c=C, Hk=HK, Hv=HV):
    o, S = chunked_delta_rule(qkv, g, beta, S0, Hk=Hk, Hv=Hv, Dk=D, Dv=D,
                              c=c)
    return o.reshape(o.shape[:2] + (Hv, D)), S


# (rows, sub-chunk): one, two and four sub-chunks of 64, and the
# program's own sub-chunk
SIZES = [(64, 64), (128, 64), (256, 64), (128, C), (256, C)]


@pytest.mark.parametrize("T,c", SIZES)
def test_kernel_equals_the_recurrence(T, c):
    """Across sub-chunk boundaries, from a state that is not zero."""
    qkv, g, beta, S0 = _operands(T, seed=T + c)
    q, k, v = _heads(qkv)
    S, want = S0, []
    for t in range(T):
        o, S = tr._delta_rule_step(q[:, t], k[:, t], v[:, t], g[:, t],
                                   beta[:, t], S)
        want.append(o)
    o, S_kernel = _kernel(qkv, g, beta, S0, c)
    np.testing.assert_allclose(o, jnp.stack(want, 1), atol=2e-5)
    np.testing.assert_allclose(S_kernel, S, atol=2e-5)


@pytest.mark.parametrize("T,c", SIZES)
def test_kernel_equals_the_plain_chunked_form(T, c):
    qkv, g, beta, S0 = _operands(T, seed=T + c + 1)
    want_o, want_S = tr._delta_rule_chunks(*_heads(qkv), g, beta, S0)
    o, S = _kernel(qkv, g, beta, S0, c)
    np.testing.assert_allclose(o, want_o, atol=2e-5)
    np.testing.assert_allclose(S, want_S, atol=2e-5)


@pytest.mark.parametrize("Hk,Hv", [(2, 4), (4, 4), (1, 4), (2, 6)])
def test_a_step_takes_the_value_heads_of_its_key_heads(Hk, Hv):
    """Four value heads a grid step over two key heads and over four;
    two a step where four would start v off a whole block (one key
    head); one a step where neither two nor four are whole groups of
    three or a part of one."""
    assert _heads_per_step(C, Hk, Hv, D, D) == {
        (2, 4): 4, (4, 4): 4, (1, 4): 2, (2, 6): 1}[Hk, Hv]
    qkv, g, beta, S0 = _operands(C, seed=Hk * 10 + Hv, B=1, Hk=Hk, Hv=Hv)
    want_o, want_S = tr._delta_rule_chunks(*_heads(qkv, Hk, Hv), g, beta, S0)
    o, S = _kernel(qkv, g, beta, S0, Hk=Hk, Hv=Hv)
    np.testing.assert_allclose(o, want_o, atol=2e-5)
    np.testing.assert_allclose(S, want_S, atol=2e-5)


def test_keys_that_are_nearly_one_vector_do_not_break_the_inverse():
    """The worst case of a product-form solve: every key of a sub-chunk
    nearly the same, beta near 1 and no decay, where powers of A grow
    like binomials. The doubling forms blocks of the true inverse only
    and stays with the recurrence."""
    rng = np.random.default_rng(9)
    T, B = 128, 1
    qkv, _, _, S0 = _operands(T, seed=9, B=B)
    key = rng.standard_normal((B, 1, HK * D)) + 0.05 * rng.standard_normal(
        (B, T, HK * D))
    qkv = qkv.at[..., HK * D:2 * HK * D].set(jnp.asarray(key, jnp.float32))
    g = jnp.full((B, T, HV), -1e-3, jnp.float32)
    beta = jnp.full((B, T, HV), 0.98, jnp.float32)
    want_o, want_S = tr._delta_rule_chunks(*_heads(qkv), g, beta, S0)
    o, S = _kernel(qkv, g, beta, S0)
    np.testing.assert_allclose(o, want_o, atol=2e-5)
    np.testing.assert_allclose(S, want_S, atol=5e-5)


def _x(T, B, seed):
    return jnp.asarray(
        np.random.default_rng(seed).standard_normal((B, T, CFG.d_model)),
        jnp.float32)


def _state(B, seed):
    """A state that is not zero, S and the conv rows."""
    rng = np.random.default_rng(seed)
    zero = tr.gdn_zero_state(CFG, B)
    return {leaf: jnp.asarray(rng.standard_normal(a.shape), a.dtype)
            for leaf, a in zero.items()}


@pytest.mark.parametrize("count", [0, 70, 128])
def test_padding_leaves_the_state_and_the_real_rows_alone(count):
    """``valid`` a vector with 0, a count inside a sub-chunk and T among
    its entries: each member's real rows and state are what the unpadded
    call on its rows alone gives (a call of 70 rows takes the plain
    form: the two forms agree across the route)."""
    T, valid = 128, [0, 70, 128]
    b = valid.index(count)
    x, state = _x(T, 3, seed=4), _state(3, seed=5)
    got, after = tr.gdn_half(x, LP, state, CFG,
                             valid=jnp.asarray(valid, jnp.int32))
    mine = {leaf: a[b:b + 1] for leaf, a in state.items()}
    if count == 0:
        want_state = mine
    else:
        want, want_state = tr.gdn_half(x[b:b + 1, :count], LP, mine, CFG)
        np.testing.assert_allclose(got[b:b + 1, :count], want, atol=2e-5)
    for leaf in ("S", "conv"):
        np.testing.assert_allclose(after[leaf][b:b + 1], want_state[leaf],
                                   atol=2e-5)


def test_two_calls_on_halves_equal_one_call_on_the_whole():
    x, state = _x(256, 2, seed=6), _state(2, seed=7)
    whole, s_whole = tr.gdn_half(x, LP, state, CFG)
    a, s = tr.gdn_half(x[:, :128], LP, state, CFG)
    b, s_two = tr.gdn_half(x[:, 128:], LP, s, CFG)
    np.testing.assert_allclose(jnp.concatenate([a, b], 1), whole, atol=2e-5)
    for leaf in ("S", "conv"):
        np.testing.assert_allclose(s_two[leaf], s_whole[leaf], atol=2e-5)


# (rows, key heads, value heads, key dim, value dim) -> kernel?
ROUTES = [
    ((256, 16, 32, 128, 128), True),   # the cell's chunk
    ((128, 1, 2, 128, 128), True),     # one sub-chunk
    ((2048, 16, 32, 128, 128), True),  # a dense forward: a head a step
    ((4096, 16, 32, 128, 128), False),  # more rows than VMEM holds
    ((256, 4, 8, 128, 256), True),     # a wider value
    ((64, 2, 4, 8, 8), False),         # the tiny configurations' widths
    ((150, 1, 2, 128, 128), False),    # not whole sub-chunks
    ((1, 16, 32, 128, 128), False),    # one token: the tick's step
    ((64, 1, 2, 128, 128), False),     # short of a sub-chunk
    ((128, 1, 2, 128, 512), False),    # v does not start on a block of Dv
]


@pytest.mark.parametrize("shape,kernel", ROUTES)
def test_the_route_follows_the_shapes(shape, kernel):
    assert delta_rule_viable(*shape) is kernel


@pytest.mark.parametrize("T,widths,kernel", [
    (128, D, True), (150, D, False), (64, D, False), (128, 8, False)])
def test_gdn_half_takes_the_route(monkeypatch, T, widths, kernel):
    """Widths of 8 and a T of 150 go through ``_delta_rule_chunks``, a
    chunk of whole sub-chunks at widths of 128 through the kernel, and
    neither through both."""
    cfg = dataclasses.replace(CFG, gdn_key_dim=widths, gdn_value_dim=widths)
    lp = LP if widths == D else init_params(cfg, seed=3)["layers"][0]
    took = []

    def spy(name):
        real = getattr(tr, name)

        def call(*a, **kw):
            took.append(name)
            return real(*a, **kw)
        return call

    for name in ("chunked_delta_rule", "_delta_rule_chunks"):
        monkeypatch.setattr(tr, name, spy(name))
    x = jnp.asarray(np.random.default_rng(8).standard_normal(
        (1, T, cfg.d_model)), jnp.float32)
    tr.gdn_half(x, lp, tr.gdn_zero_state(cfg, 1), cfg)
    assert took == ["chunked_delta_rule" if kernel else "_delta_rule_chunks"]


def test_what_the_kernel_cannot_take_is_refused_by_name():
    qkv, g, beta, S0 = _operands(C + 32, seed=1)
    with pytest.raises(ValueError, match="plain form"):
        chunked_delta_rule(qkv, g, beta, S0, Hk=HK, Hv=HV, Dk=D, Dv=D, c=C)


# -- the serving programs ----------------------------------------------------

SERVED = dataclasses.replace(CFG, n_layers=2, layer_mixers=("gdn", "attn"),
                             max_context=2 * C + 16)


@pytest.mark.parametrize("model,route", [
    ("kernel_widths", "kernel"), ("tiny_widths", "xla"),
    ("no_state_layer", None)])
def test_the_chunk_span_names_the_form_of_the_delta_rule(monkeypatch, model,
                                                         route):
    """``gdn_rule`` on ``serving.prefill_chunk``: the route a chunk's rows
    take through the delta rule, from the test ``gdn_half`` itself asks;
    absent where no layer keeps state. The served tokens are the dense
    forward's, whose 200 rows take the plain form."""
    from mpistragglers_jl_tpu.models import serving
    from mpistragglers_jl_tpu.models.decode import generate_dense

    cfg = {"kernel_widths": SERVED,
           "tiny_widths": dataclasses.replace(SERVED, gdn_key_dim=8,
                                              gdn_value_dim=8),
           "no_state_layer": dataclasses.replace(
               SERVED, layer_mixers=None)}[model]
    seen = []

    class Spy:
        def __init__(self, name, **args):
            self.name, self.args = name, dict(args)

        def __enter__(self):
            seen.append(self)
            return self

        def __exit__(self, *exc):
            return None

        def set_metadata(self, **args):
            self.args.update(args)

    params = init_params(cfg, seed=2)
    sched = serving.ServingScheduler(
        params, cfg, slots=2, n_inner=2, quantize_kv=False, page_tokens=16,
        prompt_chunk=C, max_prompt=2 * C)
    prompt = np.random.default_rng(0).integers(0, cfg.vocab, 200).astype(
        np.int32)
    req = sched.submit(prompt, 4)
    monkeypatch.setattr(serving, "_annotate", Spy)
    sched.run()
    chunks = [s for s in seen if s.name == "serving.prefill_chunk"]
    assert len(chunks) == 2
    if route is None:
        assert not any("gdn_rule" in c.args for c in chunks)
    else:
        assert [c.args["gdn_rule"] for c in chunks] == [route] * 2
    want = generate_dense(params, jnp.asarray(prompt)[None], 4, cfg)
    assert req.tokens == [int(t) for t in np.asarray(want)[0]]
