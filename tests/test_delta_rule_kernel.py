"""The gated delta rule's two Pallas kernels (ops/delta_rule.py),
interpreted here. The chunked one: against the token-by-token
recurrence and the plain chunked form at head sizes of 128, through
``gdn_half`` with padding and across calls. The single-token one, which
updates S where it lies: against the plain step over consecutive steps,
through ``gdn_half`` and through the serving tick. And the route that
sends everything else to the plain forms. What Mosaic says of them is
tests/test_decode_attention_tpu_compile.py's; what the chip says,
PERF.md's (section 6, PR 40 and PR 43).
"""

from __future__ import annotations

import dataclasses
import types

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
    _heads_per_token,
    chunked_delta_rule,
    delta_rule_step,
    delta_rule_viable,
    delta_step_viable,
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
    (128, D, True), (150, D, False), (64, D, False), (128, 8, False),
    (1, D, True), (1, 8, False)])
def test_gdn_half_takes_the_route(monkeypatch, T, widths, kernel):
    """Widths of 8 and a T of 150 go through ``_delta_rule_chunks``, a
    chunk of whole sub-chunks at widths of 128 through the chunked
    kernel; one token through the kernel that updates S where it lies at
    widths of 128 and through ``_delta_rule_step`` at 8; and none
    through two forms."""
    cfg = dataclasses.replace(CFG, gdn_key_dim=widths, gdn_value_dim=widths)
    lp = LP if widths == D else init_params(cfg, seed=3)["layers"][0]
    took = []

    def spy(name):
        real = getattr(tr, name)

        def call(*a, **kw):
            took.append(name)
            return real(*a, **kw)
        return call

    for name in ("chunked_delta_rule", "_delta_rule_chunks",
                 "delta_rule_step", "_delta_rule_step"):
        monkeypatch.setattr(tr, name, spy(name))
    x = jnp.asarray(np.random.default_rng(8).standard_normal(
        (1, T, cfg.d_model)), jnp.float32)
    tr.gdn_half(x, lp, tr.gdn_zero_state(cfg, 1), cfg)
    want = {(True, False): "chunked_delta_rule",
            (False, False): "_delta_rule_chunks",
            (True, True): "delta_rule_step",
            (False, True): "_delta_rule_step"}[kernel, T == 1]
    assert took == [want]


def test_what_the_kernel_cannot_take_is_refused_by_name():
    qkv, g, beta, S0 = _operands(C + 32, seed=1)
    with pytest.raises(ValueError, match="plain form"):
        chunked_delta_rule(qkv, g, beta, S0, Hk=HK, Hv=HV, Dk=D, Dv=D, c=C)


# -- one token: the kernel that updates S where it lies -----------------------


def _token(rng, B, Hk, Hv):
    """One token's operands as ``gdn_half`` hands them over: q and k
    normed a KEY head each, q scaled; decays from nearly none to nearly
    all."""
    f = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)
    l2 = lambda a: a * jax.lax.rsqrt((a * a).sum(-1, keepdims=True) + 1e-6)
    g = -jnp.asarray(rng.random((B, Hv)) ** 4 * 3.0, jnp.float32)
    beta = jnp.asarray(rng.random((B, Hv)), jnp.float32)
    return (l2(f(B, Hk, D)) * D ** -0.5, l2(f(B, Hk, D)), 0.5 * f(B, Hv, D),
            g, beta)


@pytest.mark.parametrize("Hk,Hv", [(16, 32), (1, 2)])
def test_step_kernel_equals_the_plain_step_over_32_steps(Hk, Hv):
    """Each form carries its own S from one random state through 32
    consecutive tokens: every step's row and the last S agree to 1e-6
    (the Dk-term sums' order is all that differs)."""
    rng = np.random.default_rng(Hk)
    B = 2
    S = S_kernel = jnp.asarray(0.5 * rng.standard_normal((B, Hv, D, D)),
                               jnp.float32)
    rep = lambda a: jnp.repeat(a, Hv // Hk, axis=1)
    for _ in range(32):
        q, k, v, g, beta = _token(rng, B, Hk, Hv)
        want, S = tr._delta_rule_step(rep(q), rep(k), v, g, beta, S)
        o, S_kernel = delta_rule_step(q, k, v, g, beta, S_kernel)
        np.testing.assert_allclose(o, want, rtol=0, atol=1e-6)
    np.testing.assert_allclose(S_kernel, S, rtol=0, atol=1e-6)


def test_a_padded_step_leaves_the_state_bit_for_bit():
    """g = 0 and beta = 0 (what ``valid`` makes of a padded row): S
    comes back as it went in, every bit, whatever q, k and v hold; the
    member beside it moves."""
    rng = np.random.default_rng(11)
    q, k, v, g, beta = _token(rng, 2, HK, HV)
    g, beta = g.at[0].set(0.0), beta.at[0].set(0.0)
    S0 = jnp.asarray(rng.standard_normal((2, HV, D, D)), jnp.float32)
    _, S = delta_rule_step(q, k, v, g, beta, S0)
    bits = lambda a: np.asarray(a).view(np.uint32)
    assert np.array_equal(bits(S[0]), bits(S0[0]))
    assert not np.array_equal(bits(S[1]), bits(S0[1]))


# (key heads, value heads, key dim, value dim) -> (kernel?, heads a step)
STEP_ROUTES = [
    ((16, 32, 128, 128), 32),  # the cell's: a slot's heads, 2 MiB of S
    ((1, 2, 128, 128), 2),     # all there are
    ((16, 32, 128, 256), 16),  # a wider value: half a slot's heads
    ((4, 12, 128, 128), 12),   # three value heads a key head
    ((2, 4, 8, 8), 0),         # the tiny configurations' widths
    ((2, 3, 128, 128), 0),     # a key head serves no whole number
    ((16, 32, 128, 192), 0),   # not whole lane tiles
    ((2, 4, 1024, 1024), 0),   # one head's S is past a step's block
]


@pytest.mark.parametrize("shape,heads", STEP_ROUTES)
def test_the_steps_route_follows_the_shapes(shape, heads):
    assert delta_step_viable(*shape) is (heads > 0)
    if heads:
        assert _heads_per_token(*shape) == heads


@pytest.mark.parametrize("heads,widths,route", [
    ((HK, HV), D, "kernel"), ((HK, HV), 8, "xla"), ((2, 3), D, "xla")])
def test_gdn_rule_route_of_one_token(heads, widths, route):
    """``gdn_rule_route(cfg, 1)``, which ``gdn_half`` and the serving
    scheduler's ``serving.decode`` ask: by widths and by heads (a
    configuration refuses 2 / 3 heads for a layer, so a stand-in
    carries them)."""
    cfg = types.SimpleNamespace(
        gdn_key_heads=heads[0], gdn_value_heads=heads[1],
        gdn_key_dim=widths, gdn_value_dim=widths)
    assert tr.gdn_rule_route(cfg, 1) == route


def test_what_the_step_kernel_cannot_take_is_refused_by_name():
    q, k, v, g, beta = _token(np.random.default_rng(1), 1, 2, 3)
    with pytest.raises(ValueError, match="plain step"):
        delta_rule_step(q, k, v, g, beta, jnp.zeros((1, 3, D, D)))


def test_gdn_half_token_by_token_equals_the_plain_step(monkeypatch):
    """Six single-token calls of ``gdn_half`` from one state, the kernel
    against the plain step (the route told to answer ``"xla"``): rows,
    S and the conv rows."""
    x, state = _x(6, 2, seed=12), _state(2, seed=13)

    def walk():
        s, rows = state, []
        for t in range(x.shape[1]):
            row, s = tr.gdn_half(x[:, t:t + 1], LP, s, CFG)
            rows.append(row)
        return jnp.concatenate(rows, 1), s

    got, s_kernel = walk()
    monkeypatch.setattr(tr, "gdn_rule_route", lambda cfg, T: "xla")
    want, s_plain = walk()
    np.testing.assert_allclose(got, want, atol=2e-6)
    for leaf in ("S", "conv"):
        np.testing.assert_allclose(s_kernel[leaf], s_plain[leaf], atol=2e-6)


# -- the serving programs ----------------------------------------------------

SERVED = dataclasses.replace(CFG, n_layers=2, layer_mixers=("gdn", "attn"),
                             max_context=2 * C + 16)


MODELS = {
    "kernel_widths": SERVED,
    "tiny_widths": dataclasses.replace(SERVED, gdn_key_dim=8,
                                       gdn_value_dim=8),
    "no_state_layer": dataclasses.replace(SERVED, layer_mixers=None),
}
ROUTED = [("kernel_widths", "kernel"), ("tiny_widths", "xla"),
          ("no_state_layer", None)]


def _serve(monkeypatch, model):
    """A 200-token prompt and 4 tokens through ``ServingScheduler``
    (two chunks, then ticks of two steps): the spans it opened, the
    served tokens and the dense forward's."""
    from mpistragglers_jl_tpu.models import serving
    from mpistragglers_jl_tpu.models.decode import generate_dense

    cfg = MODELS[model]
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
    want = generate_dense(params, jnp.asarray(prompt)[None], 4, cfg)
    return seen, req.tokens, [int(t) for t in np.asarray(want)[0]]


@pytest.mark.parametrize("model,route", ROUTED)
def test_the_chunk_span_names_the_form_of_the_delta_rule(monkeypatch, model,
                                                         route):
    """``gdn_rule`` on ``serving.prefill_chunk``: the route a chunk's rows
    take through the delta rule, from the test ``gdn_half`` itself asks;
    absent where no layer keeps state. The served tokens are the dense
    forward's, whose 200 rows take the plain form."""
    seen, tokens, want = _serve(monkeypatch, model)
    chunks = [s for s in seen if s.name == "serving.prefill_chunk"]
    assert len(chunks) == 2
    if route is None:
        assert not any("gdn_rule" in c.args for c in chunks)
    else:
        assert [c.args["gdn_rule"] for c in chunks] == [route] * 2
    assert tokens == want


@pytest.mark.parametrize("model,route", ROUTED)
def test_the_decode_span_names_the_form_of_the_step(monkeypatch, model,
                                                    route):
    """``gdn_rule`` on ``serving.decode``: the route ONE token takes
    through the delta rule in the tick's steps (the kernel that updates
    S where it lies, or the plain step), from the same function
    ``gdn_half`` asks; absent where no layer keeps state."""
    seen, _, _ = _serve(monkeypatch, model)
    ticks = [s for s in seen if s.name == "serving.decode"]
    assert ticks
    if route is None:
        assert not any("gdn_rule" in t.args for t in ticks)
    else:
        assert {t.args["gdn_rule"] for t in ticks} == {route}


def test_a_stream_served_at_kernel_widths_is_the_dense_forwards(monkeypatch):
    """Twelve tokens behind a 130-token prompt, every tick's steps
    through the single-token kernel: the dense generator's tokens, whose
    decode steps are told to take the plain step."""
    from mpistragglers_jl_tpu.models import serving
    from mpistragglers_jl_tpu.models.decode import generate_dense

    params = init_params(SERVED, seed=4)
    sched = serving.ServingScheduler(
        params, SERVED, slots=2, n_inner=4, quantize_kv=False,
        page_tokens=16, prompt_chunk=C, max_prompt=2 * C)
    assert sched._step_route == {"gdn_rule": "kernel"}
    prompt = np.random.default_rng(5).integers(0, SERVED.vocab, 130).astype(
        np.int32)
    req = sched.submit(prompt, 12)
    sched.run()
    # another configuration object, so that no program traced above is
    # handed back for it
    plain = dataclasses.replace(SERVED, max_context=SERVED.max_context + 16)
    route = tr.gdn_rule_route
    monkeypatch.setattr(
        tr, "gdn_rule_route",
        lambda cfg, T: "xla" if T == 1 else route(cfg, T))
    want = generate_dense(params, jnp.asarray(prompt)[None], 12, plain)
    assert req.tokens == [int(t) for t in np.asarray(want)[0]]
