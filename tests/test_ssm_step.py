"""The state-space mixer's single-token kernel (ops/ssm_step.py) at a
head NARROWER than a lane tile: k = 128 / P neighbouring heads of a
group share a tile and the state is kept (heads / k, N, k P). Held,
interpreted on the CPU, to the plain step (``transformer._ssm_step``,
float32; only the order of the N-term sum may differ), for one and
several groups; tests/test_falcon_h1_block.py holds the same kernel at
a head of a whole lane tile, and tests/
test_decode_attention_tpu_compile.py compiles both for the chip."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mpistragglers_jl_tpu.models import transformer
from mpistragglers_jl_tpu.models.transformer import TransformerConfig
from mpistragglers_jl_tpu.ops import ssm_step as kernel

# (heads, groups, state dim, head dim): Granite-4.0-H's head of half a
# lane tile in one group (fewer heads than its 128) and in several; a
# quarter of a tile; a whole tile (Falcon-H1's form, pack 1)
SHAPES = [(16, 1, 128, 64), (32, 2, 16, 64), (64, 4, 8, 64),
          (32, 1, 8, 32), (8, 1, 16, 128)]


def _cfg(H, G, N, P):
    return TransformerConfig(
        vocab=32, d_model=32, n_heads=2, n_layers=1, d_ff=32,
        layer_mixers=("ssm",), ssm_heads=H, ssm_groups=G, ssm_state=N,
        ssm_head_dim=P)


@pytest.mark.parametrize("H,G,N,P", SHAPES)
def test_step_kernel_is_the_plain_step(H, G, N, P):
    """S updated in place over several steps, in the layout the cache
    keeps, against the plain step on a block a head."""
    assert kernel.ssm_step_viable(H, G, N, P)
    k = kernel.lane_pack(H, G, N, P)
    assert k == max(1, 128 // P)
    assert kernel.ssm_state_shape(H, G, N, P) == (H // k, N, k * P)
    cfg = _cfg(H, G, N, P)
    rng = np.random.default_rng(9)
    f = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)
    S_want = f(3, H, N, P)
    S = transformer.ssm_state_kept(S_want, cfg)
    assert S.shape == (3,) + kernel.ssm_state_shape(H, G, N, P)
    np.testing.assert_array_equal(transformer.ssm_state_heads(S, cfg),
                                  S_want)
    for _ in range(4):
        x, Bm, Cm = f(3, H, P), f(3, G, N), f(3, G, N)
        dt = jax.nn.softplus(f(3, H))
        dA = -jnp.exp(f(3, H)) * dt
        y, S = kernel.ssm_step(x, Bm, Cm, dA, dt, S, interpret=True)
        y_want, S_want = transformer._ssm_step(x, Bm, Cm, dA, dt, S_want)
        assert y.shape == (3, H, P)
        np.testing.assert_allclose(y, y_want, atol=1e-5, rtol=1e-5)
        np.testing.assert_allclose(transformer.ssm_state_heads(S, cfg),
                                   S_want, atol=1e-6, rtol=1e-6)
    # a row with a = 1 and dt = 0 leaves S bit for bit
    zero = jnp.zeros((3, H), jnp.float32)
    _, S2 = kernel.ssm_step(x, Bm, Cm, zero, zero, S, interpret=True)
    np.testing.assert_array_equal(S2, S)


def test_packed_head_holds_its_neighbours_side_by_side():
    """Packed head j's lanes [i P, (i + 1) P) are head j k + i's, each
    lane decaying by ITS head's a: heads that differ in nothing but
    their decay come out different."""
    H, G, N, P = 8, 1, 8, 64
    cfg = _cfg(H, G, N, P)
    S = jnp.broadcast_to(jnp.arange(N, dtype=jnp.float32)[:, None] + 1.0,
                         (1, H, N, P))
    x = jnp.zeros((1, H, P), jnp.float32)
    Bm = Cm = jnp.ones((1, G, N), jnp.float32)
    dA = jnp.log(jnp.arange(1, H + 1, dtype=jnp.float32) / H)[None]
    y, S2 = kernel.ssm_step(x, Bm, Cm, dA, jnp.zeros((1, H), jnp.float32),
                            transformer.ssm_state_kept(S, cfg),
                            interpret=True)
    heads = transformer.ssm_state_heads(S2, cfg)
    for h in range(H):
        np.testing.assert_allclose(heads[0, h], S[0, h] * (h + 1) / H,
                                   rtol=1e-6)
        np.testing.assert_allclose(y[0, h], N * (N + 1) / 2 * (h + 1) / H,
                                   rtol=1e-5)


def test_viable_where_it_was_and_at_half_a_lane_tile():
    assert kernel.ssm_step_viable(128, 1, 128, 64)     # Granite-4.0-H Small
    assert kernel._heads_per_step(64, 1, 128, 128) == 32  # 2 MiB a grid step
    assert kernel.ssm_state_shape(128, 1, 128, 64) == (64, 128, 128)
    assert kernel.ssm_step_viable(32, 2, 256, 128)     # Falcon-H1-34B
    assert kernel._heads_per_step(32, 2, 256, 128) == 16
    assert kernel.ssm_state_shape(32, 2, 256, 128) == (32, 256, 128)
    # false for reasons that still hold: a head that neither fills nor
    # divides a lane tile; a group that serves no whole number of (packs
    # of) heads; a state dim off the sublane tiling; no legal number of
    # heads a grid step (3 heads a group: neither 8 nor all)
    assert not kernel.ssm_step_viable(4, 2, 8, 16)
    assert not kernel.ssm_step_viable(8, 1, 8, 48)
    assert not kernel.ssm_step_viable(8, 1, 8, 192)
    assert not kernel.ssm_step_viable(6, 4, 8, 128)
    assert not kernel.ssm_step_viable(6, 2, 8, 64)     # 3 heads a group: odd
    assert not kernel.ssm_step_viable(8, 1, 12, 128)
    assert not kernel.ssm_step_viable(6, 2, 8, 128)
    assert not kernel.ssm_step_viable(0, 1, 8, 128)
    # where the kernel does not take the shape the state is a block a head
    assert kernel.lane_pack(4, 2, 8, 16) == 1
    assert kernel.ssm_state_shape(4, 2, 8, 16) == (4, 8, 16)
    with pytest.raises(ValueError, match="single-token kernel"):
        z = lambda shape: jnp.zeros(shape, jnp.float32)
        kernel.ssm_step(z((1, 6, 64)), z((1, 2, 8)), z((1, 2, 8)),
                        z((1, 6)), z((1, 6)), z((1, 6, 8, 64)))


@pytest.mark.parametrize("H,G,N,P", SHAPES[:4])
def test_the_route_and_the_kept_state_follow_the_shapes(H, G, N, P):
    cfg = _cfg(H, G, N, P)
    assert transformer.ssm_rule_route(cfg, 1) == "kernel"
    assert transformer.ssm_rule_route(cfg, 16) == "xla"
    state = transformer.ssm_zero_state(cfg, 2)
    assert state["S"].shape == (2, H * P // 128, N, 128)
    plain = dataclasses.replace(cfg, ssm_state=N + 1)
    assert transformer.ssm_rule_route(plain, 1) == "xla"
    assert transformer.ssm_zero_state(plain, 2)["S"].shape == (
        2, H, N + 1, P)
