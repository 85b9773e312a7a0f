"""The MiniCPM-SALA block (``TransformerConfig(layer_mixers=("attn",
"la", ...), sparse_block=...)``): decayed linear attention beside
attention that reads a selection of its key blocks, held to the plain
reference (chipbench/references/minicpm_sala.py, which imports nothing
of the program) in logits AND in the blocks picked, through the dense
forward, chunked prefill, paged decode and the serving scheduler.

Sizes: blocks of 8 rows (a page), the 2 best, pooling windows of 4 keys
every 2, the first block and a window of 16 rows, dense up to 32 rows;
2 K/V heads, 4 layers in the published pattern (1 attention to 3 linear
attention). A window of rows 14..17 lies across two chunks of 16 and
rows 6..9 across two pages.

Tolerances, float32 weights on the CPU: the forms of one recurrence
differ in the order of float32 sums (1e-5 on values of order 1); a
quantized cache adds the int8 rounding of one attention layer's K/V
(under 2e-3 on logits whose largest is 0.17); the recurrence computed
in bfloat16 misses the first by two orders of magnitude."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.references import minicpm_sala as ref
from mpistragglers_jl_tpu.models import decode, serving, transformer
from mpistragglers_jl_tpu.models.serving import ServingScheduler
from mpistragglers_jl_tpu.models.transformer import (
    TransformerConfig,
    forward_dense,
    init_params,
)

RES = 1.4 / np.sqrt(32)
SIZES = dict(sparse_block=8, sparse_topk=2, sparse_kernel=4, sparse_stride=2,
             sparse_init_blocks=1, sparse_window=16, sparse_dense_len=32)
Z = ref.Sizes(8, 2, 4, 2, 1, 16, 32, 12.0, RES, 4.0)


def make_cfg(d_head=16, **kw):
    return TransformerConfig(
        vocab=97, d_model=64, n_heads=4, n_kv_heads=2, d_head=d_head,
        n_layers=4, d_ff=128, norm="rmsnorm", norm_eps=1e-6, ffn="swiglu",
        tie_head=False, qk_norm=True, attn_gate=True, rope_full=False,
        emb_scale=12.0, layer_mixers=("attn", "la", "la", "la"),
        la_heads=4, la_head_dim=16, residual_scale=RES, head_scale=0.25,
        max_context=128, **SIZES, **kw)


@pytest.fixture(scope="module")
def model():
    cfg = make_cfg()
    return cfg, init_params(cfg, 0)


def tokens(n, seed=1):
    return np.random.default_rng(seed).integers(0, 97, (n,)).astype(np.int32)


# -- the linear-attention layer: three forms of one recurrence -----------------


def _la_inputs(T, H=4, D=16, seed=3):
    rng = np.random.default_rng(seed)
    q, k, v = (jnp.asarray(rng.standard_normal((1, T, H, D)), jnp.float32)
               for _ in range(3))
    return q, k, v, jnp.asarray(transformer.la_slopes(H, 1, 32), jnp.float32)


def test_recurrent_chunked_and_reference_forms_agree(monkeypatch):
    T = 70
    q, k, v, slope = _la_inputs(T)
    S0 = jnp.zeros((1, 4, 16, 16), jnp.float32)
    want, S_want = ref.lightning_rows(q[0], k[0], v[0], slope, S0[0],
                                      "float32")
    # one call over all rows, in sub-chunks of 16 with a ragged tail
    monkeypatch.setattr(transformer, "LA_SUBCHUNK", 16)
    o, S = transformer._la_chunks(q, k, v, slope, S0)
    np.testing.assert_allclose(o[0], want, atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(S[0], S_want, atol=1e-5, rtol=1e-5)
    # the state carried across calls: chunks of 16, then a row at a time
    S, outs = S0, []
    for a in range(0, 48, 16):
        o, S = transformer._la_chunks(q[:, a:a + 16], k[:, a:a + 16],
                                      v[:, a:a + 16], slope, S)
        outs.append(o)
    lam = jnp.exp(-slope)[:, None, None]
    for t in range(48, T):
        S = S * lam + k[:, t, :, :, None] * v[:, t, :, None, :]
        outs.append((S * q[:, t, :, :, None]).sum(axis=-2)[:, None])
    np.testing.assert_allclose(jnp.concatenate(outs, 1)[0], want,
                               atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(S[0], S_want, atol=1e-5, rtol=1e-5)


def test_padding_rows_leave_the_state_alone():
    q, k, v, slope = _la_inputs(16)
    S0 = jnp.asarray(np.random.default_rng(5).standard_normal(
        (1, 4, 16, 16)), jnp.float32)
    o, S = transformer._la_chunks(q, k, v, slope, S0,
                                  jnp.asarray([11], jnp.int32))
    o_want, S_want = transformer._la_chunks(q[:, :11], k[:, :11], v[:, :11],
                                            slope, S0)
    np.testing.assert_allclose(S, S_want, atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(o[:, :11], o_want, atol=1e-5, rtol=1e-5)


def test_the_recurrence_in_bfloat16_misses_the_tolerance():
    q, k, v, slope = _la_inputs(70)
    S0 = jnp.zeros((4, 16, 16), jnp.float32)
    want, _ = ref.lightning_rows(q[0], k[0], v[0], slope, S0, "float32")
    low, _ = ref.lightning_rows(q[0], k[0], v[0], slope, S0, "bfloat16")
    err = float(jnp.abs(low - want).max())
    assert err > 100 * 1e-5
    with pytest.raises(AssertionError):
        np.testing.assert_allclose(low, want, atol=1e-5, rtol=1e-5)


# -- the dense forward against the reference, picks and all --------------------


def test_dense_forward_is_the_references(model):
    cfg, params = model
    toks = tokens(96)
    got = forward_dense(params, jnp.asarray(toks[None]), cfg)[0]
    want, _ = ref.forward(params, jnp.asarray(toks), z=Z)
    assert float(jnp.abs(want).max()) > 0.1
    np.testing.assert_allclose(got, want, atol=2e-6)
    rows = ref.stream_logits(params, jnp.asarray(toks), 0, 96, z=Z)
    np.testing.assert_allclose(rows, want, atol=2e-6)


def _layer0_qk(params, toks, cfg):
    x = transformer.embed(params, jnp.asarray(toks[None]), cfg)
    q, k, _, _ = transformer.attn_qkv(x, params["layers"][0], cfg, 0, None)
    return q[0], k[0]


def test_program_and_reference_stand_on_the_same_blocks(model):
    cfg, params = model
    toks = tokens(96)
    _, picks = ref.forward(params, jnp.asarray(toks), z=Z)
    q, k = _layer0_qk(params, toks, cfg)
    stands, _ = transformer.sparse_pick(
        q, transformer.pool_cells(k, cfg), jnp.arange(96) + 1, cfg, 12)
    want = np.asarray(picks[0])
    np.testing.assert_array_equal(np.asarray(stands), want)
    # up to dense_len every visible block; past it the first block, the
    # window's blocks and two more, a K/V head its own
    assert want[31].sum(-1).tolist() == [4, 4]
    assert want[32].sum(-1).tolist() == [5, 5]      # 0, 2..4 and one more
    assert want[90].sum(-1).tolist() == [6, 6]
    assert (want[:, 0] != want[:, 1]).any()
    for n in (33, 57, 96):
        attended, visible = transformer.sparse_counts([n], cfg)
        assert attended == want[n - 1, 0].sum()
        assert visible == (n - 1) // 8 + 1


# -- chunked prefill, then decoding, against the reference ---------------------


@pytest.mark.parametrize("quantize", [False, True])
@pytest.mark.parametrize("prompt", [53, 21],
                         ids=["crosses_in_prefill", "crosses_decoding"])
def test_chunks_then_decode_steps_give_the_references_logits(model, prompt,
                                                             quantize):
    cfg, params = model
    toks, total = tokens(60, seed=7), 60
    want, picks = ref.forward(params, jnp.asarray(toks), z=Z)
    cache = decode.init_cache(cfg, 1, 64, quantize_kv=quantize)
    outs, off = [], 0
    while off < prompt:                  # chunks of 16, the last ragged
        c = min(16, prompt - off)
        lg, cache = decode._incremental_forward(
            params, jnp.asarray(toks[None, off:off + c]), cache,
            jnp.int32(off), cfg, prefill=False)
        outs.append(lg)
        off += c
    # the selection the cache's pooled cells give the last chunk's rows
    # (cells summed over two chunks and two pages' worth of rows)
    q, _ = _layer0_qk(params, toks, cfg)
    stands = decode._select_blocks(q[None, off - c:off], cache[0],
                                   jnp.arange(off - c, off), cfg)
    np.testing.assert_array_equal(np.asarray(stands[0]),
                                  np.asarray(picks[0])[off - c:off, :, :8])
    while off < total:
        lg, cache = decode.decode_step_dense(
            params, jnp.asarray(toks[off:off + 1]), cache, jnp.int32(off),
            cfg)
        outs.append(lg[:, None])
        off += 1
    got = jnp.concatenate(outs, axis=1)[0]
    np.testing.assert_allclose(got, want, atol=2e-3 if quantize else 2e-6)


def _serve(cfg, params, prompts, quantize, **kw):
    sched = ServingScheduler(params, cfg, slots=kw.pop("slots", 4), n_inner=4,
                             quantize_kv=quantize, page_tokens=8,
                             prompt_chunk=16, max_prompt=96, **kw)
    reqs = [sched.submit(p, n) for p, n in prompts]
    sched.run()
    return sched, reqs


def _gaps(params, reqs):
    """How far each served token lies below the reference's best."""
    out = []
    for r in reqs:
        seq = np.concatenate([r.prompt, np.asarray(r.tokens, np.int32)])
        lg = np.asarray(ref.forward(params, jnp.asarray(seq), z=Z)[0])
        rows = lg[len(r.prompt) - 1:len(seq) - 1]
        out.append(rows.max(-1) - rows[np.arange(len(r.tokens)), r.tokens])
    return np.concatenate(out)


PROMPTS = [(50, 30), (20, 25), (90, 8), (10, 6), (33, 5)]


@pytest.mark.parametrize("quantize", [False, True])
def test_scheduler_serves_the_references_tokens(model, quantize):
    """Five requests through four slots over the paged cache (the
    gathered-view route: pages of 8 rows are no block of the kernel's):
    prompts that see more than ``dense_len`` rows in their prefill, and
    ones that come to it while decoding."""
    cfg, params = model
    sched, reqs = _serve(cfg, params,
                         [(tokens(a, seed=a), b) for a, b in PROMPTS],
                         quantize)
    assert not sched.use_kernel
    assert not sched.shares_prefixes
    assert all(len(r.tokens) == b for r, (_, b) in zip(reqs, PROMPTS))
    assert _gaps(params, reqs).max() <= (2e-3 if quantize else 1e-6)


def test_the_kernels_route_serves_the_references_tokens():
    """Heads of 128 and an int8 cache: the tick takes the paged kernel's
    selection form (interpreted here), a page table a slot and K/V
    head."""
    cfg = make_cfg(d_head=128)
    params = init_params(cfg, 0)
    sched, reqs = _serve(cfg, params,
                         [(tokens(a, seed=a), b) for a, b in PROMPTS[:4]],
                         True)
    assert sched.use_kernel
    assert _gaps(params, reqs).max() <= 2e-3


def test_a_slot_taken_again_starts_from_nothing(model):
    """One slot, two requests: the second's stream is the stream it has
    alone (zero state, empty pooled cells, pages of its own)."""
    cfg, params = model
    a, b = (tokens(70, seed=11), 20), (tokens(45, seed=12), 30)
    _, both = _serve(cfg, params, [a, b], True, slots=1)
    _, alone = _serve(cfg, params, [b], True, slots=1)
    assert both[1].tokens == alone[0].tokens
    assert _gaps(params, both).max() <= 2e-3


def test_the_pages_pooled_cells_are_the_keys_means(model):
    cfg, params = model
    prompt = tokens(37, seed=21)
    sched = ServingScheduler(params, cfg, slots=6, n_inner=4,
                             quantize_kv=False, page_tokens=8,
                             prompt_chunk=16, max_prompt=96)
    req = sched.submit(prompt, 12)
    for _ in range(4):                   # three chunks, then decode steps
        sched.step()
    seq = np.concatenate([prompt, np.asarray(req.tokens, np.int32)])
    n = len(seq) - 1                     # rows written so far
    assert 37 < n < 37 + 12 and n % 8
    _, k = _layer0_qk(params, seq[:n], cfg)
    want = transformer.pool_cells(
        jnp.pad(k, [(0, -n % 8), (0, 0), (0, 0)]), cfg)
    pages = sched._kinds[0].pt_host[0][:-(-n // 8)]
    kp = np.asarray(sched._caches[0]["kp"])[pages]       # (pages, 4, 32)
    np.testing.assert_allclose(kp.reshape(-1, 2, 16), want, atol=1e-6)
    tick = sched._sparse_tick_counts()
    attended, visible = transformer.sparse_counts([n + 1], cfg)
    assert tick == {"sparse_slots": 1, "blocks_attended": 2 * attended,
                    "blocks_visible": 2 * visible}


def test_select_kernel_is_the_masked_softmax_over_its_pages():
    """The kernel on random pools (interpreted): every (slot, K/V head)
    its own list of pages, the last live up to a row; against the plain
    softmax over the dequantized rows of those pages."""
    from mpistragglers_jl_tpu.ops.decode_attention import (
        paged_scale_lanes,
        paged_select_attention,
    )

    rng = np.random.default_rng(0)
    B, H, Hkv, D, P, n_pages, width = 3, 4, 2, 128, 8, 40, 10
    q = jnp.asarray(rng.standard_normal((B, 1, H, D)), jnp.float32)
    pool = lambda: jnp.asarray(
        rng.integers(-127, 128, (n_pages, P, Hkv * D)), jnp.int8)
    lanes = paged_scale_lanes(P)
    scales = lambda: jnp.asarray(
        rng.uniform(0.01, 0.02, (n_pages, Hkv, lanes)), jnp.float32)
    cache = {"k": pool(), "v": pool(), "k_s": scales(), "v_s": scales()}
    count = rng.integers(1, width + 1, (B, Hkv))
    pages = np.stack([[rng.permutation(np.arange(1, n_pages))[:width]
                       for _ in range(Hkv)] for _ in range(B)])
    at = (count - 1) * P + rng.integers(0, P, (B, Hkv))
    got = paged_select_attention(
        q, cache, jnp.asarray(at, jnp.int32), jnp.asarray(pages, jnp.int32),
        scale=D ** -0.5, P=P)
    g = H // Hkv
    for b in range(B):
        for h in range(Hkv):
            ids = pages[b, h, :count[b, h]]
            rows = lambda x, s: (
                np.asarray(x)[ids][:, :, h * D:(h + 1) * D].astype(
                    np.float32)
                * np.asarray(s)[ids][:, h, :P, None]).reshape(-1, D)
            k, v = (rows(cache["k"], cache["k_s"])[:at[b, h] + 1],
                    rows(cache["v"], cache["v_s"])[:at[b, h] + 1])
            for j in range(g):
                s = (np.asarray(q)[b, 0, h * g + j] @ k.T) * D ** -0.5
                p = np.exp(s - s.max())
                want = (p / p.sum()) @ v
                np.testing.assert_allclose(got[b, 0, h * g + j], want,
                                           rtol=2e-4, atol=2e-4)


# -- the other paths refuse it by mechanism ------------------------------------


def test_other_paths_refuse_by_mechanism(model):
    cfg, params = model
    from jax.sharding import Mesh

    from mpistragglers_jl_tpu.models.transformer import make_train_step

    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1, 1),
                ("dp", "sp", "tp"))
    with pytest.raises(ValueError, match="recurrent state"):
        make_train_step(cfg, mesh)
    with pytest.raises(ValueError, match="sharded tick.*recurrent state"):
        serving.make_serving_scan(cfg, mesh, 4)
    with pytest.raises(ValueError, match="has no width"):
        decode.ring_widths(cfg)
    with pytest.raises(ValueError, match="page_tokens 16 must be "
                       "sparse_block 8"):
        ServingScheduler(params, cfg, slots=2, page_tokens=16,
                         prompt_chunk=16, max_prompt=96)
    # the selection alone, without a state layer, is refused where a
    # cache is rows and nothing else
    rows_only = TransformerConfig(
        vocab=97, d_model=64, n_heads=4, n_kv_heads=2, d_head=16,
        n_layers=2, d_ff=128, max_context=128, **SIZES)
    with pytest.raises(ValueError, match="pooled keys"):
        decode.ring_widths(rows_only)
    with pytest.raises(ValueError, match="selection of their key blocks"):
        serving.make_serving_scan(rows_only, mesh, 4)
    with pytest.raises(ValueError, match="take no window"):
        TransformerConfig(n_layers=2, attn_window=8, **SIZES)


def test_scopes_and_span_arguments(model):
    cfg, params = model
    arena = serving._fresh_cache(cfg, 1, 96, False)
    text = serving._extend_chunk_dense(cfg, 16, 96).lower(
        params, np.zeros((1, 16), np.int32), arena, np.int32(0),
        np.int32(16)).as_text(debug_info=True)
    for scope in ("la_proj", "la_rule", "la_out", "sparse_pool",
                  "sparse_select", "chunk_attn"):
        assert f"serving_prefill_chunk)/{scope}/" in text, scope
    sched = ServingScheduler(params, cfg, slots=2, n_inner=4,
                             page_tokens=8, prompt_chunk=16, max_prompt=96)
    assert sched._rule_routes == {"la_rule": "xla"}
    st = type("St", (), {})()
    st.req = type("Req", (), {"prompt": np.zeros((40,), np.int32)})()
    counts = sched._sparse_chunk_counts([st], [32])
    attended, visible = transformer.sparse_counts(np.arange(33, 41), cfg)
    assert counts == {"blocks_attended": 2 * attended,
                      "blocks_visible": 2 * visible}
    assert sched._sparse_chunk_counts([st], [0]) == {
        "blocks_attended": 0, "blocks_visible": 0}
