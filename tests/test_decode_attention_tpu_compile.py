"""The int8 decode kernel compiled for a v5e that is described, not
attached (the TPU's compiler is installed here): what the interpreter
cannot refuse, Mosaic can: a slice off the tiling, a copy it cannot
lower, more VMEM than a kernel may hold. Shapes are the serving cells'
own. Nothing runs, so nothing here is a measurement.

The topology is described inside a fixture, never at import: only one
process may load the TPU's library (on-chip-measurement guide, s. 2).
"""

import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from mpistragglers_jl_tpu.ops.decode_attention import (
    paged_scale_lanes,
    paged_select_attention,
    quantized_decode_attention,
)


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _sds(one_chip, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def _result_of(line, *ops):
    """The result's type where a compiled program's line is one of the
    operations ``ops`` ("" elsewhere)."""
    head, _, rest = line.partition(" = ")
    for op in ops:
        kind, found, _ = rest.partition(f" {op}(")
        if found and "(%" not in kind:
            return kind
    return ""


def _compiled_text(fn, *args):
    # as the chip runs it: the suite's 64-bit mode (conftest.py) is not
    # the program's, and Mosaic has no float64
    with jax.enable_x64(False):
        return jax.jit(fn).lower(*args).compile().as_text()


# (query heads, K/V heads, head size, table entries, pool pages):
# StarCoder2-3B's group of 12 on a 64-entry table, Trinity-Mini's group
# of 8 on its window table (32) and its full-attention table (68),
# Qwen3-Next's group of 8 at a head size of 256 on its one table
CELLS = [(24, 2, 128, 64, 1025), (32, 4, 128, 32, 513),
         (32, 4, 128, 68, 1089), (16, 2, 256, 68, 1089)]


@pytest.mark.parametrize("H,Hkv,D,max_pages,n_pages", CELLS)
def test_paged_kernel_compiles_for_the_v5e(one_chip, H, Hkv, D, max_pages,
                                           n_pages):
    B, P = 16, 64
    sds = functools.partial(_sds, one_chip)

    def call(q, k, ks, v, vs, pos, pt):
        return quantized_decode_attention(
            q, {"k": k, "k_s": ks, "v": v, "v_s": vs}, pos, D ** -0.5,
            ring=True, page_table=pt, page_tokens=P, interpret=False,
        )

    pool = sds((n_pages, P, Hkv * D), jnp.int8)
    scales = sds((n_pages, Hkv, paged_scale_lanes(P)), jnp.float32)
    text = _compiled_text(
        call, sds((B, 1, H, D), jnp.bfloat16), pool, scales, pool,
        scales, sds((B,), jnp.int32), sds((B, max_pages), jnp.int32),
    )
    assert "tpu_custom_call" in text
    # the pools reach the kernel as they are stored: no operation makes
    # another array of a pool leaf's shape on the way in
    for leaf in (f"s8[{n_pages},{P},{Hkv * D}]",
                 f"f32[{n_pages},{Hkv},{paged_scale_lanes(P)}]"):
        made = [ln for ln in text.splitlines()
                if f"= {leaf}" in ln and " parameter(" not in ln]
        assert not made, made[0]


def test_qwen3_next_tick_leaves_states_and_pools_where_they_lie(one_chip):
    """The whole decode tick of ``serve_q3next_mixed`` (the cell's own
    configuration file: 16 slots, 8 steps, three delta-rule layers and
    one attention layer over held experts), compiled as the scheduler
    would run it on the kernel route. The scan's body moves neither a
    delta-rule state nor a page pool through the compiler's fast memory
    space: the step kernel's result and the paged kernel's pool
    operands are declared to live in HBM. Until PR 43 a step moved 201
    MB of states and 143 MB of pools that way, in and out again (PERF.md
    section 6)."""
    import json
    import pathlib

    from chipbench.runners import serve_gdn
    from mpistragglers_jl_tpu.models import serving
    from mpistragglers_jl_tpu.ops import (
        decode_attention,
        delta_rule,
        flash_attention,
    )

    root = pathlib.Path(__file__).resolve().parent.parent
    config = json.loads((root / "chipbench" / "configs"
                         / "q3next-80b-a3b-serve.json").read_text())
    cfg, program = serve_gdn.transformer_config(config), config["program"]
    sched = serving.ServingScheduler(
        serve_gdn.param_shapes(config), cfg, slots=program["slots"],
        n_inner=program["n_inner"], quantize_kv=program["quantize_kv"],
        page_tokens=program["page_tokens"],
        prompt_chunk=program["prompt_chunk"],
        max_prompt=program["max_prompt"])
    tick = serving._serving_scan_paged(
        cfg, sched.n_inner, None, sched.temperature, None, True, sched.P)
    args = jax.tree.map(lambda a: _sds(one_chip, a.shape, a.dtype),
                        sched._scan_args())
    # as on the chip: the kernels through Mosaic, not the interpreter
    compiled = lambda: False
    with pytest.MonkeyPatch.context() as patch, jax.enable_x64(False):
        for module in (flash_attention, decode_attention, delta_rule):
            patch.setattr(module, "_use_interpret", compiled)
        text = tick.lower(*args).compile().as_text()
    for name in ("delta_rule_step", "paged_decode_attention"):
        assert f"%{name}" in text
    # (the step's row is scattered into a pool by a fusion, in place)
    for held in ("f32[16,32,128,128]", "s8[1089,64,512]"):
        moved = [line for line in text.splitlines() if held in _result_of(
            line, "copy", "copy-start", "slice-start")]
        assert not moved, moved[:3]


# (rows of the call, absorbed query heads, table entries, pool pages):
# Xing4.0's 16 slots of 32 heads on a 68-entry table (4 pages a block),
# DeepSeek-V3's drafting step, two rows a slot of 128 heads on 13
# entries (a page a block)
LATENT_CELLS = [(16, 32, 68, 1089), (32, 128, 13, 209)]


@pytest.mark.parametrize("B,H,max_pages,n_pages", LATENT_CELLS)
def test_latent_kernel_compiles_at_published_widths(one_chip, B, H,
                                                    max_pages, n_pages):
    """The latent form at both cells' widths: one 576-value int8 row a
    position in 640 lanes (a page of 576 lanes of 640 is no copy Mosaic
    takes: ``paged_row_lanes``), split at lane 512 in VMEM, the pool an
    operand as it is stored."""
    from mpistragglers_jl_tpu.ops.decode_attention import (
        latent_decode_attention,
        paged_row_lanes,
    )

    P, R, width = 64, 512, 576
    sds = functools.partial(_sds, one_chip)

    def call(q, k, ks, pos, pt):
        return latent_decode_attention(
            q, {"k": k, "k_s": ks}, pos, pt, scale=192 ** -0.5, P=P, R=R,
            interpret=False)

    text = _compiled_text(
        call, sds((B, 1, H, width), jnp.bfloat16),
        sds((n_pages, P, paged_row_lanes(width)), jnp.int8),
        sds((n_pages, 2, paged_scale_lanes(P)), jnp.float32),
        sds((B,), jnp.int32), sds((B, max_pages), jnp.int32))
    assert "tpu_custom_call" in text and "%paged_latent_attention" in text
    for leaf in (f"s8[{n_pages},{P},640]",
                 f"f32[{n_pages},2,{paged_scale_lanes(P)}]"):
        made = [ln for ln in text.splitlines()
                if f"= {leaf}" in ln and " parameter(" not in ln]
        assert not made, made[0]


def test_xing4_tick_reads_latent_pages_where_they_lie(one_chip):
    """The whole decode tick of ``serve_xing4_mixed`` (the cell's own
    configuration file: 16 slots, 8 steps, five latent layers under four
    residual streams over 64 experts), compiled as the scheduler runs
    it: on the kernel route. The latent kernel is in the program; the
    scan's body carries no layer's pool through the compiler's fast
    memory space (40 MB would fit it: the operands are declared to live
    in HBM, as PR 43's are); and no array of a layer's gathered view
    exists (``s8[16,4352,576]``: until PR 44 every slot's whole ring
    was copied out of the pages and back each tick, 203 MB read a step
    where the slots held 16)."""
    import json
    import pathlib

    from chipbench.runners import serve_mla
    from mpistragglers_jl_tpu.models import serving
    from mpistragglers_jl_tpu.ops import decode_attention, flash_attention

    root = pathlib.Path(__file__).resolve().parent.parent
    config = json.loads((root / "chipbench" / "configs"
                         / "xing4-29b-a4b-serve.json").read_text())
    cfg, program = serve_mla.transformer_config(config), config["program"]
    compiled = lambda: False
    with pytest.MonkeyPatch.context() as patch, jax.enable_x64(False):
        # as on the chip: pages of 64 rows are blocks of the compiled
        # kernel, and the kernels go through Mosaic
        for module in (flash_attention, decode_attention):
            patch.setattr(module, "_use_interpret", compiled)
        sched = serving.ServingScheduler(
            serve_mla.param_shapes(config), cfg, slots=program["slots"],
            n_inner=program["n_inner"], quantize_kv=program["quantize_kv"],
            page_tokens=program["page_tokens"],
            prompt_chunk=program["prompt_chunk"],
            max_prompt=program["max_prompt"])
        assert sched.use_kernel
        args = jax.tree.map(lambda a: _sds(one_chip, a.shape, a.dtype),
                            sched._scan_args())
        text = sched._scan.lower(*args).compile().as_text()
    assert "%paged_latent_attention" in text
    pool = sched._caches[0]["k"].shape
    assert pool == (1089, 64, 640)
    held = "s8[%d,%d,%d]" % pool
    moved = [line for line in text.splitlines() if held in _result_of(
        line, "copy", "copy-start", "slice-start")]
    assert not moved, moved[:3]
    for view in ("s8[16,4352,576]", "s8[16,4352,1,576]", "s8[16,4352,640]",
                 "s8[16,68,64,640]"):
        assert view not in text, view


def test_paged_select_kernel_compiles_at_published_widths(one_chip):
    """MiniCPM-SALA's attention layer: 32 query heads on 2 K/V heads of
    128, a list of 128 pages a slot and K/V head, each head copying in
    its own 128 lanes of a page (a slice of whole lane tiles)."""
    B, P, H, Hkv, D, width, n_pages = 16, 64, 32, 2, 128, 128, 8449
    sds = functools.partial(_sds, one_chip)

    def call(q, k, ks, v, vs, at, pages):
        return paged_select_attention(
            q, {"k": k, "k_s": ks, "v": v, "v_s": vs}, at, pages,
            scale=D ** -0.5, P=P, interpret=False)

    pool = sds((n_pages, P, Hkv * D), jnp.int8)
    scales = sds((n_pages, Hkv, paged_scale_lanes(P)), jnp.float32)
    text = _compiled_text(
        call, sds((B, 1, H, D), jnp.bfloat16), pool, scales, pool, scales,
        sds((B, Hkv), jnp.int32), sds((B, Hkv, width), jnp.int32))
    assert "tpu_custom_call" in text
    for leaf in (f"s8[{n_pages},{P},{Hkv * D}]",
                 f"f32[{n_pages},{Hkv},{paged_scale_lanes(P)}]"):
        made = [ln for ln in text.splitlines()
                if f"= {leaf}" in ln and " parameter(" not in ln]
        assert not made, made[0]


def test_ring_kernel_compiles_at_a_group_of_12(one_chip):
    B, L, H, Hkv, D = 16, 4096, 24, 2, 128
    sds = functools.partial(_sds, one_chip)

    def call(q, k, ks, v, vs, pos):
        return quantized_decode_attention(
            q, {"k": k, "k_s": ks, "v": v, "v_s": vs}, pos, D ** -0.5,
            ring=True, interpret=False,
        )

    text = _compiled_text(
        call, sds((B, 1, H, D), jnp.bfloat16),
        sds((B, L, Hkv, D), jnp.int8), sds((B, L, Hkv), jnp.float32),
        sds((B, L, Hkv, D), jnp.int8), sds((B, L, Hkv), jnp.float32),
        sds((B,), jnp.int32),
    )
    assert "tpu_custom_call" in text


def test_chunked_delta_rule_compiles_at_published_widths(one_chip):
    """A 256-row chunk of 32 value heads of 128 x 128 (the unit
    triangular solves and the products at Precision.HIGHEST, the scan
    over 64-row sub-chunks), and the single-token step for 16 slots."""
    from mpistragglers_jl_tpu.models import transformer as tr

    f32 = lambda *shape: _sds(one_chip, shape, jnp.float32)
    T, H, Dk, Dv = 256, 32, 128, 128
    text = _compiled_text(
        tr._delta_rule_chunks, f32(1, T, H, Dk), f32(1, T, H, Dk),
        f32(1, T, H, Dv), f32(1, T, H), f32(1, T, H), f32(1, H, Dk, Dv))
    assert "while" in text  # the scan that carries S
    _compiled_text(
        tr._delta_rule_step, f32(16, H, Dk), f32(16, H, Dk), f32(16, H, Dv),
        f32(16, H), f32(16, H), f32(16, H, Dk, Dv))


@pytest.mark.parametrize("B", [4, 1])
def test_delta_rule_kernel_compiles_at_published_widths(one_chip, B):
    """The chunked delta rule as ONE kernel (ops/delta_rule.py): the
    grouped program's four chunks and the lone chunk, 256 rows x 16 key
    / 32 value heads of 128 x 128 read out of the conv's 8192-wide rows,
    every product at ``contract_precision<fp32>``, the state aliased
    through. No triangular solve is left for the TPU's own lowering."""
    from mpistragglers_jl_tpu.ops.delta_rule import chunked_delta_rule

    f32 = lambda *shape: _sds(one_chip, shape, jnp.float32)
    T, Hk, Hv, D = 256, 16, 32, 128
    call = functools.partial(
        chunked_delta_rule, Hk=Hk, Hv=Hv, Dk=D, Dv=D, interpret=False)
    text = _compiled_text(
        call, f32(B, T, (2 * Hk + Hv) * D), f32(B, T, Hv), f32(B, T, Hv),
        f32(B, Hv, D, D))
    assert "tpu_custom_call" in text
    assert "InvertDiagBlocksLowerTriangular" not in text
    # q, k and v reach the kernel as the conv left them: no operation
    # makes another array of a head-major or repeated layout on the way
    for shape in (f"f32[{B},{T},{Hv},{D}]", f"f32[{B},{Hv},{T},{D}]"):
        assert f"= {shape}" not in text


def test_delta_step_kernel_leaves_the_state_where_it_lies(one_chip):
    """The single-token kernel (ops/delta_rule.py) for 16 slots of 16 key
    / 32 value heads of 128 x 128 under a scan that carries three
    layers' states, as the tick does: Mosaic takes it, and the compiled
    program moves no ``f32[16,32,128,128]`` (no copy or slice of the
    state into the fast memory and back, which is what the plain step's
    time went to: PERF.md section 6, PR 43): the kernel's operand is the
    scan's carry itself."""
    from mpistragglers_jl_tpu.ops.delta_rule import delta_rule_step

    f32 = lambda *shape: _sds(one_chip, shape, jnp.float32)
    B, Hk, Hv, D = 16, 16, 32, 128
    step = functools.partial(delta_rule_step, interpret=False)

    @functools.partial(jax.jit, donate_argnums=(0,))
    def steps(states, q, k, v, g, beta):
        def body(carry, _):
            states, x = carry
            out = []
            for S in states:
                x, S = step(q, k, v + x, g, beta, S)
                out.append(S)
            return (out, x), None
        return jax.lax.scan(body, (states, jnp.zeros_like(v)), None,
                            length=8)[0]

    with jax.enable_x64(False):
        text = steps.lower(
            [f32(B, Hv, D, D)] * 3, f32(B, Hk, D), f32(B, Hk, D),
            f32(B, Hv, D), f32(B, Hv), f32(B, Hv)).compile().as_text()
    state = f"f32[{B},{Hv},{D},{D}]"
    calls = [line for line in text.splitlines()
             if "tpu_custom_call" in line and state in line]
    assert len(calls) == 3
    for line in calls:  # the operand comes straight out of the carry
        assert "get-tuple-element" in line.split("custom-call(")[1]
    moved = [line for line in text.splitlines() if _result_of(
        line, "copy", "copy-start", "slice-start", "fusion").count(state)]
    assert not moved, moved[:3]


def test_absorbed_latent_decode_compiles_at_published_widths(one_chip):
    """The tick's latent attention for 16 slots over their gathered
    rings of 68 pages x 64 rows: 32 absorbed query heads against one
    576-wide int8 row a position with its two scales, the result the
    row's first 512 dims; and the row's write before it."""
    from mpistragglers_jl_tpu.models import serving

    S, W, H, R, rope = 16, 68 * 64, 32, 512, 64
    sds = functools.partial(_sds, one_chip)

    def call(q, row, k, ks, pos):
        ring = serving._ring_write_rows({"k": k, "k_s": ks}, row, None,
                                        jnp.mod(pos, W), R)
        return serving._ring_attention_rows(
            q, ring, pos, 192 ** -0.5, latent=R), ring

    text = _compiled_text(
        call, sds((S, 1, H, R + rope), jnp.bfloat16),
        sds((S, 1, 1, R + rope), jnp.bfloat16),
        sds((S, W, 1, R + rope), jnp.int8), sds((S, W, 2), jnp.float32),
        sds((S,), jnp.int32))
    # no copy of every slot's ring at its expanded width (32 heads of
    # 256) exists: the rows are read as the one head they are
    assert f"{S},{W},{H}," not in text and f"{S},{H},{W},256" not in text
    assert f"bf16[{S},1,{H},{R}]" in text or f"f32[{S},1,{H},{R}]" in text


def test_chunk_latent_attention_compiles_at_published_widths(one_chip):
    """A prefill chunk's 256 absorbed queries a head walking the key
    blocks of a 4,096-row arena of int8 latent rows (the walk's
    ``fori_loop``, one block's scores at a time)."""
    from mpistragglers_jl_tpu.models import decode

    T, L, H, R, rope = 256, 4096, 32, 512, 64
    sds = functools.partial(_sds, one_chip)

    def call(q, row, k, ks, off):
        cache = decode._cache_write({"k": k, "k_s": ks}, row, None, off, R)
        qpos = off + jnp.arange(T)
        return decode._cached_attention(
            q, cache, qpos, 192 ** -0.5, latent=R), cache

    text = _compiled_text(
        call, sds((1, T, H, R + rope), jnp.bfloat16),
        sds((1, T, 1, R + rope), jnp.bfloat16),
        sds((1, L, 1, R + rope), jnp.int8), sds((1, L, 2), jnp.float32),
        sds((), jnp.int32))
    assert "while" in text  # the walk over key blocks
    # a block's scores, never the arena's: (H, T, 512), not (H, T, 4096)
    assert f"{H},{T},{L}]" not in text


# (batch, length, query heads, K/V heads, head size, causal, window,
# dtype): the training cell's sweep (every kind of tile in one 2048-
# block), the odd length that is one whole block (its lse row is 1001
# lanes wide), and two lengths that no multiple of the 512-tile divides
# and whose callers name no block: 2000 in bfloat16 and 3000 in float32
# are 1000-blocks computed whole, float32 the largest working set the
# estimate admits (15.9 of 16 MiB). All inside the scoped VMEM that
# Mosaic grants unasked: the kernels ask for no more.
FLASH = [
    (2, 8192, 24, 2, 128, True, 4096, jnp.bfloat16),
    (2, 1001, 8, 8, 128, True, None, jnp.bfloat16),
    (1, 2000, 4, 4, 128, True, None, jnp.bfloat16),
    (1, 3000, 4, 2, 128, True, 1024, jnp.float32),
]


@pytest.mark.parametrize("B,L,H,Hkv,D,causal,window,dtype", FLASH)
def test_flash_kernels_compile_for_the_v5e(one_chip, B, L, H, Hkv, D,
                                           causal, window, dtype):
    """Forward, dq and dk/dv (the transposed tiles, the row-shaped lse
    and delta, the loop over compute tiles, the clamped index maps) as
    Mosaic takes them, which the interpreter cannot say."""
    from mpistragglers_jl_tpu.ops.flash_attention import flash_attention

    def grads(q, k, v):
        out, vjp = jax.vjp(
            lambda q, k, v: flash_attention(
                q, k, v, causal=causal, window=window, interpret=False),
            q, k, v)
        return vjp(out)

    text = _compiled_text(
        grads, _sds(one_chip, (B, L, H, D), dtype),
        _sds(one_chip, (B, L, Hkv, D), dtype),
        _sds(one_chip, (B, L, Hkv, D), dtype))
    assert text.count("tpu_custom_call") >= 3


def test_ssm_step_kernel_leaves_the_state_where_it_lies(one_chip):
    """The state-space mixer's single-token kernel (ops/ssm_step.py) for
    16 slots of 32 heads of 256 x 128 in 2 groups (Falcon-H1-34B's)
    under a scan that carries six layers' states, as the tick does:
    Mosaic takes it (a group's 16 heads a grid step, 2 MiB of S), and
    the compiled program moves no ``f32[16,32,256,128]``: the kernel's
    operand is the scan's carry itself."""
    from mpistragglers_jl_tpu.ops.ssm_step import ssm_step

    f32 = lambda *shape: _sds(one_chip, shape, jnp.float32)
    B, H, G, N, P = 16, 32, 2, 256, 128
    step = functools.partial(ssm_step, interpret=False)

    @functools.partial(jax.jit, donate_argnums=(0,))
    def steps(states, x, Bm, Cm, dA, dt):
        def body(carry, _):
            states, y = carry
            out = []
            for S in states:
                y, S = step(x + y, Bm, Cm, dA, dt, S)
                out.append(S)
            return (out, y), None
        return jax.lax.scan(body, (states, jnp.zeros_like(x)), None,
                            length=8)[0]

    with jax.enable_x64(False):
        text = steps.lower(
            [f32(B, H, N, P)] * 6, f32(B, H, P), f32(B, G, N), f32(B, G, N),
            f32(B, H), f32(B, H)).compile().as_text()
    state = f"f32[{B},{H},{N},{P}]"
    calls = [line for line in text.splitlines()
             if "tpu_custom_call" in line and state in line]
    assert len(calls) == 6
    for line in calls:  # the operand comes straight out of the carry
        assert "get-tuple-element" in line.split("custom-call(")[1]
    moved = [line for line in text.splitlines() if _result_of(
        line, "copy", "copy-start", "slice-start", "fusion").count(state)]
    assert not moved, moved[:3]


def test_falcon_h1_tick_leaves_states_and_pools_where_they_lie(one_chip):
    """The whole decode tick of ``serve_falconh1_chat`` (the cell's own
    configuration file: 16 slots, 8 steps, six layers that each hold
    pages AND a state), compiled as the scheduler would run it on the
    kernel route: the paged attention kernel at a group of 5 query heads
    to a K/V head and the step kernel are both there, and the scan's
    body moves neither a layer's states (67 MB) nor a page pool through
    the compiler's fast memory space."""
    import json
    import pathlib

    from chipbench.runners import serve_ssm
    from mpistragglers_jl_tpu.models import serving
    from mpistragglers_jl_tpu.ops import (
        decode_attention,
        flash_attention,
        ssm_step,
    )

    root = pathlib.Path(__file__).resolve().parent.parent
    config = json.loads((root / "chipbench" / "configs"
                         / "falcon-h1-34b-serve.json").read_text())
    cfg, program = serve_ssm.transformer_config(config), config["program"]
    sched = serving.ServingScheduler(
        serve_ssm.param_shapes(config), cfg, slots=program["slots"],
        n_inner=program["n_inner"], quantize_kv=program["quantize_kv"],
        page_tokens=program["page_tokens"],
        prompt_chunk=program["prompt_chunk"],
        max_prompt=program["max_prompt"])
    assert sched.use_kernel
    tick = serving._serving_scan_paged(
        cfg, sched.n_inner, None, sched.temperature, None, True, sched.P)
    args = jax.tree.map(lambda a: _sds(one_chip, a.shape, a.dtype),
                        sched._scan_args())
    # as on the chip: the kernels through Mosaic, not the interpreter
    compiled = lambda: False
    with pytest.MonkeyPatch.context() as patch, jax.enable_x64(False):
        for module in (flash_attention, decode_attention, ssm_step):
            patch.setattr(module, "_use_interpret", compiled)
        text = tick.lower(*args).compile().as_text()
    for name in ("ssm_step", "paged_decode_attention"):
        assert f"%{name}" in text
    for held in ("f32[16,32,256,128]", "s8[193,64,512]"):
        assert held in text
        moved = [line for line in text.splitlines() if held in _result_of(
            line, "copy", "copy-start", "slice-start")]
        assert not moved, moved[:3]


def test_ssm_step_kernel_takes_a_head_of_half_a_lane_tile(one_chip):
    """The same kernel for 16 slots of 128 heads of 128 x 64 in ONE
    group (Granite-4.0-H Small's), two heads a lane tile: the state is
    kept ``f32[16,64,128,128]`` and the kernel runs on 64 heads of 128
    (32 a grid step, 2 MiB of S), under a scan that carries nine
    layers' states as the tick does. Mosaic takes it, and the compiled
    program moves no layer's state: the kernel's operand is the scan's
    carry itself."""
    from mpistragglers_jl_tpu.ops.ssm_step import ssm_state_shape, ssm_step

    f32 = lambda *shape: _sds(one_chip, shape, jnp.float32)
    B, H, G, N, P = 16, 128, 1, 128, 64
    kept = (B,) + ssm_state_shape(H, G, N, P)
    assert kept == (16, 64, 128, 128)
    step = functools.partial(ssm_step, interpret=False)

    @functools.partial(jax.jit, donate_argnums=(0,))
    def steps(states, x, Bm, Cm, dA, dt):
        def body(carry, _):
            states, y = carry
            out = []
            for S in states:
                y, S = step(x + y, Bm, Cm, dA, dt, S)
                out.append(S)
            return (out, y), None
        return jax.lax.scan(body, (states, jnp.zeros_like(x)), None,
                            length=8)[0]

    with jax.enable_x64(False):
        text = steps.lower(
            [f32(*kept)] * 9, f32(B, H, P), f32(B, G, N), f32(B, G, N),
            f32(B, H), f32(B, H)).compile().as_text()
    state = "f32[16,64,128,128]"
    calls = [line for line in text.splitlines()
             if "tpu_custom_call" in line and state in line]
    assert len(calls) == 9
    for line in calls:  # the operand comes straight out of the carry
        assert "get-tuple-element" in line.split("custom-call(")[1]
    moved = [line for line in text.splitlines() if _result_of(
        line, "copy", "copy-start", "slice-start", "fusion").count(state)]
    assert not moved, moved[:3]


def test_granite4h_tick_leaves_states_and_pool_where_they_lie(one_chip):
    """The whole decode tick of ``serve_granite4h_chat`` (the cell's
    own configuration file: 16 slots, 8 steps, nine layers that each
    hold a state and NO page, one that holds pages and no state, 36
    held experts behind each), compiled as the scheduler would run it
    on the kernel route: the step kernel nine times, the paged
    attention kernel once at a group of 4 query heads to a K/V head,
    three grouped products a layer; the scan's body moves neither a
    layer's states (67 MB) nor the page pool through the compiler's
    fast memory space."""
    import json
    import pathlib

    from chipbench.runners import serve_ssm_moe
    from mpistragglers_jl_tpu.models import serving
    from mpistragglers_jl_tpu.ops import (
        decode_attention,
        flash_attention,
        ssm_step,
    )

    root = pathlib.Path(__file__).resolve().parent.parent
    config = json.loads((root / "chipbench" / "configs"
                         / "granite-4.0-h-small-serve.json").read_text())
    cfg, program = serve_ssm_moe.transformer_config(config), config["program"]
    sched = serving.ServingScheduler(
        serve_ssm_moe.param_shapes(config), cfg, slots=program["slots"],
        n_inner=program["n_inner"], quantize_kv=program["quantize_kv"],
        page_tokens=program["page_tokens"],
        prompt_chunk=program["prompt_chunk"],
        max_prompt=program["max_prompt"])
    assert sched.use_kernel
    assert sched._step_route == {"ssm_rule": "kernel"}
    assert sched._layer_kinds == {"state_layers": 9, "row_layers": 1}
    tick = serving._serving_scan_paged(
        cfg, sched.n_inner, None, sched.temperature, None, True, sched.P)
    args = jax.tree.map(lambda a: _sds(one_chip, a.shape, a.dtype),
                        sched._scan_args())
    # as on the chip: the kernels through Mosaic, not the interpreter
    compiled = lambda: False
    with pytest.MonkeyPatch.context() as patch, jax.enable_x64(False):
        for module in (flash_attention, decode_attention, ssm_step):
            patch.setattr(module, "_use_interpret", compiled)
        text = tick.lower(*args).compile().as_text()
    for name in ("ssm_step", "paged_decode_attention"):
        assert f"%{name}" in text
    assert text.count("tpu_custom_call") == 9 + 1 + 3 * 10
    for held in ("f32[16,64,128,128]", "s8[193,64,1024]"):
        assert held in text
        moved = [line for line in text.splitlines() if held in _result_of(
            line, "copy", "copy-start", "slice-start")]
        assert not moved, moved[:3]


def test_wide_prefill_program_compiles_at_the_long_cells_widths(one_chip):
    """``serve_q3next_long``'s scheduler (the cell's own configuration
    file: 16 slots, chunks of 256, prompts of up to 32,768) is the one
    of the benchmark's that holds the wide prefill program, and the
    program compiles as the chip would run it: 1,024 rows of one
    request through the chunked delta-rule kernel in its three state
    layers (whole sub-chunks of 128, two value heads a grid step) and
    three grouped products in each of the four expert layers, the arena
    donated. The same model with ``serve_q3next_mixed``'s program has
    none: a prompt of 4,096 is 16 chunks, and there are 16 slots."""
    import json
    import pathlib

    from chipbench.runners import serve_gdn
    from mpistragglers_jl_tpu.models import serving
    from mpistragglers_jl_tpu.ops import (
        decode_attention,
        delta_rule,
        flash_attention,
    )

    root = pathlib.Path(__file__).resolve().parent.parent / "chipbench"

    def scheduler(name):
        config = json.loads((root / "configs" / name).read_text())
        cfg, program = serve_gdn.transformer_config(config), config["program"]
        return serving.ServingScheduler(
            serve_gdn.param_shapes(config), cfg, slots=program["slots"],
            n_inner=program["n_inner"], quantize_kv=program["quantize_kv"],
            page_tokens=program["page_tokens"],
            prompt_chunk=program["prompt_chunk"],
            max_prompt=program["max_prompt"])

    mixed = scheduler("q3next-80b-a3b-serve.json")
    assert mixed._group == 4 and mixed._extend_wide is None
    sched = scheduler("q3next-80b-a3b-serve-long.json")
    wide, rows = sched._extend_wide, sched._group * sched.C
    assert wide.__name__ == "serving_prefill_chunk_w4" and rows == 1024
    assert sched._wide_routes == {"gdn_rule": "kernel"}
    arena = jax.eval_shape(lambda: serving._fresh_cache(
        sched.cfg, 1, sched.Lmax, sched.quantize_kv))
    args = jax.tree.map(
        lambda a: _sds(one_chip, a.shape, a.dtype),
        (sched.params, jnp.zeros((1, rows), jnp.int32), arena,
         jnp.int32(0), jnp.int32(0)))
    # as on the chip: the kernels through Mosaic, not the interpreter
    compiled = lambda: False
    with pytest.MonkeyPatch.context() as patch, jax.enable_x64(False):
        for module in (flash_attention, decode_attention, delta_rule):
            patch.setattr(module, "_use_interpret", compiled)
        program = wide.lower(*args).compile()
    text = program.as_text()
    assert "%delta_rule" in text
    assert text.count("tpu_custom_call") == 3 + 3 * 4
    # the arena goes in and comes out in place
    memory = program.memory_analysis()
    assert memory.alias_size_in_bytes >= 40e6
    assert memory.temp_size_in_bytes < 1e9
