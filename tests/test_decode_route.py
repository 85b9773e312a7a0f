"""The decode route as a table: which inputs send single-query cached
attention through the int8 Pallas kernel, which through the einsum.

Nothing sets the route (models/decode.py's module note): the dense
programs resolve ``_kernel_possible(cfg, quantize_kv) and
_route_kernel(B)``, the paged serving tick ``_paged_kernel_possible(cfg,
quantize_kv, page_tokens) and _route_kernel(slots)``, and a
``ServingScheduler`` publishes what it resolved as ``use_kernel``.
"""

import functools

import pytest

from mpistragglers_jl_tpu.models.decode import (
    KERNEL_MIN_BATCH,
    _kernel_possible,
    _paged_kernel_possible,
    _route_kernel,
)
from mpistragglers_jl_tpu.models.serving import ServingScheduler
from mpistragglers_jl_tpu.models.transformer import (
    TransformerConfig,
    init_params,
)

W = 128  # every case's window: two pages of 64, or one page of 128


@functools.lru_cache(maxsize=None)
def _model(head_dim: int, n_heads: int, kv_heads: int):
    cfg = TransformerConfig(
        vocab=17, d_model=head_dim * n_heads, n_heads=n_heads,
        n_kv_heads=kv_heads, n_layers=1, d_ff=32, attn_window=W,
    )
    assert cfg.head_dim == head_dim
    return cfg, init_params(cfg, seed=1)


# (quantize_kv, head_dim, n_heads, kv_heads, rows, page_tokens) -> kernel?
# rows: the dense program's batch and the scheduler's slots;
# page_tokens None: the dense programs' rings (the generators, the
# sharded tick), and the scheduler at one page a window
ROUTES = {
    "ring_at_min_batch": (True, 128, 2, 1, KERNEL_MIN_BATCH, None, True),
    "ring_under_min_batch": (
        True, 128, 2, 1, KERNEL_MIN_BATCH - 1, None, False),
    "ring_one_row": (True, 128, 2, 1, 1, None, False),
    "ring_bfloat16_cache": (False, 128, 2, 1, 16, None, False),
    "ring_head_size_8": (True, 8, 8, 2, 8, None, False),
    "paged_16_slots": (True, 128, 2, 1, 16, 64, True),
    "paged_at_min_batch": (True, 128, 2, 1, KERNEL_MIN_BATCH, 64, True),
    "paged_under_min_batch": (
        True, 128, 2, 1, KERNEL_MIN_BATCH - 1, 64, False),
    "paged_bfloat16_cache": (False, 128, 2, 1, 16, 64, False),
    "paged_head_size_64": (True, 64, 4, 2, 16, 64, False),
    "paged_head_size_256": (True, 256, 2, 1, 16, 64, True),
    "paged_starcoder2_group_of_12": (True, 128, 12, 1, 16, 64, True),
    "paged_group_of_3": (True, 128, 6, 2, 16, 64, True),
    "paged_page_of_4_rows": (True, 128, 12, 1, 16, 4, False),
}


@pytest.mark.parametrize("case", ROUTES)
def test_decode_route_table(case):
    quantize_kv, head_dim, n_heads, kv_heads, rows, P, kernel = ROUTES[case]
    cfg, params = _model(head_dim, n_heads, kv_heads)
    if P is None:
        assert (_kernel_possible(cfg, quantize_kv)
                and _route_kernel(rows)) == kernel
        P = W
    possible = _paged_kernel_possible(cfg, quantize_kv, P)
    # what a page adds can only refuse
    assert _kernel_possible(cfg, quantize_kv) or not possible
    assert (possible and _route_kernel(rows)) == kernel
    sched = ServingScheduler(
        params, cfg, slots=rows, n_inner=2, prompt_chunk=16,
        max_prompt=32, quantize_kv=quantize_kv, page_tokens=P,
    )
    assert sched.use_kernel == kernel
