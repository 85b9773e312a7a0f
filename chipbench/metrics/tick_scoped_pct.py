"""Share of the decode tick program's device time under any of the
program's scopes, the block's own (``embed``, ``attn_qkv``,
``attn_out``, ``ffn``, ``head``) or the older ones (``decode_attn``,
``decode_mlp``, ``kv_page_*``, ``moe_*``, ``gdn_*``, ``mla_*``,
``hc_mix``). What is left is listed by operation in ``note
tick_time_by_scope_ms``. Layer: model step."""
from chipbench.metrics._scope_time import pct, tick_time


def read(run):
    t = tick_time(run)
    return None if t is None else pct(t["any"], t)
