"""Share of the train step's device time (``jit_step``) that the
program names: operations under any of its scopes (``embed``,
``attn_qkv``, ``attn_out``, ``ffn``, ``head``, ``loss``,
``sgd_update``) or in a flash kernel (told by name, as
``flash_share_pct`` tells them). What is left is listed by operation in
``note train_step_time_by_scope_ms``. Layer: trainer."""
from chipbench.metrics._scope_time import pct, train_step_time


def read(run):
    t = train_step_time(run)
    return None if t is None else pct(t["any"] + t["kernel"], t)
