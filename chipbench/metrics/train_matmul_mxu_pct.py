"""The train step's weight products against the bf16 peak: the
operations of the projections', the feed-forward's and the head's
products, forward and twice that (chipbench/counts_train_scopes.py),
over the device time under the five scopes that hold them
(``attn_qkv``, ``attn_out``, ``ffn``, ``head``, ``loss``). Norms,
activations, rotary and the loss's reductions run under the same scopes
and count against the share, whatever implements the products. ``note
train_matmul_mxu_pct_by_family`` gives the three families apart.
Layer: trainer."""
from chipbench import counts_train_scopes
from chipbench.metrics._scope_time import program_steps, train_step_time
from chipbench.metrics._util import peak
from chipbench.runners import _model

FAMILIES = {
    "projections": ("attn_qkv", "attn_out"),
    "feed_forward": ("ffn",),
    "head": ("head", "loss"),
}


def read(run):
    t, flops = train_step_time(run), peak(run, "bf16_flops_per_s")
    if t is None or flops is None:
        return None
    steps = program_steps(run, lambda name: name.startswith("jit_step"))
    need = counts_train_scopes.matmul_train_flops(
        batch=run.traffic["batch"], seq=run.traffic["seq"],
        **_model.sizes(run.config))
    seconds = {f: sum(t["scope"][s] for s in scopes)
               for f, scopes in FAMILIES.items()}
    if steps <= 0 or min(seconds.values()) <= 0:
        return None
    share = lambda f, s: 100.0 * f * steps / (s * run.summary.chips * flops)
    print("note train_matmul_mxu_pct_by_family " + " ".join(
        f"{f}={share(need[f], seconds[f]):.2f}" for f in FAMILIES
    ) + f" steps={steps:.2f}", flush=True)
    return share(sum(need.values()), sum(seconds.values()))
