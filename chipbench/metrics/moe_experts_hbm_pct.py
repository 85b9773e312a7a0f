"""The grouped expert products against the chip's memory bandwidth: the
bytes of the experts that got a token (the scheduler's ``experts_hit``
count times one expert's three matrices, chipbench/counts_moe.py, in
every expert layer of every step of every tick) over the device time
under ``moe_experts`` in the tick program. Layer: expert kernel."""
from chipbench import counts_moe
from chipbench.metrics._moe_scopes import mean_experts_hit, tick_time_by_scope
from chipbench.metrics._util import peak


def read(run):
    t, bw = tick_time_by_scope(run), peak(run, "hbm_bytes_per_s")
    hit = mean_experts_hit(run)
    if t is None or bw is None or hit is None or t["moe_experts"] <= 0:
        return None
    cfg = run.config
    layer_step_bytes = counts_moe.experts_hit_bytes(
        hit, d_model=cfg["hidden_size"], d_expert=cfg["moe_intermediate_size"])
    expert_layers = cfg["num_hidden_layers"] - cfg["num_dense_layers"]
    steps = t["runs"] * run.info["n_inner"]
    return 100.0 * layer_step_bytes * expert_layers * steps / (
        t["moe_experts"] * bw)
