"""Share of a layer's experts that got at least one token in a decode
step: the ``experts_hit`` argument of ``serving.harvest`` (the tick's
own count, a mean over its expert layers and steps) over the number of
experts. 16 slots x 8 experts a token falling evenly on 128 experts
would hit 63%. Layer: router."""
from chipbench.metrics._moe_scopes import mean_experts_hit


def read(run):
    hit = mean_experts_hit(run)
    if hit is None:
        return None
    return 100.0 * hit / run.config["num_experts"]
