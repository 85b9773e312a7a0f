"""Share of the drafts a decode step verified that it accepted: the
window's sum of ``accepted`` over its sum of ``drafted``, the
scheduler's own counts on ``serving.tick`` (a draft counts where the
token it guessed was delivered). With seeded random weights at
temperature 1 the module's logits and the model's are independent and
the shared noise alone makes them agree, about 40 in 100; a trained
model's authors report 85 to 90. Layer: scheduler (host)."""
from chipbench.metrics._mtp_scopes import window_sum
from chipbench.metrics._program_spans import TICK


def read(run):
    drafted = window_sum(run, TICK, "drafted")
    accepted = window_sum(run, TICK, "accepted")
    if not drafted or accepted is None:
        return None
    return 100.0 * accepted / drafted
