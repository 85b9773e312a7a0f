"""How much of a long request's cache is read: ``blocks_attended`` over
``blocks_visible``, summed over the traced window's ``serving.tick``
and ``serving.prefill_chunk`` spans (the scheduler's own counts, from
lengths alone: the key blocks that stand for the decoding slots and
the chunks' rows that see more than ``dense_len`` rows, and the blocks
they see, over slots, rows and K/V heads). 100 would be the whole
cache. None where no query of the window saw that many rows. Layer:
model step."""
from chipbench.metrics import _program_spans as ps

SPANS = ("serving.tick", "serving.prefill_chunk")


def read(run):
    spans = ps.load(run)
    if spans is None:
        return None
    counted = [s.args for name in SPANS for s in spans.named(name)
               if "blocks_visible" in s.args]
    visible = sum(float(a["blocks_visible"]) for a in counted)
    if visible <= 0:
        return None
    return 100.0 * sum(float(a["blocks_attended"]) for a in counted) / visible
