"""What the readers of the multi-token-prediction module's metrics
share: the program's own counters on ``serving.tick`` and
``serving.harvest``, summed over the traced window, and the device time
of the tick program under the module's scopes.

The counters are arguments a drafting scheduler puts on its spans
(models/serving.py): ``drafted`` (decode steps of live requests, each
of which verified one draft) and ``accepted`` (those whose draft was
the token) on ``serving.tick``; ``tokens`` (delivered by the tick's
decode steps) on ``serving.harvest``. The scopes are ``mtp`` around
everything the module runs in a step (``mtp_proj``: the two norms and
the projection of their concatenation; the block under the block's own
scopes; ``mtp_head``: its final norm, the head's product and the
draft's pick). A program without them (a parent commit, a scheduler
with the drafter off) gives None everywhere here.
"""

from __future__ import annotations

from chipbench.metrics import _program_spans as ps
from chipbench.metrics import _scope_time
from chipbench.metrics._util import decode_tick_module

HARVEST = "serving.harvest"
SCOPES = ("mtp", "mtp_proj", "mtp_head")


def window_sum(run, span: str, key: str) -> float | None:
    """Sum of one argument over the window's spans of one name; None
    where no such span carries it."""
    spans = ps.load(run)
    if spans is None:
        return None
    values = [float(s.args[key]) for s in spans.named(span) if key in s.args]
    return sum(values) if values else None


def tick_time(run) -> dict | None:
    """The decode tick program's time under the module's scopes
    (``_scope_time.reduce_scopes``'s dictionary)."""
    if run.summary is None:
        return None
    tick = decode_tick_module(run.summary)
    if tick is None:
        return None
    return _scope_time.time_by_scope(
        run, "tick_mtp", lambda name: name == tick, SCOPES, ("mtp",))
