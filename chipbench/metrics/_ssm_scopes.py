"""What the readers of the state-space mixer's metrics share: the
device time of a serving program (the decode tick, or the prefill
programs) by ``ssm_*`` scope.

The scopes are ``jax.named_scope`` names in models/transformer.py
(``ssm_half``): ``ssm_proj`` (the in-projection and its five
multipliers), ``ssm_conv`` (the causal depthwise conv, its bias and
silu, the rows kept for the next call), ``ssm_rule`` (dt, the decay and
the recurrence: one step a slot in the tick, reading and writing ``S``
where it lies; products over the chunk's rows in a prefill chunk; the
skip), ``ssm_out`` (the gate, the grouped norm, the out-projection and
its multiplier). A program without them (a parent commit, another
model) gives None everywhere here.

The reduction is ``_gdn_scopes.py``'s, with these scopes as its
parameter.
"""

from __future__ import annotations

import functools

from chipbench.metrics import _gdn_scopes

SCOPES = ("ssm_proj", "ssm_conv", "ssm_rule", "ssm_out")
CACHE_KEY = "ssm_scopes"


def time_by_scope(run, program: str):
    """{'whole': s, 'runs': n, 'moves': s, 'ssm_proj': s, ...} for
    "tick" or "chunk"; None where the program carries no such scope."""
    t = _gdn_scopes.time_by_scope(run, program, scopes=SCOPES,
                                  cache_key=CACHE_KEY, label="ssm")
    if t is None or not any(t[s] > 0 for s in SCOPES):
        return None
    return t


reduce_scopes = functools.partial(_gdn_scopes.reduce_scopes, scopes=SCOPES)
