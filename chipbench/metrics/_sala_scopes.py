"""What the readers of the linear-attention layers' and of the
selection's metrics share: the device time of a serving program (the
decode tick, or the prefill programs) by ``la_*`` / ``sparse_*`` scope,
beside the two scopes the attention itself stays under.

The scopes are ``jax.named_scope`` names in models/transformer.py,
models/decode.py and models/serving.py: ``la_proj`` (q, k, v and the
gate's projection, the q/k norms, rotary), ``la_rule`` (the recurrence:
one step a slot in the tick, reading and writing ``S``; products over
the chunk's rows in a prefill chunk), ``la_out`` (the output norm, the
gate, the out-projection); ``sparse_pool`` (a row's key into its pooled
cell), ``sparse_select`` (the query against the pooled keys, the
blocks' scores, the pick, and on the kernel's route the list of pages
a head); ``decode_attn`` / ``chunk_attn`` as in every cell. A program
without the new ones (a parent commit, another model) gives None
everywhere here.

The reduction is ``_gdn_scopes.py``'s, with these scopes as its
parameter.
"""

from __future__ import annotations

import functools

from chipbench.metrics import _gdn_scopes

LA_SCOPES = ("la_proj", "la_rule", "la_out")
SELECT_SCOPES = ("sparse_pool", "sparse_select")
SCOPES = LA_SCOPES + SELECT_SCOPES + ("decode_attn", "chunk_attn")
CACHE_KEY = "sala_scopes"


def time_by_scope(run, program: str):
    """{'whole': s, 'runs': n, 'moves': s, 'la_proj': s, ...} for
    "tick" or "chunk"; None where the program carries none of the NEW
    scopes."""
    t = _gdn_scopes.time_by_scope(run, program, scopes=SCOPES,
                                  cache_key=CACHE_KEY, label="sala")
    if t is None or not any(t[s] > 0 for s in LA_SCOPES + SELECT_SCOPES):
        return None
    return t


reduce_scopes = functools.partial(_gdn_scopes.reduce_scopes, scopes=SCOPES)
