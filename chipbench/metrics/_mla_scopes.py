"""What the readers of the latent-attention layers' and of the residual
mixing's metrics share: the device time of a serving program (the
decode tick, or the prefill programs) by ``mla_*`` / ``hc_mix`` scope.

The scopes are ``jax.named_scope`` names in models/transformer.py and
models/decode.py: ``mla_q`` (the query's down- and up-projection, its
norm, rotary, the absorption of the key's up-projection), ``mla_kv``
(the latent's down-projection, its norm, the shared key's rotary),
``mla_attn`` (the row's write into the cache, scores, softmax, the
probabilities' product with the rows), ``mla_out`` (the value's
up-projection of the result and the out-projection) and ``hc_mix``
(the streams' norm, the product with ``phi``, the Sinkhorn rounds, the
mix a half reads and the way its result goes back). A program without
them (a parent commit, or a model without such a layer) gives None
everywhere here.

The reduction is ``_gdn_scopes.py``'s, with these scopes as its
parameter.
"""

from __future__ import annotations

import functools

from chipbench.metrics import _gdn_scopes

SCOPES = ("mla_q", "mla_kv", "mla_attn", "mla_out", "hc_mix")
CHUNK_PROGRAM = _gdn_scopes.CHUNK_PROGRAM
CACHE_KEY = "mla_scopes"

# {'whole': s, 'runs': n, 'mla_q': s, ...} for "tick" or "chunk"
time_by_scope = functools.partial(
    _gdn_scopes.time_by_scope, scopes=SCOPES, cache_key=CACHE_KEY,
    label="mla")
reduce_scopes = functools.partial(_gdn_scopes.reduce_scopes, scopes=SCOPES)
