"""The one general generator of request traffic: a traffic file of
parameters in, the same requests in the same order for every seed out.

A mix is a list of classes, each with a share of the requests and a
distribution of prompt and output lengths. One **round** holds ``round``
requests: for each class its share of them, with the mid-quantile points
of the class's prompt and output distributions as lengths (paired by a
fixed stride, so that the pairs, too, are fixed). Inside a round the
requests stand in one fixed order that spreads short and long evenly
(sorted by length, then taken in bit-reversed order). The traffic is
that round, again and again, ``rounds`` times.

The seed decides what the prompts contain (and, in the runner, the
weights); it does not decide which request follows which. The scheduler
under test takes its decisions tick by tick from lengths alone (decoding
is greedy and there is no end-of-sequence token), so with the order
fixed every seed's window holds the same ticks doing the same work, and
what is left between runs is timing. Two earlier designs did not give
that (my chip runs, PR 23; PERF.md section 6): lengths shuffled by the
seed read 385 to 399 tokens/s on the chat mix in 30 s windows, because
what was in flight when the window opened and closed differed; the
fixed round rotated by the seed still read 271 to 280 on the mixed mix,
because after two rounds of warm-up the schedules of different rotations
had not met.
"""

from __future__ import annotations

import math

from chipbench.common import quantile_points, seeded_rng


def _stride(n: int) -> int:
    """A stride near n * 0.618 that is coprime with n: index i of the
    prompt points pairs with index (i * stride) % n of the outputs."""
    s = max(1, int(n * 0.6180339887))
    while math.gcd(s, n) != 1:
        s += 1
    return s


def _bit_reversed(n: int) -> list[int]:
    """0..n-1 in the order of their bit-reversed value: neighbours in
    the sorted list end up far apart."""
    bits = max(1, (n - 1).bit_length())
    return sorted(range(n), key=lambda i: int(format(i, f"0{bits}b")[::-1], 2))


def one_round(traffic: dict) -> list[tuple[int, int, int]]:
    """The round's (class, prompt_len, output_len) triples in their
    fixed order."""
    size = int(traffic["round"])
    pairs = []
    for ci, c in enumerate(traffic["classes"]):
        n = int(round(size * c["share"]))
        lo, hi, dist = c["prompt"]
        prompts = quantile_points(lo, hi, n, dist)
        lo, hi, dist = c["output"]
        outputs = quantile_points(lo, hi, n, dist)
        stride = _stride(n)
        pairs += [
            (ci, prompts[i], outputs[(i * stride) % n]) for i in range(n)
        ]
    if len(pairs) != size:
        raise ValueError(
            f"the classes' shares give {len(pairs)} requests to a round "
            f"of {size}"
        )
    pairs.sort(key=lambda p: (p[2], p[1], p[0]))
    return [pairs[i] for i in _bit_reversed(size)]


def ordered_requests(traffic: dict) -> list[tuple[int, int, int]]:
    return one_round(traffic) * int(traffic["rounds"])


def prompts_for(requests, vocab: int, seed: int):
    """Seeded token contents, one numpy array per request."""
    import numpy as np

    rng = seeded_rng(seed, 12)
    flat = rng.integers(
        0, vocab, sum(r[1] for r in requests), dtype=np.int32
    )
    out, at = [], 0
    for _, plen, _ in requests:
        out.append(flat[at:at + plen])
        at += plen
    return out
