"""Request traffic for cells that serve generation: a stream of
``(prompt tokens, max_new_tokens)`` from a workload file's ``inputs``
entry and ``--seed``. (A module beside ``traffic.py``, not a ``KINDS``
entry inside it: a PR that edits a file the benchmark already has gets
every cell measured anew.)

Every seed gets the SAME work in another order, so that the seed changes
which request goes where and not how much work a stretch of the stream
holds. The entry's ``table`` = g * g sizes are the quantiles of its two
clipped lognormal lengths, laid out as g rounds of g requests: every
round holds one prompt of each g-tile of the prompt lengths and one
output of each g-tile of the output lengths, and over the g rounds every
prompt g-tile meets every output g-tile once (a Latin square), so the two
lengths are independent and every round is nearly the same work. The
stream is that table over and over; the seed draws the order of the
rounds of each pass and the order inside each round. Token ids are
uniform over ``[token_low, token_high)`` and independent from request to
request: no two prompts share a prefix.
"""

from __future__ import annotations

import math
from statistics import NormalDist

import numpy as np


def lognormal_quantiles(spec: dict, n: int) -> np.ndarray:
    """``n`` whole lengths: the quantiles ``(i + 0.5) / n`` of a
    lognormal with the given ``median`` and ``sigma``, clipped to
    ``[min, max]``."""
    z = np.array([NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)])
    lengths = np.rint(float(spec["median"])
                      * np.exp(float(spec["sigma"]) * z))
    return np.clip(lengths, int(spec["min"]), int(spec["max"])).astype(
        np.int64)


def size_table(spec: dict) -> np.ndarray:
    """``[table, 2]`` of ``(prompt length, max_new_tokens)``, the same
    for every seed: row ``r * g + k`` is round ``r``'s request from the
    ``k``-th g-tile of the prompt lengths."""
    n = int(spec["table"])
    g = math.isqrt(n)
    if g * g != n:
        raise ValueError(f"table has to be a square number, not {n}")
    prompt = np.sort(lognormal_quantiles(spec["prompt"], n))
    output = np.sort(lognormal_quantiles(spec["output"], n))

    def rank(tile: int, r: int) -> int:
        # neighbouring g-tiles run through their ranks in opposite
        # directions, so that a round's total hardly moves with r
        return r if tile % 2 == 0 else g - 1 - r

    rows = []
    for r in range(g):
        for k in range(g):
            j = (k + r) % g                     # the output g-tile it meets
            rows.append((prompt[k * g + rank(k, r)],
                         output[j * g + rank(j, r)]))
    return np.asarray(rows, np.int64)


class RequestStream:
    """Request ``k`` of the seed's stream, for any ``k``: made when asked
    for, the same whenever asked."""

    def __init__(self, spec: dict, seed: int):
        self.spec, self.seed = spec, int(seed)
        self.table = size_table(spec)
        self._orders: dict = {}

    def size(self, k: int) -> tuple:
        n = len(self.table)
        g = math.isqrt(n)
        epoch, within = divmod(int(k), n)
        if epoch not in self._orders:
            rng = np.random.default_rng([self.seed, 0, epoch])
            rounds = rng.permutation(g)
            self._orders[epoch] = np.concatenate(
                [r * g + rng.permutation(g) for r in rounds])
        prompt_len, max_new = self.table[self._orders[epoch][within]]
        return int(prompt_len), int(max_new)

    def request(self, k: int) -> tuple:
        """``(prompt int32 array, max_new_tokens)``."""
        prompt_len, max_new = self.size(k)
        rng = np.random.default_rng([self.seed, 1, int(k)])
        prompt = rng.integers(int(self.spec["token_low"]),
                              int(self.spec["token_high"]),
                              size=prompt_len, dtype=np.int32)
        return prompt, max_new
