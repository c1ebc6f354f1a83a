"""Request traffic for cells that ask questions of a few long documents:
a stream of ``(document + suffix tokens, max_new_tokens)`` from a workload
file's ``inputs`` entry and ``--seed``. (A module beside
``traffic_requests.py``, whose table it lays out, for the reason that one
gives: no file the benchmark already has changes.)

The entry names ``g`` documents by length and a ``table`` of ``g * g``
sizes: the quantiles of its two clipped lognormal lengths (a request's own
suffix, its answer) laid out by ``traffic_requests.size_table`` as ``g``
rounds of ``g`` requests, every round holding one suffix and one answer of
each g-tile, and here also EVERY DOCUMENT ONCE (request ``k`` of round
``r`` asks document ``(3k + r) mod g``, which over the rounds meets every
g-tile of the suffixes). So every round is nearly the same work, and a
seed changes the order — of the rounds in each pass over the table and of
the requests inside each round — and not the work. Token ids are uniform
over ``[token_low, token_high)``: a document's are drawn from the seed
and its index, the same whenever asked for, a suffix's from the seed and
the request's number, so two requests share exactly their document.
"""

from __future__ import annotations

import math

import numpy as np

from benchmark import traffic_requests


def size_table(spec: dict) -> np.ndarray:
    """``[table, 3]`` of ``(document index, suffix length,
    max_new_tokens)``, the same for every seed."""
    docs = [int(n) for n in spec["documents"]]
    g = len(docs)
    if g * g != int(spec["table"]):
        raise ValueError(f"table has to be the documents' count squared "
                         f"({g * g}), not {spec['table']}")
    if math.gcd(3, g) != 1:
        raise ValueError("the documents' count may not be a multiple of 3")
    sizes = traffic_requests.size_table({**spec, "prompt": spec["suffix"]})
    doc = [(3 * k + r) % g for r in range(g) for k in range(g)]
    return np.column_stack([np.asarray(doc, np.int64), sizes])


class DocQAStream:
    """Request ``k`` of the seed's stream, for any ``k``: made when asked
    for, the same whenever asked."""

    def __init__(self, spec: dict, seed: int):
        self.spec, self.seed = spec, int(seed)
        self.table = size_table(spec)
        self.doc_lens = [int(n) for n in spec["documents"]]
        self._orders: dict = {}
        self._docs: dict = {}

    def _ids(self, rng, n: int) -> np.ndarray:
        return rng.integers(int(self.spec["token_low"]),
                            int(self.spec["token_high"]), size=n,
                            dtype=np.int32)

    def document(self, d: int) -> np.ndarray:
        if d not in self._docs:
            self._docs[d] = self._ids(
                np.random.default_rng([self.seed, 2, int(d)]),
                self.doc_lens[d])
        return self._docs[d]

    def size(self, k: int) -> tuple:
        """``(document index, suffix length, max_new_tokens)``."""
        n = len(self.table)
        g = len(self.doc_lens)
        epoch, within = divmod(int(k), n)
        if epoch not in self._orders:
            rng = np.random.default_rng([self.seed, 0, epoch])
            self._orders[epoch] = np.concatenate(
                [r * g + rng.permutation(g) for r in rng.permutation(g)])
        return tuple(int(v) for v in self.table[self._orders[epoch][within]])

    def request(self, k: int) -> tuple:
        """``(prompt int32 array: the document then the suffix,
        max_new_tokens)``."""
        d, suffix_len, max_new = self.size(k)
        suffix = self._ids(np.random.default_rng([self.seed, 1, int(k)]),
                           suffix_len)
        return np.concatenate([self.document(d), suffix]), max_new
