"""The one traffic generator: a mix file's parameters -> requests.

A mix gives each length as a distribution (``{"512": 0.5, ...}``).  So
that every seed does the same amount of work, the generator does not
sample lengths: it takes the distribution's quota of ``n`` requests by
largest remainder (0.5/0.3/0.2 of 8 is 4, 2, 2) and pairs prompt and
answer lengths in ascending order.  A serving round's order is fixed too
(a round with more requests than slots schedules differently in another
order); the seed chooses the prompt tokens.  A relayout cycle's order is
the seed's: its window runs hundreds of cycles.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

import numpy as np


def quota(dist: Dict[str, float], n: int) -> List[int]:
    """The ``n`` values of ``dist`` in proportion, ascending."""
    values = sorted(int(k) for k in dist)
    total = sum(float(dist[str(v)]) for v in values)
    exact = [n * float(dist[str(v)]) / total for v in values]
    counts = [int(np.floor(x)) for x in exact]
    rest = sorted(range(len(values)), key=lambda i: (-(exact[i] - counts[i]), i))
    for i in rest[: n - sum(counts)]:
        counts[i] += 1
    return [v for v, c in zip(values, counts) for _ in range(c)]


def rng(seed: int, stream: str) -> np.random.Generator:
    """A numpy generator for one named use of the seed."""
    tag = [ord(c) for c in stream]
    return np.random.default_rng(np.random.SeedSequence([int(seed)] + tag))


def serve_round(mix: dict, vocab: int, seed: int) -> List[Tuple[np.ndarray, int]]:
    """One round: (prompt tokens int32, answer length incl. the first token)
    per request, in one fixed order; the tokens are the seed's."""
    n = int(mix["requests_per_round"])
    pairs = list(zip(quota(mix["prompt_len"], n), quota(mix["answer_len"], n)))
    order = rng(0, "serve-order").permutation(n)
    g = rng(seed, "serve")
    return [(g.integers(0, vocab, pairs[j][0], dtype=np.int32), pairs[j][1])
            for j in order]


def relayout_requests(mix: dict, seed: int) -> List[int]:
    """Sequence lengths of the cycle of requests, in the seed's order."""
    n = int(mix["requests"])
    lens = quota(mix["seq_len"], n)
    return [lens[j] for j in rng(seed, "relayout").permutation(n)]
