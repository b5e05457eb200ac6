"""Topic law ``zipf_levels``: a topic is ``depth`` levels deep, depth
uniform in ``depth`` = [lo, hi], and at each level the word is drawn
from the population's words with a Zipf-like law of exponent ``a``
(copied from ``bench.py::zipf_choice``). The pool keeps duplicates: a
head topic fills many positions, which is the law."""

from __future__ import annotations

import random


def _zipf(rng, items, a):
    n = len(items)
    while True:
        k = int(rng.paretovariate(a)) - 1
        if k < n:
            return items[k]


def pool(params: dict, vocab, seed: int) -> list:
    rng = random.Random(seed ^ 0x5EED70)
    lo, hi = params["depth"]
    a = params["a"]
    return ["/".join(_zipf(rng, vocab[lvl], a)
                     for lvl in range(rng.randint(lo, hi)))
            for _ in range(params["pool"])]
