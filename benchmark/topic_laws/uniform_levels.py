"""Topic law ``uniform_levels``: ``zipf_levels`` with its skew taken
out. A topic is ``depth`` levels deep, depth uniform in ``depth`` =
[lo, hi], and at each level the word is drawn from the population's
words with equal shares: every topic of a depth is as likely as any
other, as in a fleet whose devices each publish under their own id at
one period. The pool keeps duplicates (at 60 words a level only the
3,600 two-level topics recur in it to speak of)."""

from __future__ import annotations

import random


def pool(params: dict, vocab, seed: int) -> list:
    rng = random.Random(seed ^ 0x5EED70)
    lo, hi = params["depth"]
    return ["/".join(rng.choice(vocab[lvl])
                     for lvl in range(rng.randint(lo, hi)))
            for _ in range(params["pool"])]
