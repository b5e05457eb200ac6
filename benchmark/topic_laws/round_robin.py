"""Topic law ``round_robin``: the pool is the configuration's list of
``topics`` repeated in order, started at an offset drawn from the
seed; every topic takes the same share of the traffic."""

from __future__ import annotations

import random


def pool(params: dict, vocab, seed: int) -> list:
    topics = params["topics"]
    off = random.Random(seed ^ 0x5EED70).randrange(len(topics))
    return [topics[(off + i) % len(topics)]
            for i in range(params["pool"])]
