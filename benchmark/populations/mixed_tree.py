"""Population kind ``mixed_tree``: BASELINE.json configs 2 and 3 blended
(copied from ``bench.py::build_filters``, which later PRs may edit).

``filters`` distinct topic filters over a ``levels``-deep tree with
``words_per_level`` words ``w<level>_<i>`` at each level, depth 2 to
``levels``; a share ``plus`` of them has one level replaced by ``+``,
a share ``hash`` is cut at a random level and ended by ``#``, the rest
are literal. Everything comes from the seed."""

from __future__ import annotations

import random


def build(params: dict, seed: int):
    """-> (filters, vocab): the list of filters and the words of each
    level."""
    rng = random.Random(seed)
    n = params["filters"]
    levels = params["levels"]
    p_plus = params["mix"]["plus"]
    p_hash = p_plus + params["mix"]["hash"]
    words = vocab(params)
    lo = 1 if levels == 1 else 2
    filters: set = set()
    while len(filters) < n:
        depth = rng.randint(lo, levels)
        ws = [rng.choice(words[i]) for i in range(depth)]
        r = rng.random()
        if r < p_plus:
            ws[rng.randrange(depth)] = "+"
        elif r < p_hash:
            ws = ws[: rng.randint(1, depth)] + ["#"]
        filters.add("/".join(ws))
    # a set's order depends on the interpreter's string hashing, which
    # differs between processes; the trie child and the broker must
    # see one population
    return sorted(filters), words


def vocab(params: dict):
    """The words of each level alone (the generator needs no filter)."""
    return [[f"w{lvl}_{i}" for i in range(params["words_per_level"])]
            for lvl in range(params["levels"])]
