"""Topic law ``interleave``: the pool of the ``main`` law with every
``every``-th position taken from the pool of the ``background`` law
instead — a steady trickle of other traffic beside the main stream.
Both parts are laws of this directory with their own parameters."""

from __future__ import annotations

import importlib


def pool(params: dict, vocab, seed: int) -> list:
    n, every = params["pool"], params["every"]

    def part(which: str) -> list:
        law = dict(params[which], pool=n)
        return importlib.import_module(
            "topic_laws." + law["law"]).pool(law, vocab, seed)

    out = part("main")
    back = part("background")
    for i in range(every - 1, n, every):
        out[i] = back[i]
    return out
