"""Warm batches for the match dispatch's shape list.

:meth:`Router.dispatch_shapes` lists one batch — so many cache hits,
so many misses, the deepest miss so many levels — for every program
the dispatch can be asked for. This module turns that list into the
topics of each batch; the device work happens in
``Broker.warm_dispatch``, which drives the REAL ``_begin_device`` /
``_fetch_device`` seams over them, so exactly the production kernel
set compiles: encode → the match's program (walk, cache insert and
merge; one chip keys it by the batch's bucket and the depth, so one
batch of misses a (bucket, depth) and one fully hit batch a bucket
are the whole list) → pack → fan-out expand → bundle → fetch. The
device-loss
rewarm (``Broker.warm_device_path``, docs/ROBUSTNESS.md "Device-loss
recovery") and a harness's warm-up are the same walk.

Pure host planning (no jax imports, nothing to sync). Warm topics are
rooted at ``"\\x00devloss"`` — no real filter matches them (MQTT
topics cannot contain NUL), so a warm batch delivers nothing, and
their match-cache entries are ordinary slots that age out under the
clock sweep.
"""

from __future__ import annotations

import itertools
from typing import Iterable, Iterator, List, Tuple

ROOT = "\x00devloss"


def warm_batches(shapes: Iterable[Tuple[int, int, int]], slots: int
                 ) -> Iterator[Tuple[Tuple[int, int, int], List[str]]]:
    """``(shape, topics)`` for every ``(hits, misses, depth)`` of
    ``shapes``, in their order: ``hits`` topics an earlier batch of
    this walk left in the match cache, then ``misses`` fresh ones, the
    first of them ``depth`` levels deep (the walk compiles for the
    batch's deepest topic; one spine selects the variant, the rest
    stay at two levels).

    ``slots`` is the cache's size. The hits are the head of a *hot*
    list that one batch of fresh topics put there — an extra batch,
    yielded under its own shape ``(0, n, 2)``, before the first shape
    that needs it — and the clock sweep takes a slot for every fresh
    topic since, so the list is laid again once the sweep has reached
    it. With the cache off (``slots`` 0) no shape has hits."""
    shapes = list(shapes)
    fresh = itertools.count()
    n_hot = max((h for h, _m, _d in shapes), default=0)
    hot: List[str] = []
    room = -1  # fresh topics the ring takes before it reaches `hot`
    for shape in shapes:
        hits, misses, depth = shape
        if hits and room < 0:
            hot = [f"{ROOT}/h{next(fresh)}" for _ in range(n_hot)]
            room = slots - n_hot
            yield (0, n_hot, 2), list(hot)
        topics = hot[:hits]
        for i in range(misses):
            tail = ["d"] * ((depth if i == 0 else 2) - 2)
            topics.append("/".join([ROOT, f"m{next(fresh)}"] + tail))
        room -= misses
        yield shape, topics
