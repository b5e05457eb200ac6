"""Warmer ``dispatch_buckets``: make the served path load every program
its match dispatch can need for a batch the ingress can form, through
the broker's own ``publish_batch``.

The dispatch pads the batch, its cache hits and its cache misses each
to a power of two and walks to the depth of the deepest miss, and
every combination is a program of its own. Real traffic meets the rare
combinations for minutes (on the chip a new one still turned up in
most 3-second rounds after forty seconds), so the cell's own traffic
cannot warm them all; this walks the combinations instead: one batch
for every depth of every miss bucket, and one for every triple of
batch, hit and miss bucket that a batch can fall into, of topics that
no filter matches — nothing is delivered.

This module knows the program's padding rule (``min_batch``, powers of
two, ``ingress.batch_size``); a configuration names it under
``warmers``. When the program can list its own shapes, a warmer that
asks it replaces this one (PERF.md §7)."""

from __future__ import annotations

import asyncio
import time


async def warm(node, clock, say) -> int:
    """Returns the batches sent."""
    from emqx_tpu.types import Message

    # a batch can pass the ingress's batch size by one read's worth
    floor = node.router.config.min_batch
    top = floor
    while top < 2 * node.ingress.batch_size:
        top *= 2

    def pad(n: int) -> int:
        b = floor
        while b < n:
            b *= 2
        return b

    buckets = []
    b = floor
    while b <= top:
        buckets.append(b)
        b *= 2
    # the fewest and the most topics that pad to each bucket
    ends = {b: (1 if b == floor else b // 2 + 1, b) for b in buckets}
    depths = list(range(2, max(node.router.observed_levels() + [2]) + 1))
    fresh = iter(range(1 << 30))
    plan = [(0, top, 2)]  # fill the cache with the topics to hit later
    # the walk: every miss bucket at every depth
    plan += [(0, ends[mb][0], d) for mb in buckets for d in depths]
    # the merge: every (batch, hit, miss) bucket triple that can occur
    done = set()
    for hb in buckets:
        for mb in [0] + buckets:
            for h in ends[hb]:
                for m in ends[mb] if mb else (0,):
                    key = (pad(h + m), hb, mb)
                    if h + m <= top and key not in done:
                        done.add(key)
                        plan.append((h, m, depths[-1]))
    seen: list = []
    slow: list = []
    for h, m, depth in plan:
        topics = seen[:h]
        for i in range(m):
            tail = ["d"] * ((depth if i == 0 else 2) - 2)
            topics.append("/".join(
                ["bench-warm", f"m{next(fresh)}"] + tail))
        if (h, m) == (0, top):
            seen = list(topics)
        t0, c0 = time.monotonic(), clock.compiles
        node.broker.publish_batch(
            [Message(topic=t, payload=b"") for t in topics])
        slow.append((time.monotonic() - t0, h, m, depth,
                     clock.compiles - c0))
        await asyncio.sleep(0)
    slow.sort(reverse=True)
    say("warmer dispatch_buckets: slowest batches (seconds, hits, misses, "
        f"depth, programs first used): "
        f"{[(round(s, 3), *r) for s, *r in slow[:6]]}"
        f"; median {slow[len(slow) // 2][0]:.3f}s")
    return len(plan)
