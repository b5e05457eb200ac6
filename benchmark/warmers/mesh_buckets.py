"""Warmer ``mesh_buckets``: what ``dispatch_buckets`` does for one chip,
for a node with a ``[matcher] mesh``: make the served path load every
program its mesh dispatch can need for a batch the ingress can form,
through the broker's own ``publish_batch``.

The mesh pads by another rule than one chip. The batch and its cache
misses each pad to a power of two from ``min_batch x data`` up (a
bucket has to split evenly over the ``data`` axis), its cache hits to
a power of two from 8 up, and the misses alone take the collective
step (``parallel/sharded.py::publish_step``), which is a program for
each miss bucket; the merge is a program for each triple of batch, hit
and miss bucket. There is no depth axis: the mesh encodes every batch
at ``max_levels``, so the deepest miss changes no shape. The step is
also keyed by the learned ``boost_k`` / ``boost_d``: the harness runs
its first rounds of the cell's own traffic before the warmers, so the
sweep walks at the boosts that traffic reached, and a boost learned
later shows as programs first used in a later warm round. This walks
one batch for every triple that can occur, of topics that no filter
matches: nothing is delivered.

This module knows the program's padding rule (``min_batch``, the mesh's
``data`` axis, the match cache's floor of 8, ``ingress.batch_size``); a
configuration names it under ``warmers``. On a node without a mesh it
has nothing to walk and says so by raising."""

from __future__ import annotations

import asyncio
import time

#: ops/match_cache.py pads a batch's hits to a power of two from here
HIT_FLOOR = 8


def _buckets(floor: int, top: int) -> list:
    out = []
    while floor <= top:
        out.append(floor)
        floor *= 2
    return out


def _pad(n: int, floor: int) -> int:
    while floor < n:
        floor *= 2
    return floor


async def warm(node, clock, say) -> int:
    """Returns the batches sent."""
    from emqx_tpu.types import Message

    cfg = node.router.config
    if cfg.mesh is None:
        raise RuntimeError("warmer mesh_buckets: the node has no mesh")
    unit = cfg.min_batch * cfg.mesh.shape["data"]
    # a batch can pass the ingress's batch size by one read's worth
    top = _pad(2 * node.ingress.batch_size, unit)
    miss_buckets = _buckets(unit, top)
    hit_buckets = _buckets(HIT_FLOOR, top)
    # the fewest and the most topics that pad to each bucket
    miss_ends = {b: (1 if b == unit else b // 2 + 1, b) for b in miss_buckets}
    hit_ends = {b: (0 if b == HIT_FLOOR else b // 2 + 1, b)
                for b in hit_buckets}
    fresh = iter(range(1 << 30))
    plan = [(0, top)]  # fill the cache with the topics to hit later
    done = {(top, HIT_FLOOR, top)}
    for hb in hit_buckets:
        for mb in [0] + miss_buckets:
            for h in hit_ends[hb]:
                for m in miss_ends[mb] if mb else (0,):
                    key = (_pad(h + m, unit), hb, mb)
                    if 0 < h + m <= top and key not in done:
                        done.add(key)
                        plan.append((h, m))
    seen: list = []
    slow: list = []
    boosts = (node.router.effective_k(), node.router.effective_d())
    for h, m in plan:
        topics = seen[:h] + [f"bench-warm/m{next(fresh)}" for _ in range(m)]
        if (h, m) == (0, top):
            seen = list(topics)
        t0, c0 = time.monotonic(), clock.compiles
        node.broker.publish_batch(
            [Message(topic=t, payload=b"") for t in topics])
        slow.append((time.monotonic() - t0, h, m, clock.compiles - c0))
        await asyncio.sleep(0)
    slow.sort(reverse=True)
    say(f"warmer mesh_buckets: unit {unit}, buckets up to {top}, walked at "
        f"k={boosts[0]} d={boosts[1]}; slowest batches (seconds, hits, "
        f"misses, programs first used): "
        f"{[(round(s, 3), *r) for s, *r in slow[:6]]}"
        f"; median {slow[len(slow) // 2][0]:.3f}s")
    return len(plan)
