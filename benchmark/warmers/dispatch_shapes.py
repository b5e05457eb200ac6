"""Warmer ``dispatch_shapes``: make the served path load every program
its match dispatch can need for a batch the ingress can form, by
asking the program. ``Broker.warm_dispatch`` drives one batch for every
entry of ``Router.dispatch_shapes`` (every miss bucket at every depth,
every reachable triple of batch, hit and miss bucket, up to the
ingress's ``batch_cap`` unique topics) through the broker's own device
seams, of topics no filter matches: nothing is delivered.

This module holds no padding rule: the program owns the list, and its
device-loss rewarm walks the same one. A configuration names it under
``warmers`` (``dispatch_buckets`` is the harness's own copy of the rule
for batches of up to ``2 x batch_size``)."""

from __future__ import annotations

import asyncio


async def warm(node, clock, say) -> int:
    """Returns the batches sent."""
    if not hasattr(node.broker, "warm_dispatch"):
        raise RuntimeError("warmer dispatch_shapes: this program does not "
                           "list its dispatch shapes (Broker.warm_dispatch)")
    slow: list = []
    c0 = clock.compiles
    for secs, shape in node.broker.warm_dispatch(node.ingress.batch_cap):
        slow.append((secs, *shape, clock.compiles - c0))
        c0 = clock.compiles
        await asyncio.sleep(0)
    slow.sort(reverse=True)
    say("warmer dispatch_shapes: slowest batches (seconds, hits, misses, "
        f"depth, programs first used): "
        f"{[(round(s, 3), *r) for s, *r in slow[:6]]}"
        f"; median {slow[len(slow) // 2][0]:.3f}s")
    return len(slow)
