"""Loop kind ``flood``: closed loop. Each publisher sends a burst of
``burst`` messages — QoS 0, the last one a QoS 1 fence — and sends the
next burst when the fence's PUBACK has come back. A slow broker gets
less load. The due time of a message is the instant its burst was
written."""

from __future__ import annotations

import time


async def publisher(pubs, pub: int, phase: int, t0: float, t_end: float,
                    late) -> int:
    """Run publisher ``pub`` from ``t0`` to ``t_end``; return how many
    messages it sent (sequence numbers 0..n-1)."""
    import asyncio

    burst = pubs.plan.traffic["burst"]
    base = pubs.plan.base(pub, pubs.start)
    _r, w = pubs.conns[pub]
    await asyncio.sleep(max(0.0, t0 - time.monotonic()))
    seq = 0
    while True:
        now = time.monotonic()
        if now >= t_end:
            return seq
        w.write(pubs.frames(pub, phase, base, seq, burst, now, True))
        await w.drain()
        seq += burst
        await pubs.await_fence(pub, seq - 1)
