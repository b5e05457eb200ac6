"""Loop kind ``paced``: open loop. The publishers together send
``rate`` messages a second on a fixed schedule: publisher ``p`` of
``n`` owes message ``k`` at ``t0 + (k + p/n) * n / rate``, whether or
not earlier ones have arrived. Latency counts from that due time, and
``late`` collects how long after it each message was really written.
A phase sends exactly the messages due before its end, then one QoS 1
fence, so the count depends on nothing but the rate and the length."""

from __future__ import annotations

import time


async def publisher(pubs, pub: int, phase: int, t0: float, t_end: float,
                    late) -> int:
    import asyncio

    n_pubs = pubs.plan.n_pubs
    period = n_pubs / pubs.plan.traffic["rate"]
    first = t0 + pub * period / n_pubs
    total = int((t_end - first) / period) + 1  # due before t_end
    base = pubs.plan.base(pub, pubs.start)
    _r, w = pubs.conns[pub]
    await asyncio.sleep(max(0.0, first - time.monotonic()))
    seq = 0
    while seq < total:
        now = time.monotonic()
        n_due = min(total, int((now - first) / period) + 1) - seq
        if n_due <= 0:
            await asyncio.sleep(max(0.001, first + seq * period - now))
            continue
        dues = [first + (seq + j) * period for j in range(n_due)]
        late.extend(now - d for d in dues)
        w.write(pubs.frames(pub, phase, base, seq, n_due, dues, False))
        await w.drain()
        seq += n_due
    # the fence: one more message, so the parent knows all were taken
    now = time.monotonic()
    w.write(pubs.frames(pub, phase, base, seq, 1, now, True))
    await w.drain()
    await pubs.await_fence(pub, seq)
    return seq + 1
