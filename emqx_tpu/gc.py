"""Forced garbage-collection policies.

Mirrors ``src/emqx_gc.erl`` (per-connection: force a collection after
N messages / M bytes handled, driven from the connection loop at
src/emqx_connection.erl:650-655) and ``src/emqx_global_gc.erl``
(periodic whole-VM collect). Python has one shared heap, so the
per-connection trigger counts per-transport work but runs the same
``gc.collect``; the win is the same as the reference's: bound the
drift between traffic bursts and collection points instead of letting
the allocator decide mid-burst.
"""

from __future__ import annotations

import asyncio
import gc as _gc
import logging
from typing import Optional

log = logging.getLogger("emqx_tpu.gc")


class GcPolicy:
    """Count/bytes-triggered collection (emqx_gc:run/3; defaults
    from etc/emqx.conf force_gc_policy 16000|16MB)."""

    def __init__(self, count: int = 16000,
                 bytes_: int = 16 * 1024 * 1024) -> None:
        self.count_limit = count
        self.bytes_limit = bytes_
        self._cnt = 0
        self._oct = 0
        self.collections = 0

    def inc(self, cnt: int = 1, oct: int = 0) -> bool:
        """Record work; returns True when a collection ran."""
        self._cnt += cnt
        self._oct += oct
        if self._cnt >= self.count_limit or self._oct >= self.bytes_limit:
            self.reset()
            self.collections += 1
            _gc.collect(0)  # young generation: cheap, frequent
            return True
        return False

    def reset(self) -> None:
        self._cnt = 0
        self._oct = 0


class GlobalGc:
    """Periodic full collection (emqx_global_gc: run_gc every
    15min default, disabled when interval is None)."""

    def __init__(self, interval: Optional[float] = 15 * 60.0) -> None:
        self.interval = interval
        self.runs = 0

    def run_gc(self) -> int:
        self.runs += 1
        return _gc.collect()

    async def run(self) -> None:
        if self.interval is None:
            return
        while True:
            await asyncio.sleep(self.interval)
            freed = self.run_gc()
            log.debug("global gc: %d objects collected", freed)


#: routes a node holds when it starts to serve from which its heap is
#: frozen (below it a collection is cheap, and a process that builds
#: many small nodes, as a test run does, keeps its collector)
FREEZE_MIN_ROUTES = 100_000
#: gen-1 collections between two full ones once the heap is frozen
#: (the interpreter's own threshold is 10, with the quarter rule
#: behind it, which a frozen heap switches off)
FROZEN_FULL_EVERY = 100


def freeze_resident(n_routes: int) -> bool:
    """Move what the process holds now, the subscription tables a node
    restored at boot, to the collector's permanent generation
    (``gc.freeze``): no collection during service walks millions of
    entries that live as long as the node does (a full one over 4M
    filters holds the event loop for seconds), and neither does the
    interpreter's last collection at exit (25 s at 4M filters).
    Entries freed later are still freed by their reference counts;
    only cycles among what is frozen now are never reclaimed, and the
    tables hold none. Returns whether the heap was frozen."""
    if n_routes < FREEZE_MIN_ROUTES:
        return False
    _gc.freeze()
    # what is frozen no longer counts as long-lived, so the collector's
    # rule of a quarter (a full collection only once a quarter as much
    # again has survived) would pass every time: under flood a full
    # collection of ~0.1 s every tenth gen-1 one, 13-15 in 20 s where
    # the unfrozen heap had one. Held to every hundredth instead
    t0, t1, t2 = _gc.get_threshold()
    _gc.set_threshold(t0, t1, max(t2, FROZEN_FULL_EVERY))
    log.info("gc: heap frozen at start (%d routes resident)", n_routes)
    return True
