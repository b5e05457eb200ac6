"""Ingress publish batcher: per-tick aggregation across connections.

The reference ingests one message per connection-process receive;
its generic size/interval accumulator (``src/emqx_batch.erl:1-91``)
is applied to outbound bridges only. Here batching IS the ingress
design (SURVEY §2.2 row 1): every connection's PUBLISH lands in one
shared accumulator, and the whole batch goes through the broker's
three-phase batched publish — one compiled device match + fan-out +
pack for all messages that arrived in the same event-loop tick.
QoS1/2 acks (PUBACK/PUBREC) are deferred and complete when the batch
returns, so the wire contract is unchanged.

Pipelining: the device phases are split (broker.publish_begin /
publish_fetch / publish_finish) so the blocking device→host transfer
runs on an executor thread while the event loop keeps parsing
sockets — along with everything else publish_fetch hangs off that
thread: the dispatch-plan grouping pass and the egress
pre-serialization of wire images/templates (docs/DISPATCH.md), so
the loop-side tail is little more than buffer writes. Up to
``max_inflight`` batches may be in the pipeline at once. What that
depth is for: at the size trigger (a flood) the next batch's
``publish_begin`` runs on the loop while the executor fetches the
last one's, so neither thread waits for the other. What it is not
for: hiding the device's round trip behind ticks of a few messages.
The loop has one thread; every batch begun costs it the same
milliseconds of dispatch whatever the batch holds, and a landed
batch's tail waits behind the begins of the batches opened beside it
(PERF.md §6, PR 41). Delivery stays ordered: batch N+1's delivery
tail awaits batch N's, so per-publisher in-order semantics hold
across batch boundaries.

Flush policy: a batch flushes when it reaches the size trigger, else
on the next event-loop iteration (``call_soon`` — "everything that
arrived this tick"), or after ``linger_ms`` when configured (trades
latency for bigger device batches under light load) — **unless a
batch stands on the device path** (between its ``publish_begin`` and
the return of its fetch, ``_on_path``): a flush short of the size
trigger is then held, and what it would have taken leaves with
the flush that the landed batch's completion schedules, as one batch
with whatever arrived meanwhile. The release is the *completion*
(``_complete``'s ``finally``), not the fetch's return: the landed
batch's wait for its predecessor in the ordered chain, its delivery
tail and, on a multi-loop node, its cross-loop join (bounded by
``Broker.XLOOP_JOIN_TIMEOUT``) all come first — nothing could be
acked ahead of that batch anyway. A held flush waits for nothing but
that or the size trigger: no timer, and with the path free at every
tick the policy is the tick's.

The size trigger (``_trigger``) is ``batch_size`` with the pipeline
empty. **While a begun batch has not completed** (``_inflight`` > 0:
on the device path, or landed with its tail not done) it is
``2 × batch_size``, or the high-water mark where that is lower (an
explicit ``queue_hiwater``, or critical overload dividing the mark:
the accumulator cannot pass the mark, and the rule must not pin an
overloaded node's pipeline at depth 1). The dispatch is a fixed price
a batch, so what arrives during one batch's life leaves as one batch
at its completion (PERF.md §6, PR 44); at the grown trigger the next
slot opens beside the batches in flight, up to ``max_inflight``. The
default mark is the same ``2 × batch_size``: the readers park there
(why twice and not ``batch_cap``: docs/DISPATCH.md, "Why twice"). When
all ``max_inflight`` slots are busy, arrivals keep accumulating and
flush as a bigger batch the moment a slot frees — backpressure becomes
batch growth, exactly the regime the device prefers.

Callers without a running event loop (sync drivers, unit tests that
poke the channel directly) fall back to the synchronous path:
:meth:`submit` returns ``None`` and the caller publishes inline.
"""

from __future__ import annotations

import asyncio
import collections
import contextlib
import logging
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Deque, List, Optional, Tuple

from emqx_tpu import faults
from emqx_tpu.concurrency import (any_thread, owner_loop,
                                  shared_state)
from emqx_tpu.types import Message

log = logging.getLogger("emqx_tpu.ingress")

# single-loop mode has no ``_plock``: the one event loop is the lock
_UNLOCKED = contextlib.nullcontext()

# a parked reader's place in the admission line
_WAITING, _GRANTED, _GONE = 0, 1, 2  # _GONE: timed out, or dead


class _Waiter:
    """One read loop parked in the admission line."""

    __slots__ = ("fut", "weight", "deadline", "state")

    def __init__(self, fut, weight: int,
                 deadline: Optional[float]) -> None:
        self.fut = fut  # on the reader's own loop: True = granted
        self.weight = weight  # the PUBLISHes of the chunk it holds
        self.deadline = deadline  # time.monotonic(); None = no bound
        self.state = _WAITING


def _running_loop() -> Optional[asyncio.AbstractEventLoop]:
    try:
        return asyncio.get_running_loop()
    except RuntimeError:
        return None


@shared_state(lock="_plock", attrs=("_pending", "_line", "_granted"))
class IngressBatcher:
    def __init__(self, broker, batch_size: int = 256,
                 linger_ms: float = 0.0, max_inflight: int = 4,
                 batch_cap: int = 0, queue_hiwater: int = 0,
                 finish_chunk: int = 64) -> None:
        self.broker = broker
        self.batch_size = batch_size
        self.linger_ms = linger_ms
        self.max_inflight = max(1, max_inflight)
        # largest batch one flush may take (0 = 4× batch_size). An
        # uncapped flush of an accumulated backlog walks through ever
        # bigger pow2 padding buckets, each a fresh XLA compile on
        # the hot path; the cap keeps steady-state traffic inside a
        # handful of already-compiled buckets
        self.batch_cap = batch_cap or batch_size * 4
        # accumulator high-water mark: at it, connections PAUSE
        # their read loops (the admission line, ``admit``) until a
        # flush makes room — the reference bounds per-connection ingest with
        # active_n (src/emqx_connection.erl:99); without a bound, a
        # saturating publisher turns the accumulator into an
        # unbounded standing queue and every delivery's tail latency
        # becomes queue depth (round-4: 627ms p99 at saturation).
        # Bounding here moves the queue into the publishers' TCP
        # buffers, where backpressure belongs. The default is the
        # size trigger beside a batch in the pipeline (``_trigger``)
        self.queue_hiwater = queue_hiwater or 2 * batch_size
        # delivery-tail streaming: yield to the event loop every this
        # many finished rows so early deliveries flush while later
        # rows still route
        self.finish_chunk = max(1, finish_chunk)
        self._pending: List[Tuple[Message, asyncio.Future]] = []
        # telemetry (per batch, never per message): when the first
        # message landed in the empty accumulator — the start of the
        # batch's ``ingress_wait`` and of its span's ``end_to_end`` —
        # and when the ordered chain's last batch completed (the end
        # of the next one's ``chain_wait``). 0.0 = not stamped
        self._t_first = 0.0
        self._t_done = 0.0
        self._handle = None
        self._inflight = 0
        # batches on the DEVICE PATH: enqueued by publish_begin, their
        # publish_fetch not yet back on the loop. While it is not 0 a
        # flush short of the size trigger is held (``_flush``). With
        # ``_inflight`` and ``_pending`` it is also what the selector's
        # shadow (monitors.SysMon) reads to say what the loop waits
        # for; all three change on the home loop (a peer loop's append
        # wakes it), so the shadow takes no lock
        self._on_path = 0
        self._chain: Optional[asyncio.Task] = None  # ordered delivery
        self._pool: Optional[ThreadPoolExecutor] = None
        # the admission line (``admit``): read loops that met the
        # mark, first come first served; ``_granted`` is the room
        # that woken readers hold until they resume, ``_timer`` the
        # line's one deadline (its head's), ``_regrant`` a grant pass
        # already scheduled behind the readers just woken
        self._line: Deque[_Waiter] = collections.deque()
        self._granted = 0
        self._timer: Optional[asyncio.TimerHandle] = None
        self._regrant = False
        # multi-loop front door (Node.start → bind_multiloop): the
        # accumulator is then fed from several event-loop threads —
        # appends/takes and the admission line go under _plock,
        # flushes are marshaled onto the home loop, futures (acks and
        # grants alike) resolve on their own loops. None on a
        # single-loop node: every hot-path branch below stays the
        # legacy code byte-for-byte
        self._plock: Optional[threading.Lock] = None
        self._home: Optional[asyncio.AbstractEventLoop] = None
        # overload protection (overload.py): at critical the monitor
        # divides the effective high-water mark by this, so publisher
        # read-pauses engage earlier; 1 = the configured mark, the
        # hot-path cost is one int compare
        self._pressure_div = 1
        # bound on a publisher's wait in the admission line (seconds;
        # 0 = unbounded, the legacy behavior) — set from
        # [overload] ingress_wait_timeout_s by Node; connections shed
        # the publisher when it expires (docs/ROBUSTNESS.md)
        self.submit_wait_timeout = 0.0
        # observability (emqx_batch keeps a counter too)
        self.flushes = 0
        self.submitted = 0
        self.max_batch = 0
        self.max_queue = 0

    _DONE = object()  # sentinel: fire-and-forget submission accepted

    def bind_multiloop(self, loop_group) -> None:
        """Arm the thread-safe submission mode (multi-loop front
        door): the accumulator's home is the loop group's main loop;
        peer-loop submits append under a lock and kick a flush over
        ``call_soon_threadsafe``."""
        self._home = loop_group.home
        if self._plock is None:
            self._plock = threading.Lock()

    def accepts_threadsafe(self) -> bool:
        return self._plock is not None

    def _executor(self) -> ThreadPoolExecutor:
        if self._pool is None:
            self._pool = ThreadPoolExecutor(
                max_workers=self.max_inflight,
                thread_name_prefix="ingress-fetch")
        return self._pool

    @any_thread
    def submit(self, msg: Message, want_result: bool = True):
        """Queue one message. With ``want_result`` the returned future
        resolves to the delivery count at flush; without (QoS0 — no
        ack, nobody awaits) no future is created, avoiding orphaned
        'exception never retrieved' noise on a failed flush. ``None``
        = no running loop, the caller must publish synchronously.

        On a multi-loop node the future belongs to the CALLER'S loop
        (acks flush from there) while the batch always flushes on the
        home loop."""
        trc = self.broker.tracing
        if trc is not None and trc.active:
            # trace-context stamp at INGRESS: the context's t0 anchors
            # the ingress-wait span (submit → batch pickup). Stamping
            # only mutates the message's own headers — safe from any
            # submitting loop; idempotent for forwarded messages
            trc.stamp(msg)
        loop = _running_loop()
        if self._plock is not None:
            return self._submit_threadsafe(msg, want_result, loop)
        if loop is None:
            return None
        fut = loop.create_future() if want_result else None
        first = not self._pending
        # lint: ok-CD102 single-loop mode: _plock is None and every
        # submit runs on the node's one event loop (the multi-loop
        # build takes _submit_threadsafe above instead)
        self._pending.append((msg, fut))
        self.submitted += 1
        self._appended(loop, first)
        return fut if fut is not None else self._DONE

    def _appended(self, loop, first: bool) -> None:
        """After an append in single-loop mode (this thread IS the
        home loop): flush at the size trigger (``_full``), else arm
        the tick's flush behind the accumulator's ``first`` message."""
        n = len(self._pending)
        if n > self.max_queue:
            self.max_queue = n
        if first and self.batch_size > 1:
            tel = self.broker.telemetry
            if tel is not None and tel.enabled:
                self._t_first = time.perf_counter()
        if self._full(n):
            self._flush()  # the direct flush is the legacy fast path
        elif first:
            if self.linger_ms > 0:
                self._handle = loop.call_later(
                    self.linger_ms / 1000.0, self._flush)
            else:
                self._handle = loop.call_soon(self._flush)

    @any_thread
    def submit_many(self, msgs: List[Message]) -> bool:
        """Queue a connection's run of fire-and-forget messages
        (QoS0: no future) as ``submit(msg, want_result=False)`` called
        on each in turn would — a flush at exactly the size trigger's
        boundary (``_trigger``, read again after every flush), so the
        batches are the same lists — with one look
        at the loop and at tracing for the run. False = no running
        loop, nothing queued: the caller publishes synchronously.

        On a multi-loop node (``_plock``) the run is submitted one
        message at a time: appends from peer loops interleave under
        the lock, and the home loop owns every flush decision."""
        trc = self.broker.tracing
        if trc is not None and trc.active:
            for msg in msgs:
                trc.stamp(msg)
        loop = _running_loop()
        if self._plock is not None:
            for msg in msgs:
                self._submit_threadsafe(msg, False, loop)
            return True
        if loop is None:
            return False
        i, n = 0, len(msgs)
        while i < n:
            pend = self._pending  # single-loop mode, as in submit()
            first = not pend
            # up to the boundary; over it (a standing backlog: every
            # slot was busy at the last flush) one at a time, each
            # with its own try at a flush, as submit() does
            j = min(n, i + max(1, self._trigger() - len(pend)))
            pend.extend([(m, None) for m in msgs[i:j]])
            self.submitted += j - i
            i = j
            self._appended(loop, first)
        return True

    @any_thread
    def _submit_threadsafe(self, msg: Message, want_result: bool,
                           loop):
        """Multi-loop submit: append under the lock; flush decisions
        run on the home loop (kicked over ``call_soon_threadsafe``
        from peer loops — at most one kick outstanding per tick, the
        linger/soon coalescing the legacy path gets from ``_handle``)."""
        if want_result and loop is None:
            return None  # sync caller: publish inline, as before
        fut = loop.create_future() if want_result else None
        with self._plock:
            self._pending.append((msg, fut))
            self.submitted += 1
            n = len(self._pending)
            if n > self.max_queue:
                self.max_queue = n
            if n == 1:
                tel = self.broker.telemetry
                if tel is not None and tel.enabled:
                    self._t_first = time.perf_counter()
        home = self._home or loop
        if loop is home:
            if self._full(n):
                # lint: ok-CD101 guarded by `loop is home`: this
                # submit is already running on the home loop
                self._flush()
            elif n == 1:
                if self.linger_ms > 0:
                    self._handle = home.call_later(
                        self.linger_ms / 1000.0, self._flush)
                else:
                    self._handle = home.call_soon(self._flush)
        elif n == 1 or self._full(n):
            try:
                home.call_soon_threadsafe(self._remote_kick)
            except RuntimeError:
                pass  # home loop gone (shutdown race)
        return fut if fut is not None else self._DONE

    @owner_loop
    def _remote_kick(self) -> None:
        """A peer-loop submit's flush request, now ON the home loop:
        the kick itself IS the next-tick callback, so an un-lingered
        accumulator flushes immediately ("everything that arrived
        this tick"), and a lingering one arms the timer once."""
        if not self._pending:
            return
        if self._full(len(self._pending)):
            self._flush()
            return
        if self._handle is not None:
            return  # a flush is already scheduled
        if self.linger_ms > 0:
            self._handle = self._home.call_later(
                self.linger_ms / 1000.0, self._flush)
        else:
            self._flush()

    @owner_loop
    def _take_pending(self, cap: int = 0):
        """Shared flush prologue: cancel the linger timer, take up to
        ``cap`` messages (0 = all) off the accumulator, bump the
        counters."""
        if self._handle is not None:
            self._handle.cancel()
            self._handle = None
        # multi-loop: peer loops append concurrently — the swap must be
        # atomic with their appends or a message lands in a list
        # already captured by the flush
        with self._plock or _UNLOCKED:
            if cap and len(self._pending) > cap:
                pending = self._pending[:cap]
                del self._pending[:cap]
            else:
                pending, self._pending = self._pending, []
            self._grant_locked()  # the room this take made
        if pending:
            self.flushes += 1
            self.max_batch = max(self.max_batch, len(pending))
        return pending

    # -- ingest backpressure: the admission line ---------------------------

    def _mark(self) -> int:
        """The effective high-water mark: the configured one, divided
        at critical overload (``set_pressure``); 0, which nothing is
        under, while the ``ingress.saturate`` fault reads "full"."""
        if faults.enabled and faults.fire("ingress.saturate"):
            return 0
        return self._hiwater()

    def _hiwater(self) -> int:
        """The mark without the fault's say: what the accumulator can
        reach before the readers park."""
        hw = self.queue_hiwater
        if self._pressure_div > 1:
            hw = max(1, hw // self._pressure_div)
        return hw

    def _trigger(self) -> int:
        """The size trigger: ``batch_size`` with the pipeline empty;
        while a begun batch has not completed (on the device path, or
        landed with its tail not done) ``2 × batch_size``, so that
        what arrives during one batch's life pays one dispatch and not
        two; or the high-water mark where that is lower (an explicit
        ``queue_hiwater``; critical overload divides it,
        ``set_pressure``): the accumulator stands at the mark with the
        readers parked, and a flush held for more (``_flush``) would
        pin the pipeline's depth at 1 just when the node is
        overloaded."""
        if not self._inflight:
            return self.batch_size
        return min(2 * self.batch_size, self._hiwater())

    def _full(self, n: int) -> bool:
        """``n`` pending is a batch's worth (``_trigger``). Short of
        it, with the path free, the tick's flush takes what there is,
        as ever."""
        return n >= self._trigger()

    def backlogged(self) -> bool:
        """Accumulator at/over the high-water mark — connections
        pause reading (the active_n analogue). At critical overload
        the effective mark shrinks (``set_pressure``), so the pause
        engages earlier."""
        return len(self._pending) >= self._mark()

    def waiting(self) -> int:
        """Read loops parked in the admission line."""
        return len(self._line)

    def set_pressure(self, div: int) -> None:
        """Overload-monitor knob: divide the effective high-water
        mark by ``div`` (1 restores the configured mark, and admits
        what the restored mark holds)."""
        self._pressure_div = max(1, int(div))
        with self._plock or _UNLOCKED:
            self._grant_locked()

    @any_thread
    async def admit(self, weight: int) -> bool:
        """A read loop's one question a chunk, asked before it hands
        the chunk's ``weight`` PUBLISHes to the channel. True at once
        where the queue is under the mark and nobody waits (no
        barging). Otherwise the reader joins the tail of one FIFO
        line and reads nothing more, so its publisher's next packets
        stand in the TCP buffer; whatever makes room wakes from the
        head the readers the room holds, once each, and a woken
        reader is admitted: it does not ask again. False = the wait
        outlasted ``submit_wait_timeout`` (0 = no bound): the caller
        sheds the publisher instead of wedging its read loop."""
        lock = self._plock or _UNLOCKED
        with lock:
            w = self._join_locked(weight)
        if w is None:
            return True
        tel = self.broker.telemetry
        timed = tel is not None and tel.enabled
        if timed:
            t_park = time.perf_counter()
            self.broker.metrics.inc("ingress.parks")
        try:
            return await w.fut
        finally:
            # the reader is back on its loop: by a grant, a time-out
            # or its task's cancellation
            if timed:
                m = self.broker.metrics
                m.inc("ingress.wakes")
                m.inc("ingress.park.ns",
                      int((time.perf_counter() - t_park) * 1e9))
            with lock:
                self._resumed_locked(w)

    def _join_locked(self, weight: int) -> Optional[_Waiter]:
        """None = admitted at once; else the caller's place at the
        tail of the line."""
        if not self._line and \
                len(self._pending) + self._granted < self._mark():
            return None
        timeout = self.submit_wait_timeout
        w = _Waiter(asyncio.get_running_loop().create_future(), weight,
                    (time.monotonic() + timeout) if timeout > 0
                    else None)
        self._line.append(w)
        # room behind a line (a grant that was handed back) goes to
        # the head, not to the arrival; this arms the timer too
        self._grant_locked()
        return w

    def _resumed_locked(self, w: _Waiter) -> None:
        if w.state == _GRANTED:
            # the grant is spent here: what the reader now submits
            # counts in the queue itself. Room it does not use
            # (nothing to add, cancelled) is offered again, behind
            # the readers that were woken with it
            self._granted -= w.weight
            if self._line and not self._regrant:
                self._regrant = True
                w.fut.get_loop().call_soon(self._grant)
        elif w.state == _WAITING:
            self._line.remove(w)  # cancelled where it stood
            self._arm_locked()

    @any_thread
    def _grant(self) -> None:
        with self._plock or _UNLOCKED:
            self._regrant = False
            self._grant_locked()

    def _grant_locked(self) -> None:
        """Wake from the head what the room holds: while the queue
        plus the room woken readers hold (this pass's grants too) is
        under the mark. The head goes whatever its weight, so a chunk
        larger than the mark is never starved. Invariant: with queue
        + granted room under the mark, the line is empty."""
        line = self._line
        if not line:
            return
        mark = self._mark()
        used = len(self._pending) + self._granted
        while line and used < mark:
            w = line.popleft()
            if self._wake_locked(w, True):
                self._granted += w.weight
                used += w.weight
        self._arm_locked()

    def _wake_locked(self, w: _Waiter, granted: bool) -> bool:
        """Resolve a waiter's future, on ITS loop; False = nobody is
        there to wake (cancelled, or its front-door loop is gone):
        the grant passes to the next."""
        fut = w.fut
        w.state = _GONE
        if fut.done() or not fut.get_loop().is_running():
            return False
        if self._plock is None:
            fut.set_result(granted)
        else:
            self._set_future(fut, granted, None)
        if granted:
            w.state = _GRANTED
        return True

    def _arm_locked(self) -> None:
        """The line's one timer: armed for the head's deadline while
        anyone waits (every wait carries the same time-out, so the
        line is in deadline order), cancelled when the line empties.
        It lives on the home loop."""
        running = _running_loop()
        home = self._home or running
        if not self._line:
            if self._timer is not None and running is home:
                self._timer.cancel()
                self._timer = None
        elif self._timer is None and self._line[0].deadline is not None:
            if running is home:
                self._timer = home.call_at(self._line[0].deadline,
                                           self._expire)
            else:
                try:  # a grant pass there arms it
                    home.call_soon_threadsafe(self._grant)
                except RuntimeError:
                    pass  # home loop gone (shutdown race)

    @owner_loop
    def _expire(self) -> None:
        """The timer: the waiters whose deadline has passed leave the
        line unadmitted (the head, and whoever joined in its very
        tick); the timer moves on to the new head."""
        with self._plock or _UNLOCKED:
            self._timer = None
            line = self._line
            now = time.monotonic()
            while line and line[0].deadline is not None \
                    and line[0].deadline <= now:
                self._wake_locked(line.popleft(), False)
            self._arm_locked()

    @owner_loop
    def _flush(self) -> None:
        # a capped take can leave a backlog: keep flushing chunks
        # while pipeline slots are free
        while self._pending and self._inflight < self.max_inflight:
            if self._on_path and not self._full(len(self._pending)):
                # a batch stands on the device path and this is a
                # tick's handful: it joins what arrives until the
                # landed batch's completion flushes (``_complete``'s
                # ``finally``) or the size trigger fires. Opening a
                # slot for it would cost the loop one more
                # ``publish_begin`` and the landed batch a wait
                # behind it
                tel = self.broker.telemetry
                if tel is not None and tel.enabled:
                    self.broker.metrics.inc("ingress.flush.held")
                return
            pending = self._take_pending(cap=self.batch_cap)
            # while earlier batches are in flight, a host-path batch
            # must not route (and no batch may resolve) ahead of them
            # — begin with deferred host routing, chain the completion.
            # (Deferring LARGE host batches unconditionally was tried
            # and measured strictly worse: the ordered chain then
            # stretches every batch across interleaved publisher
            # reads, and probe latency tripled while throughput fell.)
            chain_active = (self._chain is not None
                            and not self._chain.done())
            span = None
            tel = self.broker.telemetry
            if tel is not None and tel.enabled:
                if len(pending) > self.batch_size:
                    self.broker.metrics.inc("ingress.flush.grown")
                # the span starts at the batch's first arrival; a
                # capped take restarts the clock for what it leaves
                # behind (those arrivals carry no stamp of their own)
                span = tel.begin(len(pending),
                                 t_first=self._t_first or None,
                                 inflight=self._inflight)
                self._t_first = span.t_mark if self._pending else 0.0
            try:
                pb = self.broker.publish_begin(
                    [m for m, _ in pending], defer_host=chain_active,
                    span=span)
            except Exception as e:
                log.exception("ingress batch publish failed")
                if span is not None:
                    span.stop()  # the stage the failure left open
                self._resolve_exc(pending, e)
                continue
            if pb.done and not chain_active:
                self._resolve(pending, pb.results)
                continue
            self._inflight += 1
            if not pb.done and pb.host_topics is None:
                self._on_path += 1  # down where its fetch returns
            loop = asyncio.get_running_loop()
            prev = self._chain if chain_active else None
            task = loop.create_task(self._complete(pb, pending, prev))
            self._chain = task

    @owner_loop
    async def _complete(self, pb, pending, prev) -> None:
        """Fetch off-loop, then deliver in batch order."""
        loop = asyncio.get_running_loop()
        # the span (None = telemetry off, or a host batch that already
        # closed it); kept here because the last chunk closes pb.span
        sp = pb.span
        try:
            if sp is not None:
                # publish_begin returned → this task got the loop
                sp.wait_mark("loop_wait")
            if not pb.done and pb.host_topics is None:
                # everything from here to the fetch's return sits
                # under the ``finally`` that takes the batch off the
                # device path: a held flush waits for that
                try:
                    if faults.enabled and self._pool is not None \
                            and faults.fire("executor.death"):
                        # injected: the fetch pool dies out from under
                        # this batch — the supervision below must
                        # respawn
                        self._pool.shutdown(wait=False)
                    await loop.run_in_executor(
                        self._executor(), self.broker.publish_fetch,
                        pb)
                except RuntimeError as e:
                    if "shutdown" not in str(e):
                        raise
                    # the fetch executor died (its threads are gone /
                    # the pool was shut down): respawn it and retry —
                    # asyncio supervision standing in for the OTP
                    # restart the reference gets for free
                    log.warning("ingress fetch executor dead (%s): "
                                "respawning", e)
                    self.broker.metrics.inc("overload.heal.executor")
                    self._pool = None
                    await loop.run_in_executor(
                        self._executor(), self.broker.publish_fetch,
                        pb)
                finally:
                    self._on_path -= 1  # off the device path
            if prev is not None:
                # ordered delivery across batches; a failed
                # predecessor already resolved its own futures
                try:
                    await asyncio.shield(prev)
                except Exception:
                    pass
            if sp is not None:
                # fetch returned (publish_fetch left the mark) → the
                # chain's previous batch completed → the loop came
                # back to this one
                if prev is not None and self._t_done > sp.t_mark:
                    sp.wait("chain_wait", sp.t_mark, self._t_done)
                    sp.t_mark = self._t_done
                sp.wait_mark("loop_wait")
            if pb.done:
                results = self.broker.publish_finish(pb)
            else:
                # stream the delivery tail: the loop gets a turn
                # between the broker's steps, so finished work's
                # deliveries flush to subscriber sockets while the
                # rest still routes
                for _ in self.broker.finish_steps(pb, self.finish_chunk):
                    await asyncio.sleep(0)
                    if sp is not None:
                        # what the tail gave back to the loop
                        sp.wait_mark("tail_yield")
                # multi-loop: the batch's results/metrics fold — and
                # therefore the ack futures below — wait for the
                # cross-loop handoffs to report back. None on a
                # single-loop node
                ev = self.broker.xloop_event(pb)
                if ev is not None:
                    # bounded, like the sync join: a wedged or dead
                    # owning loop must not hang this batch (and every
                    # batch chained behind it) forever — fold partial
                    # counts with the loss counted
                    # (delivery.xloop.orphaned)
                    try:
                        await asyncio.wait_for(
                            ev.wait(), self.broker.XLOOP_JOIN_TIMEOUT)
                    except asyncio.TimeoutError:
                        log.error(
                            "cross-loop delivery handoff incomplete "
                            "after %.0fs — folding partial counts",
                            self.broker.XLOOP_JOIN_TIMEOUT)
                    self.broker.xloop_fold(pb)
                pb.done = True
                results = pb.results
        except Exception as e:
            log.exception("ingress batch completion failed")
            self._resolve_exc(pending, e)
            return
        finally:
            self._inflight -= 1
            if sp is not None:
                self._t_done = time.perf_counter()
            if self._pending:
                # a slot freed while messages accumulated, or a flush
                # was held while this batch stood on the device path
                # (``_flush``): this is the landing's release — but
                # flushing HERE would run inside this batch's
                # completion, BEFORE its futures resolve below: a
                # host-path flush can resolve newer publishes'
                # futures synchronously, acking them ahead of this
                # batch's older ones (MQTT-4.6.0 ack order), and a
                # re-entrant failure path could touch this batch's
                # futures twice. Schedule the flush for after this
                # completion instead.
                loop.call_soon(self._flush)
        self._resolve(pending, results)

    def _resolve(self, pending, results) -> None:
        xloop = self._plock is not None
        for (_, fut), n in zip(pending, results):
            if fut is None or fut.done():
                continue
            if xloop:
                self._set_future(fut, n, None)
            else:
                fut.set_result(n)

    def _resolve_exc(self, pending, e) -> None:
        xloop = self._plock is not None
        for _, fut in pending:
            if fut is None or fut.done():
                continue
            if xloop:
                self._set_future(fut, None, e)
            else:
                fut.set_exception(e)

    @staticmethod
    @any_thread
    def _set_future(fut, value, exc) -> None:
        """Resolve a submit future on ITS loop (multi-loop: peer-loop
        futures must not be completed from the home thread — the ack
        callbacks hanging off them touch that loop's channel)."""
        floop = fut.get_loop()
        running = _running_loop()

        def _do(f=fut, v=value, e=exc):
            if f.done():
                return
            if e is not None:
                f.set_exception(e)
            else:
                f.set_result(v)

        if floop is running:
            _do()
        else:
            try:
                floop.call_soon_threadsafe(_do)
            except RuntimeError:
                pass  # owner loop gone; QoS>0 clients re-send

    def flush_now(self) -> None:
        """Drain whatever is pending synchronously (shutdown path and
        loop-less callers); in-flight async batches are awaited by
        :meth:`drain`."""
        pending = self._take_pending()
        self._t_first = 0.0
        if not pending:
            return
        try:
            results = self.broker.publish_batch([m for m, _ in pending])
        except Exception as e:
            log.exception("ingress batch publish failed")
            self._resolve_exc(pending, e)
            return
        self._resolve(pending, results)

    async def drain(self) -> None:
        """Wait for every in-flight batch, THEN flush what queued
        behind them (node shutdown) — accumulated messages are always
        newer than in-flight ones, so this order preserves delivery
        order."""
        while True:
            chain = self._chain
            if chain is not None and not chain.done():
                try:
                    await chain
                except Exception:
                    pass
                continue
            if self._pending:
                self.flush_now()
                continue
            break
        if self._pool is not None:
            # reap the fetch threads; a restarted node lazily
            # recreates the pool on its first device-path flush
            self._pool.shutdown(wait=True)
            self._pool = None

    def stats(self) -> dict:
        return {
            "ingress.submitted": self.submitted,
            "ingress.flushes": self.flushes,
            "ingress.max_batch": self.max_batch,
            "ingress.max_queue": self.max_queue,
            "ingress.inflight": self._inflight,
            "ingress.avg_batch": (
                round(self.submitted / self.flushes, 2)
                if self.flushes else 0.0),
        }
