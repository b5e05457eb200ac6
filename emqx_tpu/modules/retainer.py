"""Retained-message store and delivery.

The reference core delegates retained messages to the separate
``emqx_retainer`` plugin application (the core only carries the
``retain`` flag and the v5 Retain-Handling/Retain-As-Published
subscription options); a broker users can actually switch to needs
the behavior in the box, so it ships here as a built-in module wired
through the same two hookpoints the reference plugin uses:

  - ``'message.publish'``: a retained PUBLISH stores its message
    under the topic (an empty retained payload deletes — MQTT
    3.3.1-6/-7); the message still routes normally.
  - ``'session.subscribed'``: a new subscription receives every
    stored message matching its filter, with the retain flag SET
    (MQTT 3.3.1-8) regardless of RAP, honoring Retain-Handling
    (0 = always send, 1 = only if the subscription did not exist,
    2 = never — MQTT 3.8.3.1) and skipping shared subscriptions
    (retained messages are never sent to ``$share`` groups) and
    expired messages (Message-Expiry-Interval).

Bounded: ``max_retained`` topics (new stores beyond it are dropped
with a counter, like the plugin's ``max_retained_messages``) and
``max_payload`` bytes per message.
"""

from __future__ import annotations

import logging
import threading
from typing import Dict, List, Optional, Sequence

import numpy as np

from emqx_tpu import topic as T
from emqx_tpu.modules import Module
from emqx_tpu.types import Message

log = logging.getLogger(__name__)


#: '+' sentinel in an encoded FILTER row — never collides with real
#: word ids (≥0) or the topic-side UNKNOWN (-1) / PAD (-2)
_PLUS_ID = -3


class RetainIndex:
    """Device-side reverse index over retained topic NAMES.

    The reference plugin indexes retained topics in its own Mnesia
    trie so a wildcard subscribe doesn't scan the store. The
    TPU-first equivalent inverts the publish problem: retained names
    live as a persistent encoded ``[cap, L]`` word-id matrix, and a
    wildcard subscribe matches its ONE filter against every stored
    name in a single data-parallel device pass instead of N Python
    ``T.match`` calls.

    A filter needs no automaton walk at all: per level the filter
    word either equals the topic word or is ``+``, with a ``#``
    suffix relaxing the depth check and the ``$``-root rule masking
    system topics — a pure elementwise program over ``[cap, L]``
    (zero gathers, HBM-bandwidth bound; an earlier automaton-based
    variant spent its time in per-level gather chains). Since PR 19
    the kernel is batched on the filter side too
    (ops/retained_match.py): :meth:`match_many` encodes a whole
    subscribe burst as ``[F, L]`` and matches every filter against
    every stored name in ONE dispatch; :meth:`match` is the F=1
    special case of the same path.

    Rows are slot-allocated (free list); a deleted row gets
    ``n_words = 0``, which matches nothing. Names deeper than ``L``
    levels live in a host-matched side set, the same overflow
    contract as the publish path. Below ``device_threshold`` live
    rows (or on any device failure) matching falls back to the host
    scan. With a router attached (:meth:`attach_router`) the index
    rides device-loss recovery: a suspended device plane forces the
    host scan and drops the cached matrix (its HBM references may be
    dead), and suspension lifting (``rebuild_complete``) forgives the
    failure breaker — a fresh backend deserves a clean slate.
    """

    L = 16
    GROW = 1024

    def __init__(self) -> None:
        from emqx_tpu.ops.tokenize import PAD, WordTable

        self._pad = PAD
        self._table = WordTable()
        self._word_refs: Dict[str, int] = {}
        self._cap = self.GROW
        self._ids = np.full((self._cap, self.L), PAD, dtype=np.int32)
        self._n = np.zeros(self._cap, dtype=np.int32)
        self._sys = np.zeros(self._cap, dtype=bool)
        self._row_topic: List[Optional[str]] = [None] * self._cap
        self._row_of: Dict[str, int] = {}
        self._free = list(range(self._cap - 1, -1, -1))
        self._deep: set = set()
        self._epoch = 0
        self._dev = None  # (epoch, cap, ids, n, sys) device cache
        self._dirty: set = set()  # rows mutated since _dev was built
        self._device_broken = 0  # consecutive failures; >=3 disables
        self._router = None  # devloss riding (attach_router)
        self._suspended_seen = False
        self._last_batch = 0  # filters in the last device dispatch
        # store mutations run on the broker's home loop but subscribe
        # bursts match from every front-door loop; the lock covers
        # the matrix + device-cache critical sections (uncontended on
        # a single-loop node)
        self._lock = threading.RLock()

    def __len__(self) -> int:
        return len(self._row_of) + len(self._deep)

    def attach_router(self, router) -> None:
        """Arm device-loss riding (docs/ROBUSTNESS.md): the index
        holds its own device references outside
        ``Router.rebuild_device_state()``, so instead of being
        rebuilt it watches the router's suspension flag — see the
        class docstring."""
        self._router = router

    def add(self, topic: str) -> None:
        with self._lock:
            self._add_locked(topic)

    def _add_locked(self, topic: str) -> None:
        if topic in self._row_of or topic in self._deep:
            return  # overwrite of the same name: index unchanged
        ws = topic.split("/")
        if len(ws) > self.L:
            self._deep.add(topic)
            return
        if not self._free:
            self._grow()
        row = self._free.pop()
        for j, w in enumerate(ws):
            self._ids[row, j] = self._table.intern(w)
            self._word_refs[w] = self._word_refs.get(w, 0) + 1
        self._ids[row, len(ws):] = self._pad
        self._n[row] = len(ws)
        self._sys[row] = ws[0].startswith("$")
        self._row_topic[row] = topic
        self._row_of[topic] = row
        self._touch(row)

    def remove(self, topic: str) -> None:
        with self._lock:
            self._remove_locked(topic)

    def _remove_locked(self, topic: str) -> None:
        if topic in self._deep:
            self._deep.discard(topic)
            return
        row = self._row_of.pop(topic, None)
        if row is None:
            return
        for w in topic.split("/"):
            left = self._word_refs.get(w, 0) - 1
            if left <= 0:
                self._word_refs.pop(w, None)
            else:
                self._word_refs[w] = left
        self._ids[row, :] = self._pad
        self._n[row] = 0
        self._sys[row] = False
        self._row_topic[row] = None
        self._free.append(row)
        self._touch(row)
        # backstop only (loop-less library usage): the periodic sweep
        # task owns compaction; this inline trigger fires far later
        # so the publish hook never pays a big rebuild in the common
        # case
        self._maybe_compact(backstop=True)

    def clear(self) -> None:
        router = self._router
        self.__init__()
        self._router = router

    def _touch(self, row: int) -> None:
        self._epoch += 1
        if self._dev is not None:
            self._dirty.add(row)

    def _compact_due(self, backstop: bool = False) -> bool:
        dead = len(self._table) - len(self._word_refs)
        live = len(self._word_refs)
        if backstop:
            return dead >= max(65536, 4 * max(live, 1))
        return dead >= max(4096, live)

    def _maybe_compact(self, backstop: bool = False) -> None:
        """Re-intern into a fresh WordTable when most interned words
        are dead — name churn must not grow the table forever (the
        same leak class the stability soak exists to catch).
        Synchronous; the periodic sweep prefers :meth:`compact_async`
        which chunks the rebuild so the event loop never stalls."""
        if not self._compact_due(backstop):
            return
        from emqx_tpu.ops.tokenize import WordTable

        table = WordTable()
        for row, topic in enumerate(self._row_topic):
            if topic is None:
                continue
            for j, w in enumerate(topic.split("/")):
                self._ids[row, j] = table.intern(w)
        self._table = table
        self._dev = None
        self._dirty.clear()
        self._epoch += 1

    async def compact_async(self, chunk: int = 4096) -> bool:
        """Cooperative compaction: rebuild the id matrix + table in
        row chunks, yielding between chunks; a store mutation during
        the rebuild aborts it (epoch guard) and the next sweep cycle
        retries. Returns True when a swap happened."""
        import asyncio

        if not self._compact_due():
            return False
        from emqx_tpu.ops.tokenize import WordTable

        start_epoch = self._epoch
        table = WordTable()
        new_ids = np.full_like(self._ids, self._pad)
        for base in range(0, self._cap, chunk):
            for row in range(base, min(base + chunk, self._cap)):
                topic = self._row_topic[row]
                if topic is None:
                    continue
                for j, w in enumerate(topic.split("/")):
                    new_ids[row, j] = table.intern(w)
            await asyncio.sleep(0)
            if self._epoch != start_epoch:
                return False
        with self._lock:
            if self._epoch != start_epoch:
                return False
            self._ids = new_ids
            self._table = table
            self._dev = None
            self._dirty.clear()
            self._epoch += 1
        return True

    def _grow(self) -> None:
        old = self._cap
        self._cap = old * 2
        for name, fill in (("_ids", self._pad), ("_n", 0), ("_sys", False)):
            arr = getattr(self, name)
            shape = (self._cap,) + arr.shape[1:]
            new = np.full(shape, fill, dtype=arr.dtype)
            new[:old] = arr
            setattr(self, name, new)
        self._row_topic.extend([None] * old)
        self._free.extend(range(self._cap - 1, old - 1, -1))

    def match(self, flt: str, device_threshold: int = 4096) -> List[str]:
        """All stored names matching ``flt`` (exact oracle parity)."""
        return self.match_many([flt], device_threshold)[0]

    def match_many(self, filters: Sequence[str],
                   device_threshold: int = 4096) -> List[List[str]]:
        """Batched match: every filter of a subscribe burst against
        every stored name in ONE device dispatch (``[F, L] ×
        [cap, L]`` elementwise kernel, ops/retained_match.py).
        Returns per-filter hit lists aligned with ``filters``, exact
        host-oracle (``T.match``) parity — including the ``$``-root
        mask, ``#`` depth relax and the deep (> L levels) host side
        set, which is scanned per filter either way."""
        if not filters:
            return []
        deep = self._deep
        deep_hits = ([[t for t in deep if T.match(t, f)]
                      for f in filters] if deep
                     else [[] for _ in filters])
        with self._lock:
            if (len(self._row_of) < device_threshold
                    or not self._device_ok()):
                return [self._host_scan(f, dh)
                        for f, dh in zip(filters, deep_hits)]
            try:
                hits = self._match_device_many(filters)
                self._device_broken = 0
                return [h + dh for h, dh in zip(hits, deep_hits)]
            except Exception:
                # circuit breaker: a host with a permanently failing
                # backend must not pay a failed dispatch + a stack
                # trace on EVERY wildcard subscribe
                self._device_broken += 1
                if self._device_broken >= 3:
                    log.exception(
                        "retain index device match failed %d times; "
                        "host scan from now on", self._device_broken)
                else:
                    log.warning(
                        "retain index device match failed; "
                        "host fallback (%d/3)", self._device_broken)
                return [self._host_scan(f, dh)
                        for f, dh in zip(filters, deep_hits)]

    def _host_scan(self, flt: str, deep_hits: List[str]) -> List[str]:
        return [t for t in self._row_of if T.match(t, flt)] + deep_hits

    def _device_ok(self) -> bool:
        """Device-path gate: the failure breaker, plus devloss riding
        when a router is attached — suspended means the device plane
        is mid-recovery (the cached matrix may reference a LOST
        backend: drop it, host-scan, and don't let the doomed
        dispatch burn breaker strikes); the suspension lifting means
        ``rebuild_complete`` ran, so the breaker resets."""
        r = self._router
        if r is not None:
            try:
                suspended = bool(r.device_suspended())
            except Exception:
                suspended = False
            if suspended:
                self._dev = None
                self._dirty.clear()
                self._suspended_seen = True
                return False
            if self._suspended_seen:
                self._suspended_seen = False
                self._device_broken = 0
        return self._device_broken < 3

    def _match_device_many(self, filters: Sequence[str]
                           ) -> List[List[str]]:
        import jax.numpy as jnp

        from emqx_tpu.ops.retained_match import match_names_many

        F = len(filters)
        # pad the burst to a power of two so compile count stays
        # logarithmic in burst size (capacity is already pow-2);
        # padding rows (fn=0, no '#') match nothing
        Fp = max(1, 1 << (F - 1).bit_length()) if F > 1 else 1
        fw = np.full((Fp, self.L), self._pad, dtype=np.int32)
        fn = np.zeros(Fp, dtype=np.int32)
        hh = np.zeros(Fp, dtype=bool)
        for i, flt in enumerate(filters):
            ws = flt.split("/")
            if ws[-1] == "#":
                hh[i] = True
                ws = ws[:-1]
            if len(ws) > self.L:
                # deeper than any indexed name can be: leave the row
                # a no-match (the deep side set covers such names)
                hh[i] = False
                continue
            fn[i] = len(ws)
            for j, w in enumerate(ws):
                # lookup, NOT intern: an unseen filter word
                # (UNKNOWN=-1) matches no stored id >= 0 — identical
                # result, and subscribe traffic can't grow the table
                fw[i, j] = _PLUS_ID if w == "+" else self._table.lookup(w)
        dev = self._device_arrays()
        ok = np.asarray(match_names_many(
            jnp.asarray(fw), jnp.asarray(fn), jnp.asarray(hh),
            dev[2], dev[3], dev[4]))
        self._last_batch = F
        rt = self._row_topic
        return [[rt[row] for row in np.nonzero(ok[i])[0]
                 if rt[row] is not None] for i in range(F)]

    def _device_arrays(self):
        import jax.numpy as jnp

        dev = self._dev
        if dev is None or dev[0] != self._epoch or dev[1] != self._cap:
            if (dev is not None and dev[1] == self._cap
                    and len(self._dirty) <= 256):
                # interleaved store/subscribe traffic: patch the few
                # mutated rows instead of re-uploading the matrix
                rows = np.fromiter(self._dirty, dtype=np.int32)
                dev = (self._epoch, self._cap,
                       dev[2].at[rows].set(self._ids[rows]),
                       dev[3].at[rows].set(self._n[rows]),
                       dev[4].at[rows].set(self._sys[rows]))
            else:
                dev = (self._epoch, self._cap, jnp.asarray(self._ids),
                       jnp.asarray(self._n), jnp.asarray(self._sys))
            self._dev = dev
            self._dirty.clear()
        return dev

    def device_info(self) -> dict:
        """Diagnostic snapshot for ``ctl retained``
        (docs/OPERATIONS.md): live/deep row counts, device-cache
        state, breaker/suspension state and the last batch size."""
        r = self._router
        suspended = False
        if r is not None:
            try:
                suspended = bool(r.device_suspended())
            except Exception:
                pass
        return {
            "rows": len(self._row_of),
            "deep": len(self._deep),
            "cap": self._cap,
            "epoch": self._epoch,
            "cached": self._dev is not None,
            "dirty_rows": len(self._dirty),
            "device_broken": self._device_broken,
            "suspended": suspended,
            "last_batch": self._last_batch,
        }


class RetainerModule(Module):
    name = "retainer"

    def __init__(self, node) -> None:
        super().__init__(node)
        self._store: Dict[str, Message] = {}
        self._index = RetainIndex()
        self.index_device_threshold = 4096
        # delete tombstones (topic -> delete time): a stale
        # rejoiner's sync must not resurrect a deleted message
        self._tombstones: Dict[str, float] = {}
        # durability (docs/DURABILITY.md): store/delete journal
        # through node.durability; True while crash recovery is
        # refilling the store (those mutations must not re-journal)
        self._restoring = False
        self.max_retained = 0
        self.max_payload = 0
        # replay accumulator (PR 19): per-event-loop pending
        # (session, filter, subopts) triples; the first append on a
        # loop schedules a same-tick drain, so every session.subscribed
        # firing queued behind one SUBACK burst lands in ONE batched
        # index match + ONE delivery plan — the subscribe-side mirror
        # of IngressBatcher's zero-linger coalescing
        self._pending: Dict[object, list] = {}
        self._replay_last_batch = 0
        self._gc_tick = 0
        # cluster seam: Cluster sets node.retain_replicate so stores/
        # deletes broadcast (the reference plugin replicates via
        # Mnesia); applied remotely through apply_remote (no re-fan)

    #: stats ticks between expired-entry sweeps — the stats tick runs
    #: on every $SYS heartbeat, far more often than eviction needs
    _GC_EVERY = 6

    def load(self, env: dict) -> None:
        self.max_retained = int(env.get("max_retained", 1_000_000))
        self.max_payload = int(env.get("max_payload", 1 << 20))
        self.index_device_threshold = int(
            env.get("index_device_threshold", 4096))
        self.sweep_interval = float(env.get("sweep_interval", 60.0))
        self._sweep_task = None
        self._kick_on_loop()
        self.node.metrics.new("retained.count")
        self.node.metrics.new("retained.dropped")
        self.node.metrics.new("retained.expired")
        self.node.metrics.new("retained.replay.batches")
        self.node.metrics.new("retained.replay.messages")
        router = getattr(self.node, "router", None)
        if router is None:
            router = getattr(getattr(self.node, "broker", None),
                             "router", None)
        if router is not None:
            # devloss riding: a suspended device plane host-scans and
            # the breaker resets on rebuild_complete
            self._index.attach_router(router)
        stats = getattr(self.node, "stats", None)
        if stats is not None:
            # expired-retained GC on the stats tick (low frequency):
            # entries past Message-Expiry must leave the store/index
            # even when nothing ever subscribes to them again
            stats.register_update(self._on_stats_tick)
        self.node.hooks.add("message.publish", self.on_publish,
                            priority=50)
        self.node.hooks.add("session.subscribed", self.on_subscribed,
                            priority=50)

    def _on_stats_tick(self, stats) -> None:
        self._gc_tick += 1
        if self._gc_tick >= self._GC_EVERY:
            self._gc_tick = 0
            self.sweep_expired()

    def on_loop_start(self) -> None:
        import asyncio

        if getattr(self, "_sweep_task", None) is None \
                or self._sweep_task.done():
            self._sweep_task = asyncio.get_running_loop().create_task(
                self._sweep_loop())

    def on_loop_stop(self) -> None:
        task = getattr(self, "_sweep_task", None)
        if task is not None:
            task.cancel()
            self._sweep_task = None

    async def _sweep_loop(self) -> None:
        """Periodic expiry sweep (the reference plugin expires on a
        timer too, not only lazily) + cooperative index compaction —
        both off the publish hot path."""
        import asyncio

        while True:
            await asyncio.sleep(self.sweep_interval)
            try:
                self.sweep_expired()
                await self._index.compact_async()
            except Exception:
                log.exception("retainer sweep failed")

    def unload(self) -> None:
        self.on_loop_stop()
        self.node.hooks.delete("message.publish", self.on_publish)
        self.node.hooks.delete("session.subscribed", self.on_subscribed)
        self._pending.clear()
        self._store.clear()
        self._index.clear()

    # every store mutation goes through these so the reverse index
    # (device matrix) stays in lockstep with the dict — and, with
    # durability on, the journal sees exactly the store's mutations
    def _put(self, topic: str, msg: Message) -> None:
        self._store[topic] = msg
        self._index.add(topic)
        if not self._restoring:
            dur = getattr(self.node, "durability", None)
            if dur is not None:
                dur.journal_retain(topic, msg, msg.timestamp)

    def _pop(self, topic: str):
        msg = self._store.pop(topic, None)
        if msg is not None:
            self._index.remove(topic)
            if not self._restoring:
                dur = getattr(self.node, "durability", None)
                if dur is not None:
                    dur.journal_retain(topic, None)
        return msg

    def restore_entries(self, items, tombstones=()) -> None:
        """Crash-recovery refill (durability.py): install recovered
        (topic, Message) pairs + delete tombstones without
        re-journaling, honoring expiry and the store bounds."""
        self._restoring = True
        try:
            for topic, msg in items:
                if msg is None or msg.is_expired():
                    continue
                if self.max_retained \
                        and len(self._store) >= self.max_retained:
                    self.node.metrics.inc("retained.dropped")
                    continue
                if topic not in self._store:
                    self.node.metrics.inc("retained.count")
                self._put(topic, msg)
            for topic, ts in tombstones:
                self._tombstones[topic] = max(
                    self._tombstones.get(topic, 0.0), float(ts))
        finally:
            self._restoring = False

    # -- store maintenance -------------------------------------------------

    def on_publish(self, msg: Message):
        if not msg.flags.get("retain") or msg.topic.startswith("$SYS/"):
            return None
        if not msg.payload:
            if self._pop(msg.topic) is not None:
                self.node.metrics.dec("retained.count")
                # monotone like apply_remote/apply_tombstone: a local
                # delete must not move an (ahead-clock) peer's
                # tombstone backwards
                self._tombstones[msg.topic] = max(
                    self._tombstones.get(msg.topic, 0.0), msg.timestamp)
                self._replicate(msg.topic, None, msg.timestamp)
            return None
        if len(msg.payload) > self.max_payload or (
                msg.topic not in self._store
                and len(self._store) >= self.max_retained):
            self.node.metrics.inc("retained.dropped")
            return None
        if msg.topic not in self._store:
            self.node.metrics.inc("retained.count")
        stored = msg.copy()
        # the broadcast wire cache is per-live-delivery state, not
        # part of the retained record
        stored.headers.pop("_wire", None)
        self._put(msg.topic, stored)
        self._replicate(msg.topic, stored)
        return None  # the message still routes normally

    def _replicate(self, topic: str, msg, ts: float = None) -> None:
        fn = getattr(self.node, "retain_replicate", None)
        if fn is not None:
            fn(topic, msg, ts)

    def apply_remote(self, topic: str, msg, sync: bool = False,
                     ts: float = None) -> None:
        """A peer's store/delete (idempotent, never re-broadcast).

        LIVE replication (``sync=False``) applies in arrival order —
        concurrent publishes race exactly as the reference's Mnesia
        writes do, and a node with a lagging clock must not have its
        updates silently dropped cluster-wide. JOIN sync
        (``sync=True``) is the anti-entropy path: it applies
        last-WRITER-wins by message timestamp and respects delete
        tombstones, so a rejoiner's stale snapshot can neither
        clobber newer values nor resurrect deletions."""
        if msg is None:
            if self._pop(topic) is not None:
                self.node.metrics.dec("retained.count")
            # tombstone carries the DELETING message's origin
            # timestamp (not local wall-clock) so join-sync LWW stays
            # consistent under clock skew; monotone like apply_tombstone
            if ts is None:
                import time as _time

                ts = _time.time()
            self._tombstones[topic] = max(
                self._tombstones.get(topic, 0.0), ts)
            return
        if msg.is_expired():
            return
        if len(msg.payload) > self.max_payload:
            # same bound on_publish enforces — a peer with a larger
            # limit must not replicate oversize payloads into ours
            self.node.metrics.inc("retained.dropped")
            return
        if sync:
            tomb = self._tombstones.get(topic)
            if tomb is not None and tomb >= msg.timestamp:
                return
        cur = self._store.get(topic)
        if cur is not None:
            if not sync or msg.timestamp > cur.timestamp:
                self._put(topic, msg)
            return
        if len(self._store) >= self.max_retained:
            self.node.metrics.inc("retained.dropped")
            return
        self.node.metrics.inc("retained.count")
        self._put(topic, msg)

    def sweep_expired(self) -> int:
        """Drop expired entries (lazy pruning otherwise happens only
        on a matching subscribe — the stats-tick GC and the periodic
        sweep both land here)."""
        dead = [t for t, m in self._store.items() if m.is_expired()]
        for t in dead:
            self._pop(t)
            self.node.metrics.dec("retained.count")
            self.node.metrics.inc("retained.expired")
        self._sweep_tombstones()
        return len(dead)

    def entries(self):
        """Live snapshot for cluster join sync (expired swept
        first — a join must not resurrect dead entries)."""
        self.sweep_expired()
        return list(self._store.items())

    def tombstones(self):
        return list(self._tombstones.items())

    def apply_tombstone(self, topic: str, ts: float) -> None:
        """A peer's delete record (join sync): drop any locally
        stored message older than the deletion."""
        cur = self._store.get(topic)
        if cur is not None and cur.timestamp <= ts:
            self._pop(topic)
            self.node.metrics.dec("retained.count")
        prev = self._tombstones.get(topic, 0.0)
        self._tombstones[topic] = max(prev, ts)

    _TOMBSTONE_TTL = 3600.0

    def _sweep_tombstones(self) -> None:
        import time as _time

        cutoff = _time.time() - self._TOMBSTONE_TTL
        for t in [t for t, ts in self._tombstones.items()
                  if ts < cutoff]:
            self._tombstones.pop(t, None)

    # -- delivery on subscribe ---------------------------------------------

    def on_subscribed(self, clientinfo: dict, flt: str,
                      subopts: dict) -> None:
        """Hook entry: Retain-Handling/shared-sub gating happens here
        at submit time (both are per-subscription properties, fully
        known now); the matched set, expiry eviction and the delivery
        plan are deferred one event-loop tick so a SUBSCRIBE burst
        coalesces into one batched replay (:meth:`_replay_flush`)."""
        if flt.startswith(("$share/", "$queue/")):
            return  # never to shared subscriptions
        rh = subopts.get("rh", 0)
        if rh == 2 or (rh == 1 and subopts.get("resub")):
            return
        chan = self.node.cm.lookup_channel(
            clientinfo.get("clientid", ""))
        session = getattr(chan, "session", None)
        if session is None or not self._store:
            return
        import asyncio

        try:
            loop = asyncio.get_running_loop()
        except RuntimeError:
            loop = None
        if loop is None:
            # loop-less (library/sync) callers keep the synchronous
            # semantics: a one-item burst, flushed inline
            self._replay_flush([(session, flt, subopts)])
            return
        # the hook fires on the subscribing channel's owner loop and
        # delivery targets that same loop's session, so pending lists
        # are per-loop: append + drain never cross threads
        pend = self._pending.get(loop)
        if pend is None:
            self._pending[loop] = pend = []
        pend.append((session, flt, subopts))
        if len(pend) == 1:
            # first item this tick: drain at the end of the current
            # loop iteration — every hook firing queued behind the
            # same SUBSCRIBE burst lands in THIS batch (zero-linger
            # coalescing, like IngressBatcher.submit)
            loop.call_soon(self._replay_kick, loop)

    def _replay_kick(self, loop) -> None:
        items = self._pending.pop(loop, None)
        if items:
            try:
                self._replay_flush(items)
            except Exception:
                log.exception("retained replay flush failed")

    def _replay_flush(self, items: list) -> None:
        """One subscribe burst → one batched index match → one
        subscriber-grouped delivery plan.

        The publish path's full PR 3/5 treatment applied to replay
        (docs/DISPATCH.md "Retained replay"): unique wildcard filters
        match in ONE device dispatch (RetainIndex.match_many), every
        stored topic materializes ONE shared out-copy per burst
        (retain flag kept per MQTT-3.3.1-8, expiry filtered here in
        the plan stage with lazy eviction), the (session, filter,
        row) triples group by subscriber through
        ops/dispatch_plan.DispatchPlan, wire frames pre-build through
        preserialize_plan (retain-set and RAP variants are serialize
        classes there), and each session takes its whole group in one
        ``deliver_many`` = one notify wakeup per connection per
        burst. ``dispatch.planner=false`` restores the legacy
        per-delivery walk byte-for-byte."""
        store = self._store
        if not store:
            return
        metrics = self.node.metrics
        # unique filters across the burst; wildcards batch through
        # the index, exact filters stay a dict probe
        flt_list: List[str] = []
        fidx: Dict[str, int] = {}
        for _sess, flt, _opts in items:
            if flt not in fidx:
                fidx[flt] = len(flt_list)
                flt_list.append(flt)
        wild = [f for f in flt_list if T.wildcard(f)]
        hits: Dict[str, List[str]] = {}
        if wild:
            hits.update(zip(wild, self._index.match_many(
                wild, device_threshold=self.index_device_threshold)))
        for f in flt_list:
            if f not in hits:
                hits[f] = [f] if f in store else []
        # burst-local message rows: ONE copy per stored topic however
        # many sessions/filters matched it, so wire caches and the
        # pre-serialized frames are shared across the whole burst
        row_of: Dict[str, int] = {}
        rows: List[Message] = []

        def row_for(topic: str) -> int:
            r = row_of.get(topic)
            if r is not None:
                return r
            msg = store.get(topic)
            if msg is None or msg.is_expired():
                if msg is not None:
                    self._pop(topic)
                    metrics.dec("retained.count")
                    metrics.inc("retained.expired")
                row_of[topic] = -1
                return -1
            out = msg.copy()
            # retained-delivery keeps retain=1 (MQTT-3.3.1-8); the
            # 'retained' header tells the session's RAP logic this
            # flag is not subject to clearing
            out.set_header("retained", True)
            row_of[topic] = r = len(rows)
            rows.append(out)
            return r

        sess_of: Dict[int, int] = {}
        sessions: List[object] = []
        sids: List[int] = []
        fids: List[int] = []
        rids: List[int] = []
        opts_of: Dict[tuple, object] = {}
        for sess, flt, _opts in items:
            topics = hits.get(flt, ())
            if not topics:
                continue
            key = id(sess)
            sid = sess_of.get(key)
            if sid is None:
                sid = sess_of[key] = len(sessions)
                sessions.append(sess)
            fid = fidx[flt]
            subs = getattr(sess, "subscriptions", None)
            # the REAL SubOpts object (the hook hands a plain dict):
            # deliver_many and preserialize_plan key serialize
            # classes off its qos/rap/share/subid fields
            opts_of[(sid, fid)] = subs.get(flt) if subs else None
            for t in topics:
                r = row_for(t)
                if r >= 0:
                    sids.append(sid)
                    fids.append(fid)
                    rids.append(r)
        if not sids:
            return
        metrics.inc("retained.replay.batches")
        metrics.inc("retained.replay.messages", len(sids))
        self._replay_last_batch = len(sids)
        cfg = getattr(getattr(self.node, "broker", None),
                      "dispatch_config", None)
        if cfg is None or not cfg.planner:
            # legacy per-delivery path (dispatch.planner=false),
            # byte-for-byte the pre-batching replay loop
            for k in range(len(sids)):
                sessions[sids[k]].deliver(
                    flt_list[fids[k]], rows[rids[k]])
            return
        from emqx_tpu.ops.dispatch_plan import (DispatchPlan,
                                                preserialize_plan)

        plan = DispatchPlan(np.asarray(sids, np.int64),
                            np.asarray(fids, np.int64),
                            np.asarray(rids, np.int64))
        if cfg.preserialize:
            subscribers: Dict[str, dict] = {}
            for (sid, fid), opts in opts_of.items():
                if opts is not None:
                    subscribers.setdefault(
                        flt_list[fid], {})[sessions[sid]] = opts
            preserialize_plan(plan, list(enumerate(rows)), flt_list,
                              subscribers, lambda sid: sessions[sid])
        g_ptr = plan.g_ptr
        for g in range(plan.n_groups):
            sid = plan.g_sids[g]
            sess = sessions[sid]
            group = []
            for k in range(g_ptr[g], g_ptr[g + 1]):
                fid = plan.fids[k]
                group.append((flt_list[fid], rows[plan.rows[k]],
                              opts_of.get((sid, fid)), False))
            dm = getattr(sess, "deliver_many", None)
            if dm is not None:
                dm(group)
            else:
                # plain subscriber objects (tests, adapters) without
                # the batched protocol
                for gflt, gmsg, _o, _f in group:
                    sess.deliver(gflt, gmsg)

    def replay_info(self) -> dict:
        """``ctl retained`` snapshot: store/replay-side counters to
        pair with ``RetainIndex.device_info``."""
        m = self.node.metrics
        return {
            "store": len(self._store),
            "tombstones": len(self._tombstones),
            "dropped": m.val("retained.dropped"),
            "expired": m.val("retained.expired"),
            "replay_batches": m.val("retained.replay.batches"),
            "replay_messages": m.val("retained.replay.messages"),
            "replay_last_batch": self._replay_last_batch,
        }

    def info(self) -> dict:
        return {"retained": len(self._store)}
