"""Subscriber-id registry + device fan-out tables.

The reference's ``emqx_broker_helper`` assigns every subscriber a
dense integer id from a per-topic sequence and splits a topic's
subscriber set into shards once it passes 1024 members
(src/emqx_broker_helper.erl:63-100 register_sub/SubId maps, :55 the
``?SHARD`` threshold, :82-92 the shard split); dispatch then walks
shard records instead of one huge bag (src/emqx_broker.erl:305-309).

TPU-native redesign (SURVEY §2.2 "topic sharding → bitmap tiles"):

  - :class:`SubRegistry` assigns **globally** dense subscriber ids
    (the emqx_sequence analogue) so subscriber sets become integer
    arrays / bitmap rows a device kernel can index.
  - :class:`FanoutManager` keeps the authoritative host map
    ``filter → {subscriber ids}`` and derives the two device tables
    the broker's publish step uses:

      * small filters (≤ ``threshold`` members) → one CSR
        :class:`~emqx_tpu.ops.fanout.FanoutTable`; fan-out is the
        vmapped searchsorted gather (``gather_subscribers_src``);
      * big filters (> ``threshold``) → bitmap rows in a
        :class:`~emqx_tpu.ops.bitmap.BitmapTable`; fan-out is the
        Pallas OR-streaming kernel over the matched rows.

    This is the product wiring of the round-1 kernels: tables are
    built lazily against the **automaton's id-map snapshot**, so
    device match ids index them consistently even as filter ids are
    recycled across automaton rebuilds. A membership change costs
    what it changes: the changed rows are written behind the table's
    live entries and their ``row_pairs`` repointed, on the host mirror
    and by one small scatter program on the device (docs/DELTA.md
    "Fan-out tables"). A compaction's swap hands the tables over to
    its epoch (:meth:`FanoutManager.carry`: a swap keeps every
    filter's id, and the new map says which freed ids were taken
    again); any other new epoch, a row that is or becomes a bitmap,
    or a table out of room rebuilds whole.

Capacities grow in powers of two and never shrink, keeping device
array shapes stable across rebuilds (no recompilation churn).
"""

from __future__ import annotations

import functools
import threading
import time
from typing import Dict, List, Optional, Sequence, Set

import jax
import jax.numpy as jnp
import numpy as np

from emqx_tpu.ops.bitmap import BitmapTable, build_bitmaps
from emqx_tpu.ops.fanout import FanoutTable, build_fanout


class SubRegistry:
    """Dense subscriber ids with quarantined free-list reuse
    (emqx_broker_helper.erl:63-72 + emqx_sequence.erl semantics).

    A released id is NOT immediately reusable: device fan-out tables
    built earlier may still reference it, and handing it to a new
    subscriber would deliver the old subscriber's messages to the new
    one. Freed ids sit in a quarantine until :meth:`flush_free` —
    called by the fan-out manager right after it builds fresh tables
    (at which point no live table references the id; the reference
    sidesteps this with monotone emqx_sequence counters, at the cost
    of unbounded id growth)."""

    def __init__(self) -> None:
        self._by_sub: Dict[object, int] = {}
        self._by_id: List[Optional[object]] = []
        self._free: List[int] = []
        self._quarantine: List[int] = []

    def register(self, sub: object) -> int:
        sid = self._by_sub.get(sub)
        if sid is None:
            if not self._free and self._quarantine:
                # opportunistic aged reclaim keeps steady churn from
                # growing the table (round-4 leak)
                self.flush_free()
            if self._free:
                sid = self._free.pop()
                self._by_id[sid] = sub
            else:
                sid = len(self._by_id)
                self._by_id.append(sub)
            self._by_sub[sub] = sid
        return sid

    def sid(self, sub: object) -> Optional[int]:
        return self._by_sub.get(sub)

    def lookup(self, sid: int) -> Optional[object]:
        if 0 <= sid < len(self._by_id):
            return self._by_id[sid]
        return None

    #: quarantine dwell before a sid may recycle. Freed sids are
    #: resolved against the LIVE registry by the delivery tail, so a
    #: sid referenced by an in-flight pipelined device batch must not
    #: retranslate while that batch can still gather it — table swaps
    #: alone don't prove safety (up to max_inflight batches hold old
    #: tables). Batches live milliseconds; 5s covers any sane batch
    #: lifetime, and it also bounds the quarantine to the last 5s of
    #: churn (the round-4 leak fix). Defense in depth, not the sole
    #: guard: even a sid that DOES retranslate mid-batch is harmless,
    #: because Broker._deliver_one only delivers when the resolved
    #: sub is CURRENTLY subscribed to the matched filter — a stale
    #: slot either drops or reaches a legitimate subscriber.
    QUARANTINE_S = 5.0

    def release(self, sub: object) -> None:
        sid = self._by_sub.pop(sub, None)
        if sid is not None:
            self._by_id[sid] = None
            self._quarantine.append((sid, time.monotonic()))

    def flush_free(self) -> None:
        """Recycle quarantined ids older than :attr:`QUARANTINE_S`
        (entries are in release order, so the aged prefix suffices)."""
        cutoff = time.monotonic() - self.QUARANTINE_S
        i = 0
        for sid, ts in self._quarantine:
            if ts > cutoff:
                break
            self._free.append(sid)
            i += 1
        if i:
            del self._quarantine[:i]

    def count(self) -> int:
        return len(self._by_sub)

    def capacity(self) -> int:
        return len(self._by_id)


class FanoutState:
    """One consistent device snapshot: CSR + bitmap tables whose
    filter axis is the automaton epoch's id map."""

    __slots__ = ("epoch", "version", "fan", "bm", "big_fids")

    def __init__(self, epoch: int, version: int,
                 fan: Optional[FanoutTable],
                 bm: Optional[BitmapTable],
                 big_fids: frozenset) -> None:
        self.epoch = epoch
        self.version = version
        self.fan = fan      # device FanoutTable (small filters) or None
        self.bm = bm        # device BitmapTable (big filters) or None
        self.big_fids = big_fids  # snapshot fids on the bitmap path


class ShardedFanoutState:
    """Per-trie-shard fan tables for the mesh publish step: the
    device half is a stacked ``ShardedFanout`` (shard t's CSR holds
    only the filters :func:`~emqx_tpu.parallel.sharded.shard_of`
    assigns to t — the same stable assignment the sharded automaton
    uses, so each trie shard gathers exactly its own matches'
    subscribers) plus a stacked ``ShardedBitmaps`` for the big
    filters (membership past the per-topic ``d`` bound): their
    subscriber sets live as bitmap rows in THEIR shard's HBM and
    fan out via the per-shard OR + ICI union. ``big_fids`` names
    those filters for the broker's bitmap delivery tail."""

    __slots__ = ("epoch", "version", "fan", "bm", "big_fids", "d")

    def __init__(self, epoch: int, version: int, fan, bm,
                 big_fids: frozenset, d: int) -> None:
        self.epoch = epoch
        self.version = version
        self.fan = fan
        self.bm = bm
        self.big_fids = big_fids
        self.d = d


#: rows one launch of the patch program repoints
_PATCH_ROWS = 256


@functools.partial(jax.jit, static_argnames=("k",))
def _patch_fan(pairs: jax.Array, subs: jax.Array, buf: jax.Array, *,
               k: int):
    """One chunk of a fan-out patch on the device: repoint up to ``k``
    rows of ``row_pairs`` and write their entries, which lie in one
    run behind the table's live ones. ``buf`` is the chunk's one
    transfer: ``[first entry position, entries, k filter ids (pad: an
    id past the table, dropped), k (start, end) pairs, the entries]``.
    The tables are not donated: a batch in flight holds the old
    ones."""
    with jax.named_scope("fanout_patch"):
        e = buf.shape[0] - 2 - 3 * k
        pos = jnp.arange(e, dtype=jnp.int32)
        at = jnp.where(pos < buf[1], buf[0] + pos, subs.shape[0])
        return (pairs.at[buf[2:2 + k]].set(
                    buf[2 + k:2 + 3 * k].reshape(k, 2), mode="drop"),
                subs.at[at].set(buf[2 + 3 * k:], mode="drop"))


class FanoutManager:
    """Host truth for local subscriber sets + lazy device tables.

    ``subscribe``/``unsubscribe`` maintain ``filter → {sid}``;
    :meth:`state` returns the device tables for an automaton snapshot.
    They are built whole when the automaton epoch moves (filter ids
    are only meaningful per epoch) and patched row by row when
    membership changed inside one (:meth:`_patch`).
    """

    def __init__(self, threshold: int = 1024, use_device: bool = True):
        self.registry = SubRegistry()
        self.threshold = threshold
        self.use_device = use_device
        self.rows: Dict[str, Set[int]] = {}
        self._lock = threading.RLock()
        self._version = 0
        self._state: Optional[FanoutState] = None
        self._sharded: Optional[ShardedFanoutState] = None
        # what a patch works from (set by a whole build of the CSR
        # table, dropped with it): the host mirror of ``row_pairs``
        # and ``sub_ids`` with the first free entry position, the
        # epoch's filter → id, how much of its id map has been read
        # (the ids appended to it, and its log of ids set in place),
        # the filters whose membership changed since the tables were
        # brought up to date, and the ids an earlier epoch's map had
        # set in place when the tables were carried over
        self._mirror: Optional[tuple] = None
        self._tail = 0
        self._fid_of: Dict[str, int] = {}
        self._seen = 0
        self._seen_reused = 0
        self._changed: Set[str] = set()
        self._carried: Set[int] = set()
        # the epoch the tables were carried over from and the map of
        # the one they stand under: a batch matched just before the
        # swap still asks with the old pair
        self._behind: Optional[int] = None
        self._map: Optional[Sequence[Optional[str]]] = None
        # entries a patch chunk carries: any CSR row fits in one
        self._patch_entries = max(2048, 2 * threshold)
        # publish-path telemetry (Node wires it next to the broker's):
        # fanout.* count into its Metrics while it is enabled
        self.telemetry = None
        #: table syncs by kind and the rows the patches wrote
        #: (cumulative; ``stats`` and the tests read them)
        self.patches = 0
        self.rebuilds = 0
        self.rows_patched = 0
        self.carries = 0
        # capacity retention (pow2, never shrinks → stable jit shapes)
        self._caps: Dict[str, Optional[int]] = {
            "filter": None, "entry": None, "row": None, "nsub": 1}
        self._sh_caps: Dict[str, Optional[int]] = {
            "filter": None, "entry": None}

    # -- membership (called from Broker.subscribe/unsubscribe) ------------

    def subscribe(self, filter_: str, sub: object) -> int:
        with self._lock:
            sid = self.registry.register(sub)
            self.rows.setdefault(filter_, set()).add(sid)
            self._version += 1
            if self._mirror is not None:
                self._changed.add(filter_)
            return sid

    def unsubscribe(self, filter_: str, sub: object) -> None:
        with self._lock:
            sid = self.registry.sid(sub)
            if sid is None:
                return
            row = self.rows.get(filter_)
            if row is not None:
                row.discard(sid)
                if not row:
                    del self.rows[filter_]
            self._version += 1
            if self._mirror is not None:
                self._changed.add(filter_)

    def release(self, sub: object) -> None:
        """Drop the subscriber's id (after its last unsubscribe).
        Recycling is TIME-gated (SubRegistry.QUARANTINE_S), not
        snapshot-gated: in-flight pipelined batches resolve sids
        against the live registry, so table swaps alone never proved
        reuse safe — and the host regime (no swaps at all) previously
        leaked the quarantine unboundedly (round-4 soak)."""
        with self._lock:
            self.registry.release(sub)
            self.registry.flush_free()

    def members(self, filter_: str) -> Set[int]:
        return self.rows.get(filter_, set())

    def members_sorted(self, filter_: Optional[str]) -> np.ndarray:
        """Sorted member-sid array, copied under the lock: the
        dispatch planner's bitmap attribution runs on the ingress
        fetch thread, so it must not iterate the live (mutable) set
        the way the on-loop delivery tail may."""
        with self._lock:
            row = self.rows.get(filter_) if filter_ is not None else None
            if not row:
                return np.empty(0, np.int64)
            return np.sort(np.fromiter(row, np.int64, len(row)))

    def stats(self) -> Dict[str, int]:
        return {
            "subscribers.count": self.registry.count(),
            "fanout.filters": len(self.rows),
            "fanout.version": self._version,
            "fanout.patches": self.patches,
            "fanout.rebuilds": self.rebuilds,
        }

    def invalidate_device(self) -> None:
        """Device-loss recovery (devloss.py, docs/ROBUSTNESS.md):
        the cached fan-out snapshots hold CSR/bitmap tables in a
        dead backend's HBM. Drop them — the next :meth:`state` /
        :meth:`sharded_state` call re-derives the tables from the
        live membership ``rows`` at the rebuilt automaton's epoch.
        Host truth (registry, rows, version) is untouched."""
        with self._lock:
            self._state = None
            self._sharded = None
            self._drop_mirror()

    # -- device snapshot ---------------------------------------------------

    def _drop_mirror(self) -> None:
        self._mirror = None
        self._fid_of = {}
        self._changed = set()
        self._carried = set()
        self._seen_reused = 0
        self._behind = self._map = None

    def _count_sync(self, kind: str, t0: float) -> None:
        """One sync that changed the tables (metrics.FANOUT_METRICS;
        gated on [telemetry] enabled like the dispatch's counters)."""
        tel = self.telemetry
        if tel is not None and tel.loop_clock() is not None:
            m = tel.metrics
            m.inc(kind)
            m.inc("fanout.sync.ns",
                  int((time.perf_counter() - t0) * 1e9))

    def state(self, epoch: int,
              id_map: Sequence[Optional[str]]) -> Optional[FanoutState]:
        """Device tables consistent with the automaton snapshot
        ``(epoch, id_map)``; ``None`` when there are no local
        subscribers (device fan-out has nothing to do). Unchanged
        membership costs one compare; a change costs the rows it
        changed (:meth:`_patch`), over a compaction's swap too
        (:meth:`carry`); any other new epoch builds whole."""
        with self._lock:
            st = self._state
            if st is not None and epoch == self._behind:
                # a batch matched just before a swap: its ids are the
                # new epoch's too, so it is served from the one table,
                # brought up to date against the map that is live
                epoch, id_map = st.epoch, self._map
            if st is not None and st.epoch == epoch:
                if st.version == self._version \
                        and (self._mirror is None
                             or (self._seen == len(id_map)
                                 and not self._carried
                                 and self._seen_reused == len(
                                     getattr(id_map, "reused", ())))):
                    return st
                if self._mirror is not None and self.rows:
                    t0 = time.perf_counter()
                    patched = self._patch(st, id_map)
                    if patched is not None:
                        self.patches += 1
                        self._count_sync("fanout.patches", t0)
                        self.registry.flush_free()
                        return patched
            t0 = time.perf_counter()
            st = self._build(epoch, id_map)
            if st is not None:
                self.rebuilds += 1
                self._count_sync("fanout.rebuilds", t0)
            # the previous state (the last table referencing any
            # quarantined sid) is gone; freed ids may recycle now
            self.registry.flush_free()
            return st

    def _build(self, epoch: int,
               id_map: Sequence[Optional[str]]) -> Optional[FanoutState]:
        """The tables from scratch: every filter of ``id_map`` looked
        up in ``rows``. What :meth:`_patch` is held to (call under the
        lock)."""
        self._drop_mirror()
        if not self.rows:
            self._state = None
            return None
        small: Dict[int, List[int]] = {}
        big: Dict[int, Sequence[int]] = {}
        big_fids = set()
        fid_of: Dict[str, int] = {}
        for fid, f in enumerate(id_map):
            if f is None:
                continue
            fid_of[f] = fid
            row = self.rows.get(f)
            if not row:
                continue
            if len(row) > self.threshold:
                big[fid] = sorted(row)
                big_fids.add(fid)
            else:
                small[fid] = sorted(row)
        n_filters = len(id_map)
        fan = bm = None
        if small or not big:
            fan = build_fanout(
                small, n_filters,
                filter_capacity=self._caps["filter"],
                entry_capacity=self._caps["entry"])
            self._caps["filter"] = fan.row_ptr.shape[0] - 1
            self._caps["entry"] = fan.sub_ids.shape[0]
            # the arrays built here stay the host's copy; the state
            # gets the device's (or, off the device, its own)
            self._mirror = (fan.row_pairs, fan.sub_ids)
            self._tail = fan.n_entries
            self._fid_of = fid_of
            self._seen = n_filters
            self._seen_reused = len(getattr(id_map, "reused", ()))
        if big:
            nsub = max(self._caps["nsub"], self.registry.capacity())
            bm = build_bitmaps(
                big, n_filters, nsub,
                row_capacity=self._caps["row"])
            self._caps["row"] = bm.bitmaps.shape[0]
            self._caps["nsub"] = nsub
        if self.use_device:
            if fan is not None:
                fan = jax.device_put(fan)
            if bm is not None:
                bm = jax.device_put(bm)
        elif fan is not None:
            fan = fan._replace(row_pairs=fan.row_pairs.copy(),
                               sub_ids=fan.sub_ids.copy())
        st = FanoutState(epoch, self._version, fan, bm,
                         frozenset(big_fids))
        self._state = st
        return st

    def _patch(self, st: FanoutState,
               id_map: Sequence[Optional[str]]) -> Optional[FanoutState]:
        """Bring ``st``'s CSR table up to the membership changes and
        the ids appended to ``id_map`` since it was current, in work
        proportional to them: each changed row is written anew behind
        the live entries (what it held before stays where it was,
        unreachable) and its ``row_pairs`` entry repointed; a row that
        lost its filter or its last member is pointed at nothing.
        ``row_ptr`` keeps the last whole build's values: with
        ``row_pairs`` there no program reads them. None where a whole
        build is due instead: a row that is or would be a bitmap, an
        id past the table's capacity, or no room behind the entries
        (call under the lock)."""
        pairs, subs = self._mirror
        f_cap, fid_of = pairs.shape[0], self._fid_of
        n_map = len(id_map)
        if n_map > f_cap:
            return None
        # the ids whose row may have changed: those appended to the
        # map or set in place in it since (a freed id taken again),
        # and those the changed filters bear or bore. What a touched
        # id's row holds is then read from the map as it stands, so
        # the order in which it was touched does not matter
        reused = getattr(id_map, "reused", ())
        touched = set(self._carried)
        touched.update(range(self._seen, n_map))
        touched.update(reused[self._seen_reused:])
        for f in self._changed:
            fid = fid_of.get(f)
            if fid is not None:
                touched.add(fid)
        writes = []  # (id, sorted members or None)
        room = subs.shape[0] - 1 - self._tail
        for fid in sorted(touched):
            f = id_map[fid]
            row = None
            if f is not None:
                # the filter came (back) under this id: the id it
                # had, if any, was touched when its route was dropped
                fid_of[f] = fid
                row = self.rows.get(f)
            if fid in st.big_fids or (row and len(row) > self.threshold):
                return None
            if row:
                room -= len(row)
                writes.append((fid, sorted(row)))
            elif pairs[fid, 0] != pairs[fid, 1]:
                writes.append((fid, None))
        if room < 0:
            return None
        for f in self._changed:
            # a filter whose route was dropped bears no id any more
            fid = fid_of.get(f)
            if fid is not None and id_map[fid] != f:
                del fid_of[f]
        self._seen = n_map
        self._seen_reused = len(reused)
        self._changed = set()
        self._carried = set()
        fan = st.fan
        dev_pairs, dev_subs = fan.row_pairs, fan.sub_ids
        k, e_cap = _PATCH_ROWS, self._patch_entries
        i = 0
        while i < len(writes):
            buf = np.full(2 + 3 * k + e_cap, -1, np.int32)
            buf[2:2 + k] = f_cap
            start = tail = self._tail
            n = 0
            while i < len(writes) and n < k:
                fid, members = writes[i]
                m = len(members) if members else 0
                if tail - start + m > e_cap:
                    break
                if m:
                    subs[tail:tail + m] = members
                    buf[2 + 3 * k + tail - start:
                        2 + 3 * k + tail - start + m] = members
                pairs[fid] = (tail, tail + m) if m else (0, 0)
                buf[2 + n] = fid
                buf[2 + k + 2 * n:2 + k + 2 * n + 2] = pairs[fid]
                tail += m
                n += 1
                i += 1
            buf[0], buf[1] = start, tail - start
            self._tail = tail
            # the chunk's one transfer (the buffer, as the launch's
            # argument) and one launch
            dev_pairs, dev_subs = _patch_fan(dev_pairs, dev_subs, buf,
                                             k=k)
        self.rows_patched += len(writes)
        st = FanoutState(
            st.epoch, self._version,
            fan._replace(row_pairs=dev_pairs, sub_ids=dev_subs),
            st.bm, st.big_fids)
        self._state = st
        return st

    def carry(self, epoch: int, id_map: Sequence[Optional[str]],
              new_epoch: int,
              new_map: Sequence[Optional[str]]) -> None:
        """A compaction's swap (``Router.on_swap``, under the router's
        lock on the compaction thread): the automaton epoch moves from
        ``epoch``, whose map is ``id_map``, to ``new_epoch`` with
        ``new_map`` (entry for entry the old one), and every filter
        keeps its id; ids freed before the swap may be taken by later
        route adds, which the new map's ``reused`` will say. The
        tables stand as they are under the new epoch: what ``id_map``
        had set in place and this manager had not read yet is kept for
        the next :meth:`_patch`, which costs the rows that changed and
        no other. Tables of another epoch, or none a patch can work
        from, are left for :meth:`state` to build whole."""
        with self._lock:
            st = self._state
            if st is None or st.epoch != epoch or self._mirror is None:
                return
            self._carried.update(
                getattr(id_map, "reused", ())[self._seen_reused:])
            self._seen_reused = 0
            self._behind, self._map = epoch, new_map
            self._state = FanoutState(new_epoch, st.version, st.fan,
                                      st.bm, st.big_fids)
            self.carries += 1

    def sharded_state(self, epoch: int,
                      id_map: Sequence[Optional[str]],
                      mesh, d: int) -> Optional[ShardedFanoutState]:
        """Per-shard device fan tables consistent with the automaton
        snapshot, for ``publish_step(with_fanout=True)`` (the mesh
        analogue of :meth:`state`). Filters whose membership exceeds
        ``min(threshold, d)`` get bitmap rows in their shard instead
        of CSR entries — materializing them in the ``d``-bounded
        gather would overflow every batch."""
        from emqx_tpu.parallel.sharded import (build_sharded_bitmaps,
                                               build_sharded_fanout,
                                               place_sharded, shard_of)

        n_shards = mesh.shape["trie"]
        with self._lock:
            st = self._sharded
            if (st is not None and st.epoch == epoch
                    and st.version == self._version and st.d == d):
                return st
            if not self.rows:
                self._sharded = None
                self.registry.flush_free()
                return None
            limit = min(self.threshold, d)
            rows_per_shard: List[Dict[int, List[int]]] = [
                {} for _ in range(n_shards)]
            big_per_shard: List[Dict[int, List[int]]] = [
                {} for _ in range(n_shards)]
            big_fids = set()
            for fid, f in enumerate(id_map):
                if f is None:
                    continue
                row = self.rows.get(f)
                if not row:
                    continue
                if len(row) > limit:
                    big_fids.add(fid)
                    big_per_shard[shard_of(f, n_shards)][fid] = \
                        sorted(row)
                else:
                    rows_per_shard[shard_of(f, n_shards)][fid] = \
                        sorted(row)
            fan = build_sharded_fanout(
                rows_per_shard, len(id_map),
                filter_capacity=self._sh_caps["filter"],
                entry_capacity=self._sh_caps["entry"])
            self._sh_caps["filter"] = fan.row_ptr.shape[1] - 1
            self._sh_caps["entry"] = fan.sub_ids.shape[1]
            bm = None
            if big_fids:
                nsub = max(self._caps["nsub"], self.registry.capacity())
                self._caps["nsub"] = nsub
                bm = build_sharded_bitmaps(
                    big_per_shard, len(id_map), nsub,
                    row_capacity=self._sh_caps.get("row"))
                self._sh_caps["row"] = bm.bitmaps.shape[1]
            if self.use_device:
                fan = place_sharded(mesh, fan)
                if bm is not None:
                    bm = place_sharded(mesh, bm)
            st = ShardedFanoutState(epoch, self._version, fan, bm,
                                    frozenset(big_fids), d)
            self._sharded = st
            self.registry.flush_free()
            return st


def unpack_sids(row_words: np.ndarray) -> np.ndarray:
    """uint32 bitmap row → sorted int array of set bit positions
    (subscriber ids). Little-endian bit order matches
    :func:`~emqx_tpu.ops.bitmap.build_bitmaps`."""
    bits = np.unpackbits(row_words.view(np.uint8), bitorder="little")
    return np.flatnonzero(bits)
