"""The router: authoritative route state + the compiled device matcher.

Replaces the reference's ``emqx_router``/``emqx_trie`` pair
(src/emqx_router.erl:113-133, src/emqx_trie.erl): routes are a host
map ``filter → {dest: refcount}`` (the Mnesia ``emqx_route`` bag), and
the *match* side is a TPU-resident CSR automaton rebuilt incrementally
from the host trie. Differences by design (SURVEY §7):

  - the reference keeps exact-match routes out of the trie and unions
    a direct ETS lookup at match time (emqx_router.erl:127-133); here
    *all* filters live in the automaton, so one device walk returns
    the full route set — an exact filter is just a literal path;
  - rebuilds are double-buffered: matching continues against the live
    automaton while the new one is flattened; the swap is atomic from
    the caller's perspective (the reference's transactional trie
    insert, emqx_router.erl:229-234);
  - topics that exceed the kernel's static bounds fall back to the
    host oracle (exact parity, never truncation).

Thread-safety follows the reference's serialization model: writes go
through one writer (the reference hashes topics onto router_pool
workers, emqx_router.erl:185-186); here a mutex serializes mutations.
"""

from __future__ import annotations

import logging
import threading
import time
import zlib
from collections import deque
from dataclasses import dataclass
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

import jax
import numpy as np

from emqx_tpu import faults
from emqx_tpu import topic as T
from emqx_tpu.oracle import TrieOracle
from emqx_tpu.ops.csr import (Automaton, build_automaton, capacity_for,
                              device_view)
from emqx_tpu.ops.match import depth_bucket, match_batch
from emqx_tpu.ops.patch import AutoPatcher, PatchOverflow
from emqx_tpu.ops.tokenize import WordTable, encode_batch
from emqx_tpu.telemetry import enqueue_mark
from emqx_tpu.types import Route

log = logging.getLogger("emqx_tpu.router")


class DispatchShape(NamedTuple):
    """One batch as the match dispatch's programs see it: how many of
    its unique topics the match cache serves, how many walk the
    automaton, and the levels of the deepest that walks. The padding
    rule (:meth:`Router.shape_programs`) turns it into the shapes the
    programs are compiled for; :meth:`Router.dispatch_shapes` lists
    one batch for every program."""

    hits: int
    misses: int
    depth: int


class IdMap(list):
    """Filter id → filter as one automaton epoch publishes it: a list
    that is appended and tombstoned in place while the epoch lasts
    (what lock-free matchers rely on), and that says which of its ids
    were given a filter in place: ``reused`` holds, in order, every
    id below the map's length that went from ``None`` to a filter (an
    id a flatten gave back, taken by a later route add). Who keeps
    tables by filter id (``broker_helper.FanoutManager``) reads the
    appended ids and this log, and so never walks the map."""

    __slots__ = ("reused",)

    def __init__(self, ids=()) -> None:
        super().__init__(ids)
        self.reused: List[int] = []


@dataclass
class MatcherConfig:
    max_levels: int = 16    # L — deeper topics fall back to the oracle
    # NFA active-set capacity: the walk's cost is ~linear in K (3
    # packed gathers per state-level), and real active sets are tiny
    # (≤ matching prefix paths). Overflow → exact host fallback.
    active_k: int = 16
    max_matches: int = 64   # match output capacity
    min_batch: int = 8      # batch padding bucket floor (pow2 buckets)
    use_device: bool = True
    use_native: bool = True  # C++ trie/encoder when the .so is present
    # multi-chip: a (data × trie) jax Mesh shards the filter set over
    # the 'trie' axis and the publish batch over 'data'; matching goes
    # through parallel.sharded.publish_step (ICI all-gather of match
    # ids). BASELINE config 5's product path. From a file it is
    # ``[matcher] mesh = { data = 2, trie = 2 }``: config.parse_config
    # keeps the axis sizes, config.build_node places the Mesh over the
    # host's first data x trie devices. Restart-only.
    mesh: Optional[object] = None
    # device fan-out (broker_helper): filters with more subscribers
    # than the threshold move from the CSR gather to bitmap rows
    # (the reference's ?SHARD=1024, src/emqx_broker_helper.erl:55)
    fanout_threshold: int = 1024
    # per-message small-filter delivery slots: gather cost is ~linear
    # in d; a message exceeding it host-dispatches (and >threshold
    # filters ride the bitmap path, so d only covers the small tail)
    fanout_d: int = 128
    fanout_mb: int = 16      # per-message big(bitmap)-filter slots
    # below this many live filters the broker matches on HOST (the
    # C++ trie): a device dispatch + result transfer costs fixed
    # round-trip latency that only amortizes at scale, while the host
    # walk is O(depth) hash lookups. The device automaton still
    # maintains itself (patching/rebuilds) so crossing the threshold
    # is usually just a branch flip — unless host-regime churn piled
    # more than host_reclaim_pending freed ids, in which case the
    # stale automaton is dropped (reclaim_host_regime) and the next
    # device use re-flattens.
    device_min_filters: int = 1024
    # host-regime quarantined-id bound before the stale automaton is
    # dropped and ids recycle (bounded hysteresis; round-4 leak fix)
    host_reclaim_pending: int = 1024
    # packed-transfer budgets (ops/pack.py): expected average matched
    # filters / deliveries per message and bitmap rows per batch; the
    # publish path re-packs with the next pow2 bucket on overflow
    pack_m: int = 8
    pack_q: int = 16
    pack_rows: int = 8
    # mutation-side patch drain: once this many device updates are
    # queued, the MUTATOR applies them (amortized O(1) per route
    # change). Matchers then find at most one small chunk to drain —
    # under 10K route-mutations/s the round-4 churn bench showed the
    # match path paying a multi-chunk drain (each chunk copy-on-
    # writes the full walk tables) on nearly every call, a 90ms p99
    # tail the reference's O(levels) dirty inserts never had
    # (src/emqx_router.erl:226-234).
    patch_drain_batch: int = 256
    # publish match cache (ops/match_cache.py): epoch-guarded HBM
    # memo of per-topic match rows — a repeat topic across batches
    # costs one gather instead of an NFA walk. A route add/delete
    # bumps the affected partition's epoch (or the global one — see
    # cache_partitions below), rebuilds/capacity boosts bump
    # globally, so stale entries self-invalidate; overflow topics are
    # never served from it (exact host fallback, as always). False
    # restores the pre-cache dispatch byte-for-byte. Slot count is a
    # power of two; footprint ≈ slots × (max_matches + 1) × 4 B
    # (default 64K slots × 65 ints ≈ 16 MB of HBM).
    match_cache: bool = True
    match_cache_slots: int = 65536
    # match-cache invalidation granularity: P-way partitioned epoch
    # keys over the topic's FIRST LEVEL. A filter mutation whose root
    # is a literal bumps only its partition's revision (a filter
    # `a/+/c` can only change the match set of topics rooted at `a`),
    # so disjoint-prefix subscribe/unsubscribe churn no longer
    # collapses the hit rate to zero; root `+`/`#` filters (and
    # rebuilds, reclaims) still bump the global revision — exactly as
    # safe as whole-epoch. Power of two; 1 = legacy whole-epoch
    # invalidation byte-for-byte (the PR-1 behavior).
    cache_partitions: int = 64
    # online delta automaton (ops/delta.py, docs/DELTA.md): route adds
    # batch into a small side-automaton probed alongside the main walk
    # (terminal-id union), deletes become a post-match tombstone-id
    # mask — the main tables stay PRISTINE during storms (no patch
    # splits, no hop decay, no full-table scatter copies), and the
    # background compaction flattens the persistent trie OFF-lock
    # (route ops during the flatten complete in ms and land in the
    # next delta generation via the mutation log). False restores the
    # patch-in-place path byte-for-byte. A configured mesh keeps
    # per-shard patch-in-place regardless (the delta is single-chip).
    delta: bool = True
    # pending delta adds that trigger the background merge compaction
    # (also bounds the side-automaton walk cost)
    delta_max_filters: int = 4096

    #: live-reloadable knobs (emqx_tpu/reload.py,
    #: docs/OPERATIONS.md): only fields the match/mutation paths read
    #: at use time — everything else is kernel/table geometry copied
    #: into built device structures at flatten time (not a dataclass
    #: field: unannotated)
    RELOADABLE = frozenset({
        "delta", "delta_max_filters", "device_min_filters",
        "patch_drain_batch", "host_reclaim_pending"})


def topic_partition(topic: str, parts: int) -> int:
    """Match-cache partition of a concrete topic: a stable hash of
    its first level (``parts`` is a power of two). Stable across
    processes (crc32, not ``hash``) so bench A/B runs and checkpoint
    restores key identically."""
    return zlib.crc32(topic.partition("/")[0].encode()) & (parts - 1)


def filter_partitions(filter_: str, parts: int) -> Optional[Tuple[int, ...]]:
    """Invalidation scope of a filter mutation under partitioned
    epochs: the partition indices to bump, or ``None`` when only a
    global bump is safe.

    A filter whose first level is a **literal** ``L`` can only change
    the match set of topics whose first level is exactly ``L`` (the
    automaton descends level-by-level; ``+``/``#`` deeper in the
    filter never widen the root), so bumping partition ``h(L)``
    suffices. A root ``+`` or ``#`` matches topics of any root →
    ``None``. A ``$share``/``$queue`` prefix is group routing, not
    matching — the broker strips it before ``add_route`` — so a
    prefixed filter reaching the router verbatim partitions on the
    level AFTER the prefix (the root of the filter that actually
    matches subscribers' topics) *plus* the raw ``$share`` root
    (covering the literal interpretation: a trie handed the prefixed
    string matches topics rooted ``$share``). A malformed or
    wildcard-rooted inner filter falls back to ``None`` —
    conservatively correct, never stale."""
    root = filter_.partition("/")[0]
    if root == T.PLUS or root == T.HASH:
        return None
    p0 = zlib.crc32(root.encode()) & (parts - 1)
    if not filter_.startswith((T.SHARE_PREFIX, T.QUEUE_PREFIX)):
        return (p0,)
    try:
        inner, _opts = T.parse(filter_)
    except T.TopicError:
        return None
    iroot = inner.partition("/")[0]
    if iroot == T.PLUS or iroot == T.HASH:
        return None
    p1 = zlib.crc32(iroot.encode()) & (parts - 1)
    return (p0,) if p1 == p0 else (p0, p1)


class Router:
    """Cluster route table + compiled matcher (one per node)."""

    def __init__(self, config: Optional[MatcherConfig] = None,
                 node: str = "local") -> None:
        self.config = config or MatcherConfig()
        self.node = node
        self._lock = threading.RLock()
        # word-table guard, finer than _lock: interning rehashes the
        # word map, which must not race the match path's encode reads.
        # Matchers take ONLY this lock (briefly, around encode), so a
        # long flatten under _lock — background compaction — never
        # stalls them. Order: _lock before _wt_lock, never the reverse.
        self._wt_lock = threading.RLock()
        self._native = None
        # C++ engine on both layouts: one monolithic trie single-chip,
        # one trie per trie shard on a mesh (ShardedNativeEngine —
        # same stable shard_of assignment as the Python builder)
        if self.config.use_native:
            try:
                from emqx_tpu.ops import native as _native_mod
                if _native_mod.available():
                    if self.config.mesh is None:
                        self._native = _native_mod.NativeEngine()
                    else:
                        self._native = _native_mod.ShardedNativeEngine(
                            self.config.mesh.shape["trie"])
            except Exception:
                self._native = None
        # pure-Python structures double as the fallback path when the
        # native engine is absent (parity pinned in tests/test_native)
        self._trie = TrieOracle() if self._native is None else None
        self._table = WordTable() if self._native is None else None
        # filter -> {dest: refcount}; bag semantics (emqx_route)
        self._routes: Dict[str, Dict[object, int]] = {}
        self._filter_ids: Dict[str, int] = {}
        self._id_to_filter: List[Optional[str]] = []
        # ids are recycled only across rebuild generations: a freed id
        # quarantines in _pending_free until the next full flatten
        # (which replaces the published id-map object), so any map a
        # matcher holds is append-only + tombstone-only — a recycled
        # id can never retranslate to a different filter mid-read
        self._free_ids: List[int] = []
        self._pending_free: List[int] = []
        self._auto: Optional[Automaton] = None  # live device automaton
        # id→filter list the live automaton encodes: appended/tombstoned
        # in place by the patcher, REPLACED (new object) on rebuild
        self._auto_map: IdMap = IdMap()
        # (auto, map, epoch) snapshot: one-reference read for matchers
        # (attribute assignment is atomic — no lock on the match path)
        self._published: Optional[tuple] = None
        self._dirty = True
        self._rebuilds = 0
        self._patches = 0
        # O(delta) maintenance (ops/patch.py): host mirror of the live
        # automaton; None until the first flatten. Mesh mode keeps ONE
        # PATCHER PER TRIE SHARD (stable hash assignment — a mutation
        # patches exactly its shard's row of the stacked automaton)
        self._patcher: Optional[AutoPatcher] = None
        self._shard_patchers: List[AutoPatcher] = []
        self._sharded_caps = {"state": None, "nb": None}
        self._grow = {"state": 1, "edge": 1}  # rebuild growth factors
        # static walk parameters of the LIVE tables (read host-side,
        # never through jit): slot layout, max take, step bounds, and
        # whether any '+' edge exists (no '+' ⇒ the active set is
        # provably ≤1 lane, so the walk runs k=1)
        self._walk_meta = {"slots": 2, "take": 1, "hops": None,
                           "has_plus": True}
        # level-compression facts of the LIVE tables (set alongside
        # _walk_meta at rebuild/restore): chains = compressed edges
        # carrying a fused run (take > 1), fused_edges = interior
        # states those runs absorbed, ratio = permille of walk steps
        # compression shaved off the deepest level
        self._compaction = {"mode": "narrow", "chains": 0,
                            "fused_edges": 0, "ratio": 0}
        # level-bucket shapes live dispatches have compiled (lb after
        # depth_bucket) — devloss rewarm replays exactly these so a
        # deep post-recovery batch pays zero compile (ops/warmup.py)
        self._seen_levels: set = set()
        self._compacting = False  # background compaction in flight
        # crashed-compaction supervision (docs/ROBUSTNESS.md): a
        # background flatten that raised arms an exponential backoff
        # before the next attempt; on_bg_error(exc|None) reports the
        # outcome (Node turns it into the alarm) — the callback may
        # run ON the compaction thread, so it must only store
        self._compact_failures = 0
        self._compact_backoff_until = 0.0
        self.on_bg_error = None
        # the hand-over of a compaction's swap: on_swap(old epoch,
        # old id map, new epoch, new id map) runs under the lock on the
        # compaction thread just before the swap, for whoever keeps tables by
        # filter id (the broker's fan-out manager carries its tables
        # over the epoch: a swap keeps every filter's id). If it
        # raises, the swap is not made: the old tables and the old
        # epoch stay live and the compaction counts as crashed
        self.on_swap = None
        self._dummy_fan = None    # sharded publish_step filler fan
        # learned active-set boost: an overflow-storm batch (many
        # topics exceeding active_k) doubles the effective K (bounded)
        # instead of host-matching that workload forever — one extra
        # compile per growth step, exact fallback in the meantime;
        # _d_boost is the same mechanism for the mesh gather's
        # per-topic delivery slots
        self._k_boost = 0
        self._d_boost = 0
        # device stat accumulators (sharded publish_step psums),
        # drained asynchronously by the stats flush — appending the
        # jax scalars defers the host transfer to drain time
        self._dev_stats: deque = deque(maxlen=65536)
        # publish match cache (ops/match_cache.py), lazily built on
        # first device match. _cache_rev is the GLOBAL epoch guard:
        # bumped on rebuild (ids recycle), host-regime reclaim, and
        # any mutation whose invalidation scope can't be narrowed —
        # cached rows are only served while their insert-time
        # (epoch, rev[, partition_rev], boosts) key matches exactly.
        # _part_revs scopes literal-rooted filter mutations to the
        # one partition owning that first level (docs/MATCH_CACHE.md
        # "Partitioned epochs"); sized at construction, bumped under
        # _lock, snapshotted (tuple copy) by probes BEFORE the
        # automaton snapshot so a racing mutation can only make
        # entries look stale, never fresh
        P = self.config.cache_partitions
        if P < 1 or (P & (P - 1)):
            raise ValueError(
                f"cache_partitions must be a power of two >= 1, "
                f"got {P}")
        if self.config.delta_max_filters < 1:
            raise ValueError(
                f"delta_max_filters must be >= 1, "
                f"got {self.config.delta_max_filters}")
        self._cache_rev = 0
        self._part_revs: List[int] = [0] * P
        # epoch-bump accounting (cache.match.bump.* counters): how
        # much of the invalidation traffic was scoped vs global — the
        # churn-diagnosis split (a hit-rate collapse with bump.global
        # racing means root-wildcard churn; with bump.partition it
        # means literal churn colliding into hot partitions)
        self._bump_global = 0
        self._bump_partition = 0
        self._bump_drained = (0, 0)
        self._match_cache_obj = None
        self._sharded_cache_obj = None
        self._sharded_cache_meta = None  # (T, m, d) the table is sized for
        # the batch buffer's capacity so far (a shape of every program
        # of the cache-split dispatch: ops/match_cache.py header);
        # only ever grows
        self._batch_buf_len = 0
        # publish-path telemetry (telemetry.Telemetry), wired by Node
        # alongside broker.telemetry. When enabled, the cache-split
        # dispatch leaves its per-batch probe/merge timing + hit/miss
        # split in _last_dispatch for the broker's span to consume
        # (PublishSpan.stop_match pops it) — None otherwise, and the
        # dispatch path pays nothing
        self.telemetry = None
        self._last_dispatch: Optional[dict] = None
        # online delta automaton (ops/delta.py, docs/DELTA.md): the
        # side structures holding route mutations the main tables
        # haven't absorbed yet. Lazily created on the first delta-mode
        # mutation against a live automaton; None = empty. _pub2 is
        # the atomically-published (main snapshot, delta snapshot,
        # delta version, k_boost) pair matchers read in ONE reference
        # (reading main and delta separately could double- or
        # zero-count a filter across a compaction swap). _freeze is
        # the trie defer-log active while an off-lock flatten reads
        # the (frozen) trie; _rebuild_inflight gates inline rebuilds
        # away from the flatten window.
        self._delta = None
        self._delta_ver = 0
        self._pub2: Optional[tuple] = None
        self._freeze: Optional[dict] = None
        self._rebuild_inflight = False
        # device-loss recovery (devloss.py, docs/ROBUSTNESS.md
        # "Device-loss recovery"): while True every match routes
        # through the host trie — the published device snapshots
        # reference a dead backend's HBM and must not be touched.
        # Set by suspend_device() at lost-backend classification,
        # cleared when rebuild_device_state() publishes fresh tables
        self._device_suspended = False
        # automaton.delta.* / automaton.rebuild.* counters, drained by
        # the stats flush (drain_automaton_stats)
        self._delta_probes = 0
        self._delta_filters = 0
        self._delta_merges = 0
        self._delta_tombstones = 0
        self._delta_retracts = 0
        self._rebuild_stall_ms = 0.0
        self._compaction_ns = 0      # freeze to end of swap, off-lock
        self._delta_grows = 0        # of delta generations retired
        self._freeze_deferred = 0    # route ops a freeze log took
        self._auto_drained = (0,) * 11

    # -- engine dispatch (native C++ or pure Python) ----------------------

    @property
    def _delta_active(self) -> bool:
        """Delta mode in effect: configured on and single-chip (the
        mesh keeps per-shard patch-in-place — its collective step has
        no two-probe seam). Read per call so :meth:`set_delta` can
        flip it at runtime (bench A/B on one router)."""
        return self.config.delta and self.config.mesh is None

    def _intern_fn(self):
        """The engine's word-intern callable (the delta's side
        structures must share the main word-id space — both walks
        consume the same encoded batch)."""
        if self._native is not None:
            return self._native.intern
        return self._table.intern

    def _ensure_delta(self):
        if self._delta is None:
            from emqx_tpu.ops.delta import DeltaAutomaton

            self._delta = DeltaAutomaton(self._intern_fn(),
                                         self.config.use_device)
            # room for the bound's filters at four levels each: the
            # side tables keep one capacity up to a compaction
            self._delta.floor_states = capacity_for(
                4 * self.config.delta_max_filters)
        return self._delta

    def _t_insert(self, filter_: str, fid: int) -> None:
        with self._wt_lock:  # interning mutates the word table
            if self._native is not None:
                self._native.insert(filter_, fid)
            else:
                self._trie.insert(filter_)
                # pre-intern literal words so the flatten (which may
                # run on the compaction thread concurrently with
                # encode reads) never mutates the word table
                for w in T.words(filter_):
                    if w not in (T.PLUS, T.HASH):
                        self._table.intern(w)

    def _t_delete(self, filter_: str) -> None:
        if self._native is not None:
            self._native.delete(filter_)
        else:
            self._trie.delete(filter_)

    # -- freeze protocol (off-lock compaction, docs/DELTA.md) -------------
    #
    # While a background flatten reads the persistent trie OFF-lock,
    # the trie must not be mutated (the flatten is read-only, so
    # concurrent host matches stay safe — concurrent inserts would
    # not). Route ops landing in that window defer into _freeze: the
    # ordered log replays into the trie at swap time, and the small
    # side trie/set compensate host matches meanwhile. Word interning
    # still happens immediately (the word table is not the trie — the
    # flatten never reads it on the native engine, and on the Python
    # engine all its words are pre-interned), so concurrently encoded
    # batches resolve the new vocabulary.

    def _t_insert_route(self, filter_: str, fid: int) -> None:
        fz = self._freeze
        if fz is None:
            self._t_insert(filter_, fid)
            return
        fz["log"].append(("+", filter_, fid))
        fz["adds"].insert(filter_)
        fz["add_fids"][filter_] = fid
        fz["dels"].discard(filter_)
        with self._wt_lock:
            intern = self._intern_fn()
            for w in T.words(filter_):
                if w not in (T.PLUS, T.HASH):
                    intern(w)

    def _t_delete_route(self, filter_: str, fid: int) -> None:
        fz = self._freeze
        if fz is None:
            self._t_delete(filter_)
            return
        fz["log"].append(("-", filter_, fid))
        if filter_ in fz["add_fids"]:
            fz["adds"].delete(filter_)
            del fz["add_fids"][filter_]
        else:
            fz["dels"].add(filter_)

    def _unfreeze_locked(self) -> None:
        """Replay the deferred trie mutations in order and lift the
        freeze (call under the lock, after the off-lock flatten is
        done with the trie)."""
        fz = self._freeze
        if fz is None:
            return
        self._freeze = None
        self._rebuild_inflight = False
        self._freeze_deferred += len(fz["log"])
        for op, f, fid in fz["log"]:
            if op == "+":
                self._t_insert(f, fid)
            else:
                self._t_delete(f)

    def _t_match(self, topic: str) -> List[str]:
        """Host-side exact match (fallback path); call under lock."""
        if self._native is not None:
            out = []
            for fid in self._native.match(topic):
                f = self._id_to_filter[fid] \
                    if fid < len(self._id_to_filter) else None
                if f is not None:
                    out.append(f)
            return out
        return self._trie.match(topic)

    def _host_match_locked(self, topic: str) -> List[str]:
        """:meth:`_t_match` plus the freeze-window compensation: while
        an off-lock flatten holds the trie frozen, deferred adds come
        from the freeze side-trie and deferred deletes are subtracted
        (the native engine's are already dropped by the id map's
        ``None`` translation). Exact at every instant."""
        out = self._t_match(topic)
        fz = self._freeze
        if fz is not None:
            if self._native is None and fz["dels"]:
                out = [f for f in out if f not in fz["dels"]]
            if fz["add_fids"]:
                seen = set(out)
                out = out + [f for f in fz["adds"].match(topic)
                             if f not in seen]
        return out

    def _encode(self, topics: Sequence[str], max_levels: int):
        if self._native is not None:
            return self._native.encode_batch(topics, max_levels)
        return encode_batch(self._table, topics, max_levels)

    # -- route table mutation (emqx_router:do_add_route/do_delete_route) --

    def _assign_id(self, filter_: str) -> int:
        fid = self._filter_ids.get(filter_)
        if fid is None:
            if self._free_ids:
                fid = self._free_ids.pop()
                self._id_to_filter[fid] = filter_
            else:
                fid = len(self._id_to_filter)
                self._id_to_filter.append(filter_)
            self._filter_ids[filter_] = fid
        return fid

    def _bump_cache_rev(self, filter_: Optional[str] = None) -> None:
        """Invalidate cached match rows a mutation can affect (call
        under the lock). ``filter_=None`` — or any filter whose
        invalidation scope can't be narrowed (root wildcard, malformed
        share prefix), or legacy ``cache_partitions = 1`` — bumps the
        global revision; a literal-rooted filter bumps only its
        partition(s)."""
        if filter_ is not None and self.config.cache_partitions > 1:
            parts = filter_partitions(filter_,
                                      self.config.cache_partitions)
            if parts is not None:
                for p in parts:
                    self._part_revs[p] += 1
                self._bump_partition += 1
                return
        self._cache_rev += 1
        self._bump_global += 1

    def add_route(self, filter_: str, dest: object = None) -> int:
        """Add a route; returns the filter's dense id."""
        dest = self.node if dest is None else dest
        with self._lock:
            dests = self._routes.get(filter_)
            fid = self._assign_id(filter_)
            if dests is None:
                dests = {}
                self._routes[filter_] = dests
                self._t_insert_route(filter_, fid)
                if self._delta_active and self._auto is not None \
                        and not self._dirty:
                    # delta mode: the main tables stay pristine — the
                    # add lands in the side-automaton probed alongside
                    # the main walk (docs/DELTA.md)
                    self._delta_add_locked(filter_, fid)
                else:
                    self._patch_insert(filter_, fid)
                # the new filter may change cached topics' match sets
                # — invalidate its partition (literal root) or the
                # whole epoch (root wildcard); see ops/match_cache.py
                self._bump_cache_rev(filter_)
            dests[dest] = dests.get(dest, 0) + 1
            return fid

    def _delta_add_locked(self, filter_: str, fid: int) -> None:
        d = self._ensure_delta()
        with self._wt_lock:  # side-patcher insert interns new words
            d.add(filter_, fid)
        self._map_set(fid, filter_)
        self._delta_ver += 1
        self._delta_filters += 1
        if d.n_pending >= self.config.delta_max_filters:
            self._maybe_compact_locked()

    def _delta_delete_locked(self, filter_: str, fid: int) -> None:
        d = self._ensure_delta()
        if filter_ in d.fids:
            self._delta_retracts += 1
        else:
            self._delta_tombstones += 1
        with self._wt_lock:  # retracting a pending add walks words
            d.delete(filter_, fid)
        self._map_set(fid, None)
        self._delta_ver += 1
        if d.needs_compaction(self.config.delta_max_filters,
                              len(self._filter_ids)):
            self._maybe_compact_locked()

    def _retire_delta(self, successor) -> None:
        """Replace the delta generation (call under the lock): what
        the outgoing one counted is kept for the drain."""
        if self._delta is not None:
            self._delta_grows += self._delta.grows
        self._delta = successor
        self._delta_ver += 1

    def _maybe_compact_locked(self) -> None:
        if not self._compacting and not self._dirty \
                and self._needs_compaction_locked():
            self._schedule_compaction()

    def _patcher_for(self, filter_: str) -> Optional[AutoPatcher]:
        """The patcher owning ``filter_`` (per-shard on a mesh, the
        single mirror otherwise); None = no live patcher."""
        if self.config.mesh is not None:
            if not self._shard_patchers:
                return None
            from emqx_tpu.parallel.sharded import shard_of

            return self._shard_patchers[
                shard_of(filter_, len(self._shard_patchers))]
        return self._patcher

    def _shard_live_estimate(self) -> int:
        """Per-shard live-filter estimate (compaction thresholds on a
        mesh must compare a shard's tombstones against ITS share of
        the filter set, not the global count)."""
        n = len(self._shard_patchers)
        return len(self._filter_ids) // n if n else len(self._filter_ids)

    def _patch_insert(self, filter_: str, fid: int) -> None:
        """O(depth) patch of the live automaton; falls back to a full
        rebuild flag on capacity overflow (call under the lock)."""
        # a '+' edge revokes the k=1 fast path BEFORE the patch can
        # reach any matcher (same lock; lock-free readers see the
        # patch only after a locked sync, which follows this write)
        if not self._walk_meta["has_plus"] and T.PLUS in T.words(filter_):
            self._walk_meta["has_plus"] = True
        p = None if self._dirty else self._patcher_for(filter_)
        if p is None:
            self._dirty = True
            return
        try:
            with self._wt_lock:  # patcher.insert interns new words
                p.insert(filter_, fid)
            self._map_set(fid, filter_)
            self._patches += 1
            self._drain_if_backlogged()
        except PatchOverflow as e:
            # the patcher may hold a dangling partial insert now
            # (broken flag set); _dirty forces a re-flatten before
            # any apply, so the partial queue is discarded
            self._grow[e.kind] = 2
            self._dirty = True

    def _patch_delete(self, filter_: str, fid: int) -> None:
        p = None if self._dirty else self._patcher_for(filter_)
        if p is None:
            self._dirty = True
            return
        with self._wt_lock:  # delete's word walk may intern
            p.delete(filter_)
        self._map_set(fid, None)
        self._patches += 1
        self._drain_if_backlogged()
        live = (self._shard_live_estimate()
                if self.config.mesh is not None
                else len(self._filter_ids))
        if p.needs_compaction(live):
            # tombstones dominate. The tombstoned automaton is still
            # CORRECT (just wasteful), so compaction runs on a
            # background thread and swaps atomically — matchers never
            # stall on it (only capacity overflows rebuild inline)
            self._schedule_compaction()

    def _drain_if_backlogged(self) -> None:
        """Apply queued device patches once the backlog reaches the
        drain batch — on the MUTATOR's thread, under the lock it
        already holds. The published snapshot stays hot for lock-free
        matchers; a matcher that does hit the dirty branch drains at
        most one chunk. Skipped when no automaton is live (_dirty)."""
        if self._dirty or self._auto is None:
            return
        q = 0
        if self._patcher is not None:
            q = self._patcher.queued
        elif self._shard_patchers:
            q = max(p.queued for p in self._shard_patchers)
        if q >= self.config.patch_drain_batch:
            self._apply_patches_locked()

    def _map_set(self, fid: int, filter_: Optional[str]) -> None:
        m = self._auto_map
        if fid >= len(m):
            m.extend([None] * (fid - len(m)))
            m.append(filter_)
            return
        if filter_ is not None:
            m.reused.append(fid)  # a recycled id, set in place
        m[fid] = filter_

    def delete_route(self, filter_: str, dest: object = None) -> None:
        dest = self.node if dest is None else dest
        with self._lock:
            dests = self._routes.get(filter_)
            if dests is None or dest not in dests:
                return
            dests[dest] -= 1
            if dests[dest] <= 0:
                del dests[dest]
            if not dests:
                del self._routes[filter_]
                self._drop_filter_locked(filter_)

    def _drop_filter_locked(self, filter_: str) -> None:
        """The last route for ``filter_`` went away: tombstone it out
        of the matcher (delta tombstone mask or patch-in-place,
        depending on mode) and retire its id. Call under the lock,
        AFTER removing it from ``_routes``."""
        self._t_delete_route(filter_, self._filter_ids[filter_])
        fid = self._filter_ids.pop(filter_)
        self._id_to_filter[fid] = None
        self._retire_id(fid)
        if self._delta_active and self._auto is not None \
                and not self._dirty:
            self._delta_delete_locked(filter_, fid)
        else:
            self._patch_delete(filter_, fid)
        # cached rows may hold this fid — but only rows whose
        # topic the filter matched, all inside its partition
        self._bump_cache_rev(filter_)

    def _retire_id(self, fid: int) -> None:
        """Freed filter id → quarantine or immediate recycle.

        Quarantine exists because published device snapshots hold the
        id→filter map; the id may only recycle after the next flatten
        replaces them. In the HOST regime no automaton was ever
        built, so nothing references the id — recycle now. (Round-4
        soak: below the device threshold nothing ever rebuilds, and
        pending_free grew by ~200K ids/minute of subscribe churn,
        a linear leak.)"""
        if self._auto is None:
            self._free_ids.append(fid)
        else:
            self._pending_free.append(fid)

    def has_route(self, filter_: str) -> bool:
        return filter_ in self._routes

    def has_dest(self, filter_: str, dest: object) -> bool:
        return dest in self._routes.get(filter_, ())

    def ensure_route(self, filter_: str, dest: object) -> None:
        """Idempotent add — one logical route per (filter, dest), used
        by replication (Mnesia-bag semantics, no refcount)."""
        with self._lock:
            if not self.has_dest(filter_, dest):
                self.add_route(filter_, dest=dest)

    def drop_route(self, filter_: str, dest: object) -> None:
        """Remove a (filter, dest) route regardless of refcount."""
        with self._lock:
            dests = self._routes.get(filter_)
            if dests is not None and dest in dests:
                dests[dest] = 1
                self.delete_route(filter_, dest=dest)

    def topics(self) -> List[str]:
        return list(self._routes)

    def has_routes(self) -> bool:
        """O(1) emptiness probe for the publish hot path."""
        return bool(self._routes)

    def lookup_routes(self, filter_: str) -> List[Route]:
        dests = self._routes.get(filter_, {})
        return [Route(filter_, d) for d in dests]

    # -- durability seams (wal.py / durability.py) ------------------------

    def route_refs(self, filter_: str, dest: object) -> int:
        """Current refcount for ``(filter, dest)`` — the absolute
        value the journal records after every route mutation, so a
        doubly-replayed record is idempotent (docs/DURABILITY.md)."""
        with self._lock:
            return self._routes.get(filter_, {}).get(dest, 0)

    def route_table(self) -> Dict[str, Dict[object, int]]:
        """Consistent copy of the full (filter → dest → refs) table
        (recovery's orphan-ref pruning pass reads it)."""
        with self._lock:
            return {f: dict(d) for f, d in self._routes.items()}

    def set_route_refs(self, filter_: str, dest: object,
                       refs: int) -> None:
        """Drive ``(filter, dest)`` to an absolute refcount — journal
        replay's idempotent apply (the lock is reentrant; add/delete
        below keep every automaton/delta/cache side effect)."""
        with self._lock:
            cur = self._routes.get(filter_, {}).get(dest, 0)
            for _ in range(refs - cur):
                self.add_route(filter_, dest=dest)
            for _ in range(cur - refs):
                self.delete_route(filter_, dest=dest)

    def filter_id(self, filter_: str) -> Optional[int]:
        return self._filter_ids.get(filter_)

    def cleanup_routes(self, node: object) -> None:
        """Purge all routes pointing at a dead node
        (emqx_router_helper.erl:173-177)."""
        with self._lock:
            for f in [f for f, d in self._routes.items() if node in d]:
                dests = self._routes[f]
                del dests[node]
                if not dests:
                    del self._routes[f]
                    self._drop_filter_locked(f)

    def stats(self) -> Dict[str, int]:
        return {
            "routes.count": sum(len(d) for d in self._routes.values()),
            "topics.count": len(self._routes),
            "rebuilds": self._rebuilds,
            "patches": self._patches,
        }

    # -- automaton lifecycle ---------------------------------------------

    def rebuild(self) -> Automaton:
        """Flatten the trie to a fresh automaton (double-buffered: the
        previous one stays live for concurrent matchers until swap).
        While an off-lock compaction flatten is in flight the trie is
        frozen — that compaction IS the rebuild, so return the live
        automaton instead of racing it."""
        with self._lock:
            if self._freeze is not None:
                return self._auto
            return self._rebuild_locked()

    def _rebuild_locked(self):
        import time as _time

        tel = self.telemetry
        ann = tel.rebuild_begin() \
            if tel is not None and tel.enabled else None
        t0 = _time.perf_counter()
        try:
            if self.config.mesh is not None:
                return self._rebuild_sharded_locked()
            return self._rebuild_single_locked()
        finally:
            if ann is not None:
                tel.rebuild_done(ann)
                tel.observe_stage(
                    "rebuild", (_time.perf_counter() - t0) * 1000.0)

    def _rebuild_single_locked(self) -> Automaton:
        prev = self._auto
        cap_s2 = nb = None
        if prev is not None and prev.node2 is not None:
            # honor the growth factors a PatchOverflow requested, so
            # near-full generations don't re-overflow immediately
            # (what must stay shape-stable are the WALK tables — the
            # CSR flatten arrays never reach the device)
            cap_s2 = prev.node2.shape[0] * self._grow["state"]
            nb = prev.wt.shape[0] * self._grow["edge"]
        if self._native is not None:
            host_auto = self._native.flatten(
                v2_state_capacity=cap_s2, n_buckets=nb)
            intern = self._native.intern
        else:
            host_auto = build_automaton(
                self._trie, self._filter_ids, self._table,
                v2_state_capacity=cap_s2, v2_n_buckets=nb)
            intern = self._table.intern
        self._install_walk_meta(host_auto)
        auto = device_view(host_auto)
        if self.config.use_device:
            auto = jax.device_put(auto)
        if self._delta_active:
            # delta mode keeps no main-table mirror (the mirror copies
            # the full walk tables — dead weight when nothing patches
            # them); the trie had every mutation applied, so any
            # pending delta is folded by this flatten
            self._patcher = None
            self._retire_delta(None)
        else:
            # the mirror copies host arrays (no device→host readback)
            self._patcher = AutoPatcher(host_auto, intern)
        self._auto = auto
        self._auto_map = IdMap(self._id_to_filter)  # NEW object: old
        # snapshots freeze, so quarantined ids may recycle now
        self._free_ids.extend(self._pending_free)
        self._pending_free.clear()
        self._dirty = False
        self._grow = {"state": 1, "edge": 1}
        self._rebuilds += 1
        self._bump_cache_rev()  # fresh id map: quarantined ids recycle
        self._published = (auto, self._auto_map, self._rebuilds,
                           self._cache_rev)
        self._publish_pair_locked()
        return auto

    def _rebuild_sharded_locked(self):
        """Flatten the filter set into per-shard automatons stacked
        over the mesh's trie axis (parallel/sharded.py), and seed one
        :class:`AutoPatcher` per shard so subsequent route churn
        patches only the affected shard's row — O(delta) on the mesh,
        same as single-chip (the shard assignment is a stable filter
        hash, so a mutation never reshuffles other shards)."""
        from emqx_tpu.parallel.sharded import (
            ShardedFanout, build_sharded, place_sharded, shard_filters)

        mesh = self.config.mesh
        n_trie = mesh.shape["trie"]
        caps = self._sharded_caps
        grow_s = caps["state"] * self._grow["state"] \
            if caps["state"] else None
        grow_nb = caps["nb"] * self._grow["edge"] if caps["nb"] else None
        if self._native is not None:
            # C++ per-shard tries flatten straight into the stacked
            # device layout (VERDICT r3 item 8: the mesh rebuild was
            # the last Python-builder path)
            host_auto, parts = self._native.flatten_sharded(
                state_capacity=grow_s, n_buckets=grow_nb)
            intern = self._native.intern
        else:
            shards = shard_filters(sorted(self._routes), n_trie)
            host_auto, parts = build_sharded(
                shards, self._filter_ids, self._table,
                state_capacity=grow_s, n_buckets=grow_nb,
                return_parts=True)
            intern = self._table.intern
        caps["state"] = parts[0].node2.shape[0]
        caps["nb"] = parts[0].wt.shape[0]
        self._install_walk_meta(parts[0], parts=parts)
        auto = place_sharded(mesh, host_auto) \
            if self.config.use_device else host_auto
        self._shard_patchers = [AutoPatcher(p, intern) for p in parts]
        if self._dummy_fan is None:
            # publish_step's fan input when the caller only matches
            # (with_fanout=False): minimal, never read
            self._dummy_fan = place_sharded(mesh, ShardedFanout(
                row_ptr=np.zeros((n_trie, 2), np.int32),
                sub_ids=np.full((n_trie, 1), -1, np.int32),
                row_pairs=np.zeros((n_trie, 1, 2), np.int32)))
        self._auto = auto
        self._auto_map = IdMap(self._id_to_filter)
        self._free_ids.extend(self._pending_free)
        self._pending_free.clear()
        self._patcher = None
        self._dirty = False
        self._grow = {"state": 1, "edge": 1}
        self._rebuilds += 1
        self._bump_cache_rev()  # fresh id map: quarantined ids recycle
        self._published = (auto, self._auto_map, self._rebuilds,
                           self._cache_rev)
        self._publish_pair_locked()
        return auto

    def _install_walk_meta(self, host_auto: Automaton,
                           parts=None) -> None:
        """Record the live tables' static walk parameters (call under
        the lock, at rebuild/restore time). ``parts`` = per-shard host
        automatons on a mesh."""
        pool = parts if parts is not None else [host_auto]
        has_plus = any(
            bool((np.asarray(p.node2)[:max(p.v2_states, 1), 0] >= 0)
                 .any()) for p in pool)
        self._walk_meta = {
            "slots": int(host_auto.wt_slots),
            "take": int(host_auto.wt_take),
            "hops": np.array(host_auto.hops_for_level),
            "has_plus": has_plus,
        }
        chains = fused = 0
        if int(host_auto.wt_take) > 1:
            from emqx_tpu.ops.csr import WIDE_SLOT
            for p in pool:
                wt = np.asarray(p.wt).reshape(-1, WIDE_SLOT)
                takes = wt[wt[:, 0] >= 0, 2]
                chains += int((takes > 1).sum())
                fused += int((takes - 1).sum())
        hops = self._walk_meta["hops"]
        levels = len(hops)
        deepest = int(hops[-1]) if levels else 0
        self._compaction = {
            "mode": "wide" if int(host_auto.wt_take) > 1 else "narrow",
            "chains": chains,
            "fused_edges": fused,
            "ratio": (1000 * (levels - deepest)) // levels
            if levels else 0,
        }

    def _steps_for(self, lb: int) -> int:
        """Scan-step bound for a batch sliced to ``lb`` levels — read
        from the live patchers (they grow the bound when a patch
        deepens a walk path) or the rebuild-time snapshot."""
        if self._shard_patchers:
            return max(
                int(p.hops_for_level[min(lb, len(p.hops_for_level) - 1)])
                for p in self._shard_patchers)
        p = self._patcher
        hl = (p.hops_for_level if p is not None
              else self._walk_meta["hops"])
        if hl is None:
            return lb + 1
        return int(hl[min(lb, len(hl) - 1)])

    def _walk_kw(self, lb: int) -> dict:
        """Static kernel kwargs for the live tables at batch depth
        ``lb``."""
        self._seen_levels.add(int(lb))  # GIL-atomic; rewarm reads it
        m = self._walk_meta
        return {"steps": self._steps_for(lb), "slots": m["slots"],
                "take": m["take"]}

    def observed_levels(self) -> List[int]:
        """Level-bucket shapes live dispatches have used (each is one
        jit compile family) — the devloss rewarm's level axis."""
        return sorted(self._seen_levels)

    def _patchers_dirty(self) -> bool:
        """Any live patcher holding queued device updates?"""
        if self._patcher is not None and self._patcher.dirty:
            return True
        return any(p.dirty for p in self._shard_patchers)

    def _needs_compaction_locked(self) -> bool:
        if self._delta_active and self._delta is not None \
                and self._auto is not None:
            return self._delta.needs_compaction(
                self.config.delta_max_filters, len(self._filter_ids))
        if self._patcher is not None:
            return self._patcher.needs_compaction(len(self._filter_ids))
        if self._shard_patchers:
            per = self._shard_live_estimate()
            return any(p.needs_compaction(per)
                       for p in self._shard_patchers)
        return False

    def _apply_patches_locked(self) -> None:
        """Drain every dirty patcher's update queue into a fresh
        device automaton and publish it (call under the lock). On a
        mesh each dirty shard scatters into its own row of the
        stacked automaton."""
        if self._patcher is not None:
            self._auto = self._patcher.apply_updates(self._auto)
        else:
            from emqx_tpu.ops.patch import apply_stacked_multi

            dirty = [(t, p) for t, p in enumerate(self._shard_patchers)
                     if p.dirty]
            if dirty:
                self._auto = apply_stacked_multi(dirty, self._auto)
        self._published = (self._auto, self._auto_map,
                           self._rebuilds, self._cache_rev)

    def _schedule_compaction(self) -> None:
        if self._compacting:
            return
        if self._compact_failures \
                and time.monotonic() < self._compact_backoff_until:
            # a recent compaction crashed: hold the retry until the
            # backoff elapses (route ops keep landing in the delta /
            # patch queue meanwhile — correctness never depends on
            # the flatten, only memory/latency headroom does)
            return
        self._compacting = True
        offlock = self._delta_active

        def _bg():
            try:
                if offlock:
                    # delta mode: flatten OFF-lock with the freeze
                    # protocol — route ops and matchers never wait on
                    # the multi-second build (docs/DELTA.md)
                    self._compact_offlock()
                else:
                    with self._lock:
                        # a sync rebuild may have beaten us to it
                        # (fresh patcher, tombstones gone): re-check,
                        # don't re-flatten for nothing
                        if not self._dirty \
                                and self._needs_compaction_locked():
                            # drain queued patches FIRST: with the
                            # queue clean, matchers arriving during
                            # the long flatten stay on the lock-free
                            # fast path (patcher.dirty would send
                            # them to the locked branch — stalling
                            # the whole match plane for the flatten)
                            if self._patchers_dirty():
                                self._apply_patches_locked()
                            self._rebuild_locked()
                self._compact_failures = 0
                cb = self.on_bg_error
                if cb is not None:
                    cb(None)
            except Exception as e:
                # the compaction thread must not die silently (the
                # BEAM restarts its crashed workers; here the crash
                # arms a backoff-retry and surfaces through the
                # router_compaction_failed alarm). The freeze paths
                # already unfroze on their own error handling.
                log.exception("background compaction crashed")
                self._compact_failures += 1
                self._compact_backoff_until = time.monotonic() + min(
                    2.0 ** self._compact_failures, 60.0)
                cb = self.on_bg_error
                if cb is not None:
                    cb(e)
            finally:
                self._compacting = False

        threading.Thread(target=_bg, daemon=True,
                         name="router-compaction").start()

    def retry_compaction(self) -> None:
        """Re-attempt a crashed background compaction once its
        backoff elapsed (overload monitor tick) — without this, a
        traffic lull after the crash would leave the rebuild pending
        until the next route op."""
        if not self._compact_failures or self._compacting \
                or time.monotonic() < self._compact_backoff_until:
            return
        with self._lock:
            need = self._auto is not None \
                and self._needs_compaction_locked()
        if need:
            self._schedule_compaction()

    def _flatten_main(self, cap_s2, nb):
        """Flatten the persistent trie into a fresh host automaton —
        the ONLY long step of a compaction, and (under the freeze
        protocol) the only one that runs off-lock. Split out so tests
        can interpose a slow build."""
        if faults.enabled:
            faults.fire("compaction.flatten")
        if self._native is not None:
            return self._native.flatten(
                v2_state_capacity=cap_s2, n_buckets=nb)
        return build_automaton(
            self._trie, self._filter_ids, self._table,
            v2_state_capacity=cap_s2, v2_n_buckets=nb)

    def _compact_offlock(self) -> None:
        """Delta-mode background compaction: freeze the trie + mark
        the delta log under a SHORT lock, flatten OFF-lock (the
        multi-second step at scale — concurrent route ops defer into
        the freeze log and the next delta generation, concurrent
        matchers keep the published (main, delta) pair), then swap +
        replay under another short lock. The lock is held for
        milliseconds total — `automaton.rebuild.stall_ms` counts
        exactly that, `automaton.compaction.ns` the whole of it; its
        stages (``freeze``, ``flatten``, ``put``, ``handover``,
        ``swap``: telemetry.REBUILD_STAGES) are observed beside the
        ``rebuild`` stage."""
        clock = time.perf_counter
        t_begin = clock()
        with self._lock:
            t0 = clock()
            if self._dirty or self._auto is None \
                    or not self._delta_active \
                    or not self._needs_compaction_locked():
                return
            self._freeze = {"log": [], "adds": TrieOracle(),
                            "add_fids": {}, "dels": set()}
            self._rebuild_inflight = True
            mark = self._delta.mark() if self._delta is not None else 0
            n_pend = len(self._pending_free)
            prev = self._auto
            cap_s2 = nb = None
            if prev is not None and prev.node2 is not None:
                cap_s2 = prev.node2.shape[0] * self._grow["state"]
                nb = prev.wt.shape[0] * self._grow["edge"]
            t_frozen = clock()
            stall = t_frozen - t0
        tel = self.telemetry
        ann = tel.rebuild_begin() \
            if tel is not None and tel.enabled else None
        took = {"freeze": t_frozen - t_begin}
        try:
            try:
                host_auto = self._flatten_main(cap_s2, nb)
                t_flat = clock()
                took["flatten"] = t_flat - t_frozen
                auto = device_view(host_auto)
                if self.config.use_device:
                    auto = jax.device_put(auto)
                took["put"] = clock() - t_flat
            except BaseException:
                with self._lock:
                    self._unfreeze_locked()
                raise
            with self._lock:
                t1 = clock()
                # a NEW object: snapshots of the old epoch freeze
                new_map = IdMap(self._id_to_filter)
                hand = self.on_swap
                if hand is not None:
                    # before anything of the router moves: a hand-over
                    # that fails leaves the old tables live
                    try:
                        hand(self._rebuilds, self._auto_map,
                             self._rebuilds + 1, new_map)
                    except BaseException:
                        self._unfreeze_locked()
                        raise
                t_hand = clock()
                took["handover"] = t_hand - t1
                self._install_walk_meta(host_auto)
                self._auto = auto
                self._patcher = None  # delta mode: no main-table mirror
                self._auto_map = new_map
                # recycle ONLY ids quarantined before the freeze: an id
                # freed DURING the flatten may still be emitted by the
                # new tables (its path was in the snapshot) — it waits a
                # generation
                self._free_ids.extend(self._pending_free[:n_pend])
                del self._pending_free[:n_pend]
                self._dirty = False
                self._grow = {"state": 1, "edge": 1}
                self._rebuilds += 1
                self._bump_cache_rev()
                self._published = (auto, self._auto_map, self._rebuilds,
                                   self._cache_rev)
                # fold: log entries before the mark are in the new tables;
                # the rest replay into a fresh delta generation
                self._retire_delta(self._delta.split_after(mark)
                                   if self._delta is not None else None)
                self._delta_merges += 1
                self._unfreeze_locked()
                self._publish_pair_locked()
                t_end = clock()
                took["swap"] = t_end - t_hand
                stall += t_end - t1
                self._rebuild_stall_ms += stall * 1000.0
                self._compaction_ns += int((t_end - t_begin) * 1e9)
        finally:
            if ann is not None:
                tel.rebuild_done(ann)
        if ann is not None:
            tel.observe_stage("rebuild", (clock() - t_begin) * 1000.0)
            for stage, secs in took.items():
                tel.observe_stage("rebuild." + stage, secs * 1000.0)

    def automaton(self) -> tuple:
        """(automaton, id→filter snapshot, epoch) — a consistent
        triple. The epoch (rebuild counter) keys derived device state
        (fan-out tables) to this snapshot's id space.

        Fast path is lock-free: one reference read of the published
        snapshot. The lock is taken only to re-flatten (automaton
        dirty — capacity overflow or first build) or to drain queued
        O(delta) patches into a new buffer generation. The dirty check
        always precedes the patch drain: a broken patcher (partial
        insert after overflow) is discarded by the rebuild before its
        queue could ever reach the device."""
        return self.snapshot_cached()[:3]

    def snapshot_cached(self) -> tuple:
        """:meth:`automaton` plus the snapshot's cache revision —
        ``(automaton, id→filter map, epoch, cache_rev)``. The rev is
        stamped into the published tuple AT publish time (under the
        lock), so it names exactly the mutation set the snapshot
        includes: the match cache keys entries on it, and a mutation
        concurrent with a probe can only make entries look stale
        (re-walked, safe) — never serve pre-mutation rows as
        fresh."""
        pub = self._published
        if pub is not None and not self._dirty \
                and not self._patchers_dirty():
            return pub
        with self._lock:
            return self._sync_locked()

    def _sync_locked(self) -> tuple:
        """Bring the published snapshot current (call under the
        lock). Dirty check FIRST — that ordering is the invariant
        that discards a broken patcher's partial queue via the
        rebuild before it could ever be applied. A frozen trie
        (off-lock compaction flatten in flight) defers the rebuild to
        that compaction's swap — the published pair stays exact
        meanwhile (delta mode never dirties a live automaton)."""
        if self._dirty or self._auto is None:
            if self._freeze is None:
                self._rebuild_locked()
        elif self._patchers_dirty():
            self._apply_patches_locked()
        return self._published

    # -- published (main, delta) pair (delta mode, docs/DELTA.md) ---------

    def _publish_pair_locked(self) -> None:
        """Re-publish the (main snapshot, delta snapshot, version,
        k_boost) tuple matchers read in one reference. Call under the
        lock after any main swap or (lazily, from the match path)
        after delta mutations."""
        if not self._delta_active:
            self._pub2 = None
            return
        main = self._published
        if main is not None and main[3] != self._cache_rev:
            # re-stamp the published snapshot's cache revision: in
            # delta mode a mutation never dirties the main tables, so
            # the 4-tuple would otherwise keep its flatten-time rev
            # forever and globally-bumped cache entries (root
            # wildcards, partitions=1) would probe as FRESH — a stale
            # serve. The pair published below includes the delta, so
            # the current rev names exactly what matchers see.
            main = (main[0], main[1], main[2], self._cache_rev)
            self._published = main
        d = self._delta
        snap = None
        if d is not None:
            k_cap = max(self.config.active_k, self._k_boost)
            with self._wt_lock:  # a deferred-build flatten may intern
                snap = d.snapshot(len(self._id_to_filter), k_cap)
        self._pub2 = (main, snap, self._delta_ver,
                      self._k_boost)

    def warm_delta(self) -> int:
        """The delta's part of the dispatch's warm-up
        (``Broker.warm_dispatch``): with a delta live, its side tables
        staged and the patch scatter first-used at every chunk size a
        drain can take; -> programs launched (0 with no delta)."""
        with self._lock:
            if self._delta is None or self._auto is None or self._dirty:
                return 0
            self._publish_pair_locked()
            return self._delta.warm_apply()

    def _snapshot_pair(self):
        """Consistent ``((auto, id_map, epoch, rev), delta_snap)``
        for the two-probe match path. Fast path is one reference
        read; the lock is taken only to refresh a stale delta
        snapshot (small apply/flatten — milliseconds) or to build the
        first automaton."""
        pair = self._pub2
        if pair is not None and not self._dirty \
                and pair[0] is self._published \
                and pair[2] == self._delta_ver \
                and pair[3] == self._k_boost:
            return pair[0], pair[1]
        with self._lock:
            self._sync_locked()
            self._publish_pair_locked()
            pair = self._pub2
            return pair[0], pair[1]

    # -- matching (emqx_router:match_routes/1) ----------------------------

    def match_routes(self, topic: str) -> List[Route]:
        """All routes whose filter matches ``topic``."""
        [filters] = self.match_filters([topic])
        out: List[Route] = []
        for f in filters:
            out.extend(self.lookup_routes(f))
        return out

    def host_match(self, topic: str) -> List[str]:
        """Host-side exact match (the oracle fallback path)."""
        with self._lock:
            return self._host_match_locked(topic)

    def use_device_now(self) -> bool:
        """The host/device matching policy for the product publish
        path: the device automaton pays fixed dispatch + transfer
        latency per call, so it only wins past a filter-count
        threshold (below it the C++ trie walk is microseconds — the
        reference's regime, where ETS reads are always 'host'). A
        configured mesh is an explicit opt-in to sharded device
        matching, so it bypasses the threshold (the dryrun exercises
        tiny shapes); ``use_device=False`` wins over everything (the
        debugging escape hatch)."""
        cfg = self.config
        if not cfg.use_device or not self._routes:
            return False
        if self._device_suspended:
            # lost backend: every published device snapshot points at
            # dead HBM — host trie until the rebuild publishes fresh
            # tables (devloss.py)
            return False
        if cfg.mesh is not None:
            return True
        return len(self._filter_ids) >= cfg.device_min_filters

    def reclaim_host_regime(self) -> None:
        """Called by the publish path when it chose the HOST regime:
        if a previously published automaton's id quarantine has grown
        past ``host_reclaim_pending``, drop the automaton (the next
        device use re-flattens from scratch) and drain the ids.

        The size bound is hysteresis: a filter count oscillating
        around ``device_min_filters`` must not pay a full re-flatten
        per crossing — a stale automaton pins at most the bound
        (~28B/id) until churn actually accumulates. Without any
        reclaim, a broker that crossed the threshold once and fell
        back would pin ``_pending_free`` forever (the round-4 leak's
        second head). In-flight matchers are safe: they hold their
        own (auto, map) snapshot references, and recycling only
        mutates the live list."""
        if self._auto is None or \
                len(self._pending_free) <= self.config.host_reclaim_pending:
            return
        with self._lock:
            if self._auto is None or len(self._pending_free) <= \
                    self.config.host_reclaim_pending:
                return
            if self._freeze is not None:
                # an off-lock compaction flatten is mid-flight; its
                # swap will recycle the quarantine anyway
                return
            self._auto = None
            self._published = None
            self._patcher = None
            self._shard_patchers = []
            # the delta's pending adds/deletes are all in the trie
            # (mutations apply immediately outside a freeze), so the
            # next flatten re-derives them — drop the side structures
            self._retire_delta(None)
            self._pub2 = None
            self._dirty = True  # next device use must re-flatten
            self._free_ids.extend(self._pending_free)
            self._pending_free.clear()
            self._bump_cache_rev()  # drained ids may recycle

    # -- device-loss recovery (devloss.py, docs/ROBUSTNESS.md) ------------

    def suspend_device(self) -> None:
        """Lost-backend classification, step 0: route every match
        through the host trie until :meth:`rebuild_device_state`
        publishes fresh tables. One attribute write — matchers that
        would have gathered from dead HBM buffers (publish dispatch,
        retained replay, ``match_routes``) take the exact host path
        instead."""
        self._device_suspended = True
        log.error("device matching suspended: backend lost — host "
                  "trie serves until the rebuild publishes")

    def device_suspended(self) -> bool:
        return self._device_suspended

    def match_filters_host(self, topics: Sequence[str]) -> List[List[str]]:
        """Host-only batch match — the breaker's exact oracle
        fallback. Unlike :meth:`match_filters` this NEVER consults
        the device, whatever ``use_device_now()`` says: an open or
        rebuilding breaker means the device plane is suspect, and
        the fallback must not re-execute against it."""
        if not topics:
            return []
        with self._lock:
            return [self._host_match_locked(t) for t in topics]

    def _quarantine_locked(self) -> None:
        """Drop every published reference to the dead backend's HBM
        state (call under the lock, device already suspended): the
        published (main, delta) snapshots, the match caches (their
        table gathers would read dead buffers — cold start), the
        mesh filler fan, the delta's staged device view. The
        host-authoritative structures — persistent trie, route
        table, word table, filter-id assignment — are untouched:
        they are exactly what the rebuild reads."""
        self._published = None
        self._pub2 = None
        self._match_cache_obj = None
        self._sharded_cache_obj = None
        self._sharded_cache_meta = None
        self._dummy_fan = None
        if self._delta is not None:
            self._delta.invalidate_device()
        self._bump_cache_rev()

    def rebuild_device_state(self) -> dict:
        """Device-loss recovery (devloss.DeviceRecovery): quarantine
        the dead published snapshot and rebuild ALL device-resident
        state from the host-authoritative structures — the
        persistent trie re-flattens to fresh tables placed straight
        into HBM (the ``checkpoint.load`` path), the delta
        side-automaton and tombstone mask re-stage against the new
        id map, and the match cache cold-starts under a global epoch
        bump so no stale cached row can ever serve.

        Delta mode reuses the PR 7 off-lock freeze protocol: the
        flatten runs OFF the router lock, so route ops arriving
        mid-rebuild complete in ms (deferred into the freeze log +
        the next delta generation) and host matches stay exact
        throughout. Non-delta and mesh configurations rebuild under
        the lock — route ops stall for the flatten (documented
        degrade, docs/ROBUSTNESS.md; the mesh rebuild is best-effort
        per-shard via the stacked flatten).

        Raises when the fresh placement fails (backend still dead,
        or died again mid-rebuild) — the recovery loop retries with
        backoff. On success the device suspension lifts and the
        published snapshot serves again."""
        import time as _time

        # claim the compaction slot: a background flatten may be
        # mid-flight against the dead device — wait it out (its own
        # error handling arms the compaction backoff)
        deadline = _time.monotonic() + 120.0
        while True:
            with self._lock:
                if not self._compacting and self._freeze is None:
                    self._compacting = True
                    break
            if _time.monotonic() > deadline:
                raise RuntimeError(
                    "device-state rebuild: background compaction "
                    "would not yield")
            _time.sleep(0.01)
        t0 = _time.perf_counter()
        try:
            if faults.enabled:
                faults.fire("device.lost")
            with self._lock:
                offlock = (self._delta_active
                           and self._auto is not None
                           and not self._dirty)
            if offlock:
                self._rebuild_devloss_offlock()
            else:
                with self._lock:
                    self._quarantine_locked()
                    self._dirty = True
                    self._rebuild_locked()
                    self._device_suspended = False
        finally:
            self._compacting = False
        return {"rebuild_s": _time.perf_counter() - t0,
                "epoch": self._rebuilds,
                "filters": len(self._filter_ids)}

    def _rebuild_devloss_offlock(self) -> None:
        """The delta-mode rebuild body: freeze + quarantine under a
        short lock, flatten off-lock, place fresh tables, swap +
        replay under another short lock — :meth:`_compact_offlock`'s
        protocol with the quarantine folded into the freeze window
        (route ops landing mid-rebuild go to the freeze log AND the
        live delta, so the swap's ``split_after`` re-stages them
        against the fresh id map exactly as a compaction would)."""
        with self._lock:
            self._quarantine_locked()
            self._freeze = {"log": [], "adds": TrieOracle(),
                            "add_fids": {}, "dels": set()}
            self._rebuild_inflight = True
            mark = self._delta.mark() if self._delta is not None else 0
            n_pend = len(self._pending_free)
            prev = self._auto
            cap_s2 = nb = None
            if prev is not None and prev.node2 is not None:
                cap_s2 = prev.node2.shape[0] * self._grow["state"]
                nb = prev.wt.shape[0] * self._grow["edge"]
        try:
            host_auto = self._flatten_main(cap_s2, nb)
            if faults.enabled:
                faults.fire("device.lost")
            auto = device_view(host_auto)
            if self.config.use_device:
                # straight to HBM — the checkpoint.load restore path
                auto = jax.device_put(auto)
        except BaseException:
            with self._lock:
                self._unfreeze_locked()
            raise
        with self._lock:
            self._install_walk_meta(host_auto)
            self._auto = auto
            self._patcher = None  # delta mode: no main-table mirror
            self._auto_map = IdMap(self._id_to_filter)
            # recycle ONLY ids quarantined before the freeze (the
            # compaction rule: an id freed mid-flatten waits a
            # generation)
            self._free_ids.extend(self._pending_free[:n_pend])
            del self._pending_free[:n_pend]
            self._dirty = False
            self._grow = {"state": 1, "edge": 1}
            self._rebuilds += 1
            self._bump_cache_rev()
            self._published = (auto, self._auto_map, self._rebuilds,
                               self._cache_rev)
            self._retire_delta(self._delta.split_after(mark)
                               if self._delta is not None else None)
            self._unfreeze_locked()
            self._publish_pair_locked()
            self._device_suspended = False

    # -- the dispatch's shapes (the one padding rule, and its list) --------

    def pad_topics(self, n: int) -> int:
        """The padding rule of the match dispatch: a batch's unique
        topics pad to a power of two from ``min_batch`` (on a mesh
        from ``min_batch × data``: a bucket has to split evenly over
        the data axis). One chip lays a batch's cache hits and its
        misses at that same bucket; on the mesh the misses pad by this
        rule on their own and the cache pads the hits
        (``match_cache.pad_hits``). Every padded length is a shape
        some program is compiled for."""
        cfg = self.config
        bucket = cfg.min_batch
        if cfg.mesh is not None:
            bucket *= cfg.mesh.shape["data"]
        while bucket < n:
            bucket *= 2
        return bucket

    def cache_slots(self) -> int:
        """Topics the publish match cache holds (0 = disabled): no
        batch hits more."""
        cfg = self.config
        if not cfg.match_cache or cfg.match_cache_slots <= 0:
            return 0
        from emqx_tpu.ops.match_cache import ring_slots

        return ring_slots(cfg.match_cache_slots)

    def shape_programs(self, shape: DispatchShape):
        """``(walk, merge)``: the keys of the programs a batch of this
        shape asks the dispatch for.

        One chip: ``walk`` = ``(batch bucket, depth)``, the match's
        one program (the walk of the misses, the cache's insert and
        the merge: ``match_cache.walk_merge``), None where every topic
        hits; ``merge`` = ``(batch bucket, batch bucket, 0)``, the
        walk-free merge of a batch that fully hit, None where one
        misses. Neither holds how many topics hit or miss. With the
        match cache off the batch walks whole: ``walk`` alone.

        The mesh: ``walk`` = ``(miss bucket, max_levels)``, the
        collective step with the insert over the topics that miss
        (the mesh encodes at ``max_levels`` whatever the topics),
        None where none does; ``merge`` = ``(batch, hit, miss)``
        buckets, its merge (miss 0 = the batch fully hit).

        The packers and the fetch's bundle that follow are keyed by
        the batch bucket and the budgets the broker learns for it."""
        cfg = self.config
        hits, misses, depth = shape
        bucket = self.pad_topics(hits + misses)
        if not self.cache_slots():
            return (bucket, cfg.max_levels if cfg.mesh is not None
                    else depth), None
        if cfg.mesh is None:
            return ((bucket, depth), None) if misses else (
                None, (bucket, bucket, 0))
        from emqx_tpu.ops.match_cache import pad_hits

        mb = self.pad_topics(misses) if misses else 0
        return ((mb, cfg.max_levels) if misses else None,
                (bucket, pad_hits(hits), mb))

    def dispatch_shapes(self, max_topics: int) -> List[DispatchShape]:
        """One batch for every program the match dispatch can be asked
        for by a batch of up to ``max_topics`` unique topics (the
        ingress forms up to ``batch_cap``), smallest first. One chip:
        a batch of misses for every batch bucket at every depth from 2
        to the deepest level live traffic has used
        (:meth:`observed_levels`), then a fully hit batch for every
        bucket the cache can fill. The mesh: every miss bucket, then
        every reachable (batch, hit, miss) triple of the cache's
        merge. A bucket is reached by the fewest and by the most
        topics that pad to it, and a batch hits no more topics than
        the cache holds.

        This is the list the served path must have met before traffic
        is free of first-use stalls (2–7 s each on the event loop):
        ``Broker.warm_dispatch`` drives it through the real seams, for
        the device-loss rewarm and for a harness's warm-up. The
        learned axes (``boost_k`` / ``boost_d``, the pack budgets, a
        pending delta) are at what they are now."""
        floor, top = self.pad_topics(1), self.pad_topics(max_topics)
        buckets = [floor]
        while buckets[-1] < top:
            buckets.append(buckets[-1] * 2)

        def ends(b: int, lowest: int, most: int):
            # the fewest and the most topics that pad to bucket b
            return (1 if b == lowest else b // 2 + 1, min(b, most))

        mesh = self.config.mesh is not None
        depths = [2] if mesh else list(
            range(2, max(self.observed_levels() + [2]) + 1))
        shapes = [DispatchShape(0, ends(mb, floor, top)[0], d)
                  for mb in buckets for d in depths]
        slots = self.cache_slots()
        if not slots:
            return shapes
        if not mesh:
            return shapes + [
                DispatchShape(ends(b, floor, top)[0], 0, depths[-1])
                for b in buckets if ends(b, floor, top)[0] <= slots]
        from emqx_tpu.ops.match_cache import pad_hits

        done = {self.shape_programs(s)[1] for s in shapes}
        hb = pad_hits(0)
        while hb <= pad_hits(min(top, slots)):
            for mb in [0] + buckets:
                for h in ends(hb, pad_hits(0), min(top, slots)):
                    for m in ends(mb, floor, top) if mb else (0,):
                        shape = DispatchShape(h, m, depths[-1])
                        merge = self.shape_programs(shape)[1]
                        if h + m <= top and merge not in done:
                            done.add(merge)
                            shapes.append(shape)
            hb *= 2
        return shapes

    def match_dispatch(self, topics: Sequence[str], span=None):
        """Dispatch-only device match: encode + enqueue the compiled
        walk and return WITHOUT any device→host sync. ``span`` is the
        batch's telemetry span (None = untimed): the first device
        call is made inside its ``emqx/enqueue`` mark
        (telemetry.enqueue_mark).

        Returns ``(ids_dev, ovf_dev, id_map, epoch)`` — both arrays
        are in-flight device values ([B_pad, M] / [B_pad]), the
        padding rows of ``ids_dev`` (row ≥ ``len(topics)``: wildcards
        match the pad topic) blanked to -1, ready for the packers; feed
        ``ids_dev`` straight into the fan-out/pack kernels and fetch
        everything in one coalesced transfer later
        (:meth:`Broker.publish_fetch`). ``(id_map, epoch)`` is the
        automaton snapshot giving the ids meaning. On a mesh the
        match runs the sharded ICI publish step ([B_pad, T·m] ids).
        """
        cfg = self.config
        if cfg.mesh is not None:
            return self._match_dispatch_sharded(topics)
        cache = self._match_cache()
        if cache is not None:
            return self._match_dispatch_cached(topics, cache, span)
        from emqx_tpu.ops.pack import mask_pad_rows

        dsnap = None
        if self._delta_active:
            main, dsnap = self._snapshot_pair()
            auto, id_map, epoch = main[:3]
        else:
            auto, id_map, epoch = self.automaton()
        self._count_dispatch(len(topics), len(topics))
        bucket = self.pad_topics(len(topics))
        padded = list(topics) + ["\x00/pad"] * (bucket - len(topics))
        # the word table must not be read (wt_lookup) while a
        # concurrent add_route interns into it — ctypes calls drop
        # the GIL, so the map can rehash mid-read. The fine-grained
        # _wt_lock (not _lock) keeps matchers running through a long
        # background-compaction flatten
        with self._wt_lock:
            ids, n, sysm = self._encode(padded, cfg.max_levels)
        ids, n = depth_bucket(ids, n)
        with enqueue_mark(span):
            res = match_batch(auto, ids, n, sysm,
                              k=self.effective_k(),
                              m=cfg.max_matches, pack_ids=False,
                              **self._walk_kw(ids.shape[1]))
        out_ids, out_ovf = res.ids, res.overflow
        if dsnap is not None:
            # two-probe: union the side-automaton's raw emits +
            # tombstone-mask deleted fids (ops/delta.py)
            from emqx_tpu.ops.delta import probe_raw

            self._delta_probes += 1
            out_ids, out_ovf = probe_raw(dsnap, ids, n, sysm,
                                         out_ids, out_ovf,
                                         m=cfg.max_matches)
        return (mask_pad_rows(out_ids, np.int32(len(topics))), out_ovf,
                id_map, epoch)

    # -- publish match cache (ops/match_cache.py) -------------------------

    def _match_cache(self):
        """The single-chip publish match cache, lazily built (None =
        disabled by config)."""
        cfg = self.config
        if not self.cache_slots():
            return None
        if self._match_cache_obj is None:
            from emqx_tpu.ops.match_cache import MatchCache

            self._match_cache_obj = MatchCache(
                cfg.match_cache_slots, cfg.max_matches)
        return self._match_cache_obj

    def _match_dispatch_cached(self, topics: Sequence[str], cache,
                               span=None):
        """Cache-split device match: probe the epoch-guarded cache,
        walk ONLY the misses (``pack_ids=True`` — the per-topic
        compaction buys fixed-width rows the cache and merge reuse),
        insert the fresh rows and merge one combined ``[B_pad,
        max_matches]`` id array, pad rows blanked. Same contract as
        the plain dispatch: all device values in flight, no sync.

        What the event loop hands the device for the batch is ONE
        host→device transfer (the batch's int32 buffer:
        ops/match_cache.py's header has its layout) and ONE program
        here: ``walk_merge`` (the walk of the misses with a live
        delta's two-probe, the insert, the gather of the hits and the
        merge) or, where every topic hit, the merge alone. Hits and
        misses are both laid at the batch's bucket, so the program is
        keyed by (bucket, depth) and never by how the batch splits.
        No numpy argument and no eager operation: each is a transfer
        or a launch of its own, and gives up the interpreter lock to
        the fetch's thread.

        Ordering: the revision is read BEFORE the automaton snapshot,
        so a racing mutation can only make fresh results look stale
        (re-walked, safe) — never stale results look fresh."""
        from emqx_tpu.ops.match_cache import walk_merge

        cfg = self.config
        k_boost = self._k_boost  # read BEFORE the snapshot/walk: a
        # concurrent boost then stales these entries, never the reverse
        # partition revisions: same read-before-snapshot rule (a
        # mutation landing after this copy makes the probed keys look
        # stale — re-walked, safe). Tuple copy = a consistent host
        # snapshot the per-topic keys index into
        part_snap = (tuple(self._part_revs)
                     if cfg.cache_partitions > 1 else None)
        dsnap = None
        if self._delta_active:
            main, dsnap = self._snapshot_pair()
            auto, id_map, epoch, rev = main
        else:
            auto, id_map, epoch, rev = self.snapshot_cached()
        key = (epoch, rev, k_boost)
        keys = None
        if part_snap is not None:
            mask = cfg.cache_partitions - 1
            keys = [key + (part_snap[zlib.crc32(
                t.partition("/")[0].encode()) & mask],)
                for t in topics]
        bucket = self.pad_topics(len(topics))
        tel = self.telemetry
        timed = tel is not None and tel.enabled
        t0 = time.perf_counter() if timed else 0.0
        probe = cache.probe(topics, key, keys)
        t1 = time.perf_counter() if timed else 0.0
        misses = probe.miss_topics
        # one launch below, whichever branch takes it
        self._count_dispatch(len(topics), len(misses), programs=1)
        enc = None
        if misses:
            # the misses and one pad topic: the buffer lays them at
            # the batch's bucket, like the hits, so the program's
            # shapes are the batch's, whatever misses
            with self._wt_lock:
                ids, n, sysm = self._encode(
                    list(misses) + ["\x00/pad"], cfg.max_levels)
            enc = (*depth_bucket(ids, n), sysm)
        lay, buf = cache.batch_buffer(bucket, probe, enc, len(topics),
                                      self._batch_buf_len, rows=bucket)
        self._batch_buf_len = lay.size
        with enqueue_mark(span):  # the batch's one transfer
            buf = jax.device_put(buf)
        if misses:
            delta, dkw = None, {}
            if dsnap is not None:
                # two-probe, folded into the match's program: the
                # side-automaton's union + the tombstone mask
                # (ops/delta.py)
                self._delta_probes += 1
                delta = (dsnap.auto, dsnap.mask)
                dkw = {"dk": dsnap.k,
                       "dsteps": dsnap.steps_for(lay.levels)}
            # hits from the probe's snapshot, the insert into the
            # current table: one array for both unless another
            # batch's insert landed in between
            ids_dev, ovf_dev = cache.insert_through(
                probe, lambda table: walk_merge(
                    auto, delta, probe.table, table, buf, lay=lay,
                    k=self.effective_k(), m=cfg.max_matches, **dkw,
                    **self._walk_kw(lay.levels)))
        else:
            ids_dev, ovf_dev, _movf = cache.merge_batch(
                bucket, probe, lay, buf, None)
        if timed:
            # the probe (a host hash walk) is the cache_gather share
            # of this dispatch; the remainder (encode + transfer +
            # the one launch) is the match share
            self._last_dispatch = {
                "hit": len(probe.hit_pos),
                "miss": len(misses),
                "cache_gather_ms": (t1 - t0) * 1000.0,
            }
        return ids_dev, ovf_dev, id_map, epoch

    def drain_cache_stats(self) -> Dict[str, int]:
        """Match-cache counter deltas since the last drain (hit/miss/
        insert/stale, summed over the single-chip and sharded caches)
        plus the router-level epoch-bump split (``bump.global`` /
        ``bump.partition``) — folded into Metrics by the stats flush
        under the ``cache.match.`` prefix."""
        out: Dict[str, int] = {}
        for c in (self._match_cache_obj, self._sharded_cache_obj):
            if c is None:
                continue
            for k2, v in c.drain_stats().items():
                out[k2] = out.get(k2, 0) + v
        if self.cache_slots():
            g, p = self._bump_global, self._bump_partition
            out["bump.global"] = g - self._bump_drained[0]
            out["bump.partition"] = p - self._bump_drained[1]
            self._bump_drained = (g, p)
        return out

    def cache_bump_totals(self) -> Dict[str, int]:
        """Cumulative epoch-bump split (not deltas — `ctl cache` and
        bench introspection; the metrics fold uses
        :meth:`drain_cache_stats`)."""
        return {"global": self._bump_global,
                "partition": self._bump_partition}

    def cache_entries(self) -> int:
        """Live entries across the publish match caches (gauge)."""
        return sum(c.entries() for c in
                   (self._match_cache_obj, self._sharded_cache_obj)
                   if c is not None)

    def cache_partitions_live(self) -> int:
        """Partition epoch keys in effect for the publish match cache
        (the ``match.cache.partition.live`` gauge): 0 = cache
        disabled, 1 = legacy whole-epoch, else ``cache_partitions``."""
        if not self.cache_slots():
            return 0
        return self.config.cache_partitions

    def quarantined_ids(self) -> int:
        """Freed filter ids quarantined until the next flatten (the
        ``router.ids.quarantined`` gauge — the round-4 soak leak's
        visibility: between flattens this is the linear-growth
        regime, and sustained growth without a rebuild means churn
        is outpacing compaction)."""
        return len(self._pending_free)

    def effective_k(self) -> int:
        """Active-set capacity: configured + any learned boost — or 1
        when the live automaton has no ``+`` edges at all (the walk
        is then a deterministic trie descent: the active set is
        provably ≤ 1 lane, and gather volume scales with k)."""
        if not self._walk_meta["has_plus"]:
            return max(1, self._k_boost)
        return max(self.config.active_k, self._k_boost)

    def boost_k(self, cap: int = 64) -> bool:
        """Double the effective active-set capacity (≤ ``cap``);
        called by the publish path when a batch's overflow rate shows
        the configured K undersizes the live workload. Returns
        whether a grow happened."""
        with self._lock:
            k = self.effective_k()
            if k >= cap:
                return False
            self._k_boost = min(k * 2, cap)
            return True

    def effective_d(self) -> int:
        """Configured per-topic fan-out slots plus any learned boost
        (mesh publish step; learned like K, from fan-only overflow)."""
        return max(self.config.fanout_d, self._d_boost)

    def boost_d(self, cap: int = 1024) -> bool:
        """Double the mesh gather's per-topic delivery slots (≤
        ``cap``) when a batch's FAN-ONLY overflow rate shows ``d``
        undersizes the live fan-out (one recompile per growth step,
        exact host fallback in the meantime — same contract as
        :meth:`boost_k`)."""
        with self._lock:
            d = self.effective_d()
            if d >= cap:
                return False
            self._d_boost = min(d * 2, cap)
            return True

    def note_match_fallbacks(self, n: int) -> None:
        """The publish path resolved ``n`` topics on the host oracle
        because their device walk overflowed. In the stale-hop regime
        (a patch split deepened walk paths past what the mirror's hop
        accounting tracks, ADVICE r5) those fallbacks are the only
        signal the automaton needs a compacting rebuild — forward the
        count to the live patcher(s), which count it alongside
        splits/tombstones, and schedule compaction once it dominates.
        Keeps hot deep topics eligible for the match cache instead of
        pinned to the host oracle until 1024 splits accumulate."""
        if n <= 0:
            return
        with self._lock:
            pool = ([self._patcher] if self._patcher is not None
                    else self._shard_patchers)
            for p in pool:
                p.note_hop_fallbacks(n)
            if pool and not self._dirty and not self._compacting \
                    and self._needs_compaction_locked():
                self._schedule_compaction()

    def set_delta(self, enabled: bool) -> None:
        """Flip delta mode at runtime with a clean transition (bench
        A/B on one router/filter set): wait out any in-flight
        background compaction, then one synchronous rebuild folds
        whatever the outgoing mode had pending (the trie always has
        everything) and re-publishes under the new mode."""
        while self._compacting:
            time.sleep(0.005)
        with self._lock:
            self.config.delta = bool(enabled)
            if self._auto is not None and self._freeze is None:
                self._rebuild_locked()
            else:
                self._publish_pair_locked()

    def drain_automaton_stats(self) -> Dict[str, int]:
        """Delta/rebuild counter deltas since the last drain — folded
        into Metrics by the stats flush under the ``automaton.``
        prefix (docs/OBSERVABILITY.md)."""
        comp = self._compaction
        d = self._delta
        cur = (self._delta_probes, self._delta_filters,
               self._delta_merges, int(self._rebuild_stall_ms),
               comp["fused_edges"], comp["chains"],
               self._delta_tombstones, self._delta_retracts,
               self._compaction_ns,
               self._delta_grows + (d.grows if d is not None else 0),
               self._freeze_deferred)
        prev = self._auto_drained
        self._auto_drained = cur
        keys = ("delta.probes", "delta.filters", "delta.merges",
                "rebuild.stall_ms",
                # table-state gauges carried as deltas (GAUGE_METRICS
                # — a rebuild may shrink them)
                "compaction.fused_edges", "compaction.chains",
                "delta.tombstones", "delta.retracts",
                "compaction.ns", "delta.grows", "freeze.deferred")
        return {k: c - p for k, c, p in zip(keys, cur, prev)}

    def walk_info(self) -> Dict[str, object]:
        """Live walk facts for `ctl cache`: the level-compression
        snapshot of the live tables (mode, fused chains, permille of
        deepest-walk steps saved)."""
        return dict(self._compaction)

    def delta_info(self) -> Dict[str, object]:
        """Live delta-automaton state for `ctl cache` / bench
        introspection (cumulative counters, not deltas)."""
        d = self._delta
        return {
            "active": self._delta_active,
            "pending": d.n_pending if d is not None else 0,
            "tombstones": d.n_tombstones if d is not None else 0,
            "probes": self._delta_probes,
            "filters": self._delta_filters,
            "merges": self._delta_merges,
            "rebuild_stall_ms": round(self._rebuild_stall_ms, 3),
            "rebuild_inflight": self._rebuild_inflight,
        }

    def match_ids(self, topics: Sequence[str]):
        """Device match of a topic batch in snapshot-id space.

        Returns ``(ids_dev, ids_np, ovf_np, id_map, epoch)``:
        ``ids_dev`` is the device int32[B_pad, M] match array (feed it
        straight into the fan-out gather — no host round-trip),
        ``ids_np``/``ovf_np`` are host copies sliced to ``len(topics)``,
        and ``(id_map, epoch)`` is the automaton snapshot that gives
        the ids meaning. Rows with ``ovf_np`` set exceeded a kernel
        bound — resolve those topics via :meth:`host_match`.
        """
        if self.config.mesh is not None:
            return self._match_ids_sharded(topics)
        B = len(topics)
        ids_dev, ovf_dev, id_map, epoch = self.match_dispatch(topics)
        ids_np = np.asarray(ids_dev)[:B]
        ovf_np = np.asarray(ovf_dev)[:B]
        return ids_dev, ids_np, ovf_np, id_map, epoch

    def _match_dispatch_sharded(self, topics: Sequence[str]):
        """Multi-chip match dispatch: the batch is sharded over the
        mesh's 'data' axis, each trie shard matches its slice, match
        ids are all-gathered over ICI; no device→host sync (same
        contract as :meth:`match_dispatch`, ids are [B_pad, T·m])."""
        all_ids, _subs, _src, ovf, _movf, id_map, epoch = \
            self._dispatch_sharded(topics, fan=None)
        return all_ids, ovf, id_map, epoch

    def publish_dispatch_sharded(self, topics: Sequence[str],
                                 fan_provider, span=None):
        """The PRODUCT multi-chip publish dispatch: match AND fan-out
        in one collective step (``parallel.sharded.publish_step`` with
        real per-shard fan tables, ``with_fanout=True``).

        ``fan_provider(epoch, id_map) -> ShardedFanoutState | None``
        supplies fan tables (CSR + big-filter bitmaps) consistent
        with the automaton snapshot (the broker's FanoutManager).
        Returns ``(ids_dev [B_pad, T·m], subs_dev [B_pad, T·d],
        src_dev [B_pad, T·d], bm [(union, has_big, bovf) | None],
        ovf_dev [B_pad], movf_dev [B_pad], id_map, epoch, big_fids)``
        — ``movf_dev`` is the match-only overflow (the ``boost_k``
        signal; fan overflow must not grow k); no device→host sync.
        The padding rows of ids / subs / src (row ≥ ``len(topics)``:
        wildcards match the pad topic) come back blanked to -1, ready
        for the packers.
        Reference: the dispatch fold src/emqx_broker.erl:283-309 run
        as one compiled mesh program.

        With the publish match cache enabled (and no big-filter
        bitmaps live), repeat topics skip the collective step and the
        whole batch leaves as one transfer and two or three programs
        (:meth:`_dispatch_fused`)."""
        self._count_mesh("mesh.batches", "mesh.topics", len(topics))
        out = self._dispatch_fused(topics, fan_provider, span)
        if out is not None:
            self._count_mesh("mesh.fused")
            return out
        out = self._dispatch_sharded(topics, fan=fan_provider,
                                     with_big=True, span=span)
        from emqx_tpu.ops.pack import mask_pad_rows

        n = np.int32(len(topics))
        return tuple(x if x is None else mask_pad_rows(x, n)
                     for x in out[:3]) + out[3:]

    def _live_metrics(self):
        """Where the dispatch's per-batch counters go: the node's
        Metrics while [telemetry] is enabled, like the loop's
        counters; else None, and the dispatch counts nothing."""
        tel = self.telemetry
        if tel is not None and tel.loop_clock() is not None:
            return tel.metrics
        return None

    def _count_dispatch(self, topics: int, walked: int,
                        programs: int = 0) -> None:
        """One batch of the one-chip match dispatch
        (metrics.DISPATCH_METRICS): its unique topics, and those that
        walk the automaton (the rest are the match cache's gather),
        before padding, and on the cache-split path the one program
        it launches (the broker adds the fused packer:
        :meth:`count_fused`; the cache-off dispatch and the packers
        kept apart count none); stamped per batch, so current at any
        instant. ``cache.match.*`` and ``automaton.*`` are brought up
        to the batch before here as well (with telemetry off they wait
        for the stats flush)."""
        m = self._live_metrics()
        if m is not None:
            m.inc("dispatch.batches")
            m.inc("dispatch.topics", topics)
            m.inc("dispatch.walk.topics", walked)
            m.inc("dispatch.programs", programs)
            # the families the stats flush folds once a sys_interval
            # (a minute): folded here too, so that a window cut at any
            # two instants reads them to a batch (a drain hands out
            # what moved since the last one, whoever asks)
            m.fold_cache_stats(self.drain_cache_stats())
            m.fold_automaton_stats(self.drain_automaton_stats())

    def count_fused(self) -> None:
        """The broker's: the batch :meth:`_count_dispatch` just
        counted left the loop as one transfer and the fused packer
        (``dispatch.fused``, the twin of ``mesh.fused``), its second
        program (``dispatch.programs``)."""
        m = self._live_metrics()
        if m is not None:
            m.inc("dispatch.fused")
            m.inc("dispatch.programs")

    def _count_mesh(self, events: str, topics: Optional[str] = None,
                    n: int = 0) -> None:
        """One event of the mesh dispatch and the unique topics it
        carries (metrics.MESH_METRICS), stamped where the decision is
        taken."""
        m = self._live_metrics()
        if m is not None:
            m.inc(events)
            if topics is not None:
                m.inc(topics, n)

    def _sharded_cache_for(self, n_trie: int, d: int):
        """The mesh publish cache, sized for the CURRENT (T, m, d)
        row widths — a ``boost_d`` regrows it (entries drop; they
        were keyed to the old d anyway). Its table is replicated over
        the mesh."""
        cfg = self.config
        meta = (n_trie, cfg.max_matches, d)
        if self._sharded_cache_obj is None \
                or self._sharded_cache_meta != meta:
            from jax.sharding import NamedSharding, PartitionSpec

            from emqx_tpu.ops.match_cache import MatchCache

            width = n_trie * cfg.max_matches + 2 * n_trie * d
            self._sharded_cache_obj = MatchCache(
                cfg.match_cache_slots, width,
                sharding=NamedSharding(cfg.mesh, PartitionSpec()))
            self._sharded_cache_meta = meta
        return self._sharded_cache_obj

    def _dispatch_fused(self, topics: Sequence[str], fan_provider,
                        span=None):
        """Cache-split mesh publish dispatch, or None when the cache
        does not apply (disabled, no fan state, or big-filter bitmaps
        live — a bitmap union row is megabytes at 10M subs, far past
        any sane per-entry budget, so that regime stays uncached) or
        the snapshot moved under the split.

        One cache entry is a topic's concatenated (match ids [T·m],
        gathered subs [T·d], src [T·d]) rows — everything the
        collective step produces for it except the per-step stats
        psums (device.match counters therefore count WALKED topics
        only; the host-side hit counters carry the rest).

        What the event loop hands the device for the batch is ONE
        host→device transfer (the batch's int32 buffer, replicated:
        ops/match_cache.py's header has its layout) and at most two
        programs here: the step with the insert, for the misses, and
        the merge, which also splits the row and blanks the pad rows.
        No eager operation: each is a dispatch to every chip of the
        mesh from Python."""
        import jax

        from emqx_tpu.parallel.sharded import publish_step_insert

        cfg = self.config
        if not self.cache_slots():
            return None
        boosts = (self._k_boost, self._d_boost)
        # partition revisions snapshot BEFORE the automaton snapshot
        # (same stale-not-fresh ordering as the single-chip path)
        part_snap = (tuple(self._part_revs)
                     if cfg.cache_partitions > 1 else None)
        auto, id_map, epoch, rev = self.snapshot_cached()
        st = fan_provider(epoch, id_map)
        if st is None or st.fan is None or st.bm is not None \
                or st.big_fids:
            return None
        d = self.effective_d()
        n_trie = cfg.mesh.shape["trie"]
        cache = self._sharded_cache_for(n_trie, d)
        key = (epoch, rev, boosts, st.version)
        keys = None
        if part_snap is not None:
            mask = cfg.cache_partitions - 1
            keys = [key + (part_snap[zlib.crc32(
                t.partition("/")[0].encode()) & mask],)
                for t in topics]
        bucket = self.pad_topics(len(topics))
        tel = self.telemetry
        timed = tel is not None and tel.enabled
        t0 = time.perf_counter() if timed else 0.0
        probe = cache.probe(topics, key, keys)
        t1 = time.perf_counter() if timed else 0.0
        misses = probe.miss_topics
        enc = None
        if misses:
            mb = self.pad_topics(len(misses))
            padded = list(misses) + ["\x00/pad"] * (mb - len(misses))
            with self._wt_lock:
                enc = self._encode(padded, cfg.max_levels)
            if self.snapshot_cached()[2] != epoch:
                # the snapshot moved while we split: abandon the
                # cached path for this batch — the pending miss slots
                # stay keyless (permanent miss), and the caller runs
                # the legacy dispatch on the new snapshot
                return None
        lay, buf = cache.batch_buffer(bucket, probe, enc, len(topics),
                                      self._batch_buf_len)
        self._batch_buf_len = lay.size
        with enqueue_mark(span):  # the batch's one transfer
            buf = jax.device_put(buf, cache.sharding)
        miss_vals = None
        if misses:
            # a collective program is enqueued for these topics
            self._count_mesh("mesh.steps", "mesh.step.topics",
                             len(misses))
            miss_vals, stats = cache.insert_through(
                probe, lambda table: publish_step_insert(
                    cfg.mesh, auto, st.fan, table, buf,
                    lay=lay._replace(hit=0), k=self.effective_k(),
                    m=cfg.max_matches, d=d, mb=cfg.fanout_mb,
                    **self._walk_kw(cfg.max_levels)))
            self._dev_stats.append(stats)
        t2 = time.perf_counter() if timed else 0.0
        ids, subs, src, ovf, movf = cache.merge_batch(
            bucket, probe, lay, buf, miss_vals,
            (n_trie * cfg.max_matches, n_trie * d))
        if timed:
            self._last_dispatch = {
                "hit": len(probe.hit_pos),
                "miss": len(misses),
                "cache_gather_ms": ((t1 - t0) + (
                    time.perf_counter() - t2)) * 1000.0,
            }
        return (ids, subs, src, None, ovf, movf, id_map, epoch,
                frozenset())

    def _encode_place_sharded(self, topics: Sequence[str], span=None):
        """Host half of :meth:`_dispatch_sharded`: encode a topic
        batch (padded to a bucket that splits evenly over the data
        axis) and place it on the mesh. Returns ``(ids, n, sysm)``."""
        from emqx_tpu.parallel.sharded import place_batch

        cfg = self.config
        B = len(topics)
        bucket = self.pad_topics(B)
        padded = list(topics) + ["\x00/pad"] * (bucket - B)
        with self._wt_lock:
            ids, n, sysm = self._encode(padded, cfg.max_levels)
        with enqueue_mark(span):  # the legacy dispatch's transfer
            return place_batch(cfg.mesh, ids, n, sysm)

    def _dispatch_sharded(self, topics: Sequence[str], fan=None,
                          with_big: bool = False, span=None):
        from emqx_tpu.parallel.sharded import publish_step

        cfg = self.config
        mesh = cfg.mesh
        auto, id_map, epoch = self.automaton()
        big_fids = frozenset()
        fan_tables = None
        bmt = None
        if fan is not None:
            st = fan(epoch, id_map)
            if st is not None:
                fan_tables = st.fan
                bmt = st.bm
                big_fids = st.big_fids
        ids, n, sysm = self._encode_place_sharded(topics, span)
        use_fan = fan_tables is not None
        # a collective program is enqueued for these topics (the
        # cache-split path sends only its misses here)
        self._count_mesh("mesh.steps", "mesh.step.topics", len(topics))
        with enqueue_mark(span):
            all_ids, subs, src, bm, ovf, movf, stats = publish_step(
                mesh, auto, fan_tables if use_fan else self._dummy_fan,
                ids, n, sysm, bmt, k=self.effective_k(),
                m=cfg.max_matches,
                d=self.effective_d() if use_fan else 8,
                mb=cfg.fanout_mb, with_fanout=use_fan,
                **self._walk_kw(int(ids.shape[-1])))
        self._dev_stats.append(stats)
        if with_big:
            return (all_ids, subs if use_fan else None,
                    src if use_fan else None, bm, ovf, movf, id_map,
                    epoch, big_fids)
        return all_ids, subs, src, ovf, movf, id_map, epoch

    def _match_ids_sharded(self, topics: Sequence[str]):
        """Sharded :meth:`match_ids` (host copies synced)."""
        B = len(topics)
        all_ids, ovf, id_map, epoch = self._match_dispatch_sharded(topics)
        ids_np = np.asarray(all_ids)[:B]
        ovf_np = np.asarray(ovf)[:B]
        return all_ids, ids_np, ovf_np, id_map, epoch

    def drain_device_stats(self) -> Dict[str, int]:
        """Sum and clear the accumulated device-side counters (all
        pending steps come to the host in one ``device_get`` — called
        from the periodic stats flush, on the event loop: a served
        mesh queues thousands of steps between two flushes, and a
        transfer per scalar would stall it)."""
        out = {"matches": 0, "deliveries": 0, "overflows": 0}
        pending = []
        while self._dev_stats:
            pending.append(self._dev_stats.popleft())
        if pending:
            import jax

            for st in jax.device_get(pending):
                for k in out:
                    out[k] += int(st[k])
        return out

    def match_filters(self, topics: Sequence[str]) -> List[List[str]]:
        """Batch: matched filter list per topic (device + oracle
        fallback)."""
        if not topics:
            return []
        if not self.use_device_now():
            with self._lock:
                return [self._host_match_locked(t) for t in topics]
        _, mid, ovf, id_map, _ = self.match_ids(topics)
        out: List[List[str]] = []
        for i in range(len(topics)):
            if ovf[i]:
                out.append(self.host_match(topics[i]))
            else:
                row = [id_map[j] for j in mid[i] if j >= 0]
                out.append([f for f in row if f is not None])
        return out
