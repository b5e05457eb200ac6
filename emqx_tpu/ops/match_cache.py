"""Epoch-guarded device-resident publish match cache.

The publish hot loop (``emqx_broker:publish/1`` →
``emqx_trie:match/1``, SURVEY §3.1 "HOT LOOP 1") re-walks every
unique topic per batch, yet real traffic is massively repetitive:
Zipf traffic would see the same hot topics re-walked from scratch
every batch (and EMQX itself ships a host-side route cache in front of
``emqx_router:match_routes/1`` for exactly this reason). This module
memoizes per-topic match rows in a fixed-shape HBM table so a repeat
topic costs one gather instead of an NFA walk + per-topic compaction.

Layout and contract:

  - the device table is ``int32[slots, 1 + width]``: column 0 is a
    validity/overflow flag, the rest the packed matched-filter-id row
    (-1 padded). ``slots`` is a power of two; rows never move — the
    host side owns a ``topic → slot`` index plus a per-slot epoch
    *key*, so the device never hashes strings;
  - entries are **epoch-guarded**: the key stored at insert time must
    equal the probing key exactly or the entry is a (counted) stale
    miss. The router bumps its cache revision on any filter-set
    change, rebuild, or capacity boost — wildcard filters make exact
    per-key invalidation intractable (an added ``a/+`` changes the
    match set of unboundedly many cached topics), so invalidation is
    epoch-scoped and entries self-heal by re-insert. No flush kernel
    exists or is needed. The cache itself is key-agnostic: the caller
    may hand :meth:`MatchCache.probe` one batch-wide key (whole-epoch
    invalidation, the ``cache_partitions = 1`` legacy behavior) or a
    per-topic key list (the router's partitioned epochs — each key
    carries the revision of the partition owning the topic's first
    level, so disjoint-prefix route churn leaves other partitions'
    entries valid; see docs/MATCH_CACHE.md "Partitioned epochs");
  - **overflow topics are never served from the cache**: a miss row
    whose walk overflowed is stored as an invalid marker (flag 0,
    ids all -1). A later hit on such a slot surfaces ``overflow=True``
    and the caller's exact host-oracle fallback runs, same as a fresh
    walk would have — parity by fallback, never truncation. The
    marker pins the topic to the host path only until the next epoch
    bump (route churn, compaction rebuild, k/d boost);
  - probe/insert host bookkeeping is mutex-guarded and the device
    table is updated functionally (``.at[].set`` returns a new
    array), so a concurrent reader holding the probed table snapshot
    can never observe a torn or reallocated row.

All device work is async-dispatched: probe is pure host bookkeeping,
and nothing here ever forces a device→host sync — the publish path's
coalesced fetch stays the only transfer back.

**The batch buffer, and which program owns what** (both dispatches:
one chip's ``Router._match_dispatch_cached`` and the mesh's
``Router._dispatch_fused``; docs/MATCH_CACHE.md "One buffer a batch").
What the event loop pays for is not device work but hand-overs: every
numpy argument is a host→device transfer of its own, every eager
operation and every program a launch that gives up the interpreter
lock (what one costs the loop on the cells' host: PERF.md §6, PR 43,
timed around each call). So a batch leaves as ONE transfer and, on
one chip, TWO programs (the match and the packer); on the mesh two or
three:

  - **one buffer a batch** (:class:`BatchLayout`,
    :meth:`MatchCache.batch_buffer`): every integer the device needs
    from the host for the batch is laid into one int32 numpy array
    and put once (replicated on a mesh). The programs take it whole
    and slice it at static offsets: from the front ``word_ids [MB·L]
    | n_words [MB] | sys_mask [MB] | insert slots [MB]``, which the
    walk reads, and up to the buffer's last word ``miss_pos [MB] |
    hit_slots [HB] | hit_pos [HB] | n_uniq [1]``, which the merge
    reads (MB, HB = the padded miss and hit counts; L = the batch's
    depth bucket on one chip, ``max_levels`` on the mesh; an all-hit
    batch has MB = 0). The buffer's LENGTH is a shape of every
    program that takes it, so it is not the sum of its sections but a
    capacity: a power of two from ``BATCH_BUF_FLOOR``, grown only
    when a batch needs more (the router keeps the high-water mark);
  - **one chip: MB = HB = the batch's bucket B**, so the layout has
    no dimension of its own: a batch with a miss is ``(L, B, B)``, a
    fully hit one ``(0, 0, B)``. Rows past the real counts are pad
    topics with out-of-range slots and positions, which drop. **The
    match is one program** (:func:`walk_merge`, keyed by (B, L) and
    what the walk is keyed by: the delta's presence, its lanes and
    steps, ``k``, the walk's own statics): ``match_batch`` over the
    misses with a live delta snapshot's two-probe folded in,
    :func:`flag_rows`, the scatter into the table
    (:func:`insert_rows`, through :meth:`MatchCache.insert_through`),
    the gather of the hits from the PROBE'S snapshot, the merge and
    the pad mask. The walk runs over B rows whatever the batch
    misses: device time on a chip that idles more than four fifths
    of a served window (0.5–0.6 ms more a batch where few topics
    miss, PERF.md §6, PR 43), against a launch of the loop's. A fully hit batch keeps
    the walk-free merge (:func:`_mesh_merge_jit`), keyed by B alone;
  - **the mesh: walk + insert, then merge**. The collective step
    with the insert is one program, keyed by the miss bucket
    (``parallel/sharded.py::publish_step_insert``; its row is ``ids
    [T·m] | subs [T·d] | src [T·d]``, everything the step produces
    for a topic, in a table replicated on every chip; its offsets
    depend on (MB, L) alone, and it is skipped when every topic
    hits); the merge + pad mask a second (:func:`_mesh_merge_jit`,
    keyed by the (batch, hit, miss) buckets, whatever the depth),
    which also splits the row into ids / subs / src;
  - **the packers + the fetch's bundle** are the last
    (``ops/pack.py::pack_chip`` / ``pack_mesh``, keyed by (batch
    bucket, pm, pq), so a grown budget costs one program a bucket).
    ``pack_chip`` is NOT folded into the match: a program that holds
    the walk must not be keyed by a learned budget, or a budget that
    grows in a warm round makes every depth variant of that bucket a
    first use on the loop (2–7 s each);
  - **the table is not donated**: a probe holds its snapshot and its
    hits gather from it AFTER this or another batch's insert (the
    clock sweep may hand a hit's slot to a miss of the same batch), so
    an insert must leave the old array whole. The copy is device
    time on a chip that is mostly idle; the loop pays nothing for it;
  - **the padding rule is a contract** with the benchmark's sweeps
    (``benchmark/warmers/dispatch_buckets.py`` sends a misses-only
    batch for every bucket at every depth and a batch for every
    (batch, hit, miss) triple through ``publish_batch``, a superset
    of one chip's keys; ``mesh_buckets.py`` walks the mesh's): a
    batch pads to a power of two from ``min_batch`` (× ``data`` on a
    mesh: ``Router.pad_topics``), as do the mesh's misses, the mesh's
    hits from ``_MIN_PAD`` (:func:`pad_hits`); ``Router.
    dispatch_shapes`` lists the batches the rule allows. Change it and
    runs first use programs inside their window.
"""

from __future__ import annotations

import functools
import threading
from typing import Any, List, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from emqx_tpu.ops.delta import probe_packed
from emqx_tpu.ops.match import match_batch

__all__ = ["MatchCache", "BatchLayout", "BATCH_BUF_FLOOR", "flag_rows",
           "insert_rows", "pad_hits", "ring_slots", "walk_merge"]

#: flag column values: _VALID = cached ids are the exact match set;
#: _OVF = the walk overflowed (host fallback, match-only bound);
#: _FOVF = overflow where the match side itself was fine (the mesh
#: fan-out d bound) — merged back into (ovf, movf) so the router's
#: boost_k/boost_d signals keep their meaning across cached batches
_OVF, _VALID, _FOVF = 0, 1, 2

_MIN_PAD = 8

#: the batch buffer's smallest capacity, int32 words (128 KiB): the
#: largest batch the default ingress forms (``batch_cap`` = 1,024
#: unique topics) needs 20,497 words when all of them miss at 16
#: levels, the deepest depth bucket and the mesh's ``max_levels``, so
#: a served node never grows it
BATCH_BUF_FLOOR = 1 << 15


def _pow2(n: int, floor: int = 1) -> int:
    out = floor
    while out < n:
        out *= 2
    return out


def ring_slots(slots: int) -> int:
    """The slots a cache configured with ``slots`` really has."""
    return _pow2(max(2, int(slots)))


def pad_hits(n: int) -> int:
    """The padded length of a mesh batch's ``n`` cache hits (a shape
    of its merge's program): a power of two from ``_MIN_PAD``, also
    for none. ``Router.dispatch_shapes`` lists the programs from it.
    One chip lays its hits at the batch's bucket."""
    return _pow2(max(n, 1), _MIN_PAD)


# -- the device half (the module header's three programs) ------------------


class BatchLayout(NamedTuple):
    """Where one batch's host integers lie in its one int32 buffer
    (module header). Static: a program is compiled for the sections it
    reads and for ``size``, the buffer's capacity. On one chip ``miss``
    and ``hit`` are the batch's bucket (``miss`` 0 where the batch
    fully hit), so the match's program is keyed by (bucket, depth). On
    the mesh the step's program is keyed with ``hit`` = 0 and the
    merge's with ``levels`` = 0: neither reads a section whose place
    depends on the other's."""

    levels: int   # L: word ids a topic
    miss: int     # MB: padded miss count (0 = the batch fully hit)
    hit: int      # HB: padded hit count
    size: int     # the buffer's length (≥ need, a learned capacity)

    @staticmethod
    def need(levels: int, miss: int, hit: int) -> int:
        return miss * (levels + 4) + 2 * hit + 1

    def step_sections(self, buf):
        """``(word_ids [MB, L], n_words, sys_mask, slots)`` — all the
        walk's program reads, from the buffer's front; offsets depend
        on (MB, L) alone."""
        mb, lv = self.miss, self.levels
        o = mb * lv
        return (buf[:o].reshape(mb, lv), buf[o:o + mb],
                buf[o + mb:o + 2 * mb] != 0, buf[o + 2 * mb:o + 3 * mb])

    def merge_sections(self, buf):
        """``(miss_pos [MB], hit_slots [HB], hit_pos [HB], n_uniq)``,
        which end at the buffer's last word; offsets depend on (MB,
        HB) and the capacity alone."""
        mb, hb = self.miss, self.hit
        end = self.size - 1
        h = end - 2 * hb
        return buf[h - mb:h], buf[h:h + hb], buf[h + hb:end], buf[end]


def flag_rows(rows, ovf, movf):
    """``flag | rows`` for fresh walk results: the table's row format,
    with the rows still RAW (an overflowed row's truncated ids are
    what the merge hands on; only the table stores them blanked —
    :func:`insert_rows`)."""
    flag = jnp.where(movf, _OVF, jnp.where(ovf, _FOVF, _VALID))
    return jnp.concatenate(
        [flag.astype(jnp.int32)[:, None], rows.astype(jnp.int32)], axis=1)


def insert_rows(table, idx, vals):
    """Scatter rows in :func:`flag_rows` form into their slots.
    Overflowed rows are stored as invalid markers (the flag alone,
    never truncated results); padding entries carry an out-of-range
    index and drop."""
    marker = vals.at[:, 1:].set(-1)
    return table.at[idx].set(
        jnp.where(vals[:, :1] != _VALID, marker, vals), mode="drop")


def _merge(table, buf, miss_vals, lay: BatchLayout, b_pad: int, splits):
    """A batch's merge (traced inside :func:`walk_merge` and
    :func:`_mesh_merge_jit`): gather the hit rows from the table
    snapshot, scatter them and the fresh miss rows into the ``[b_pad,
    width]`` output (OOB positions drop — that is how pad entries and
    absent hits/misses vanish), then blank the pad rows (≥ ``n_uniq``:
    wildcards match the pad topic, and those phantom rows must not
    reach the packers or the learned budgets). ``miss_vals`` is the
    walk's ``flag | row`` output, None when the batch fully hit.
    ``splits`` = the widths of ids and subs in a mesh row, which comes
    back cut into ``(ids, subs, src, ovf, movf)``; None on one chip:
    ``(ids, ovf, movf)``."""
    miss_pos, hit_slots, hit_pos, n_uniq = lay.merge_sections(buf)
    S = table.shape[0]
    out = jnp.full((b_pad, table.shape[1] - 1), -1, jnp.int32)
    ovf = jnp.zeros((b_pad,), bool)
    movf = jnp.zeros((b_pad,), bool)
    for pos, vals in ((hit_pos, table[jnp.clip(hit_slots, 0, S - 1)]),
                      (miss_pos, miss_vals)):
        if vals is None:
            continue
        flag = vals[:, 0]
        out = out.at[pos].set(vals[:, 1:], mode="drop")
        ovf = ovf.at[pos].set(flag != _VALID, mode="drop")
        movf = movf.at[pos].set(flag == _OVF, mode="drop")
    real = (jnp.arange(b_pad, dtype=jnp.int32) < n_uniq)[:, None]
    out = jnp.where(real, out, -1)
    if splits is None:
        return out, ovf, movf
    mw, dw = splits
    return out[:, :mw], out[:, mw:mw + dw], out[:, mw + dw:], ovf, movf


@functools.partial(
    jax.jit, static_argnames=("lay", "k", "m", "steps", "slots", "take",
                              "dk", "dsteps"))
def walk_merge(auto, delta, snap, table, buf, *, lay: BatchLayout, k: int,
               m: int, steps, slots: int, take: int, dk: int = 0,
               dsteps: int = 0):
    """One chip's match of a batch with a miss as ONE program: slice
    the misses' operands out of the batch buffer, walk them
    (``pack_ids=True`` — fixed-width rows are what the table holds),
    fold a live delta snapshot's two-probe in (``delta`` = its
    ``(auto, mask)``, None without one; ``dk`` / ``dsteps`` its lanes
    and its steps at this depth: the side-automaton's union and the
    tombstone mask land in the rows the cache stores, and a later
    delta mutation bumps the revision, so they are never served
    stale), lay ``flag | row``, scatter the rows into ``table`` (the
    cache's CURRENT table; functionally: the old array stays whole for
    the probes that hold it), gather the hits from ``snap`` (the
    PROBE'S snapshot: the same array as ``table`` unless another
    batch's insert landed in between) and merge both into the batch's
    ``[B, m]`` ids, pad rows blanked.

    Returns ``(new_table, (ids, ovf))``. ``lay`` is ``(L, B, B)``:
    keyed by the batch's bucket and the depth (and the buffer's
    capacity), never by how many of the batch's topics hit or miss,
    and never by a pack budget (module header)."""
    word_ids, n_words, sys_mask, slots_ = lay.step_sections(buf)
    res = match_batch(auto, word_ids, n_words, sys_mask, k=k, m=m,
                      pack_ids=True, steps=steps, slots=slots, take=take)
    rows, ovf = res.ids, res.overflow
    if delta is not None:
        rows, ovf = probe_packed(*delta, word_ids, n_words, sys_mask,
                                 rows, ovf, m=m, k=dk, steps=dsteps)
    vals = flag_rows(rows, ovf, ovf)
    ids, ovf, _movf = _merge(snap, buf, vals, lay, lay.hit, None)
    return insert_rows(table, slots_, vals), (ids, ovf)


@functools.partial(jax.jit, static_argnames=("lay", "b_pad", "splits"))
def _mesh_merge_jit(table, buf, miss_vals, *, lay: BatchLayout,
                    b_pad: int, splits):
    """A batch's merge as a program of its own (:func:`_merge`): the
    mesh's (whose tests and traces know it by this name), after its
    collective step, and one chip's for a batch that fully hit
    (``miss_vals`` None, keyed by the batch's bucket alone)."""
    return _merge(table, buf, miss_vals, lay, b_pad, splits)


class _Probe:
    """One batch's host-side split (returned by :meth:`MatchCache.
    probe`): hit/miss positions, assigned slots, the epoch key(s), and
    the device-table *snapshot* the hits must gather from (later
    inserts produce new arrays, so the snapshot can't be clobbered).
    ``miss_keys`` is the per-miss insert key: identical to ``key``
    under whole-epoch probing, the topic's own partitioned key when
    the caller passed per-topic keys."""

    __slots__ = ("table", "key", "hit_pos", "hit_slots", "miss_pos",
                 "miss_topics", "miss_slots", "miss_keys")

    def __init__(self, table, key) -> None:
        self.table = table
        self.key = key
        self.hit_pos: List[int] = []
        self.hit_slots: List[int] = []
        self.miss_pos: List[int] = []
        self.miss_topics: List[str] = []
        self.miss_slots: List[int] = []
        self.miss_keys: List[Any] = []


class MatchCache:
    """Fixed-shape device match-row cache with host topic index.

    ``width`` is the packed row width (``max_matches`` on one chip;
    the mesh cache concatenates ids+subs+src into one wider row).
    Eviction is a clock sweep over the slot ring: allocation cost is
    O(1) per miss and a hot entry is only displaced once the ring
    wraps — adequate for a cache whose entries are cheap to refill.
    """

    def __init__(self, slots: int, width: int, sharding=None) -> None:
        self.slots = ring_slots(slots)
        self.width = int(width)
        # where the table lives: None = the default device; the mesh
        # cache passes the mesh's replicated sharding, so its programs
        # see one input layout from the first batch on
        self.sharding = sharding
        self._lock = threading.Lock()
        self._table = None  # lazy: int32[slots, 1 + width]
        self._index: dict = {}                     # topic -> slot
        self._slot_topic: List[Optional[str]] = [None] * self.slots
        self._slot_key: List[Any] = [None] * self.slots
        self._clock = 0
        # cumulative counters (drain_stats hands out deltas)
        self.hits = 0
        self.misses = 0
        self.inserts = 0
        self.stale = 0
        self._drained = {"hit": 0, "miss": 0, "insert": 0, "stale": 0}

    # -- host bookkeeping --------------------------------------------------

    def _table_now(self):
        if self._table is None:
            self._table = jnp.full(
                (self.slots, 1 + self.width), -1, jnp.int32,
                device=self.sharding)
        return self._table

    def _alloc(self, topic: str) -> int:
        s = self._clock
        self._clock = (s + 1) % self.slots
        old = self._slot_topic[s]
        if old is not None:
            self._index.pop(old, None)
        self._slot_topic[s] = topic
        self._slot_key[s] = None  # pending until the insert lands
        self._index[topic] = s
        return s

    def probe(self, topics: Sequence[str], key,
              keys: Optional[Sequence[Any]] = None) -> _Probe:
        """Split a unique-topic batch into hits (slot per topic, key
        matches) and misses (slot assigned now, marked pending — a
        crash before the insert just leaves a permanent miss).

        ``keys`` (optional, parallel to ``topics``) overrides ``key``
        per topic: the router's partitioned-epoch probe passes one key
        per topic carrying that topic's partition revision. Omitted,
        every topic probes (and later inserts) under the single
        batch-wide ``key`` — byte-identical to the pre-partition
        behavior."""
        with self._lock:
            p = _Probe(self._table_now(), key)
            for i, t in enumerate(topics):
                k = key if keys is None else keys[i]
                s = self._index.get(t)
                if s is not None and self._slot_key[s] == k:
                    p.hit_pos.append(i)
                    p.hit_slots.append(s)
                    continue
                if s is not None:
                    if self._slot_key[s] is not None:
                        self.stale += 1  # pending slots aren't stale
                    self._slot_key[s] = None
                else:
                    s = self._alloc(t)
                p.miss_pos.append(i)
                p.miss_topics.append(t)
                p.miss_slots.append(s)
                p.miss_keys.append(k)
            self.hits += len(p.hit_pos)
            self.misses += len(p.miss_pos)
            return p

    # -- device ops (module header) ---------------------------------------

    def batch_buffer(self, b_pad: int, probe: _Probe, enc, n_uniq: int,
                     size: int, rows: Optional[int] = None):
        """One batch's ``(layout, int32 buffer)``. ``enc`` is the
        misses' ``(word_ids [n, L], n_words, sys_mask)`` or None when
        the batch fully hit; ``size`` the capacity so far — the
        layout's is that or the next power of two that holds the
        batch. ``rows`` = one chip's bucket, at which hits and misses
        are both laid: ``enc`` then holds the real misses and ONE pad
        topic behind them, whose row fills the misses' section up to
        ``rows`` (a pad topic encodes alike wherever it stands, and
        the loop does not pay for encoding it ``rows`` times). None =
        the mesh: ``enc`` comes padded to the miss bucket, the hits
        pad by :func:`pad_hits`."""
        mb, levels = (0, 0) if enc is None else enc[0].shape
        if rows is None:
            hb = pad_hits(len(probe.hit_pos))
        else:
            mb, hb = rows if mb else 0, rows
        lay = BatchLayout(levels, mb, hb, _pow2(
            BatchLayout.need(levels, mb, hb), max(size, BATCH_BUF_FLOOR)))
        buf = np.zeros((lay.size,), np.int32)
        end = lay.size - 1
        h = end - 2 * hb
        if mb:
            n = len(probe.miss_slots)
            k = min(len(enc[1]), mb)    # rows encoded; the last fills
            o = mb * levels
            for at, width, part in ((0, levels, enc[0]), (o, 1, enc[1]),
                                    (o + mb, 1, enc[2])):
                sec = buf[at:at + mb * width].reshape(mb, width)
                sec[:k] = part[:k].reshape(k, width)
                sec[k:] = part[k - 1]
            buf[o + 2 * mb:o + 3 * mb] = self.slots  # OOB pad -> drop
            buf[o + 2 * mb:o + 2 * mb + n] = probe.miss_slots
            buf[h - mb:h] = b_pad
            buf[h - mb:h - mb + n] = probe.miss_pos
        nh = len(probe.hit_pos)
        buf[h:h + nh] = probe.hit_slots
        buf[h + hb:end] = b_pad
        buf[h + hb:h + hb + nh] = probe.hit_pos
        buf[end] = n_uniq
        return lay, buf

    def insert_through(self, probe: _Probe, step):
        """Store the fresh walk results for ``probe``'s misses, by the
        caller's own program (:func:`walk_merge`, the mesh's
        ``publish_step_insert``): ``step(table) -> (new_table, out)``
        runs under the lock against the CURRENT table (rows past the
        real miss count drop via OOB indices; overflowed rows store
        invalid markers, never truncated ids); returns ``out``."""
        with self._lock:
            self._table, out = step(self._table_now())
            self._key_inserted(probe)
        return out

    def _key_inserted(self, probe: _Probe) -> None:
        """The misses' slots now hold their rows: key them (call
        under the lock)."""
        for s, t, k in zip(probe.miss_slots, probe.miss_topics,
                           probe.miss_keys):
            # skip slots another batch's clock sweep reassigned
            if self._slot_topic[s] == t:
                self._slot_key[s] = k
        self.inserts += len(probe.miss_slots)

    @staticmethod
    def merge_batch(b_pad: int, probe: _Probe, lay: BatchLayout, buf,
                    miss_vals, splits=None):
        """The batch's combined rows and flags, pad rows blanked —
        ``(ids, ovf, movf)``, on a mesh (``splits``) ``(ids, subs,
        src, ovf, movf)``: one program, hits from ``probe``'s
        snapshot. One chip's batches come here only fully hit."""
        return _mesh_merge_jit(probe.table, buf, miss_vals,
                               lay=lay._replace(levels=0), b_pad=b_pad,
                               splits=splits)

    # -- introspection -----------------------------------------------------

    def entries(self) -> int:
        return len(self._index)

    def stats(self) -> dict:
        """Cumulative counters (+ hit rate) — bench/introspection."""
        total = self.hits + self.misses
        return {
            "hit": self.hits, "miss": self.misses,
            "insert": self.inserts, "stale": self.stale,
            "entries": self.entries(),
            "hit_rate": (self.hits / total) if total else 0.0,
        }

    def drain_stats(self) -> dict:
        """Counter deltas since the previous drain (the metrics-fold
        contract, mirroring ``Router.drain_device_stats``)."""
        with self._lock:
            cur = {"hit": self.hits, "miss": self.misses,
                   "insert": self.inserts, "stale": self.stale}
            out = {k: cur[k] - self._drained[k] for k in cur}
            self._drained = cur
            return out
