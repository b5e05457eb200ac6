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
``merge`` is one jit'd gather+scatter producing the combined
``[B_pad, width]`` id array (hits from the table, misses from the
fresh walk), ``insert`` one jit'd scatter. Nothing here ever forces a
device→host sync — the publish path's coalesced fetch stays the only
transfer.

The mesh variant (``Router._dispatch_fused``; docs/MATCH_CACHE.md
"Mesh (sharded) variant") shares every line of the host bookkeeping
above and has device programs of its own, because its row is three
arrays wide and goes on to ``pack_fanout`` from dense rows:

  - **the mesh row** is one topic's ``ids [T·m] | subs [T·d] |
    src [T·d]`` — everything the collective ``publish_step`` produces
    for it; ``width = T·m + 2·T·d``. The table lives replicated on
    every chip of the mesh (``MatchCache(sharding=…)``);
  - **one buffer a batch** (:class:`MeshLayout`,
    :meth:`MatchCache.mesh_buffer`): every integer the device needs
    from the host for the batch is laid into one int32 numpy array
    and put once, replicated. The programs take it whole and slice it
    at static offsets: ``word_ids [MB·L] | n_words [MB] | sys_mask
    [MB] | insert slots [MB] | miss_pos [MB] | hit_slots [HB] |
    hit_pos [HB] | n_uniq [1]`` (MB, HB = the padded miss and hit
    counts, L = ``max_levels``; an all-hit batch has MB = 0). Its
    LENGTH is a shape of every program that takes it, so it is not
    the sum of its sections but a capacity: a power of two from
    ``MESH_BUF_FLOOR``, grown only when a batch needs more (the
    router keeps the high-water mark) — the step stays one program a
    miss bucket, whatever the batch's hits;
  - **which program owns what**: the step's program
    (``parallel/sharded.py::publish_step_insert``, keyed by the miss
    bucket) walks the misses, lays ``flag | ids | subs | src`` rows,
    gathers them over ``data`` once and scatters them into the table
    (:func:`insert_rows`); the merge's (:func:`_mesh_merge_jit`, keyed
    by the (batch, hit, miss) buckets like ``_merge_jit``) gathers the
    hits from the PROBE'S snapshot, scatters hits and misses, splits
    the row and blanks the pad rows; the packers are a third
    (``ops/pack.py::pack_mesh``, keyed by (batch bucket, pm, pq), so a
    grown budget costs one program a bucket, not one a triple);
  - **the table is not donated**: a probe holds its snapshot and its
    hits gather from it AFTER this or another batch's insert (the
    clock sweep may hand a hit's slot to a miss of the same batch), so
    an insert must leave the old array whole. The copy is device
    time on a chip that is mostly idle; the loop pays nothing for it;
  - **the padding rule is a contract** with the benchmark's sweep
    (``benchmark/warmers/mesh_buckets.py`` walks every (batch, hit,
    miss) triple through ``publish_batch``): batch and misses pad to
    a power of two from ``min_batch × data`` (``Router.pad_topics``),
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

__all__ = ["MatchCache", "MeshLayout", "MESH_BUF_FLOOR", "flag_rows",
           "insert_rows", "pad_hits", "ring_slots"]

#: flag column values: _VALID = cached ids are the exact match set;
#: _OVF = the walk overflowed (host fallback, match-only bound);
#: _FOVF = overflow where the match side itself was fine (the mesh
#: fan-out d bound) — merged back into (ovf, movf) so the router's
#: boost_k/boost_d signals keep their meaning across cached batches
_OVF, _VALID, _FOVF = 0, 1, 2

_MIN_PAD = 8

#: the mesh batch buffer's smallest capacity, int32 words (128 KiB):
#: the largest batch the default ingress forms (``batch_cap`` = 1,024
#: unique topics) needs 22,529 at 16 levels, so a served node never
#: grows it
MESH_BUF_FLOOR = 1 << 15


def _pow2(n: int, floor: int = 1) -> int:
    out = floor
    while out < n:
        out *= 2
    return out


def ring_slots(slots: int) -> int:
    """The slots a cache configured with ``slots`` really has."""
    return _pow2(max(2, int(slots)))


def pad_hits(n: int) -> int:
    """The padded length of a batch's ``n`` cache hits (a shape of the
    merge's program): a power of two from ``_MIN_PAD``, also for none.
    ``Router.dispatch_shapes`` lists the programs from it."""
    return _pow2(max(n, 1), _MIN_PAD)


@functools.partial(jax.jit, static_argnames=("b_pad",))
def _merge_jit(table, hit_slots, hit_pos, miss_rows, miss_ovf,
               miss_movf, miss_pos, *, b_pad: int):
    """Combined id rows + overflow flags for one batch: gather hit
    rows from the table snapshot, scatter them and the fresh miss
    rows into the ``[b_pad, width]`` output (OOB positions drop —
    that is how both pad rows and absent hits/misses vanish)."""
    S = table.shape[0]
    width = table.shape[1] - 1
    out = jnp.full((b_pad, width), -1, jnp.int32)
    ovf = jnp.zeros((b_pad,), bool)
    movf = jnp.zeros((b_pad,), bool)
    hv = table[jnp.clip(hit_slots, 0, S - 1)]
    flag = hv[:, 0]
    out = out.at[hit_pos].set(hv[:, 1:], mode="drop")
    ovf = ovf.at[hit_pos].set(flag != _VALID, mode="drop")
    movf = movf.at[hit_pos].set(flag == _OVF, mode="drop")
    out = out.at[miss_pos].set(miss_rows, mode="drop")
    ovf = ovf.at[miss_pos].set(miss_ovf | miss_movf, mode="drop")
    movf = movf.at[miss_pos].set(miss_movf, mode="drop")
    return out, ovf, movf


@jax.jit
def _insert_jit(table, idx, rows, ovf, movf):
    """Scatter fresh miss rows into their slots. Overflowed rows are
    stored as invalid markers (never as truncated results); padding
    entries carry an out-of-range index and drop."""
    flag = jnp.where(movf, _OVF, jnp.where(ovf, _FOVF, _VALID))
    rows = jnp.where((ovf | movf)[:, None], -1, rows.astype(jnp.int32))
    vals = jnp.concatenate(
        [flag.astype(jnp.int32)[:, None], rows], axis=1)
    return table.at[idx].set(vals, mode="drop")


# -- the mesh's device half (traced inside the mesh programs) --------------


class MeshLayout(NamedTuple):
    """Where one mesh batch's host integers lie in its one int32
    buffer (module header). Static: a program is compiled for the
    sections it reads and for ``size``, the buffer's capacity."""

    levels: int   # L: word ids a topic
    miss: int     # MB: padded miss count (0 = the batch fully hit)
    hit: int      # HB: padded hit count
    size: int     # the buffer's length (≥ need, a learned capacity)

    @staticmethod
    def need(levels: int, miss: int, hit: int) -> int:
        return miss * (levels + 4) + 2 * hit + 1

    def step_sections(self, buf):
        """``(word_ids [MB, L], n_words, sys_mask, slots)`` — all the
        step's program reads; offsets depend on (MB, L) alone."""
        mb, lv = self.miss, self.levels
        o = mb * lv
        return (buf[:o].reshape(mb, lv), buf[o:o + mb],
                buf[o + mb:o + 2 * mb] != 0, buf[o + 2 * mb:o + 3 * mb])

    def merge_sections(self, buf):
        """``(miss_pos [MB], hit_slots [HB], hit_pos [HB], n_uniq)``."""
        mb, hb = self.miss, self.hit
        o = mb * (self.levels + 3)
        h = o + mb
        return (buf[o:h], buf[h:h + hb], buf[h + hb:h + 2 * hb],
                buf[h + 2 * hb])


def flag_rows(rows, ovf, movf):
    """``flag | rows`` for fresh walk results: the table's row format,
    with the rows still RAW (an overflowed row's truncated ids are
    what the merge hands on, as ``_merge_jit`` does; only the table
    stores them blanked — :func:`insert_rows`)."""
    flag = jnp.where(movf, _OVF, jnp.where(ovf, _FOVF, _VALID))
    return jnp.concatenate(
        [flag.astype(jnp.int32)[:, None], rows.astype(jnp.int32)], axis=1)


def insert_rows(table, idx, vals):
    """``_insert_jit``'s scatter for rows in :func:`flag_rows` form."""
    marker = vals.at[:, 1:].set(-1)  # an overflowed row: flag alone
    return table.at[idx].set(
        jnp.where(vals[:, :1] != _VALID, marker, vals), mode="drop")


@functools.partial(jax.jit, static_argnames=("lay", "b_pad", "splits"))
def _mesh_merge_jit(table, buf, miss_vals, *, lay: MeshLayout,
                    b_pad: int, splits):
    """The mesh batch's merge: ``_merge_jit`` over the batch buffer's
    sections, then the row split at ``splits`` (the widths of ids and
    subs) and the pad rows (≥ ``n_uniq``) blanked as
    ``ops/pack.mask_pad_rows`` blanks them. ``miss_vals`` is the
    step's ``flag | row`` output, None when the batch fully hit."""
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
    mw, dw = splits
    return out[:, :mw], out[:, mw:mw + dw], out[:, mw + dw:], ovf, movf


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
        self._slot_key[s] = None  # pending until insert() lands
        self._index[topic] = s
        return s

    def probe(self, topics: Sequence[str], key,
              keys: Optional[Sequence[Any]] = None) -> _Probe:
        """Split a unique-topic batch into hits (slot per topic, key
        matches) and misses (slot assigned now, marked pending — a
        crash before :meth:`insert` just leaves a permanent miss).

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

    # -- device ops --------------------------------------------------------

    def insert(self, probe: _Probe, rows, ovf, movf=None) -> None:
        """Store the fresh walk results for ``probe``'s misses.

        ``rows`` is the (possibly batch-padded) ``[Mb, width]`` device
        result; rows past the real miss count drop via OOB indices.
        ``ovf`` rows store invalid markers, never truncated ids."""
        n = len(probe.miss_slots)
        if n == 0:
            return
        mb = int(rows.shape[0])
        idx = np.full((mb,), self.slots, np.int32)  # OOB pad -> drop
        idx[:n] = probe.miss_slots
        if movf is None:
            movf = ovf
        with self._lock:
            self._table = _insert_jit(self._table_now(), idx, rows,
                                      ovf, movf)
            self._key_inserted(probe)

    def _key_inserted(self, probe: _Probe) -> None:
        """The misses' slots now hold their rows: key them (call
        under the lock)."""
        for s, t, k in zip(probe.miss_slots, probe.miss_topics,
                           probe.miss_keys):
            # skip slots another batch's clock sweep reassigned
            if self._slot_topic[s] == t:
                self._slot_key[s] = k
        self.inserts += len(probe.miss_slots)

    def merge(self, b_pad: int, probe: _Probe, miss_rows=None,
              miss_ovf=None, miss_movf=None):
        """One jit'd gather+scatter producing the batch's combined
        ``(ids[b_pad, width], ovf[b_pad], movf[b_pad])`` device
        arrays. Pass the miss walk outputs (or nothing when the batch
        fully hit)."""
        hb = pad_hits(len(probe.hit_pos))
        hit_slots = np.zeros((hb,), np.int32)
        hit_pos = np.full((hb,), b_pad, np.int32)  # OOB pad -> drop
        if probe.hit_pos:
            hit_slots[:len(probe.hit_slots)] = probe.hit_slots
            hit_pos[:len(probe.hit_pos)] = probe.hit_pos
        if miss_rows is None:
            miss_rows = jnp.full((1, self.width), -1, jnp.int32)
            miss_ovf = jnp.zeros((1,), bool)
            miss_movf = jnp.zeros((1,), bool)
        elif miss_movf is None:
            miss_movf = miss_ovf
        mb = int(miss_rows.shape[0])
        miss_pos = np.full((mb,), b_pad, np.int32)
        miss_pos[:len(probe.miss_pos)] = probe.miss_pos
        return _merge_jit(probe.table, hit_slots, hit_pos, miss_rows,
                          miss_ovf, miss_movf, miss_pos, b_pad=b_pad)

    # -- the mesh's device ops (module header) -----------------------------

    def mesh_buffer(self, b_pad: int, probe: _Probe, enc, levels: int,
                    n_uniq: int, size: int):
        """One mesh batch's ``(layout, int32 buffer)``. ``enc`` is the
        padded misses' ``(word_ids [MB, L], n_words, sys_mask)`` or
        None when the batch fully hit; ``size`` the capacity so far —
        the layout's is that or the next power of two that holds the
        batch."""
        mb = 0 if enc is None else int(enc[0].shape[0])
        hb = pad_hits(len(probe.hit_pos))
        lay = MeshLayout(levels, mb, hb, _pow2(
            MeshLayout.need(levels, mb, hb), max(size, MESH_BUF_FLOOR)))
        buf = np.zeros((lay.size,), np.int32)
        o = mb * levels
        if mb:
            n = len(probe.miss_slots)
            buf[:o] = enc[0].reshape(-1)
            buf[o:o + mb] = enc[1]
            buf[o + mb:o + 2 * mb] = enc[2]
            buf[o + 2 * mb:o + 3 * mb] = self.slots  # OOB pad -> drop
            buf[o + 2 * mb:o + 2 * mb + n] = probe.miss_slots
            buf[o + 3 * mb:o + 4 * mb] = b_pad
            buf[o + 3 * mb:o + 3 * mb + n] = probe.miss_pos
        h = o + 4 * mb
        nh = len(probe.hit_pos)
        buf[h:h + nh] = probe.hit_slots
        buf[h + hb:h + 2 * hb] = b_pad
        buf[h + hb:h + hb + nh] = probe.hit_pos
        buf[h + 2 * hb] = n_uniq
        return lay, buf

    def insert_through(self, probe: _Probe, step):
        """:meth:`insert` for a caller whose own program scatters the
        misses: ``step(table) -> (new_table, out)`` runs under the
        lock against the CURRENT table; returns ``out``."""
        with self._lock:
            self._table, out = step(self._table_now())
            self._key_inserted(probe)
        return out

    @staticmethod
    def merge_mesh(b_pad: int, probe: _Probe, lay: MeshLayout, buf,
                   miss_vals, splits):
        """The mesh batch's ``(ids, subs, src, ovf, movf)``, pad rows
        blanked: one program, hits from ``probe``'s snapshot."""
        return _mesh_merge_jit(probe.table, buf, miss_vals, lay=lay,
                               b_pad=b_pad, splits=splits)

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
