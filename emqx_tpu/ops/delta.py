"""Online delta automaton: storm-rate route churn without touching
the main walk tables.

The patch-in-place path (:mod:`emqx_tpu.ops.patch`) keeps the main
automaton current by splitting edges and queueing device scatters per
mutation — O(depth) per op, but a sustained reconnect storm decays
the walk (splits lengthen paths, stale hop bounds pin hot topics to
the host oracle) and every drain copy-on-writes the full walk tables.
The reference broker never pays any of this: its trie writes are
O(topic depth) Mnesia ops and reads never degrade
(src/emqx_trie.erl:82-116).

This module is the churn-plane answer (ROADMAP item 5): batch route
**adds** into a small *side-automaton* probed alongside the main walk
(two-probe, terminal-id union), and handle **deletes** as a
post-match tombstone-id mask — the main tables stay byte-identical
between compactions, so the walk never decays no matter how hard the
route set churns. The side structures are tiny (bounded by
``[matcher] delta_max_filters``), so:

  - inserts patch the side-automaton's own :class:`AutoPatcher`
    mirror — the copy-on-write apply touches kilobytes, not the main
    tables' hundreds of megabytes;
  - the side-automaton is always **narrow** (take ≡ 1): no chains,
    therefore no splits and no hop decay — a filter's walk cost is
    exactly its depth, and the automaton rebuilds from its own small
    trie in milliseconds when capacity doubles;
  - a live delta looks the same to the walk's program whatever it
    holds: the side tables are sized once for the configured bound
    (``floor_states``; they double only past it), the mask is there
    with no bit set where nothing is tombstoned, the side tables
    with no filter in them where nothing is pending, and the side
    walk's step count follows the batch's depth alone. Routes that
    change while traffic flows therefore first-use one variant of
    the walk's program a shape, not one for every size and mix the
    delta passes through (2–7 s each on the event loop);
  - deletes never touch any automaton: the fid lands in a tombstone
    set, compiled into a device mask applied to the merged match ids
    (``-1``-ing them before the fan-out gathers — the id→filter map's
    ``None`` translation remains the exact host-side backstop).

A background compaction folds the delta into the main tables
(``Router`` flattens its persistent trie OFF-lock and swaps under a
short lock); the delta's ordered mutation **log** is what makes that
seamless — mutations landing mid-flatten replay into a fresh delta
via :meth:`DeltaAutomaton.split_after`, so the published
(main, delta) pair is exact on both sides of the swap. See
docs/DELTA.md.
"""

from __future__ import annotations

import functools
from typing import Callable, Dict, List, NamedTuple, Optional, Set, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from emqx_tpu import topic as T
from emqx_tpu.oracle import TrieOracle
from emqx_tpu.ops.csr import (NARROW_SLOTS, Automaton, build_automaton,
                              buckets_for_capacity, capacity_for,
                              device_view, finalize_automaton)
from emqx_tpu.ops.match import match_batch
from emqx_tpu.ops.patch import AutoPatcher, PatchOverflow


class _InternTable:
    """Adapter giving :func:`build_automaton` the one method it uses
    (``intern``) over whichever engine owns the word table — the
    delta MUST share the main automaton's word ids (both walks
    consume the same encoded batch)."""

    __slots__ = ("intern",)

    def __init__(self, intern: Callable[[str], int]) -> None:
        self.intern = intern


class DeltaSnapshot(NamedTuple):
    """One consistent, immutable view for lock-free matchers. Both
    halves are always there: ``auto`` holds no filter when no add is
    pending (tombstone-only delta), ``mask`` has no bit set when
    nothing is tombstoned, so the walk's program is one whatever the
    delta holds."""

    auto: Automaton               # walkable device view (narrow)
    k: int                        # active-set lanes the delta walk needs
    mask: jax.Array               # bool[cap] True = tombstoned fid
    version: int
    n_pending: int

    @staticmethod
    def steps_for(lb: int) -> int:
        """Scan steps of the side walk for a batch ``lb`` levels deep:
        the narrow tables take one hop a level and one more to close,
        whatever they hold (a shallower delta idles the rest)."""
        return lb + 1


class DeltaAutomaton:
    """Pending route mutations relative to the last main flatten.

    All mutation methods are called under the router's lock (the
    word-table lock additionally guards interning, same as the main
    patch path); :meth:`snapshot` publishes an immutable view."""

    def __init__(self, intern: Callable[[str], int],
                 use_device: bool = True) -> None:
        self.intern = intern
        self.use_device = use_device
        self.trie = TrieOracle()          # pending adds, host authority
        self.fids: Dict[str, int] = {}    # pending filter → fid
        self.tombs: Set[int] = set()      # fids tombstoned in MAIN tables
        self.tomb_filters: Set[str] = set()
        #: ordered mutation log — the replay seam the off-lock
        #: compaction splits at (docs/DELTA.md "Mutation-log replay")
        self.log: List[Tuple[str, str, int]] = []
        self.has_plus = False
        self.version = 0
        #: states the side tables are sized for from their first
        #: flatten on (the router sets it from ``delta_max_filters``);
        #: one capacity for the delta's life, doubled only past it
        self.floor_states = 1 << 14
        #: flattens that made the side tables larger than the one
        #: before (than the floor, the first): each is a new shape of
        #: the walk's program (``automaton.delta.grows``)
        self.grows = 0
        self._host_auto: Optional[Automaton] = None
        self._dev_auto: Optional[Automaton] = None
        self._patcher: Optional[AutoPatcher] = None
        self._flatten_dirty = False   # side-tables need a re-flatten
        self._grow = 1                # capacity growth on overflow
        self._mask_dirty = True
        self._mask_dev: Optional[jax.Array] = None
        self._mask_cap = 0
        self._snap: Optional[DeltaSnapshot] = None
        self._snap_key = None

    # -- mutation (under the router lock) ---------------------------------

    @property
    def n_pending(self) -> int:
        return len(self.fids)

    @property
    def n_tombstones(self) -> int:
        return len(self.tombs)

    def mark(self) -> int:
        """Current log position — compaction records it at freeze
        time; entries before it are folded into the flatten."""
        return len(self.log)

    def add(self, filter_: str, fid: int) -> None:
        self.trie.insert(filter_)
        self.fids[filter_] = fid
        self.log.append(("+", filter_, fid))
        if T.PLUS in T.words(filter_):
            self.has_plus = True
        self.version += 1
        if self._flatten_dirty or self._patcher is None:
            self._flatten_dirty = True
            return
        try:
            self._patcher.insert(filter_, fid)
        except PatchOverflow:
            # side tables are small: just re-flatten them (ms) at the
            # next snapshot, with doubled capacity
            self._grow = 2
            self._flatten_dirty = True

    def delete(self, filter_: str, fid: int) -> None:
        """A route delete: retract a pending add, or tombstone a
        main-table fid."""
        self.log.append(("-", filter_, fid))
        self.version += 1
        if filter_ in self.fids:
            self.trie.delete(filter_)
            del self.fids[filter_]
            if not self._flatten_dirty and self._patcher is not None:
                try:
                    self._patcher.delete(filter_)
                except PatchOverflow:
                    self._flatten_dirty = True
            return
        self.tombs.add(fid)
        self.tomb_filters.add(filter_)
        self._mask_dirty = True

    def split_after(self, mark: int) -> "DeltaAutomaton":
        """A fresh delta holding only the mutations after ``mark`` —
        everything before it is in the new main tables (the off-lock
        compaction flattened the trie they had already been applied
        to). Replays with live semantics, so an add+delete pair
        inside the window cancels and a delete of a pre-mark add
        becomes a tombstone against the NEW tables. A generation that
        holds nothing is a delta all the same: the walk's program
        stays the one with a live delta over every swap, whatever
        arrived during the flatten."""
        fresh = DeltaAutomaton(self.intern, self.use_device)
        fresh.floor_states = self.floor_states
        # the side walk's lanes are a static of the walk's program:
        # once a '+' was pending they stay, generation after generation
        fresh.has_plus = self.has_plus
        for op, f, fid in self.log[mark:]:
            if op == "+":
                fresh.add(f, fid)
            else:
                fresh.delete(f, fid)
        return fresh

    def needs_compaction(self, max_filters: int, live: int) -> bool:
        """Pending adds at the configured bound, or tombstones
        dominating the live set — fold into the main tables."""
        return (len(self.fids) >= max_filters
                or len(self.tombs) > max(1024, live))

    def warm_apply(self) -> int:
        """First-use the patch scatter at every rung of its chunk
        ladder on the side tables' shape (chunks that write nothing),
        so that a backlog of pending adds after a stall of the loop
        first-uses no program; -> the rungs launched (call under the
        router lock, after a :meth:`snapshot`)."""
        if self._dev_auto is None or self._patcher is None:
            return 0
        from emqx_tpu.ops.patch import warm_chunks

        return warm_chunks(self._dev_auto, self._patcher.sw)

    def invalidate_device(self) -> None:
        """Device-loss recovery (docs/ROBUSTNESS.md): the staged
        device view — side walk tables, tombstone mask, cached
        snapshot — references a dead backend's HBM. Drop it all and
        mark dirty; the next :meth:`snapshot` re-flattens the side
        trie and re-stages the mask on the fresh backend. Host
        authority (trie, fids, tombs, log) is untouched."""
        self._host_auto = None
        self._dev_auto = None
        self._patcher = None
        self._flatten_dirty = True
        self._mask_dev = None
        self._mask_cap = 0
        self._mask_dirty = True
        self._snap = None
        self._snap_key = None

    # -- host match (oracle-fallback union) -------------------------------

    def host_match(self, topic: str) -> List[str]:
        """Pending-add filters matching ``topic`` (host side of the
        two-probe union; tombstones are the caller's id-map ``None``
        translation)."""
        if not self.fids:
            return []
        return self.trie.match(topic)

    # -- snapshot (side tables + tombstone mask) --------------------------

    def _flatten(self) -> None:
        cap, nb = self.floor_states, buckets_for_capacity(
            self.floor_states, NARROW_SLOTS)
        if self._host_auto is not None \
                and self._host_auto.node2 is not None:
            cap = max(cap, self._host_auto.node2.shape[0] * self._grow)
            nb = max(nb, self._host_auto.wt.shape[0] * self._grow)
        table = _InternTable(self.intern)
        base = build_automaton(self.trie, self.fids, table,
                               skip_hash=True)
        host = finalize_automaton(base, force_mode="narrow",
                                  state_capacity=cap, n_buckets=nb)
        was = self._host_auto
        if host.node2.shape[0] > (self.floor_states if was is None
                                  else was.node2.shape[0]) \
                or (was is not None
                    and host.wt.shape[0] > was.wt.shape[0]):
            self.grows += 1
        self._host_auto = host
        # the side walk's steps follow the batch's depth
        # (DeltaSnapshot.steps_for), never the tables' own bound: it
        # stays on the host, or its length (the deepest pending
        # filter) would be a shape of the walk's program
        auto = device_view(host)._replace(hops_for_level=None)
        if self.use_device:
            auto = jax.device_put(auto)
        self._dev_auto = auto
        self._patcher = AutoPatcher(host, self.intern)
        self._flatten_dirty = False
        self._grow = 1

    def snapshot(self, id_cap: int, k_cap: int) -> DeltaSnapshot:
        """The current immutable view (cached by version; call under
        the router lock). ``id_cap`` sizes the tombstone mask (the
        id→filter map length); ``k_cap`` is the active-set capacity a
        wildcard-bearing delta walk gets."""
        key = (self.version, id_cap > self._mask_cap, k_cap)
        if self._snap is not None and self._snap_key == key \
                and not self._flatten_dirty and not self._mask_dirty \
                and (self._patcher is None or not self._patcher.dirty):
            return self._snap
        if self._flatten_dirty or self._host_auto is None:
            self._flatten()
        elif self._patcher.dirty:
            self._dev_auto = self._patcher.apply_updates(self._dev_auto)
        cap = self._mask_cap
        if cap < id_cap or cap == 0:
            cap = capacity_for(id_cap)
        if self._mask_dirty or cap != self._mask_cap:
            m = np.zeros(cap, bool)
            if self.tombs:
                m[np.fromiter(self.tombs, np.int64,
                              len(self.tombs))] = True
            self._mask_dev = jax.device_put(m) if self.use_device \
                else jnp.asarray(m)
            self._mask_cap = cap
            self._mask_dirty = False
        self._snap = DeltaSnapshot(
            auto=self._dev_auto, k=(k_cap if self.has_plus else 1),
            mask=self._mask_dev, version=self.version,
            n_pending=len(self.fids))
        self._snap_key = key
        return self._snap


# -- two-probe device merge -------------------------------------------------


@jax.jit
def _mask_ids(ids: jax.Array, mask: jax.Array) -> jax.Array:
    """Post-match tombstone mask: ``-1`` every id whose mask bit is
    set (deleted-but-not-yet-compacted fids never reach the fan-out
    gathers)."""
    hit = mask[jnp.clip(ids, 0, mask.shape[0] - 1)]
    return jnp.where((ids >= 0) & hit, -1, ids)


@functools.partial(jax.jit, static_argnames=("m",))
def _union_packed(a: jax.Array, b: jax.Array, *, m: int):
    """Row-wise union of two packed id arrays into ``m`` slots.
    Trie terminals are disjoint between the main tables and the delta
    (a filter lives in exactly one), so union is pure packing; rows
    whose combined set exceeds ``m`` flag overflow (host fallback,
    same contract as the walk)."""
    cat = jnp.concatenate([a, b], axis=1)

    def one(row):
        valid = row >= 0
        cnt = jnp.sum(valid)
        pos = jnp.cumsum(valid) - 1
        out = jnp.full((m,), -1, row.dtype).at[
            jnp.where(valid, pos, m)].set(row, mode="drop")
        return out, cnt > m

    return jax.vmap(one)(cat)


def probe_raw(snap: DeltaSnapshot, word_ids, n_words, sys_mask,
              main_ids, main_ovf, *, m: int):
    """Two-probe merge for the RAW (``pack_ids=False``) dispatch:
    walk the side-automaton over the already-encoded batch, CONCAT
    its raw emit slots onto the main walk's (downstream packing
    subsumes the union), OR the overflows, then tombstone-mask."""
    res = match_batch(
        snap.auto, word_ids, n_words, sys_mask, k=snap.k, m=m,
        pack_ids=False, steps=snap.steps_for(word_ids.shape[1]),
        slots=2, take=1)
    ids = jnp.concatenate([main_ids, res.ids], axis=1)
    return _mask_ids(ids, snap.mask), main_ovf | res.overflow


def probe_packed(auto, mask, word_ids, n_words, sys_mask, main_ids,
                 main_ovf, *, m: int, k: int, steps: int):
    """Two-probe merge for the PACKED (``pack_ids=True``) dispatch —
    the match-cache miss walk: union into the fixed ``[B, m]`` row
    shape cache entries carry, then tombstone-mask. Traced inside the
    match's program (``ops/match_cache.walk_merge``), so it takes a
    :class:`DeltaSnapshot`'s device half (``auto``, ``mask``) and its
    host half as statics (``k``, and ``steps`` = ``steps_for`` the
    batch's depth)."""
    res = match_batch(
        auto, word_ids, n_words, sys_mask, k=k, m=m,
        pack_ids=True, steps=steps, slots=2, take=1)
    ids, u_ovf = _union_packed(main_ids, res.ids, m=m)
    return _mask_ids(ids, mask), main_ovf | res.overflow | u_ovf
