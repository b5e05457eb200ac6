"""O(delta) automaton maintenance: patch instead of re-flatten.

The reference's trie insert/delete touches O(topic depth) Mnesia rows
(src/emqx_trie.erl:82-116). Round 1 re-flattened the whole trie on
any route change — O(all filters) under the router lock (the round-1
verdict's churn-stall finding). This module restores O(depth) against
the *compressed* walk tables (:mod:`emqx_tpu.ops.csr`):

  - a **host mirror** of the device tables (``wt`` edge-hash rows +
    ``node2`` state columns) is the patching authority;
  - ``insert``/``delete`` walk the filter's words through the mirror,
    following multi-word edges with exact chain comparison. A filter
    that diverges mid-chain **splits** the edge: the existing slot is
    rewritten to end at a new interior state and the chain remainder
    is re-inserted as its own edge — O(1) slot writes, no subtree
    touch (new states/edges land in the padded capacity, exactly the
    structure a fresh compress would produce up to state order, which
    the kernel never observes);
  - every host mutation queues a device update; :func:`apply_updates`
    replays the queue as functional ``.at[].set`` ops — the result is
    a **new** device automaton swapped in atomically while matchers
    holding the old one keep running (true double buffering);
  - ``delete`` is a tombstone (terminal id cleared, path kept);
  - hop accounting: a split lengthens one walk path, so the mirror
    bumps ``hops_for_level`` (clamped at the uncompressed bound
    ``d+1``) — the router picks the new step count up on its next
    call (one cached recompile, exact fallback meanwhile via the
    kernel's residual-overflow check). A full re-flatten happens only
    on capacity overflow or when tombstones/splits dominate —
    amortized O(1) per mutation.

Update queues drain in fixed-size chunks padded with out-of-range
indices (``mode="drop"``), so XLA compiles the scatter exactly once.
"""

from __future__ import annotations

from typing import Callable, List, Optional, Tuple

import jax
import numpy as np

from emqx_tpu import topic as T
from emqx_tpu.ops.csr import CW_PAD, NARROW_SLOT, WIDE_SLOT, Automaton

_OOB = np.int32(2**30)  # out-of-range pad index -> .set(mode="drop")
_MAX_EVICT = 64


class PatchOverflow(Exception):
    """Capacity exhausted or eviction bound hit: caller must
    re-flatten (with doubled capacity). ``kind`` is the structure
    that overflowed: "state" or "edge"."""

    def __init__(self, kind: str, msg: Optional[str] = None) -> None:
        super().__init__(msg or f"{kind} capacity")
        self.kind = kind


class AutoPatcher:
    """Host mirror + device-update queue for one automaton buffer
    generation. Recreated from each full flatten."""

    def __init__(self, auto: Automaton,
                 intern: Callable[[str], int]) -> None:
        # numpy copies = the patching authority (device arrays are
        # immutable snapshots of this state + queued updates)
        self.wt = np.array(auto.wt)
        self.node2 = np.array(auto.node2)
        self.hop = np.array(auto.v2_hop)
        self.depth = np.array(auto.v2_depth)
        self.hops_for_level = np.array(auto.hops_for_level)
        self.seed = int(np.asarray(auto.wt_seed)[0])
        self.slots = int(auto.wt_slots)
        self.take = int(auto.wt_take)
        self.sw = WIDE_SLOT if self.take > 1 else NARROW_SLOT
        self.n_states = int(auto.v2_states)
        self.n_edges = int(auto.v2_edges)
        self.s_cap = int(auto.node2.shape[0])
        self.nb = int(auto.wt.shape[0])
        # fill bound: same ≤50% discipline the builder sizes for
        self.e_cap = self.nb * self.slots // 2
        self.intern = intern
        self.tombstones = 0
        self.splits = 0
        self.hops_grown = False  # steps bound changed since flatten
        # host-fallback matches observed while the hop bound is stale
        # (a split bumps only the direct child's hop, so descendants'
        # values run one low and hops_for_level can under-grow —
        # correctness holds via the kernel's residual-overflow
        # fallback, but hot deep topics then pin to the host oracle;
        # counting those fallbacks as a compaction trigger rebuilds
        # the automaton long before 1024 splits accumulate)
        self.hop_fallbacks = 0
        # a PatchOverflow mid-insert leaves the mirror with a dangling
        # prefix (states/edges allocated for the words already walked).
        # That partial state must never reach the device: the patcher
        # marks itself broken and the owner re-flattens (discarding
        # mirror + queue) before any further patch or apply.
        self.broken = False
        # pending device updates
        self._col: List[Tuple[int, int, int]] = []  # (col, idx, val)
        self._slot: List[Tuple[int, int]] = []      # (bucket, slot)

    # -- host-mirror edge hash ops ----------------------------------------

    def _buckets(self, state: int, word: int) -> Tuple[int, int]:
        """:func:`~emqx_tpu.ops.csr.hash_mix` for one edge key, in
        Python's own integers (bit for bit the numpy form, which
        costs ten times as much a scalar: tests/test_patch.py)."""
        m = 0xFFFFFFFF
        h = ((state & m) * 0x9E3779B9 + (word & m) * 0x85EBCA6B
             + self.seed) & m
        h ^= h >> 16
        h = (h * 0x7FEB352D) & m
        h ^= h >> 15
        h2 = (h * 0x846CA68B) & m
        h2 ^= h2 >> 16
        mask = self.nb - 1
        return h & mask, h2 & mask

    def _slot_view(self, b: int, s: int) -> np.ndarray:
        return self.wt[b, s * self.sw:(s + 1) * self.sw]

    def _ht_find(self, state: int, word: int):
        """(bucket, slot) of the edge keyed (state, word); None if
        absent."""
        b1, b2 = self._buckets(state, word)
        for b in (b1, b2):
            for s in range(self.slots):
                v = self._slot_view(b, s)
                if v[0] == state and v[1] == word:
                    return b, s
        return None

    def _edge_fields(self, b: int, s: int):
        """(take, child, chain_words) of the slot. The chain words
        are COPIED — a split rewrites the slot and then reads the
        original tail, so a live view would alias the clobber."""
        v = self._slot_view(b, s)
        if self.take > 1:
            return int(v[2]), int(v[3]), v[4:4 + self.take - 1].copy()
        return 1, int(v[2]), v[:0]

    def _make_row(self, state: int, word: int, take: int, child: int,
                  cw) -> np.ndarray:
        row = np.full(self.sw, -1, np.int32)
        if self.take > 1:
            row[0], row[1], row[2], row[3] = state, word, take, child
            row[4:4 + self.take - 1] = CW_PAD
            if take > 1:
                row[4:4 + take - 1] = cw[:take - 1]
        else:
            row[0], row[1], row[2] = state, word, child
        return row

    def _write_slot(self, b: int, s: int, row: np.ndarray) -> None:
        self.wt[b, s * self.sw:(s + 1) * self.sw] = row
        self._slot.append((b, s))

    def _ht_insert(self, row: np.ndarray) -> None:
        """Place one edge row; cuckoo-evict on full buckets.
        Transactional: on failure every displaced edge is restored
        (losing a victim would silently break an existing filter) and
        PatchOverflow tells the caller to re-flatten."""
        if self.n_edges >= self.e_cap:
            raise PatchOverflow("edge")
        undo: List[Tuple[int, int, np.ndarray]] = []

        def place(b: int, s: int, r: np.ndarray) -> None:
            undo.append((b, s, self._slot_view(b, s).copy()))
            self._write_slot(b, s, r)

        cur = row
        cb, _ = self._buckets(int(cur[0]), int(cur[1]))
        for step in range(_MAX_EVICT):
            free = [s for s in range(self.slots)
                    if self._slot_view(cb, s)[0] < 0]
            if free:
                place(cb, free[0], cur)
                self.n_edges += 1
                return
            alt1, alt2 = self._buckets(int(cur[0]), int(cur[1]))
            other = alt2 if cb == alt1 else alt1
            if any(self._slot_view(other, s)[0] < 0
                   for s in range(self.slots)):
                cb = other
                continue
            victim = step % self.slots
            vrow = self._slot_view(cb, victim).copy()
            place(cb, victim, cur)
            cur = vrow
            a1, a2 = self._buckets(int(cur[0]), int(cur[1]))
            cb = a2 if cb == a1 else a1
        for b, s, r in reversed(undo):
            self.wt[b, s * self.sw:(s + 1) * self.sw] = r
            self._slot.append((b, s))
        raise PatchOverflow("edge", "eviction bound")

    # -- column / state ops ------------------------------------------------

    _PLUS, _HASHF, _ENDF = 0, 1, 2

    def _set_col(self, col: int, idx: int, val: int) -> None:
        self.node2[idx, col] = val
        self._col.append((col, idx, val))

    def _new_state(self, depth: int, hop: int) -> int:
        if self.n_states >= self.s_cap:
            raise PatchOverflow("state")
        sid = self.n_states
        self.n_states += 1
        self.hop[sid] = hop
        self.depth[sid] = depth
        self._note_hops(depth, hop)
        return sid

    def _note_hops(self, depth: int, hop: int) -> None:
        """Keep the step bound ≥ hop+1 for every batch depth ≥ depth
        (monotone array; clamped at the uncompressed bound d+1)."""
        hl = self.hops_for_level
        if depth < len(hl) and hl[depth] >= min(hop + 1, depth + 1):
            return  # monotone: every deeper level holds the bound too
        if depth >= len(hl):
            # extension: past the old max depth the walk can always
            # fall back to one hop per extra level
            d_ext = np.arange(len(hl), depth + 1, dtype=np.int64)
            ext = np.minimum(int(hl[-1]) + (d_ext - (len(hl) - 1)),
                             d_ext + 1)
            hl = np.concatenate([hl, ext.astype(hl.dtype)])
            self.hops_for_level = hl
            self.hops_grown = True
        idx = np.arange(len(hl))
        want = np.where(idx >= depth, hop + 1, 0)
        grown = np.maximum(hl, np.minimum(want, idx + 1)).astype(hl.dtype)
        if not np.array_equal(grown, hl):
            self.hops_for_level = grown
            self.hops_grown = True

    def _bump_hops_from(self, depth: int) -> None:
        """A split made every path through depth ≥ ``depth`` one hop
        longer; bump the whole tail (clamped at d+1) — cheaper and
        safer than renumbering the subtree's hop values."""
        hl = self.hops_for_level
        idx = np.arange(len(hl))
        grown = np.where(idx >= depth,
                         np.minimum(hl + 1, idx + 1), hl).astype(hl.dtype)
        if not np.array_equal(grown, hl):
            self.hops_for_level = grown
            self.hops_grown = True

    # -- public API --------------------------------------------------------

    def insert(self, filter_: str, fid: int) -> None:
        """Add ``filter_`` terminating with filter id ``fid``.

        Raises :class:`PatchOverflow` when a re-flatten is needed. A
        mid-walk overflow leaves a dangling prefix in the mirror; the
        patcher then flips :attr:`broken` and refuses all further
        work until the owner re-flattens — the partial mutations can
        never reach the device."""
        if self.broken:
            raise PatchOverflow("state", "patcher broken")
        words = T.words(filter_)
        state = 0
        i = 0
        try:
            while i < len(words):
                w = words[i]
                if w == T.HASH:  # '#' is a leaf collapsed into parent
                    self._set_col(self._HASHF, state, fid)
                    return
                if w == T.PLUS:
                    child = int(self.node2[state, self._PLUS])
                    if child < 0:
                        child = self._new_state(
                            i + 1, int(self.hop[state]) + 1)
                        self._set_col(self._PLUS, state, child)
                    state = child
                    i += 1
                    continue
                wid = self.intern(w)
                found = self._ht_find(state, wid)
                if found is None:
                    # fresh chain: consume the maximal literal run in
                    # compressed hops (exactly what a flatten builds)
                    run = 1
                    while (i + run < len(words)
                           and words[i + run] not in (T.PLUS, T.HASH)
                           and run < self.take):
                        run += 1
                    cw = np.array([self.intern(x)
                                   for x in words[i + 1:i + run]],
                                  np.int32)
                    child = self._new_state(
                        i + run, int(self.hop[state]) + 1)
                    self._ht_insert(self._make_row(
                        state, wid, run, child, cw))
                    state = child
                    i += run
                    continue
                b, s = found
                take_e, child_e, cw_e = self._edge_fields(b, s)
                # longest shared prefix of the edge's words vs ours
                match = 1
                while match < take_e:
                    j = i + match
                    if (j >= len(words)
                            or words[j] in (T.PLUS, T.HASH)
                            or self.intern(words[j]) != int(
                                cw_e[match - 1])):
                        break
                    match += 1
                if match == take_e:
                    state = child_e
                    i += take_e
                    continue
                # split: interior state at the divergence point
                mid = self._new_state(i + match,
                                      int(self.hop[state]) + 1)
                self._write_slot(b, s, self._make_row(
                    state, wid, match, mid, cw_e))
                self._ht_insert(self._make_row(
                    mid, int(cw_e[match - 1]), take_e - match,
                    child_e, cw_e[match:]))
                self.splits += 1
                # the old child (and its whole subtree) is now one hop
                # deeper; bump the bound tail rather than renumbering
                self.hop[child_e] += 1
                self._bump_hops_from(int(self.depth[mid]))
                state = mid
                i += match
            self._set_col(self._ENDF, state, fid)
        except PatchOverflow:
            self.broken = True
            raise

    def _walk(self, words) -> int:
        """Follow ``words`` through the mirror; -1 if the path is
        absent. Returns the terminal state id."""
        state = 0
        i = 0
        while i < len(words):
            w = words[i]
            if w == T.PLUS:
                state = int(self.node2[state, self._PLUS])
                if state < 0:
                    return -1
                i += 1
                continue
            found = self._ht_find(state, self.intern(w))
            if found is None:
                return -1
            take_e, child_e, cw_e = self._edge_fields(*found)
            for t in range(take_e - 1):
                j = i + 1 + t
                if (j >= len(words) or words[j] in (T.PLUS, T.HASH)
                        or self.intern(words[j]) != int(cw_e[t])):
                    return -1
            state = child_e
            i += take_e
        return state

    def delete(self, filter_: str) -> bool:
        """Tombstone ``filter_``'s terminal marker; the path stays
        (compacted by the next full flatten). False = not found."""
        if self.broken:
            raise PatchOverflow("state", "patcher broken")
        ws = T.words(filter_)
        if ws and ws[-1] == T.HASH:
            state = self._walk(ws[:-1])
            if state < 0 or int(self.node2[state, self._HASHF]) < 0:
                return False
            self._set_col(self._HASHF, state, -1)
        else:
            state = self._walk(ws)
            if state < 0 or int(self.node2[state, self._ENDF]) < 0:
                return False
            self._set_col(self._ENDF, state, -1)
        self.tombstones += 1
        return True

    def note_hop_fallbacks(self, n: int) -> None:
        """Record ``n`` host-fallback matches. Counted only while the
        hop bound has grown since the flatten (the stale-hop regime):
        overflow from an undersized active set is ``boost_k``'s
        problem, not a rebuild trigger."""
        if self.hops_grown:
            self.hop_fallbacks += n

    def needs_compaction(self, live_filters: int) -> bool:
        """Tombstones, accumulated splits, OR stale-hop host
        fallbacks dominate: the automaton is still correct, just
        wasteful/slower — rebuild off-stream."""
        bound = max(1024, live_filters)
        return self.tombstones > bound or self.splits > bound \
            or self.hop_fallbacks > bound

    # -- device replay -----------------------------------------------------

    @property
    def dirty(self) -> bool:
        return bool(self._col or self._slot)

    @property
    def queued(self) -> int:
        """Pending device updates (the router's drain-batch signal)."""
        return len(self._col) + len(self._slot)

    def apply_updates(self, auto: Automaton) -> Automaton:
        """Replay queued host mutations onto the device automaton,
        returning a NEW automaton (old buffers untouched — matchers
        holding them are safe; the caller swaps atomically).

        Updates go in FIXED-size chunks (padded with out-of-range
        indices, ``mode="drop"``): the scatter jits exactly once and
        is reused for every drain — variable pow2 padding would pay a
        fresh XLA compile per new queue size (measured as a 40x p99
        spike in the churn bench)."""
        assert not self.broken, \
            "partial mutations must not reach the device (re-flatten)"
        if not self.dirty:
            return auto
        for chunk in self._drain_chunks():
            auto = _apply_jit(auto, *chunk)
        return auto._replace(v2_states=self.n_states,
                             v2_edges=self.n_edges)

    def _drain_deduped(self):
        """Consume + dedup the raw queues, last write wins: repeated
        indices inside one ``.at[].set`` chunk apply in
        implementation-defined order (a delete+re-add of the same
        filter, or a cuckoo slot written twice, could otherwise
        resurrect the stale value on device). Slot updates read the
        mirror's CURRENT row — later host writes to the same slot are
        naturally folded."""
        col, self._col = self._col, []
        sl, self._slot = self._slot, []
        col_d = {(c, idx): val for c, idx, val in col}
        sl_d = {}
        for b, s in sl:
            sl_d[(b, s)] = self._slot_view(b, s).copy()
        return ([(c, i, v) for (c, i), v in col_d.items()],
                [(b, s, row) for (b, s), row in sl_d.items()])

    def _drain_chunks(self):
        """Consume the update queues as fixed-size padded chunks."""
        col, sl = self._drain_deduped()
        while col or sl:
            rem = max(len(col), len(sl))
            n = _CHUNKS[-1]
            for size in _CHUNKS:
                if rem >= size:
                    n = size
                    break
            c_part, col = col[:n], col[n:]
            s_part, sl = sl[:n], sl[n:]
            ci = np.full((3, n), _OOB, dtype=np.int32)
            cv = np.zeros((3, n), dtype=np.int32)
            counts = [0, 0, 0]
            for c, idx, val in c_part:
                ci[c, counts[c]] = idx
                cv[c, counts[c]] = val
                counts[c] += 1
            sb = np.full((n,), _OOB, dtype=np.int32)
            so = np.zeros((n,), dtype=np.int32)
            sv = np.zeros((n, self.sw), dtype=np.int32)
            for i, (b, s, row) in enumerate(s_part):
                sb[i] = b
                so[i] = s * self.sw
                sv[i] = row
            yield ci, cv, sb, so, sv


# drain chunk ladder, largest first: bounded compile count (one
# specialization per rung), small steady-state pad, few passes for
# a large idle-accumulated backlog. Floor 512 ≥ the router's
# patch_drain_batch so a mutator-paid drain is ONE scatter pass —
# every .at[].set chunk copy-on-writes the full table buffers, so
# chunk count, not chunk size, is the cost that matters.
_CHUNKS = (32768, 4096, 512)


def warm_chunks(auto: Automaton, sw: int) -> int:
    """Launch :func:`_apply_jit` once at every rung of the ladder on
    ``auto``'s shapes with chunks that are all pad (nothing is
    written, the result is dropped): a drain of any size then finds
    its program made. -> the rungs launched."""
    for n in _CHUNKS:
        _apply_jit(auto, np.full((3, n), _OOB, np.int32),
                   np.zeros((3, n), np.int32),
                   np.full((n,), _OOB, np.int32),
                   np.zeros((n,), np.int32),
                   np.zeros((n, sw), np.int32))
    return len(_CHUNKS)


@jax.jit
def _apply_jit(auto: Automaton, ci, cv, sb, so, sv):
    node2 = auto.node2
    for c in range(3):
        node2 = node2.at[ci[c], c].set(cv[c], mode="drop")
    sw = sv.shape[1]
    wt = auto.wt.at[sb[:, None],
                    so[:, None] + np.arange(sw)[None, :]].set(
        sv, mode="drop")
    return auto._replace(node2=node2, wt=wt)


def apply_stacked_multi(patchers, stacked):
    """Drain EVERY listed ``(shard_row, patcher)``'s queue into the
    stacked sharded automaton with SHARED chunks — one scatter pass
    per chunk regardless of how many shards are dirty (each
    ``.at[].set`` copy-on-writes the whole stacked buffer, so a
    per-shard loop would pay T full copies for a T-shard storm).
    Entries carry their shard row as an extra index column."""
    col = []  # (t, col, idx, val)
    sl = []   # (t, bucket, base, row)
    sw = None
    for t, p in patchers:
        assert not p.broken, \
            "partial mutations must not reach the device (re-flatten)"
        sw = p.sw
        c_, s_ = p._drain_deduped()
        col.extend((t, c, i, v) for c, i, v in c_)
        sl.extend((t, b, s * p.sw, row) for b, s, row in s_)
    while col or sl:
        rem = max(len(col), len(sl))
        n = _CHUNKS[-1]
        for size in _CHUNKS:
            if rem >= size:
                n = size
                break
        c_part, col = col[:n], col[n:]
        s_part, sl = sl[:n], sl[n:]
        ti = np.zeros((3, n), dtype=np.int32)
        ci = np.full((3, n), _OOB, dtype=np.int32)
        cv = np.zeros((3, n), dtype=np.int32)
        counts = [0, 0, 0]
        for t, c, idx, val in c_part:
            ti[c, counts[c]] = t
            ci[c, counts[c]] = idx
            cv[c, counts[c]] = val
            counts[c] += 1
        st = np.zeros((n,), dtype=np.int32)
        sb = np.full((n,), _OOB, dtype=np.int32)
        so = np.zeros((n,), dtype=np.int32)
        sv = np.zeros((n, sw), dtype=np.int32)
        for i, (t, b, base, row) in enumerate(s_part):
            st[i], sb[i], so[i] = t, b, base
            sv[i] = row
        stacked = _apply_jit_stacked(stacked, ti, ci, cv, st, sb, so, sv)
    return stacked


@jax.jit
def _apply_jit_stacked(stacked, ti, ci, cv, st, sb, so, sv):
    """The stacked-shard form of :func:`_apply_jit`: scatter one
    chunk into ``[T, ...]`` arrays with a per-entry shard row. Pad
    entries keep the OOB index convention (any out-of-bounds index
    drops the write)."""
    node2 = stacked.node2
    for c in range(3):
        node2 = node2.at[ti[c], ci[c], c].set(cv[c], mode="drop")
    sw = sv.shape[1]
    wt = stacked.wt.at[st[:, None], sb[:, None],
                       so[:, None] + np.arange(sw)[None, :]].set(
        sv, mode="drop")
    return stacked._replace(node2=node2, wt=wt)
