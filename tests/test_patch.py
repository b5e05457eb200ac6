"""O(delta) automaton patching: parity against full re-flattens.

The patcher must produce an automaton the match kernel cannot
distinguish from a fresh flatten of the same filter set (only state
ids differ, which the kernel never observes). Reference semantics:
src/emqx_trie.erl:82-116 insert/delete are O(depth) row updates.
"""

import random

import numpy as np
import pytest

from emqx_tpu.oracle import TrieOracle
from emqx_tpu.ops.csr import build_automaton
from emqx_tpu.ops.match import match_batch
from emqx_tpu.ops.patch import AutoPatcher, PatchOverflow
from emqx_tpu.ops.tokenize import WordTable, encode_batch

WORDS = ["a", "b", "c", "dd", "ee", "sensor", "x"]


def _rand_filter(rng):
    depth = rng.randint(1, 5)
    ws = []
    for i in range(depth):
        p = rng.random()
        if p < 0.2:
            ws.append("+")
        elif p < 0.3 and i == depth - 1:
            ws.append("#")
        else:
            ws.append(rng.choice(WORDS))
    return "/".join(ws)


def _match_set(auto, table, fids_rev, topic):
    ids, n, sysm = encode_batch(table, [topic] * 8, 8)
    res = match_batch(auto, ids, n, sysm, k=32, m=64)
    row = np.asarray(res.ids)[0]
    assert not bool(np.asarray(res.overflow)[0])
    return {fids_rev[j] for j in row if j >= 0}


def _build(filters, table, caps=(None, None)):
    trie = TrieOracle()
    fids = {}
    for f in filters:
        trie.insert(f)
        fids[f] = len(fids)
        for w in f.split("/"):
            if w not in ("+", "#"):
                table.intern(w)
    auto = build_automaton(trie, fids, table,
                           state_capacity=caps[0], edge_capacity=caps[1])
    return auto, fids


def test_patched_matches_equal_fresh_flatten():
    rng = random.Random(7)
    table = WordTable()
    base = sorted({_rand_filter(rng) for _ in range(40)})
    # padded capacity so ~25 patches fit without overflow
    auto, fids = _build(base, table, caps=(512, 512))
    patcher = AutoPatcher(auto, table.intern)

    live = dict(fids)
    extra = sorted({_rand_filter(rng) for _ in range(60)}
                   - set(base))[:25]
    for f in extra:
        fid = len(live)
        live[f] = fid
        patcher.insert(f, fid)
    drops = rng.sample(base, 8)
    for f in drops:
        assert patcher.delete(f)
        del live[f]
    patched = patcher.apply_updates(auto)

    # fresh flatten of the same live set = ground truth
    t2 = WordTable()
    fresh, fresh_fids = _build(sorted(live), t2)
    rev_p = {v: k for k, v in live.items()}
    rev_f = {v: k for k, v in fresh_fids.items()}
    for _ in range(200):
        topic = "/".join(rng.choice(WORDS)
                         for _ in range(rng.randint(1, 5)))
        got = _match_set(patched, table, rev_p, topic)
        want = _match_set(fresh, t2, rev_f, topic)
        assert got == want, (topic, got, want)


def test_patch_is_incremental_not_queued_forever():
    table = WordTable()
    auto, fids = _build(["a/b"], table, caps=(64, 64))
    p = AutoPatcher(auto, table.intern)
    p.insert("a/c", 1)
    assert p.dirty
    out = p.apply_updates(auto)
    assert not p.dirty
    # original buffers untouched (double-buffering)
    rev = {0: "a/b", 1: "a/c"}
    assert _match_set(out, table, rev, "a/c") == {"a/c"}
    assert _match_set(auto, table, rev, "a/c") == set()


def test_overflow_marks_broken_and_blocks_apply():
    table = WordTable()
    auto, fids = _build(["a"], table)  # min capacity (16)
    p = AutoPatcher(auto, table.intern)
    with pytest.raises(PatchOverflow):
        # deep filter: exhausts the 16-state capacity mid-walk
        p.insert("/".join(f"w{i}" for i in range(20)), 1)
    assert p.broken
    with pytest.raises(PatchOverflow):
        p.insert("b", 2)
    with pytest.raises(PatchOverflow):
        p.delete("a")
    with pytest.raises(AssertionError):
        p.apply_updates(auto)  # partial queue must never be applied


def test_delete_missing_filter_returns_false():
    table = WordTable()
    auto, _ = _build(["x/y", "x/+"], table, caps=(64, 64))
    p = AutoPatcher(auto, table.intern)
    assert not p.delete("x/z")
    assert not p.delete("x/y/z")
    assert not p.delete("q/#")
    assert not p.dirty
    assert p.delete("x/+")
    assert p.tombstones == 1


def test_delete_then_reinsert_same_filter_single_drain():
    """Both writes target the same automaton slot; the drain must
    dedup by index (last wins) — repeated indices in one .at[].set
    apply in implementation-defined order."""
    table = WordTable()
    auto, fids = _build(["a/b", "c"], table, caps=(64, 64))
    p = AutoPatcher(auto, table.intern)
    assert p.delete("a/b")
    p.insert("a/b", fids["a/b"])  # same drain as the delete
    out = p.apply_updates(auto)
    rev = {v: k for k, v in fids.items()}
    assert _match_set(out, table, rev, "a/b") == {"a/b"}
    assert _match_set(out, table, rev, "c") == {"c"}


def test_wide_mode_split_churn_parity():
    """Deep-chain (wide-layout) patching: inserts that diverge
    mid-chain SPLIT compressed edges; deletes tombstone; the patched
    automaton holds exact oracle parity and the hop bound grows so
    deepened walks still emit (never silently miss)."""
    from emqx_tpu.ops.csr import (attach_walk_tables,
                                  compress_automaton, device_view)
    from emqx_tpu.ops.match import walk_params

    rng = random.Random(3)
    vocab = [f"v{i}" for i in range(9)]

    def deep_filter():
        d = rng.randint(1, 12)
        ws = [rng.choice(vocab) for _ in range(d)]
        if rng.random() < 0.25:
            ws = ws[: rng.randint(1, d)] + ["#"]
        return "/".join(ws)

    base = sorted({deep_filter() for _ in range(200)})
    trie, table, fids = TrieOracle(), WordTable(), {}
    for f in base:
        trie.insert(f)
        fids[f] = len(fids)
        for w in f.split("/"):
            if w not in ("+", "#"):
                table.intern(w)
    raw = build_automaton(trie, fids, table, skip_hash=True,
                          state_capacity=1 << 13,
                          edge_capacity=1 << 13)
    auto, edges = compress_automaton(raw, force_mode="wide",
                                     state_capacity=1 << 13)
    auto = attach_walk_tables(auto, edges, edge_capacity=1 << 13)
    assert auto.wt_take > 1
    p = AutoPatcher(auto, table.intern)
    dev = device_view(auto)

    extra = sorted({deep_filter() for _ in range(250)} - set(base))
    for f in extra:
        trie.insert(f)
        fids[f] = len(fids)
        p.insert(f, fids[f])
    for f in rng.sample(base, 60):
        trie.delete(f)
        assert p.delete(f), f
    assert p.splits > 0  # the churn actually exercised splits
    dev = p.apply_updates(dev)

    topics = ["/".join(rng.choice(vocab)
                       for _ in range(rng.randint(1, 12)))
              for _ in range(400)]
    ids, n, sysm = encode_batch(table, topics, 16)
    wp = walk_params(auto, ids.shape[1])
    # the patcher's grown bound, exactly as the Router reads it
    wp["steps"] = int(p.hops_for_level[
        min(ids.shape[1], len(p.hops_for_level) - 1)])
    res = match_batch(dev, ids, n, sysm, k=8, **wp)
    out = np.asarray(res.ids)
    ovf = np.asarray(res.overflow)
    rev = {v: k for k, v in fids.items()}
    for i, t in enumerate(topics):
        assert not ovf[i], t
        got = sorted(rev[j] for j in out[i] if j >= 0)
        assert got == sorted(trie.match(t)), t


def test_wide_mode_stale_steps_flags_overflow():
    """A walk compiled with the PRE-patch hop bound must flag the
    deepened topics as overflow (exact host fallback) rather than
    silently missing their matches."""
    from emqx_tpu.ops.csr import (attach_walk_tables,
                                  compress_automaton, device_view)
    from emqx_tpu.ops.match import walk_params

    base = ["root/" + "/".join(["c"] * 9)]  # one long chain
    trie, table, fids = TrieOracle(), WordTable(), {}
    for f in base:
        trie.insert(f)
        fids[f] = len(fids)
        for w in f.split("/"):
            table.intern(w)
    raw = build_automaton(trie, fids, table, skip_hash=True,
                          state_capacity=1 << 10,
                          edge_capacity=1 << 10)
    auto, edges = compress_automaton(raw, force_mode="wide",
                                     state_capacity=1 << 10)
    auto = attach_walk_tables(auto, edges, edge_capacity=1 << 10)
    p = AutoPatcher(auto, table.intern)
    stale = walk_params(auto, 16)  # bound BEFORE the deepening patch
    # diverge mid-chain: splits lengthen the path beyond the bound
    for i, newf in enumerate(
            ["root/c/c/x1/y/z", "root/c/c/c/c/x2/y/z",
             "root/c/c/c/c/c/c/x3/y/z"]):
        trie.insert(newf)
        fids[newf] = len(fids)
        p.insert(newf, fids[newf])
    assert p.hops_grown
    dev = p.apply_updates(device_view(auto))
    topic = "root/c/c/c/c/x2/y/z"
    ids, n, sysm = encode_batch(table, [topic] * 4, 16)
    res_stale = match_batch(dev, ids, n, sysm, k=4, **stale)
    fresh = dict(stale)
    fresh["steps"] = int(p.hops_for_level[
        min(ids.shape[1], len(p.hops_for_level) - 1)])
    res_fresh = match_batch(dev, ids, n, sysm, k=4, **fresh)
    rev = {v: k for k, v in fids.items()}
    got_fresh = sorted(rev[j]
                       for j in np.asarray(res_fresh.ids)[0] if j >= 0)
    assert got_fresh == [topic], got_fresh
    if not bool(np.asarray(res_stale.overflow)[0]):
        # stale bound happened to suffice — then results must agree
        got = sorted(rev[j]
                     for j in np.asarray(res_stale.ids)[0] if j >= 0)
        assert got == got_fresh


def test_hop_fallbacks_trigger_compaction_signal():
    """ADVICE r5: host fallbacks observed while the hop bound is
    stale count toward needs_compaction alongside splits/tombstones
    — a patch-deepened automaton rebuilds long before 1024 splits."""
    table = WordTable()
    auto, fids = _build(["a/b"], table, caps=(64, 64))
    p = AutoPatcher(auto, table.intern)
    p.note_hop_fallbacks(5000)
    assert not p.needs_compaction(10)  # hops never grew: not counted
    p.insert("a/b/c/d/e", 1)  # deepens the walk -> hops_grown
    assert p.hops_grown
    p.note_hop_fallbacks(500)
    assert not p.needs_compaction(10)
    p.note_hop_fallbacks(600)  # 1100 > max(1024, live)
    assert p.needs_compaction(10)


def test_router_note_match_fallbacks_schedules_rebuild():
    import time

    from emqx_tpu.router import MatcherConfig, Router

    # stale-hop fallback accounting lives on the patch-in-place
    # path's mirror — pin it with delta off (delta mode never splits,
    # so the stale-hop regime cannot arise there)
    r = Router(MatcherConfig(device_min_filters=0, delta=False),
               node="n")
    r.add_route("a/b")
    r.match_filters(["a/b"])  # first flatten + live patcher
    rebuilds = r.stats()["rebuilds"]
    # force the stale-hop regime, then report a fallback storm
    r._patcher.hops_grown = True
    r.note_match_fallbacks(2000)
    for _ in range(200):  # background compaction thread
        if r.stats()["rebuilds"] > rebuilds:
            break
        time.sleep(0.05)
    assert r.stats()["rebuilds"] > rebuilds
    # the fresh patcher starts clean
    assert r._patcher.hop_fallbacks == 0


def test_the_patchers_hash_is_the_builders_bit_for_bit():
    """PR 42: ``AutoPatcher._buckets`` mixes one edge key in Python's
    integers; the builder (numpy) and the walk (jnp) use
    ``csr.hash_mix``. Same buckets for every key, negative ids and
    the largest included."""
    from emqx_tpu.ops.csr import hash_mix

    trie, table = TrieOracle(), WordTable()
    fids = {}
    for i, f in enumerate(["a/b", "a/+/c", "d/#"]):
        trie.insert(f)
        fids[f] = i
    p = AutoPatcher(build_automaton(trie, fids, table), table.intern)
    rng = random.Random(7)
    keys = [(0, 0), (-1, -1), (2**31 - 1, 2**31 - 1), (-2**31, 5)] + [
        (rng.randrange(-2**31, 2**31), rng.randrange(-2**31, 2**31))
        for _ in range(2000)]
    for seed in (np.uint32(p.seed), np.uint32(0), np.uint32(0xFFFFFFFF)):
        p.seed = int(seed)
        for nb in (4, 256, 1 << 21):
            p.nb = nb
            for state, word in keys:
                with np.errstate(over="ignore"):
                    h1, h2 = hash_mix(np.array(state, np.int32),
                                      np.array(word, np.int32), seed)
                mask = np.uint32(nb - 1)
                assert p._buckets(state, word) == (int(h1 & mask),
                                                   int(h2 & mask))
