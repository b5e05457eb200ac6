"""Publish match cache (ops/match_cache.py + router integration):
exact oracle parity through cache hits, misses, epoch invalidation
under route churn, overflow bypass, the cache-off legacy path, and
the sharded (mesh) cache on the 1×1 fast path."""

import random

import numpy as np
import pytest

from emqx_tpu.broker import Broker
from emqx_tpu.oracle import TrieOracle
from emqx_tpu.router import MatcherConfig, Router
from emqx_tpu.types import Message


def _mk(**kw):
    kw.setdefault("device_min_filters", 0)
    return Router(MatcherConfig(**kw), node="node1")


class Q:
    def __init__(self, client_id="c"):
        self.client_id = client_id
        self.inbox = []

    def deliver(self, topic, msg):
        self.inbox.append((topic, msg))


# -- MatchCache unit ------------------------------------------------------


def _insert(c, p, rows, ovf):
    """Store walk results for ``p``'s misses as the dispatch's walk
    program does (``walk_insert``: ``flag_rows`` → ``insert_rows``
    through ``insert_through``); returns the ``flag | row`` values the
    merge takes."""
    from emqx_tpu.ops.match_cache import flag_rows, insert_rows

    vals = flag_rows(np.asarray(rows), np.asarray(ovf), np.asarray(ovf))
    idx = np.full((len(rows),), c.slots, np.int32)  # OOB pad -> drop
    idx[:len(p.miss_slots)] = p.miss_slots
    return c.insert_through(
        p, lambda table: (insert_rows(table, idx, vals), vals))


def _merge(c, b_pad, p, n_uniq, miss_vals=None):
    """The batch's merge over its one buffer (``batch_buffer`` →
    ``merge_batch``)."""
    enc = None
    if miss_vals is not None:
        mb = miss_vals.shape[0]
        enc = (np.zeros((mb, 2), np.int32), np.zeros(mb, np.int32),
               np.zeros(mb, bool))
    lay, buf = c.batch_buffer(b_pad, p, enc, n_uniq, 0)
    return c.merge_batch(b_pad, p, lay, buf, miss_vals)


def test_cache_unit_probe_insert_merge_roundtrip():
    from emqx_tpu.ops.match_cache import MatchCache

    c = MatchCache(16, 4)
    key = ("e", 1)
    topics = ["a", "b", "c"]
    p = c.probe(topics, key)
    assert p.hit_pos == [] and p.miss_topics == topics
    rows = np.array([[1, -1, -1, -1],
                     [2, 3, -1, -1],
                     [4, 5, 6, -1]], np.int32)
    ovf = np.zeros(3, bool)
    _insert(c, p, rows, ovf)
    assert c.inserts == 3
    # second probe: all hits, merged rows identical
    p2 = c.probe(["b", "a", "c", "d"], key)
    assert p2.hit_pos == [0, 1, 2] and p2.miss_topics == ["d"]
    miss_vals = _insert(c, p2, np.full((1, 4), -1, np.int32),
                        np.zeros(1, bool))
    merged, ovf2, _ = _merge(c, 8, p2, 4, miss_vals)
    merged = np.asarray(merged)
    assert merged.shape == (8, 4)
    assert merged[0].tolist() == [2, 3, -1, -1]
    assert merged[1].tolist() == [1, -1, -1, -1]
    assert merged[2].tolist() == [4, 5, 6, -1]
    assert not np.asarray(ovf2)[:4].any()
    # epoch bump: everything is a (stale-counted) miss again
    p3 = c.probe(["a", "b"], ("e", 2))
    assert p3.miss_topics == ["a", "b"]
    assert c.stale == 2


def test_cache_unit_overflow_rows_store_invalid_markers():
    from emqx_tpu.ops.match_cache import MatchCache

    c = MatchCache(8, 4)
    key = 7
    p = c.probe(["t"], key)
    vals = _insert(c, p, np.array([[9, 9, 9, 9]], np.int32),
                   np.array([True]))
    # the walk's own batch hands its truncated row on, flagged
    merged, ovf, _ = _merge(c, 4, p, 1, vals)
    assert np.asarray(ovf)[0] and (np.asarray(merged)[0] == 9).all()
    p2 = c.probe(["t"], key)
    assert p2.hit_pos == [0]  # found — but flagged, never served
    merged, ovf, _ = _merge(c, 4, p2, 1)
    assert np.asarray(ovf)[0]            # caller must host-fallback
    assert (np.asarray(merged)[0] == -1).all()  # no truncated ids


def test_the_buffers_sections_do_not_depend_on_each_other():
    """The walk's sections lie from the buffer's front at offsets of
    (MB, L) alone, the merge's end at its last word at offsets of (MB,
    HB) alone: the walk is one program a (miss bucket, depth) whatever
    the hits, the merge one a (batch, hit, miss) triple whatever the
    depth."""
    from emqx_tpu.ops.match_cache import (BATCH_BUF_FLOOR, BatchLayout,
                                          MatchCache)

    c = MatchCache(64, 4)
    p = c.probe([f"t{i}" for i in range(20)], 0)
    _insert(c, p, np.zeros((32, 4), np.int32), np.zeros(32, bool))
    p = c.probe([f"t{i}" for i in range(10, 30)], 0)
    assert len(p.hit_pos) == len(p.miss_pos) == 10
    got = {}
    for levels in (2, 5):
        ids = np.arange(16 * levels, dtype=np.int32).reshape(16, levels)
        enc = (ids, np.full(16, levels, np.int32), np.ones(16, bool))
        lay, buf = c.batch_buffer(32, p, enc, 20, 0)
        assert lay == BatchLayout(levels, 16, 16, BATCH_BUF_FLOOR)
        w, n, sysm, slots = lay._replace(hit=0).step_sections(buf)
        assert (w == ids).all() and (n == levels).all() and sysm.all()
        assert slots[:10].tolist() == p.miss_slots
        assert (slots[10:] == c.slots).all()
        got[levels] = [np.asarray(x).tolist() for x in
                       lay._replace(levels=0).merge_sections(buf)]
    assert got[2] == got[5]
    miss_pos, hit_slots, hit_pos, n_uniq = got[2]
    assert miss_pos == p.miss_pos + [32] * 6
    assert hit_slots[:10] == p.hit_slots and hit_pos == p.hit_pos + [32] * 6
    assert n_uniq == 20
    # a batch past the capacity doubles it, and it stays doubled
    big = (np.zeros((2048, 16), np.int32), np.zeros(2048, np.int32),
           np.zeros(2048, bool))
    lay, _buf = c.batch_buffer(2048, c.probe(["x"], 0), big, 1, 0)
    assert lay.size == 2 * BATCH_BUF_FLOOR
    lay, _buf = c.batch_buffer(32, p, None, 20, lay.size)
    assert lay == BatchLayout(0, 0, 16, 2 * BATCH_BUF_FLOOR)


@pytest.mark.parametrize("n_miss", [1, 10, 32])
def test_one_chip_lays_hits_and_misses_at_the_batchs_bucket(n_miss):
    """``rows`` = the batch's bucket: the layout is (L, B, B) however
    the batch splits (and (0, 0, B) where it fully hit); the misses
    come with ONE pad topic behind them, whose row fills their section,
    bit for bit what encoding the pad B - n times would lay."""
    from emqx_tpu.ops.match_cache import (BATCH_BUF_FLOOR, BatchLayout,
                                          MatchCache)

    B, L = 32, 3
    c = MatchCache(64, 4)
    topics = [f"t{i}" for i in range(B)]
    p = c.probe(topics[n_miss:], 0)
    _insert(c, p, np.zeros((B, 4), np.int32), np.zeros(B, bool))
    p = c.probe(topics, 0)
    assert len(p.miss_pos) == n_miss and len(p.hit_pos) == B - n_miss
    rng = np.random.default_rng(n_miss)
    real = (rng.integers(1, 99, (n_miss, L)).astype(np.int32),
            np.full(n_miss, L, np.int32), rng.random(n_miss) < 0.5)
    pad = (np.array([[7, 8, -1]], np.int32), np.array([2], np.int32),
           np.array([False]))
    enc = tuple(np.concatenate([r, q]) for r, q in zip(real, pad))
    lay, buf = c.batch_buffer(B, p, enc, B, 0, rows=B)
    assert lay == BatchLayout(L, B, B, BATCH_BUF_FLOOR)
    full = tuple(np.concatenate([r] + [q] * (B - n_miss))
                 for r, q in zip(real, pad))
    lay2, buf2 = c.batch_buffer(B, p, full, B, 0, rows=B)
    assert lay2 == lay and (buf2 == buf).all()
    w, n, sysm, slots = lay.step_sections(buf)
    assert (w == full[0]).all() and (n == full[1]).all()
    assert (np.asarray(sysm) == full[2]).all()
    assert slots[:n_miss].tolist() == p.miss_slots
    assert (slots[n_miss:] == c.slots).all()
    miss_pos, hit_slots, hit_pos, n_uniq = lay.merge_sections(buf)
    assert miss_pos.tolist() == p.miss_pos + [B] * (B - n_miss)
    assert hit_pos.tolist() == p.hit_pos + [B] * n_miss
    assert hit_slots[:B - n_miss].tolist() == p.hit_slots and n_uniq == B
    # the same topics again hit whole: no walk section, the bucket alone
    lay, buf = c.batch_buffer(B, c.probe(topics[n_miss:], 0), None,
                              B - n_miss, 0, rows=B)
    assert lay == BatchLayout(0, 0, B, BATCH_BUF_FLOOR)
    assert lay.merge_sections(buf)[3] == B - n_miss


# -- single-device router path --------------------------------------------


def _oracle_for(filters):
    t = TrieOracle()
    for f in filters:
        t.insert(f)
    return t


def _assert_parity(r, oracle, topics):
    got = r.match_filters(topics)
    for t, row in zip(topics, got):
        assert sorted(row) == sorted(oracle.match(t)), t


def test_router_cached_parity_and_hit_counters():
    r = _mk(match_cache_slots=256)
    filters = ["s/+/a", "s/1/a", "s/#", "x/y", "+/y"]
    for f in filters:
        r.add_route(f)
    oracle = _oracle_for(filters)
    topics = ["s/1/a", "s/2/a", "x/y", "nope", "s/1/a", "x/y"]
    _assert_parity(r, oracle, topics)
    c = r._match_cache_obj
    assert c is not None and c.inserts > 0
    before = c.hits
    _assert_parity(r, oracle, topics)  # identical batch: pure hits
    assert c.hits > before
    assert c.stats()["hit_rate"] > 0


def test_epoch_invalidation_on_add_and_delete():
    r = _mk(match_cache_slots=64)
    r.add_route("a/b")
    oracle = _oracle_for(["a/b"])
    _assert_parity(r, oracle, ["a/b", "a/c"])
    # a new wildcard must appear in the next match (no stale hit)
    r.add_route("a/+")
    oracle.insert("a/+")
    _assert_parity(r, oracle, ["a/b", "a/c"])
    # a delete must disappear (no ghost delivery)
    r.delete_route("a/b")
    oracle.delete("a/b")
    _assert_parity(r, oracle, ["a/b", "a/c"])
    assert r._match_cache_obj.stale > 0


def test_churn_interleaved_with_cached_matches_stays_exact():
    """The satellite churn bar: interleave add/delete with cached
    matches and assert exact oracle parity after EVERY epoch bump —
    no stale delivery, no missed delivery."""
    rng = random.Random(7)
    r = _mk(match_cache_slots=512)
    oracle = TrieOracle()
    words = ["a", "b", "c", "d"]
    live = []
    for f in ["a/#", "b/+", "a/b/c"]:
        r.add_route(f)
        oracle.insert(f)
        live.append(f)
    topics = ["/".join(rng.choice(words)
                       for _ in range(rng.randint(1, 4)))
              for _ in range(24)]
    for step in range(30):
        if live and rng.random() < 0.4:
            f = live.pop(rng.randrange(len(live)))
            r.delete_route(f)
            oracle.delete(f)
        else:
            depth = rng.randint(1, 4)
            ws = [rng.choice(words + ["+"]) for _ in range(depth)]
            if rng.random() < 0.2:
                ws.append("#")
            f = "/".join(ws)
            if f not in live:
                r.add_route(f)
                oracle.insert(f)
                live.append(f)
        batch = [rng.choice(topics) for _ in range(12)]  # hot repeats
        _assert_parity(r, oracle, batch)
    st = r._match_cache_obj.stats()
    assert st["hit"] > 0 and st["stale"] > 0


def test_overflow_topics_fall_back_exact_through_cache():
    # max_matches=2 forces m-overflow for a topic matching 3 filters
    r = _mk(match_cache_slots=64, max_matches=2, active_k=2)
    filters = ["t/#", "t/+", "t/x", "other"]
    for f in filters:
        r.add_route(f)
    oracle = _oracle_for(filters)
    for _ in range(3):  # miss, then negative-cached hits
        _assert_parity(r, oracle, ["t/x", "t/x", "other"])
    assert r._match_cache_obj.hits > 0


def test_cache_off_restores_legacy_dispatch_bytes():
    """match_cache=False must run the pre-cache dispatch
    byte-for-byte: raw (pack_ids=False) walk output, no cache
    object ever built."""
    from emqx_tpu.ops.match import depth_bucket, match_batch

    filters = ["s/+/a", "s/1/a", "s/#", "x/y"]
    topics = ["s/1/a", "x/y", "s/1/a", "zz"]
    r = _mk(match_cache=False)
    for f in filters:
        r.add_route(f)
    ids_dev, ovf_dev, id_map, epoch = r.match_dispatch(topics)
    assert r._match_cache_obj is None
    # replay the legacy dispatch by hand against the same snapshot
    auto, id_map2, epoch2 = r.automaton()
    assert epoch2 == epoch
    cfg = r.config
    bucket = cfg.min_batch
    while bucket < len(topics):
        bucket *= 2
    padded = list(topics) + ["\x00/pad"] * (bucket - len(topics))
    ids, n, sysm = r._encode(padded, cfg.max_levels)
    ids, n = depth_bucket(ids, n)
    res = match_batch(auto, ids, n, sysm, k=r.effective_k(),
                      m=cfg.max_matches, pack_ids=False,
                      **r._walk_kw(ids.shape[1]))
    # the dispatch hands its ids on with the pad rows blanked
    from emqx_tpu.ops.pack import mask_pad_rows

    assert np.array_equal(
        np.asarray(ids_dev),
        np.asarray(mask_pad_rows(res.ids, np.int32(len(topics)))))
    assert np.array_equal(np.asarray(ovf_dev),
                          np.asarray(res.overflow))


def test_broker_publish_batch_hits_cache_across_batches():
    b = Broker(config=MatcherConfig(device_min_filters=0,
                                    match_cache_slots=128))
    s1, s2 = Q("c1"), Q("c2")
    b.subscribe(s1, "a/+")
    b.subscribe(s2, "a/b")
    msgs = [Message(topic=t) for t in ["a/b", "a/c", "a/b"]]
    assert b.publish_batch(msgs) == [2, 1, 2]
    c = b.router._match_cache_obj
    hits_before = c.hits
    assert b.publish_batch(msgs) == [2, 1, 2]  # all repeat topics
    assert c.hits > hits_before
    assert len(s1.inbox) == 6 and len(s2.inbox) == 4
    # churn between batches: parity must survive the epoch bump
    s3 = Q("c3")
    b.subscribe(s3, "a/#")
    assert b.publish_batch(msgs) == [3, 2, 3]


def test_drain_cache_stats_feeds_metrics():
    from emqx_tpu.metrics import Metrics

    r = _mk(match_cache_slots=64)
    r.add_route("m/1")
    r.match_filters(["m/1", "m/1"])
    r.match_filters(["m/1"])
    m = Metrics()
    drained = r.drain_cache_stats()
    assert drained["miss"] >= 1 and drained["hit"] >= 1
    m.fold_cache_stats(drained)
    assert m.val("cache.match.hit") == drained["hit"]
    assert m.val("cache.match.miss") == drained["miss"]
    assert m.val("cache.match.insert") == drained["insert"]
    # second drain: deltas only
    assert r.drain_cache_stats()["hit"] == 0
    assert r.cache_entries() >= 1


# -- sharded (mesh) cache --------------------------------------------------


def test_mesh_cached_publish_parity_1x1():
    from emqx_tpu.parallel.mesh import make_mesh

    b = Broker(router=Router(
        MatcherConfig(mesh=make_mesh(1, 1), fanout_d=8,
                      match_cache_slots=128), node="local"))
    s1, s2 = Q("c1"), Q("c2")
    b.subscribe(s1, "a/+")
    b.subscribe(s2, "a/b")
    msgs = [Message(topic="a/b"), Message(topic="a/c"),
            Message(topic="a/b")]
    assert b.publish_batch(msgs) == [2, 1, 2]
    cache = b.router._sharded_cache_obj
    assert cache is not None and cache.inserts > 0
    hits = cache.hits
    assert b.publish_batch(msgs) == [2, 1, 2]
    assert cache.hits > hits
    # epoch bump via subscribe: cached rows must not ghost-deliver
    s3 = Q("c3")
    b.subscribe(s3, "a/#")
    assert b.publish_batch(msgs) == [3, 2, 3]
    b.unsubscribe(s3, "a/#")
    assert b.publish_batch(msgs) == [2, 1, 2]


def test_mesh_big_filters_bypass_cache():
    from emqx_tpu.parallel.mesh import make_mesh

    # fanout_d=2 makes a 4-member filter "big" (bitmap path): the
    # sharded cache must refuse (a union row is unboundedly wide) and
    # the legacy collective path must stay exact
    b = Broker(router=Router(
        MatcherConfig(mesh=make_mesh(1, 1), fanout_d=2,
                      match_cache_slots=128), node="local"))
    subs = [Q(f"c{i}") for i in range(4)]
    for s in subs:
        b.subscribe(s, "big/t")
    assert b.publish(Message(topic="big/t")) == 4
    assert b.publish(Message(topic="big/t")) == 4
    cache = b.router._sharded_cache_obj
    assert cache is None or cache.hits == 0
