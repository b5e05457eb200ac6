"""One chip's match dispatch as the event loop pays for it
(``Router._match_dispatch_cached`` + ``Broker._begin_device``): a
batch leaves as ONE host→device transfer and TWO compiled programs
(the match: ``walk_merge``, or the merge alone where every topic hit;
and ``pack_chip``), with no numpy argument and no eager operation, and
hands ``Broker._fetch_device`` bit for bit what the calls apart hand
it: walk → (the delta's two-probe) → ``flag_rows`` → insert → merge →
``mask_pad_rows`` → ``pack_matches`` → ``expand_packed`` →
``bundle_i32``. The one-chip twin of ``tests/test_mesh_dispatch.py``;
the chip's runs are ``benchmark/``'s seven one-chip cells.

The calls apart are written here (``_apart``) over a second broker
that is given the same subscriptions and the same batches, so both
match caches hold the same rows in the same slots — an overflowed row
is the marker on both sides when it is hit again."""

import importlib.util
import json
import os

import numpy as np
import pytest

from emqx_tpu import faults
from emqx_tpu import topic as topic_mod
from emqx_tpu.broker import Broker
from emqx_tpu.metrics import ALL_METRICS, DISPATCH_METRICS, Metrics
from emqx_tpu.ops.delta import probe_packed
from emqx_tpu.ops.fanout import expand_packed
from emqx_tpu.ops.match import depth_bucket, match_batch
from emqx_tpu.ops.match_cache import flag_rows, insert_rows
from emqx_tpu.ops.pack import bundle_i32, mask_pad_rows, pack_matches
from emqx_tpu.overload import DeviceBreaker
from emqx_tpu.router import MatcherConfig, Router, topic_partition
from emqx_tpu.telemetry import Telemetry, TelemetryConfig
from emqx_tpu.types import Message
from emqx_tpu.utils.batch import dedup_topics
from helpers import MOVF_FILTERS, CounterTel, Inbox, LoopCost as _Loop

M = 8       # max_matches: small, so a test can overflow


def _broker(local=True, **kw):
    cfg = dict(max_matches=M, active_k=16, match_cache_slots=2048,
               device_min_filters=0)
    cfg.update(kw)
    b = Broker(router=Router(MatcherConfig(**cfg), node="local"))
    b.router.telemetry = CounterTel()
    b.filters = {}

    def sub(flt, n=1):
        for i in range(n):
            if local:
                b.subscribe(Inbox(f"{flt}#{i}"), flt)
            else:
                b.router.add_route(flt, "elsewhere")
        b.filters[flt] = b.filters.get(flt, 0) + n

    def unsub(flt):
        for q in b.subscribers(flt):
            b.unsubscribe(q, flt)
        del b.filters[flt]

    b.sub, b.unsub = sub, unsub
    for i in range(96):
        sub(f"t/{i}/+")
    sub("t/+/x")
    sub("#")
    sub("+/pad")            # the pad topic's own phantom match
    sub("$SYS/#")
    for f in MOVF_FILTERS:
        sub(f)
    return b


def _want(b, topic):
    """Deliveries by ``topic.match`` alone."""
    return sum(n for f, n in b.filters.items() if topic_mod.match(topic, f))


def _counters(b):
    return {k: b.router.telemetry.metrics.val(k) for k in DISPATCH_METRICS}


def _np(*xs):
    return [None if x is None else np.asarray(x) for x in xs]


NAMES = ("ids", "ovf", "m_ptr", "ids_packed", "f_ptr", "subs_packed",
         "src_packed", "bundle")


def _same(got, want, what):
    for name, a, w in zip(NAMES, got, want):
        assert (a is None) == (w is None), (what, name)
        if a is not None:
            assert a.dtype == w.dtype and a.shape == w.shape, (what, name)
            assert (a == w).all(), (what, name)


def _apart(b, topics, pm, pq):
    """The dispatch with its calls apart, as it was before they were
    fused: every numpy argument a transfer, every step a program or an
    eager operation of its own, the misses walked at a bucket of their
    own (the one program walks them at the batch's). The merge is
    plain numpy."""
    r = b.router
    cfg = r.config
    cache = r._match_cache()
    uniq, _inv = dedup_topics(topics)
    k_boost = r._k_boost
    part_snap = tuple(r._part_revs)
    (auto, id_map, epoch, rev), dsnap = r._snapshot_pair()
    key = (epoch, rev, k_boost)
    keys = [key + (part_snap[topic_partition(t, cfg.cache_partitions)],)
            for t in uniq]
    bucket = r.pad_topics(len(uniq))
    probe = cache.probe(uniq, key, keys)
    out = np.full((bucket, M), -1, np.int32)
    ovf = np.zeros((bucket,), bool)
    if probe.miss_topics:
        n_miss = len(probe.miss_topics)
        mb = r.pad_topics(n_miss)
        ids, n, sysm = r._encode(
            list(probe.miss_topics) + ["\x00/pad"] * (mb - n_miss),
            cfg.max_levels)
        ids, n = depth_bucket(ids, n)
        res = match_batch(auto, ids, n, sysm, k=r.effective_k(), m=M,
                          pack_ids=True, **r._walk_kw(ids.shape[1]))
        rows, m_ovf = res.ids, res.overflow
        if dsnap is not None:
            rows, m_ovf = probe_packed(
                dsnap.auto, dsnap.mask, ids, n, sysm, rows, m_ovf, m=M,
                k=dsnap.k, steps=dsnap.steps_for(ids.shape[1]))
        vals = flag_rows(rows, m_ovf, m_ovf)
        idx = np.full((mb,), cache.slots, np.int32)
        idx[:n_miss] = probe.miss_slots
        cache.insert_through(
            probe, lambda table: (insert_rows(table, idx, vals), None))
        vals = np.asarray(vals)[:n_miss]
        out[probe.miss_pos] = vals[:, 1:]
        ovf[probe.miss_pos] = vals[:, 0] != 1
    if probe.hit_pos:
        hv = np.asarray(probe.table)[probe.hit_slots]
        out[probe.hit_pos] = hv[:, 1:]
        ovf[probe.hit_pos] = hv[:, 0] != 1
    ids_dev = mask_pad_rows(out, np.int32(len(uniq)))
    st = b.helper.state(epoch, id_map)
    m_ptr, ids_packed = pack_matches(ids_dev, pm=pm)
    fan = []
    if st is not None and st.fan is not None:
        fan = list(expand_packed(st.fan, m_ptr, ids_packed, q=pq)[:3])
    bundle = None
    if st is None or st.bm is None:
        bundle = bundle_i32(m_ptr, ids_packed, ovf, *fan)
    return _np(ids_dev, ovf, m_ptr, ids_packed, *(fan or [None] * 3),
               bundle)


def _fused(b, topics):
    """One batch through the broker; its device arrays as the fetch
    will find them, then its deliveries."""
    before = _counters(b)
    pb = b.publish_begin([Message(topic=t) for t in topics])
    after = _counters(b)
    got = _np(pb.ids_dev, pb.ovf_dev, pb.m_ptr_d, pb.ids_packed_d,
              pb.f_ptr_d, pb.subs_packed_d, pb.src_packed_d, pb.bundle_d)
    budgets = (pb.pm, pb.pq if pb.f_ptr_d is not None else 0)
    b.publish_fetch(pb)
    assert pb.bundle_d is None
    delivered = b.publish_finish(pb)
    return got, delivered, budgets, {k: after[k] - before[k]
                                     for k in after}


def _delta_live(b):
    """Adds and tombstones after the first flatten: a side automaton
    and a tombstone mask, probed beside the main walk."""
    b.sub("t/+/y")
    b.sub("t/7/#")
    b.sub("late/+/z")
    b.unsub("t/3/+")
    b.unsub("t/+/x")


def _adds_only(b):
    b.sub("t/+/+", 3)


def _tombstones_only(b):
    b.unsub("t/3/+")
    b.unsub("t/+/x")


T = [f"t/{i}/x" for i in range(40)]
CASES = {
    # a case: (broker options, batches sent before, a change of the
    # routes after them, the batch); the batch runs twice, so the
    # second time every topic hits
    "misses_only": ({}, [], None, T[:24]),
    "hits_only": ({}, [T[:24]], None, T[:24]),
    "hits_and_misses": ({}, [T[:10]], None, T[:20]),
    "duplicates_in_the_batch": ({}, [T[:4]], None,
                                T[:9] + T[2:7] + ["$SYS/a", "q/pad"]),
    "one_topic": ({}, [], None, ["t/0/x"]),
    "one_topic_of_many": ({}, [], None, ["t/5/x"] * 7),
    "pad_heavy_17": ({}, [T[:3]], None, T[:17]),
    "unique_1024": ({}, [[f"t/{i}/u" for i in range(300, 500)]], None,
                    [f"t/{i}/u" for i in range(1024)]),
    "deeper_than_the_hits": ({}, [T[:10]], None,
                             T[:10] + ["t/1/x/y/z", "t/2/q"]),
    "delta_adds_and_tombstones": ({}, [T[:10]], _delta_live,
                                  T[:20] + ["t/7/y", "late/0/z", "t/3/y"]),
    "delta_all_miss_then_hit": ({}, [["warm/up"]], _delta_live, T[:24]),
    "match_overflow_hit_again": ({}, [], None,
                                 ["mo/a/b/c"] + T[:23]),
    "no_local_subscriber": ({"local": False}, [T[:6]], None, T[:12]),
    "delta_adds_only_whole_epoch_keys": (
        {"cache_partitions": 1}, [T[:10]], _adds_only, T[:20]),
    "delta_tombstones_only": ({}, [T[:10]], _tombstones_only, T[:20]),
}


@pytest.mark.parametrize("case", list(CASES))
def test_fused_equals_the_calls_apart_bit_for_bit(case):
    kw, before, change, batch = CASES[case]
    b, ref = _broker(**kw), _broker(**kw)
    for topics in before:
        b.publish_batch([Message(topic=t) for t in topics])
        _apart(ref, topics, 64, 64)
    if change is not None:
        change(b)
        change(ref)
    uniq, _inv = dedup_topics(batch)
    n_hit = len({t for ts in before for t in ts} & set(uniq)) \
        if change is None else 0
    delta = b.router._snapshot_pair()[1]
    assert (delta is not None) == (change is not None)
    if delta is not None:
        # a live delta is one shape to the walk's program: both halves
        # are there, and hold something or nothing
        assert delta.auto is not None and delta.mask is not None
        assert (delta.n_pending > 0) == (change != _tombstones_only)
        assert bool(np.asarray(delta.mask).any()) == \
            (change != _adds_only)
    want_delivered = [_want(b, t) if kw.get("local", True) else 0
                      for t in batch]
    for again in (False, True):
        probes = b.router._delta_probes
        got, delivered, (pm, pq), moved = _fused(b, batch)
        want = _apart(ref, batch, pm, pq)
        walked = 0 if again else len(uniq) - n_hit
        assert moved == {"dispatch.batches": 1, "dispatch.fused": 1,
                         "dispatch.topics": len(uniq),
                         "dispatch.walk.topics": walked,
                         "dispatch.programs": 2}, again
        assert b.router._delta_probes - probes == (
            1 if delta is not None and walked else 0)
        _same(got, want, f"{case}: again={again}")
        assert want[7] is not None and delivered == want_delivered
        ids, ovf = want[0], want[1]
        assert (ids[len(uniq):] == -1).all()
        if case == "match_overflow_hit_again":
            assert ovf[0] and not ovf[1:].any()
            # walked: the truncated row, flagged; hit again: the marker
            assert (ids[0] == -1).all() == again
        else:
            assert not ovf.any()
            assert (ids[:len(uniq)] >= 0).any(axis=1).all()
        if case == "no_local_subscriber":
            _auto, id_map, epoch, _rev = b.router.snapshot_cached()
            assert got[4] is None
            assert b.helper.state(epoch, id_map) is None
    st = b.router._match_cache().stats()
    assert st == ref.router._match_cache().stats() and st["hit"] > 0


# -- who keeps the calls apart ------------------------------------------------


def test_a_bitmap_filter_keeps_the_calls_apart():
    """Big-filter bitmaps live: the dispatch is still one transfer and
    one program, but the packers run apart (the bitmap kernels need
    the dense ids: five device functions a batch), the fetch lays the
    bundle, and ``dispatch.fused`` stands still."""
    kw = dict(fanout_threshold=4)
    b, ref = _broker(**kw), _broker(**kw)
    for x in (b, ref):
        x.sub("t/+/+", 6)
    for _ in range(2):
        pb = b.publish_begin([Message(topic=t) for t in T[:20]])
        assert pb.st.bm is not None and pb.bundle_d is None
        assert pb.sel_d is not None
        got = _np(pb.ids_dev, pb.ovf_dev, pb.m_ptr_d, pb.ids_packed_d,
                  pb.f_ptr_d, pb.subs_packed_d, pb.src_packed_d, None)
        _same(got, _apart(ref, T[:20], pb.pm, pb.pq), "bitmap")
        b.publish_fetch(pb)
        assert b.publish_finish(pb) == [_want(b, t) for t in T[:20]]
    assert _counters(b) == {
        "dispatch.batches": 2, "dispatch.fused": 0,
        "dispatch.topics": 40, "dispatch.walk.topics": 20,
        # the dispatch's one launch; the packers apart count none
        "dispatch.programs": 2}


def test_the_cache_off_dispatch_keeps_the_calls_apart():
    b = _broker(match_cache=False)
    for _ in range(2):
        pb = b.publish_begin([Message(topic=t) for t in T[:20]])
        assert pb.bundle_d is None
        assert (np.asarray(pb.ids_dev)[20:] == -1).all()
        b.publish_fetch(pb)
        assert b.publish_finish(pb) == [_want(b, t) for t in T[:20]]
    # ``dispatch.programs`` counts the cache-split path's launches alone
    assert _counters(b) == {
        "dispatch.batches": 2, "dispatch.fused": 0,
        "dispatch.topics": 40, "dispatch.walk.topics": 40,
        "dispatch.programs": 0}


# -- the fetch ----------------------------------------------------------------


def test_a_budget_overflow_repacks_with_the_packers_apart(monkeypatch):
    b, ref = _broker(), _broker()
    b.publish_batch([Message(topic=t) for t in T[:3]])
    _apart(ref, T[:3], 64, 64)
    b._pack_budgets[32] = [8, 8, 1]     # 24 topics match ~3 filters each
    loop = _Loop(monkeypatch)
    pb = b.publish_begin([Message(topic=t) for t in T[:24]])
    assert (pb.pm, pb.pq) == (8, 8)
    got = _np(pb.ids_dev, pb.ovf_dev, pb.m_ptr_d, pb.ids_packed_d,
              pb.f_ptr_d, pb.subs_packed_d, pb.src_packed_d, pb.bundle_d)
    monkeypatch.undo()
    want = _apart(ref, T[:24], 8, 8)
    _same(got, want, "truncated packs")
    n_matches = want[2][-1]
    assert n_matches > 8 == want[4][-1]   # the 8 packed, expanded
    loop = _Loop(monkeypatch)
    b.publish_fetch(pb)
    # matches re-packed and expanded, then the expansion grown too
    assert loop.programs == ["pack_matches", "expand_packed", "bundle_i32",
                             "expand_packed", "bundle_i32"]
    assert pb.bundle_d is None
    assert b.publish_finish(pb) == [_want(b, t) for t in T[:24]]
    assert b._pack_budgets[32][0] >= n_matches
    assert b._pack_budgets[32][1] >= sum(_want(b, t) for t in T[:24])
    # the grown budgets are the next batches': one packer again (the
    # first of them traces it, through the counting wrappers)
    for programs in (None, ["_mesh_merge_jit", "pack_chip"]):
        loop.reset()
        pb = b.publish_begin([Message(topic=t) for t in T[:24]])
        assert (pb.pm, pb.pq) == tuple(b._pack_budgets[32][:2])
        b.publish_fetch(pb)
        assert programs is None or loop.programs == programs
        assert b.publish_finish(pb) == [_want(b, t) for t in T[:24]]


def test_a_breaker_fallback_drops_the_laid_bundle():
    b = _broker()
    b.breaker = DeviceBreaker(Metrics(), failures=5)
    faults.clear()
    faults.set_master(True)
    try:
        with faults.injected("device.fetch", times=1):
            pb = b.publish_begin([Message(topic=t) for t in T[:20]])
            assert pb.bundle_d is not None
            b.publish_fetch(pb)
        assert pb.bundle_d is None and pb.host_only
        assert pb.host_topics == T[:20]
        assert b.publish_finish(pb) == [_want(b, t) for t in T[:20]]
    finally:
        faults.clear()
    assert b.breaker.failures == 1
    assert b.publish_batch([Message(topic=t) for t in T[:20]]) == [
        _want(b, t) for t in T[:20]]


# -- what a warm batch costs the event loop -----------------------------------


@pytest.mark.parametrize("delta", [False, True], ids=["plain", "delta"])
def test_a_warm_batch_is_one_transfer_and_two_programs(
        delta, monkeypatch):
    b = _broker()

    def batch(lo, hi, tag):
        return [Message(topic=f"t/{i}/{tag}") for i in range(lo, hi)]

    b.publish_batch(batch(0, 1, "first"))   # the first flatten
    if delta:
        _delta_live(b)
    # warm both programs the counted batches use: the match at
    # (bucket 32, depth 3) and the bucket's walk-free merge; a batch
    # that splits another way (12 hits, 12 misses) is no new shape
    b.publish_batch(batch(0, 24, "w"))
    b.publish_batch(batch(0, 24, "w"))
    loop = _Loop(monkeypatch)
    before = _counters(b)
    for what, msgs, programs in (
            ("misses", batch(40, 64, "c"), ["walk_merge", "pack_chip"]),
            ("all hit", batch(40, 64, "c"),
             ["_mesh_merge_jit", "pack_chip"]),
            ("mixed", batch(52, 76, "c"), ["walk_merge", "pack_chip"])):
        loop.reset()
        pb = b.publish_begin(msgs)
        assert (loop.eager, loop.transfers) == (0, 1), what
        assert loop.programs == programs, what
        # the fetch finds its bundle laid: nothing to launch, nothing
        # to put
        assert pb.bundle_d is not None
        loop.reset()
        b.publish_fetch(pb)
        assert (loop.eager, loop.transfers, loop.programs) == (0, 0, []), \
            what
        assert b.publish_finish(pb) == [_want(b, m.topic) for m in msgs]
    c = _counters(b)
    assert c["dispatch.fused"] == c["dispatch.batches"] == 6
    # the counter reads what the wrappers counted: two a batch
    assert c["dispatch.programs"] - before["dispatch.programs"] == 6
    assert c["dispatch.programs"] == 2 * c["dispatch.batches"]


def test_hits_are_read_from_the_probes_snapshot(monkeypatch):
    """Another batch's insert can land between a batch's probe and its
    own insert (a second loop, the rewarm's thread): the one program
    takes both tables, gathers the hits from the one the probe saw
    and inserts into the current one."""
    import types

    import jax.numpy as jnp

    b, ref = _broker(), _broker()
    b.publish_batch([Message(topic=t) for t in T[:10]])
    _apart(ref, T[:10], 64, 64)
    cache = b.router._match_cache()
    real, seen = cache.insert_through, []

    def raced(self, probe, step):
        with self._lock:
            # the other batch's insert: a new array, in which this
            # batch's hits no longer lie where the probe found them
            self._table = self._table_now().at[
                jnp.asarray(probe.hit_slots)].set(-1)
            seen.append(self._table is not probe.table)
        return real(probe, step)

    monkeypatch.setattr(cache, "insert_through",
                        types.MethodType(raced, cache))
    got, delivered, (pm, pq), moved = _fused(b, T[:20])
    monkeypatch.undo()
    assert seen == [True] and moved["dispatch.programs"] == 2
    _same(got, _apart(ref, T[:20], pm, pq), "raced")
    assert delivered == [_want(b, t) for t in T[:20]]
    # the misses went into the current table: they hit now, while the
    # overwritten rows read as overflowed and are answered by the host
    got, delivered, _budgets, moved = _fused(b, T[:20])
    assert moved["dispatch.walk.topics"] == 0
    assert got[1][:10].all() and not got[1][10:].any()
    assert (got[0][10:20] >= 0).any(axis=1).all()
    assert delivered == [_want(b, t) for t in T[:20]]


_COMPILES = []


def _count_compiles():
    """Programs XLA compiled so far in this process (the harness's
    own count: one ``backend_compile`` event each; the persistent
    cache is off under test)."""
    if not _COMPILES:
        import jax.monitoring as mon

        _COMPILES.append(0)

        def _dur(name, _secs, **_kw):
            if name.endswith("backend_compile_duration"):
                _COMPILES[0] += 1

        mon.register_event_duration_secs_listener(_dur)
    return _COMPILES[0]


def test_a_merge_leaves_the_walks_program_the_one_it_was():
    """PR 42: a compaction's swap hands the match path a new generation
    of the delta (empty, or holding what arrived during the flatten,
    shallower or deeper than the last) and fan-out tables carried over
    the epoch: the walk, the merge and the packer are the programs
    they were, nothing is compiled again, whatever the generation
    holds."""
    import time

    b = _broker(delta_max_filters=8)
    r = b.router
    for i in range(150):          # room in every table for what follows,
        b.sub(f"base/{i}/a/b/c")  # and the main tables as deep as it

    def batch(tag, n=24):
        return [Message(topic=f"t/{i}/{tag}") for i in range(n)]

    def settle(merges):
        deadline = time.time() + 20
        while (r._compacting or r._delta_merges < merges) \
                and time.time() < deadline:
            time.sleep(0.005)
        assert r._delta_merges >= merges

    b.publish_batch(batch("first", 1))      # the first flatten
    b.sub("late/+/z")                       # a live delta, with a '+'
    b.unsub("t/5/+")                        # and a tombstone
    for tag in ("a", "b"):
        b.publish_batch(batch(tag))         # all miss: the walk's shape
    b.sub("late/0/y")                       # a patched side table
    b.publish_batch(batch("b2"))
    b.publish_batch(batch("b2"))            # all hit
    deep = [Message(topic=f"base/{i}/a/b/{tag}") for i in range(12)
            for tag in "cd"]
    b.publish_batch(deep)                   # and a batch five levels deep
    compiled = _count_compiles()
    rebuilds = b.helper.rebuilds
    for i in range(6):                      # the bound (2 + 6): a merge
        b.sub(f"cmd/s0/d{i}/#")
    settle(1)
    assert r._delta is not None and r._delta.n_pending == 0
    b.publish_batch(batch("c"))             # an empty generation
    b.sub("cmd/s0/d9/#")                    # a shallow one
    b.publish_batch(batch("d"))
    b.sub("cmd/s0/+/d10/ack")               # a deeper one, with a '+'
    b.unsub("t/3/+")                        # and a tombstone
    b.publish_batch(batch("e"))
    for i in range(11, 19):
        b.sub(f"cmd/s0/d{i}/#")
    settle(2)
    msgs = batch("f")
    assert b.publish_batch(msgs) == [_want(b, m.topic) for m in msgs]
    msgs = [Message(topic="cmd/s0/x/d10/ack"),
            Message(topic="cmd/s0/d4/probe"),
            Message(topic="t/3/z/y/x")] + batch("g", 21)
    assert b.publish_batch(msgs) == [_want(b, m.topic) for m in msgs]
    assert _want(b, "cmd/s0/x/d10/ack") > _want(b, "cmd/s0/x/d11/ack")
    assert _count_compiles() == compiled
    assert b.helper.rebuilds == rebuilds and b.helper.carries == 2


def test_the_buffers_capacity_is_reached_by_the_first_batch():
    """Its length is a shape of the match's program and of the merge:
    it must never grow under traffic the ingress can form (1,024
    unique topics, hits and misses both laid at that bucket, at
    ``max_levels``)."""
    from emqx_tpu.ops.match_cache import BATCH_BUF_FLOOR, BatchLayout

    b = _broker()
    b.publish_batch([Message(topic="t/0/x")])
    assert b.router._batch_buf_len == BATCH_BUF_FLOOR
    deep = "/".join(["t", "0"] + ["d"] * (b.router.config.max_levels - 2))
    b.publish_batch([Message(topic=deep)] + [
        Message(topic=f"t/{i}/cap") for i in range(1023)])
    assert b.router._batch_buf_len == BATCH_BUF_FLOOR
    assert BatchLayout.need(b.router.config.max_levels, 1024, 1024) \
        <= BATCH_BUF_FLOOR


# -- the counters -------------------------------------------------------------


@pytest.mark.parametrize("enabled", [True, False])
def test_fused_equals_batches_equals_the_spans(enabled):
    b = _broker()
    metrics = Metrics()
    tel = Telemetry(TelemetryConfig(enabled=enabled), metrics=metrics)
    b.telemetry = b.router.telemetry = tel
    spans, finish = [], tel.finish

    def keep(span):
        spans.append((span.path, span.t_enq > 0, span.cache_hit,
                      span.cache_miss))
        finish(span)

    tel.finish = keep
    for lo, hi in ((0, 1), (0, 24), (0, 24), (12, 36), (0, 40)):
        assert b.publish_batch([Message(topic=t) for t in T[lo:hi]]) == [
            _want(b, t) for t in T[lo:hi]]
    got = {k: metrics.val(k) for k in DISPATCH_METRICS}
    if not enabled:
        assert got == dict.fromkeys(DISPATCH_METRICS, 0) and not spans
        return
    assert got["dispatch.fused"] == got["dispatch.batches"] == len(spans) \
        == 5
    assert got["dispatch.programs"] == 2 * got["dispatch.batches"]
    # every batch stamped its one transfer as its first device call
    assert all(path == "device" and enq for path, enq, *_x in spans)
    assert got["dispatch.walk.topics"] == sum(s[3] for s in spans) == 40
    assert got["dispatch.topics"] - 40 == sum(s[2] for s in spans)


# -- the benchmark's reading of the counters ----------------------------------

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FUSED = {"fused_batch_share": ["fleet_1m.flood", "fanout_1k.flood"],
         "fused_batch_share.paced": ["fleet_1m.paced"],
         "fused_batch_share.uniform": ["fleet_1m_uniform.flood"],
         "fused_batch_share.p2p": ["p2p_2k.flood"]}


def _json(*path):
    with open(os.path.join(_ROOT, *path), encoding="utf-8") as f:
        return json.load(f)


@pytest.mark.parametrize("name", list(FUSED))
def test_fused_batch_share_file_equals_its_entry_and_its_base(name):
    spec = _json("BENCHMARK.json")
    data = _json("benchmark", "layer_metrics", name + ".json")
    # appended behind everything the benchmark had then, in this order
    # (what later PRs appended follows them); the mesh cell's list is
    # pinned and gets none
    names = [m["name"] for m in spec["per_layer"]]
    at = names.index("fused_batch_share")
    assert names[at:at + 4] == list(FUSED)
    entry = next(m for m in spec["per_layer"] if m["name"] == name)
    assert entry == {
        "name": name, "unit": "batches/batch", "better": "higher",
        "source": "program_counter", "layer": "match dispatch",
        "moves": ("deliver_p50_ms" if name.endswith(".paced")
                  else "delivered_rate"),
        "workloads": FUSED[name]}
    for key in ("unit", "better", "source", "layer", "moves"):
        assert data[key] == entry[key], key
    assert entry["layer"] == next(
        m["layer"] for m in spec["per_layer"]
        if m["name"] == "match_us_per_msg")
    base = _json("benchmark", "layer_metrics", "fused_batch_share.json")
    assert {k: v for k, v in data.items() if k != "moves"} == \
        {k: v for k, v in base.items() if k != "moves"}
    assert data["reducer"] == "counter_ratio" and data["what"]
    assert data["args"] == {"counters": ["dispatch.fused"],
                            "per": "counter:dispatch.batches"}
    assert {"dispatch.fused", "dispatch.batches"} <= set(ALL_METRICS)
    mod_spec = importlib.util.spec_from_file_location(
        "_fused_counter_ratio", os.path.join(
            _ROOT, "benchmark", "reducers", "counter_ratio.py"))
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    # a program without the counters (the parent) reads nothing, never 0
    parent = {"counters": {"dispatch.topics": 900,
                           "dispatch.walk.topics": 100}}
    assert mod.reduce(parent, **data["args"]) is None
    run = {"counters": dict(parent["counters"], **{
        "dispatch.batches": 40, "dispatch.fused": 40})}
    assert mod.reduce(run, **data["args"]) == 1.0
    run["counters"]["dispatch.fused"] = 30
    assert mod.reduce(run, **data["args"]) == 0.75
