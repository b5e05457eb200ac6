"""A fleet in which every device reports on its own topic, on one chip
(benchmark cell ``fleet_1m_uniform.flood``): far more distinct topics
than match-cache slots, batches of up to ``ingress.batch_cap`` unique
topics. Held here at small size on the CPU:

(a) deliveries against the plain reference while the cache evicts and
    re-inserts, batch after batch;
(b) ``Router.dispatch_shapes`` is complete: after ``Broker.
    warm_dispatch`` no batch of such traffic first-uses a program, and
    below 512 unique topics the benchmark's older sweep
    (``warmers/dispatch_buckets.py``) meets every program key it
    lists; the match's program is keyed by the batch's bucket and the
    depth, never by how many topics hit or miss;
(c) the device-loss rewarm walks the same list;
(d) the per-batch counters ``dispatch.topics`` /
    ``dispatch.walk.topics`` equal the spans' sums.

The population and the topics are made as ``benchmark/populations/
mixed_tree.py`` and ``benchmark/topic_laws/uniform_levels.py`` make
them, written again here (``tests/test_uniform_cell.py`` holds the
benchmark's own files)."""

import random

import pytest

from emqx_tpu.broker import Broker
from emqx_tpu.metrics import DISPATCH_METRICS, Metrics
from emqx_tpu.ops import match_cache
from emqx_tpu.oracle import TrieOracle
from emqx_tpu.router import DispatchShape, MatcherConfig, Router
from emqx_tpu.telemetry import Telemetry, TelemetryConfig
from emqx_tpu.types import Message
from helpers import Compiles

LEVELS, WORDS, FILTERS = 5, 12, 3000
VOCAB = [[f"w{lvl}_{i}" for i in range(WORDS)] for lvl in range(LEVELS)]
SLOTS = 64          # the match cache: every batch below evicts
CAP = 1024          # the default ingress's batch_cap


def _population(seed):
    """3,000 filters, 60 % literal, 25 % one ``+``, 15 % cut and ended
    by ``#``, depth 2 to 5."""
    rng = random.Random(seed)
    out = set()
    while len(out) < FILTERS:
        depth = rng.randint(2, LEVELS)
        ws = [rng.choice(VOCAB[i]) for i in range(depth)]
        r = rng.random()
        if r < 0.25:
            ws[rng.randrange(depth)] = "+"
        elif r < 0.40:
            ws = ws[:rng.randint(1, depth)] + ["#"]
        out.add("/".join(ws))
    return sorted(out)


def _topic(rng):
    return "/".join(rng.choice(VOCAB[lvl])
                    for lvl in range(rng.randint(2, LEVELS)))


def _unique(rng, n, avoid=()):
    out = set()
    while len(out) < n:
        t = _topic(rng)
        if t not in avoid:
            out.add(t)
    return sorted(out)


class DictTrie:
    """MQTT 3.1.1 §4.7 over nested dicts (no topic here starts with
    ``$``)."""

    def __init__(self, filters):
        self.root = {}
        for f in filters:
            node = self.root
            for w in f.split("/"):
                node = node.setdefault(w, {})
            node[0] = f

    def match(self, topic):
        t = topic.split("/")
        out, stack = [], [(self.root, 0)]
        while stack:
            node, i = stack.pop()
            if "#" in node:
                out.append(node["#"][0])
            if i == len(t):
                if 0 in node:
                    out.append(node[0])
                continue
            for w in (t[i], "+"):
                if w in node:
                    stack.append((node[w], i + 1))
        return sorted(out)


class Sink:
    def __init__(self):
        self.got = []

    def deliver(self, topic_filter, msg):
        self.got.append((msg.topic, topic_filter))


def _broker(filters, **cfg):
    cfg.setdefault("match_cache_slots", SLOTS)
    b = Broker(router=Router(MatcherConfig(**cfg), node="local"))
    sink = Sink()
    for f in filters:
        b.subscribe(sink, f)
    return b, sink


def _publish(b, sink, topics):
    del sink.got[:]
    b.publish_batch([Message(topic=t, payload=b"") for t in topics])
    return sorted(sink.got)


class Recorder:
    """The shapes the dispatch's two keyed programs were called for:
    one chip's match (walk, insert and merge) as ``(batch bucket,
    depth)``, the merge of a batch that fully hit as ``(batch, hit,
    miss)`` buckets = ``(bucket, bucket, 0)``."""

    def __init__(self, monkeypatch):
        self.walks, self.merges = set(), set()
        walk, merge = match_cache.walk_merge, match_cache._mesh_merge_jit

        def rec_walk(*a, lay, **kw):
            # hits and misses both at the batch's bucket: no count of
            # either is a shape
            assert lay.miss == lay.hit
            self.walks.add((lay.miss, lay.levels))
            return walk(*a, lay=lay, **kw)

        def rec_merge(table, buf, miss_vals, *, lay, b_pad, **kw):
            # one chip comes here fully hit, keyed by its bucket alone
            assert miss_vals is None and lay.levels == 0
            assert (lay.miss, lay.hit) == (0, b_pad)
            self.merges.add((b_pad, lay.hit, lay.miss))
            return merge(table, buf, miss_vals, lay=lay, b_pad=b_pad, **kw)

        monkeypatch.setattr(match_cache, "walk_merge", rec_walk)
        monkeypatch.setattr(match_cache, "_mesh_merge_jit", rec_merge)

    def clear(self):
        self.walks.clear()
        self.merges.clear()


def _programs(router, shapes):
    walks, merges = set(), set()
    for s in shapes:
        w, m = router.shape_programs(s)
        walks.add(w)
        merges.add(m)
    return walks - {None}, merges - {None}


@pytest.fixture
def compiles():
    c = Compiles()
    yield c
    c.close()


# -- (a) against the plain reference -----------------------------------------


@pytest.fixture(scope="module")
def fleet():
    filters = _population(20260930)
    oracle = TrieOracle()
    for f in filters:
        oracle.insert(f)
    b, sink = _broker(filters)
    return b, sink, oracle, DictTrie(filters)


@pytest.mark.parametrize("n", [1, 8, 511, 512, 513, 700, 1024])
def test_deliveries_equal_the_reference_while_the_cache_evicts(fleet, n):
    b, sink, oracle, trie = fleet
    rng = random.Random(n)
    first = _unique(rng, n)
    other = _unique(rng, max(n, SLOTS + 1), avoid=set(first))
    seen = {}
    # the batch, another that takes every slot of the cache, the first
    # again (every topic of it evicted and walked anew), and once more
    # (its last SLOTS topics now hit)
    for topics in (first, other, first, first):
        got = _publish(b, sink, topics)
        want = sorted((t, f) for t in topics for f in trie.match(t))
        assert got == want
        assert want == sorted((t, f) for t in topics
                              for f in oracle.match(t))
        for t in topics:
            row = sorted(f for tt, f in got if tt == t)
            assert seen.setdefault(t, row) == row
    assert any(f for _t, f in want) and b.router.cache_entries() <= SLOTS
    st = b.router._match_cache().stats()
    assert st["miss"] > st["hit"]
    assert b.breaker is None  # nothing stood between this and the device


# -- (b) the shape list is complete -------------------------------------------


def _sweep_plan(floor, batch_size, depths):
    """``benchmark/warmers/dispatch_buckets.py``'s plan, written again:
    its ``(hits, misses, depth)`` batches and its padding rule."""
    top = floor
    while top < 2 * batch_size:
        top *= 2

    def pad(n):
        b = floor
        while b < n:
            b *= 2
        return b

    buckets = []
    b = floor
    while b <= top:
        buckets.append(b)
        b *= 2
    ends = {b: (1 if b == floor else b // 2 + 1, b) for b in buckets}
    plan = [(0, top, 2)]
    plan += [(0, ends[mb][0], d) for mb in buckets for d in depths]
    done = set()
    for hb in buckets:
        for mb in [0] + buckets:
            for h in ends[hb]:
                for m in ends[mb] if mb else (0,):
                    key = (pad(h + m), hb, mb)
                    if h + m <= top and key not in done:
                        done.add(key)
                        plan.append((h, m, depths[-1]))
    # what the sweep's batches ask the program for: a batch with a
    # miss the match at (batch bucket, depth), a fully hit one the
    # bucket's merge
    walks = {(pad(h + m), d) for h, m, d in plan if m}
    merges = {(pad(h),) * 2 + (0,) for h, m, _d in plan if not m}
    return walks, merges, len(plan)


def test_the_list_holds_what_the_benchmarks_sweep_asked_for():
    """The padding rule for batches of up to 512 unique topics did not
    change, and the accepted one-chip cells ``fleet_1m`` and
    ``fanout_1k`` are still warmed by ``dispatch_buckets``: its plan
    (a batch of misses for every bucket at every depth, a batch for
    every (batch, hit, miss) triple) meets every program key the
    program lists, so a run of theirs first uses nothing in its
    window. The sweep is a superset now (its triples are batches of
    programs it has met), no longer equal to the list."""
    r = Router(MatcherConfig())
    r._seen_levels.update((2, 3, 4, 5))
    shapes = r.dispatch_shapes(512)
    walks, merges = _programs(r, shapes)
    sweep_walks, sweep_merges, n_plan = _sweep_plan(8, 256, [2, 3, 4, 5])
    assert walks <= sweep_walks and merges <= sweep_merges
    assert walks == {(b, d) for b in (8, 16, 32, 64, 128, 256, 512)
                     for d in (2, 3, 4, 5)}
    assert merges == {(b, b, 0) for b in (8, 16, 32, 64, 128, 256, 512)}
    # one batch a key, where the sweep sends one a triple as well
    assert len(shapes) == len(walks) + len(merges) == 35 < 100 < n_plan
    # and the list for the ingress's cap goes one bucket further
    walks_cap, merges_cap = _programs(r, r.dispatch_shapes(CAP))
    assert walks < walks_cap and merges < merges_cap
    assert {(1024, d) for d in (2, 3, 4, 5)} == walks_cap - walks
    assert merges_cap - merges == {(1024, 1024, 0)}


@pytest.mark.parametrize("hits,misses", [
    (0, 20), (1, 19), (10, 10), (19, 1), (3, 14), (16, 1), (0, 17)])
def test_the_match_is_keyed_by_the_bucket_and_the_depth_alone(
        hits, misses, monkeypatch):
    """However a batch of bucket 32 splits into hits and misses, it
    asks for the one program of (32, its deepest miss); the same
    topics again for the bucket's merge."""
    r = Router(MatcherConfig())
    for d in (2, 3, 4):
        assert r.shape_programs(DispatchShape(hits, misses, d)) == (
            (32, d), None)
    assert r.shape_programs(DispatchShape(hits + misses, 0, 4)) == (
        None, (32, 32, 0))
    b, sink = _broker(_population(5)[:200], match_cache_slots=4096,
                      device_min_filters=0)
    rng = random.Random(hits * 31 + misses)
    old = _unique(rng, 64)
    _publish(b, sink, old)
    rec = Recorder(monkeypatch)
    topics = old[:hits] + _unique(rng, misses, avoid=set(old))
    depth = max(t.count("/") + 1 for t in topics[hits:])
    for want in (({(32, depth)}, set()), (set(), {(32, 32, 0)})):
        _publish(b, sink, topics)
        assert (rec.walks, rec.merges) == want
        rec.clear()


def test_on_a_mesh_the_list_follows_the_mesh_rule():
    """``benchmark/warmers/mesh_buckets.py``'s triples, written again:
    batch and misses pad from ``min_batch × data``, hits from 8, and
    there is no depth axis."""
    from emqx_tpu.parallel.mesh import make_mesh

    r = Router(MatcherConfig(mesh=make_mesh(2, 2)))
    r._seen_levels.update((2, 7))
    unit, top = 16, 512

    def pad(n, floor):
        while floor < n:
            floor *= 2
        return floor

    miss = [16, 32, 64, 128, 256, 512]
    m_ends = {b: (1 if b == unit else b // 2 + 1, b) for b in miss}
    h_ends = {b: (0 if b == 8 else b // 2 + 1, b) for b in [8] + miss}
    want = {(pad(h + m, unit), hb, mb)
            for hb in h_ends for mb in [0] + miss for h in h_ends[hb]
            for m in (m_ends[mb] if mb else (0,)) if 0 < h + m <= top}
    shapes = r.dispatch_shapes(2 * 256)
    walks, merges = _programs(r, shapes)
    assert merges == want
    assert walks == {(mb, r.config.max_levels) for mb in miss}
    assert len(shapes) == len(merges)


@pytest.mark.parametrize("slots,cap", [(0, 64), (4, 64), (64, 8),
                                       (64, 1024), (65536, 1024)])
def test_every_shape_of_the_list_is_reachable_and_its_own(slots, cap):
    r = Router(MatcherConfig(match_cache=bool(slots),
                             match_cache_slots=slots))
    r._seen_levels.update((3, 5))
    shapes = r.dispatch_shapes(cap)
    top = r.pad_topics(cap)
    assert all(0 < s.hits + s.misses <= top and s.hits <= max(slots, 0)
               and 2 <= s.depth <= 5 for s in shapes)
    progs = [r.shape_programs(s) for s in shapes]
    # a shape brings a walk variant or a merge triple no earlier has
    seen_w, seen_m = set(), set()
    for w, m in progs:
        assert w not in seen_w or m not in seen_m
        seen_w.add(w)
        seen_m.add(m)
    if not slots:
        assert seen_m == {None}
        assert seen_w == {(b, d) for b in (8, 16, 32, 64)
                          for d in (2, 3, 4, 5)}


def test_after_the_warm_function_traffic_first_uses_no_program(
        monkeypatch, compiles):
    """Through the real seams, at the default ingress's cap: programs
    of another width than any other test's (``max_matches`` 48), so
    none is ready before the walk."""
    filters = _population(7)
    b, sink = _broker(filters, max_matches=48)
    trie = DictTrie(filters)
    rng = random.Random(11)
    # the cell's first rounds: the router learns the depths it serves
    _publish(b, sink, _unique(rng, 600))
    assert b.router.observed_levels()[-1] == LEVELS
    shapes = b.router.dispatch_shapes(CAP)
    list_walks, list_merges = _programs(b.router, shapes)
    rec = Recorder(monkeypatch)
    driven = [s for _secs, s in b.warm_dispatch(CAP)]
    # the list, and the one batch that lays the topics to hit (it has
    # the shape of one of the list's batches of misses)
    hot = DispatchShape(0, max(s.hits for s in shapes), 2)
    assert sorted(driven) == sorted(shapes + [hot])
    assert [s for s in driven if s.hits] == [s for s in shapes if s.hits]
    assert (rec.walks, rec.merges) == (list_walks, list_merges)
    rec.clear()
    c0, last, n_big = compiles.compiles, [], 0
    for i in range(60):
        n = rng.choice([1, 5, 8, 9, 60, 200, 511, 512, 513, 600, 700,
                        1024, rng.randint(1, CAP)])
        # mixed hit and miss shares: the newest of the last batch
        # again, ahead of the fresh topics or among them (where a
        # miss's slot may be the one a later hit was to find)
        k = min(n, rng.choice([0, 0, 3, 30, SLOTS]))
        again = last[len(last) - k:]
        topics = again + _unique(rng, n - k, avoid=set(again))
        if i % 3 == 0:
            rng.shuffle(topics)
        got = _publish(b, sink, topics)
        if i % 10 == 0:
            assert got == sorted((t, f) for t in topics
                                 for f in trie.match(t))
        last = topics[-SLOTS:]
        n_big += n > 512
    assert compiles.compiles == c0, "a program was first used after the walk"
    assert n_big > 5 and rec.walks <= list_walks
    assert rec.merges <= list_merges
    # batches of the largest bucket walked, and small ones hit whole
    assert {b_ for b_, _d in rec.walks} >= {8, 64, 1024}
    assert {b_ for b_, _hb, _mb in rec.merges} >= {8}
    # a second walk has nothing to make ready either
    assert sum(1 for _ in b.warm_dispatch(CAP)) == len(driven)
    assert compiles.compiles == c0


# -- (c) the device-loss rewarm walks the same list ---------------------------


def test_the_devloss_rewarm_walks_the_list_for_the_traffic_seen(
        monkeypatch):
    filters = _population(3)
    b, sink = _broker(filters)
    rng = random.Random(5)
    for n in (3, 40):  # buckets 8 and 64
        _publish(b, sink, _unique(rng, n))
    assert max(b._pack_budgets) == 64
    want = _programs(b.router, b.router.dispatch_shapes(64))
    # devloss.DeviceRecovery's steps 1 to 3
    b.router.suspend_device()
    b.helper.invalidate_device()
    b.router.rebuild_device_state()
    rec = Recorder(monkeypatch)
    n = b.warm_device_path()
    assert (rec.walks, rec.merges) == want
    # 4 buckets × 4 depths, and a fully hit batch a bucket
    assert n >= len(b.router.dispatch_shapes(64)) == 20
    assert max(b._pack_budgets) == 64   # and learns no larger bucket
    topics = _unique(rng, 60)
    trie = DictTrie(filters)
    assert _publish(b, sink, topics) == sorted(
        (t, f) for t in topics for f in trie.match(t))


# -- (d) the counters ---------------------------------------------------------


@pytest.mark.parametrize("enabled", [True, False])
def test_dispatch_counters_equal_the_spans_sums(enabled):
    filters = _population(9)
    b, sink = _broker(filters)
    metrics = Metrics()
    tel = Telemetry(TelemetryConfig(enabled=enabled), metrics=metrics)
    b.telemetry = b.router.telemetry = tel
    spans, finish = [], tel.finish

    def keep(span):
        spans.append((span.n_uniq, span.cache_hit, span.cache_miss,
                      span.bucket, span.path))
        finish(span)

    tel.finish = keep
    rng = random.Random(13)
    last = []
    for n in (1, 8, 100, 513, 700, 30, 700):
        topics = last[:20] + _unique(rng, n, avoid=set(last[:20]))
        # duplicates in a batch dedup before the dispatch
        _publish(b, sink, topics + topics[:7])
        last = topics[-SLOTS:]
    got = {k: metrics.val(k) for k in DISPATCH_METRICS}
    if not enabled:
        assert got == dict.fromkeys(DISPATCH_METRICS, 0)
        assert not spans
        return
    assert len(spans) == 7 and all(p == "device" for *_x, p in spans)
    assert got["dispatch.topics"] == sum(s[0] for s in spans)
    assert got["dispatch.walk.topics"] == sum(s[2] for s in spans)
    assert sum(s[1] for s in spans) == \
        got["dispatch.topics"] - got["dispatch.walk.topics"] > 0
    assert 0.7 < got["dispatch.walk.topics"] / got["dispatch.topics"] < 1
    assert {s[3] for s in spans} >= {8, 128, 1024}
    # the walk's batches count too, and have no span
    n0 = len(spans)
    fresh = sum(s.hits + s.misses for _t, s in b.warm_dispatch(8))
    assert len(spans) == n0
    assert metrics.val("dispatch.topics") - got["dispatch.topics"] == fresh


def test_the_uncached_dispatch_walks_every_topic():
    filters = _population(9)
    b, sink = _broker(filters, match_cache=False)
    metrics = Metrics()
    b.telemetry = b.router.telemetry = Telemetry(
        TelemetryConfig(enabled=True), metrics=metrics)
    topics = _unique(random.Random(2), 50)
    _publish(b, sink, topics)
    _publish(b, sink, topics)
    assert metrics.val("dispatch.topics") == 100
    assert metrics.val("dispatch.walk.topics") == 100
    assert b.router.shape_programs(DispatchShape(10, 40, 3)) == (
        (64, 3), None)
