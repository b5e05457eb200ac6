"""The mesh's match dispatch as the event loop pays for it
(``Router._dispatch_fused``): a batch leaves as ONE host→device
transfer and two or three compiled programs, with no eager operation,
and hands the broker bit for bit what the legacy whole-batch dispatch
(``Router._dispatch_sharded`` + ``mask_pad_rows`` + ``pack_matches`` /
``pack_fanout``) hands it. Runs on conftest's virtual CPU devices,
meshes ``2×2`` and ``4×1``; the chip's run is ``benchmark/`` cell
``fleet_10m_mesh.flood``.

One thing is NOT bit for bit, by the cache's contract and as before
this path existed: a row whose walk overflowed is stored as a marker,
so a HIT on it comes back blank where the walk's own truncated row was.
Its flags are equal and the host oracle resolves it either way."""

import os
import sys

import numpy as np
import pytest

from emqx_tpu import topic as topic_mod
from emqx_tpu.broker import Broker
from emqx_tpu.metrics import MESH_METRICS
from emqx_tpu.ops import match_cache
from emqx_tpu.ops.pack import (mask_pad_rows, pack_fanout, pack_matches,
                               pack_mesh)
from emqx_tpu.parallel.mesh import make_mesh
from emqx_tpu.parallel.sharded import publish_step_insert
from emqx_tpu.router import MatcherConfig, Router
from emqx_tpu.types import Message
from emqx_tpu.utils.batch import dedup_topics
from helpers import (MOVF_FILTERS, Compiles, CounterTel,
                     LoopCost as _Loop)

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

MESHES = {"2x2": (2, 2), "4x1": (4, 1)}
LEVELS = 8
DEEP = "deep/" + "/".join(f"l{i}" for i in range(LEVELS + 2))
M, D = 8, 8     # max_matches, fanout_d: small, so a test can overflow


class Q:
    def __init__(self, client_id):
        self.client_id = client_id
        self.n = 0

    def deliver(self, topic, msg):
        self.n += 1


# and the fan-out bound alone: few filters, five subscribers each
FOVF_FILTERS = ["fan/x", "fan/+", "+/x", "fan/#", "fan/x/#"]


def _broker(mesh_shape, parts, **kw):
    mesh = make_mesh(*mesh_shape)
    cfg = dict(mesh=mesh, max_matches=M, fanout_d=D, active_k=16,
               max_levels=LEVELS, match_cache_slots=256,
               cache_partitions=parts, device_min_filters=0,
               fanout_threshold=64)
    cfg.update(kw)
    b = Broker(router=Router(MatcherConfig(**cfg), node="local"))
    b.router.telemetry = CounterTel()
    subs = {}

    def sub(flt, n=1):
        for i in range(n):
            q = Q(f"{flt}#{i}")
            b.subscribe(q, flt)
            subs.setdefault(flt, []).append(q)

    for i in range(96):
        sub(f"t/{i}/+")
    sub("t/+/x")
    sub("#")
    sub("+/pad")            # the pad topic's own phantom match
    sub("$SYS/#")
    sub("+/a/b")
    sub("deep/#")
    for f in MOVF_FILTERS:
        sub(f)
    for f in FOVF_FILTERS:
        sub(f, 5)
    b.filters = {f: len(q) for f, q in subs.items()}
    return b


def _want(b, topic):
    """Deliveries by ``topic.match`` alone."""
    return sum(n for f, n in b.filters.items() if topic_mod.match(topic, f))


def _counters(b):
    return {k: b.router.telemetry.metrics.val(k) for k in MESH_METRICS}


def _np(*xs):
    return [None if x is None else np.asarray(x) for x in xs]


def _legacy(b, uniq, pm, pq):
    """The whole batch through the collective step, then the masks and
    the two packers: what the broker did before the fused path."""
    r = b.router
    mesh = r.config.mesh

    def fan(epoch, id_map):
        return b.helper.sharded_state(epoch, id_map, mesh, r.effective_d())

    ids, subs, src, bm, ovf, movf, _map, _epoch, _big = \
        r._dispatch_sharded(uniq, fan=fan, with_big=True)
    assert bm is None
    n = np.int32(len(uniq))
    ids, subs, src = (mask_pad_rows(x, n) for x in (ids, subs, src))
    return _np(ids, subs, src, ovf, movf, *pack_matches(ids, pm=pm),
               *pack_fanout(subs, src, pq=pq))


def _fused(b, topics):
    """One batch through the broker; its device arrays, then its
    deliveries."""
    before = _counters(b)
    pb = b.publish_begin([Message(topic=t) for t in topics])
    after = _counters(b)
    got = _np(pb.ids_dev, pb.subs_dense_d, pb.src_dense_d, pb.ovf_dev,
              pb.movf_d, pb.m_ptr_d, pb.ids_packed_d, pb.f_ptr_d,
              pb.subs_packed_d, pb.src_packed_d)
    pm, pq = pb.pm, pb.pq
    b.publish_fetch(pb)
    delivered = b.publish_finish(pb)
    return got, delivered, (pm, pq), {k: after[k] - before[k]
                                      for k in after}


NAMES = ("ids", "subs", "src", "ovf", "movf", "m_ptr", "ids_packed",
         "f_ptr", "subs_packed", "src_packed")


def _same(got, want, what):
    for name, a, w in zip(NAMES, got, want):
        assert a.dtype == w.dtype and a.shape == w.shape, (what, name)
        assert (a == w).all(), (what, name)


CASES = {
    # a case: (topics published before, the batch); the batch runs
    # twice, so the second time every topic hits
    "all_miss_then_all_hit": ([], [f"t/{i}/x" for i in range(24)]),
    "mixed": ([f"t/{i}/y" for i in range(10)],
              [f"t/{i}/y" for i in range(20)]),
    "match_overflow": ([], ["mo/a/b/c"] + [f"t/{i}/m" for i in range(23)]),
    "fan_overflow": ([], ["fan/x"] + [f"t/{i}/f" for i in range(23)]),
    "sys": ([], ["$SYS/a/b", "$SYS/pad", "q/a/b"]
            + [f"t/{i}/s" for i in range(13)]),
    "past_max_levels": ([], [DEEP] + [f"t/{i}/d" for i in range(15)]),
    "pad_heavy": ([], ["t/0/p"]),
    "pad_heavy_17": ([], [f"t/{i}/q" for i in range(17)]),
}


@pytest.fixture(scope="module", params=[
    (m, p) for m in MESHES for p in (1, 64)],
    ids=lambda mp: f"{mp[0]}-parts{mp[1]}")
def broker(request):
    mesh, parts = request.param
    return _broker(MESHES[mesh], parts)


@pytest.mark.parametrize("case", list(CASES))
def test_fused_equals_legacy_bit_for_bit(broker, case):
    b = broker
    before, batch = CASES[case]
    if before:
        b.publish_batch([Message(topic=t) for t in before])
    uniq, _inv = dedup_topics(batch)
    assert uniq == batch
    n_hit = len(set(before) & set(batch))
    first, delivered, (pm, pq), moved = _fused(b, batch)
    want = _legacy(b, batch, pm, pq)
    assert moved == {"mesh.batches": 1, "mesh.topics": len(batch),
                     "mesh.steps": 1,
                     "mesh.step.topics": len(batch) - n_hit,
                     "mesh.fused": 1}
    ovf = want[3]
    if case == "match_overflow":
        assert want[4][0] and ovf[0]
    elif case == "fan_overflow":
        assert ovf[0] and not want[4][0]
    elif case == "past_max_levels":
        assert ovf[0]
    elif case.startswith("pad_heavy"):
        # the pad rows do match ("#", "+/pad") before the mask
        assert (first[0][len(batch):] == -1).all()
        assert (first[0][:len(batch)] >= 0).any()
    else:
        assert not ovf.any()
    _same(first, want, f"{case}: first batch")
    assert delivered == [_want(b, t) for t in batch]
    # the second batch of the same topics: all hits, no step, and the
    # same answer (an overflowed row now the marker: blank, same flags)
    second, delivered2, budgets2, moved2 = _fused(b, batch)
    assert moved2 == {"mesh.batches": 1, "mesh.topics": len(batch),
                      "mesh.steps": 0, "mesh.step.topics": 0,
                      "mesh.fused": 1}
    assert budgets2 == (pm, pq) and delivered2 == delivered
    blank = [np.where(ovf[:, None], -1, x) for x in want[:3]]
    want2 = blank + want[3:5] + _np(
        *pack_matches(blank[0], pm=pm),
        *pack_fanout(blank[1], blank[2], pq=pq))
    _same(second, want2, f"{case}: second batch")
    for got, ref in zip(second[:3], first[:3]):
        assert (got[~ovf] == ref[~ovf]).all()


# -- what a warm batch costs the event loop ----------------------------------


@pytest.mark.parametrize("mesh", list(MESHES))
def test_a_warm_batch_is_one_transfer_and_two_or_three_programs(
        mesh, monkeypatch):
    b = _broker(MESHES[mesh], 64)

    def batch(lo, hi, tag):
        return [Message(topic=f"t/{i}/{tag}") for i in range(lo, hi)]

    # warm every shape the counted batches use: (32, hit 8, miss 32),
    # (32, hit 32, no miss), (32, hit 16, miss 16)
    b.publish_batch(batch(0, 24, "w"))
    b.publish_batch(batch(0, 24, "w"))
    b.publish_batch(batch(12, 36, "w"))
    loop = _Loop(monkeypatch)
    for what, msgs, programs in (
            ("misses", batch(40, 64, "c"),
             ["publish_step_insert", "_mesh_merge_jit", "pack_mesh"]),
            ("all hit", batch(40, 64, "c"),
             ["_mesh_merge_jit", "pack_mesh"]),
            ("mixed", batch(52, 76, "c"),
             ["publish_step_insert", "_mesh_merge_jit", "pack_mesh"])):
        loop.reset()
        pb = b.publish_begin(msgs)
        assert (loop.eager, loop.transfers) == (0, 1), what
        assert loop.programs == programs, what
        b.publish_fetch(pb)
        assert b.publish_finish(pb) == [2] * len(msgs)
    assert _counters(b)["mesh.fused"] == _counters(b)["mesh.batches"] == 6


# -- the programs a run loads -------------------------------------------------


@pytest.fixture
def compiles():
    c = Compiles()
    yield c
    c.close()


async def test_the_benchmarks_sweep_warms_every_triple(compiles):
    """``benchmark/warmers/mesh_buckets.py`` as it stands, at toy
    size: after one sweep a second one — every (batch, hit, miss)
    triple the padding rule allows — makes no program ready."""
    from emqx_tpu.config import build_node, parse_config

    sys.path.insert(0, os.path.join(_ROOT, "benchmark"))
    try:
        from warmers import mesh_buckets
    finally:
        sys.path.pop(0)
    node = build_node(parse_config(
        {"matcher": {"mesh": {"data": 2, "trie": 2}}}))
    node.ingress.batch_size = 32        # buckets 16, 32, 64
    q = Q("q")
    for i in range(40):
        node.broker.subscribe(q, f"t/{i}/+")
    # one walk a miss bucket, one merge a triple, one packer a bucket
    fns = (publish_step_insert, match_cache._mesh_merge_jit, pack_mesh)
    loaded = [f._cache_size() for f in fns]
    said = []
    n = await mesh_buckets.warm(node, compiles, said.append)
    first = compiles.compiles
    assert n >= 15 and first > 0
    assert [f._cache_size() - c for f, c in zip(fns, loaded)] == [3, n, 3]
    assert await mesh_buckets.warm(node, compiles, said.append) == n
    assert compiles.compiles == first, said[-1]


def test_a_grown_budget_costs_one_program_a_bucket(compiles):
    b = _broker(MESHES["2x2"], 64)

    def batch(lo, hi, tag):
        return [Message(topic=f"t/{i}/{tag}") for i in range(lo, hi)]

    triples = (lambda tag: (batch(0, 24, tag), batch(0, 24, tag),
                            batch(12, 36, tag)))
    for msgs in triples("a"):
        b.publish_batch(msgs)
    for grown in (0, 1):                # pm, then pq
        b._pack_budgets[32][grown] *= 2
        c0 = compiles.compiles
        for msgs in triples(f"g{grown}"):
            assert b.publish_batch(msgs) == [2] * len(msgs)
        assert compiles.compiles - c0 == 1


# -- who still takes the legacy dispatch --------------------------------------


def _moved_snapshot(b, msgs):
    """The snapshot moves (a rebuild) between the probe and the step."""
    r = b.router
    encode = r._encode

    def encode_and_move(topics, levels):
        r._encode = encode
        r._dirty = True
        return encode(topics, levels)

    r._encode = encode_and_move
    epoch = r.automaton()[2]
    got = b.publish_batch(msgs)
    assert r.automaton()[2] > epoch
    return got


def _big_filter(b, msgs):
    """A filter over ``fanout_threshold``: bitmap rows, no cache."""
    if "t/+/+" not in b.filters:
        b.filters["t/+/+"] = 70
        for i in range(70):
            b.subscribe(Q(f"big{i}"), "t/+/+")
    return b.publish_batch(msgs)


LEGACY = {
    "cache_off": (dict(match_cache=False), Broker.publish_batch),
    "big_filter_bitmap": ({}, _big_filter),
    "moved_snapshot": ({}, _moved_snapshot),
}


@pytest.mark.parametrize("why", list(LEGACY))
def test_the_legacy_dispatch_still_serves(why):
    kw, publish = LEGACY[why]
    b = _broker(MESHES["2x2"], 64, **kw)
    msgs = [Message(topic=f"t/{i}/x") for i in range(20)]
    for _ in range(2):
        assert publish(b, msgs) == [_want(b, m.topic) for m in msgs]
    c = _counters(b)
    assert c["mesh.fused"] == 0
    assert c["mesh.batches"] == c["mesh.steps"] == 2
    assert c["mesh.step.topics"] == c["mesh.topics"] == 40
    if why == "moved_snapshot":
        # the next batch finds the new snapshot and leaves fused
        assert b.publish_batch(msgs) == [_want(b, m.topic) for m in msgs]
        assert _counters(b)["mesh.fused"] == 1
