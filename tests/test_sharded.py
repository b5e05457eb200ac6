"""Multi-chip publish step on the virtual 8-device CPU mesh:
parity of the sharded match vs the host oracle, and mesh-summed stats."""

import random

import jax
import numpy as np
import pytest

from emqx_tpu.oracle import TrieOracle
from emqx_tpu.ops.tokenize import WordTable, encode_batch
from emqx_tpu.parallel.mesh import make_mesh
from emqx_tpu.parallel.sharded import (
    build_sharded, build_sharded_fanout, place_batch, place_sharded,
    publish_step, shard_filters)


def _rand_filters(rng, n):
    words = ["a", "b", "c", "d", "e", "s1", "s2"]
    out = set()
    while len(out) < n:
        depth = rng.randint(1, 5)
        ws = []
        for i in range(depth):
            r = rng.random()
            if r < 0.2:
                ws.append("+")
            elif r < 0.3 and i == depth - 1:
                ws.append("#")
            else:
                ws.append(rng.choice(words))
        out.add("/".join(ws))
    return sorted(out)


@pytest.mark.parametrize("n_data,n_trie",
                         [(4, 2), (2, 4), (8, 1), (1, 1)])
def test_sharded_match_parity(n_data, n_trie):
    # (1, 1) exercises the plain-jit fast path (no shard_map): its
    # outputs must be indistinguishable from the collective program's
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 virtual devices")
    rng = random.Random(0)
    filters = _rand_filters(rng, 120)
    fids = {f: i for i, f in enumerate(filters)}
    table = WordTable()
    for f in filters:
        for w in f.split("/"):
            table.intern(w)
    oracle = TrieOracle()
    for f in filters:
        oracle.insert(f)

    mesh = make_mesh(n_data, n_trie)
    shards = shard_filters(filters, n_trie)
    auto, parts = build_sharded(shards, fids, table, return_parts=True)
    rows = [{fids[f]: [fids[f] * 10, fids[f] * 10 + 1] for f in shard}
            for shard in shards]
    fan = build_sharded_fanout(rows, len(filters))

    words = ["a", "b", "c", "d", "e", "s1", "s2", "zz"]
    B = 8 * n_data
    topics = ["/".join(rng.choice(words) for _ in range(rng.randint(1, 5)))
              for _ in range(B)]
    ids_np, n_np, sys_np = encode_batch(table, topics, 8)

    auto_d = place_sharded(mesh, auto)
    fan_d = place_sharded(mesh, fan)
    b = place_batch(mesh, ids_np, n_np, sys_np)

    from emqx_tpu.ops.match import walk_params

    ids, subs, src, _bm, ovf, movf, stats = publish_step(
        mesh, auto_d, fan_d, *b, k=32, m=32, d=64,
        **walk_params(parts[0], 8))
    assert _bm is None
    assert not np.asarray(movf).any()
    ids = np.asarray(ids)
    subs = np.asarray(subs)
    src = np.asarray(src)
    inv = {v: k for k, v in fids.items()}
    total_matches = 0
    total_deliv = 0
    for i, t in enumerate(topics):
        got = sorted(inv[j] for j in ids[i] if j >= 0)
        expect = sorted(oracle.match(t))
        assert got == expect, (t, got, expect)
        total_matches += len(expect)
        exp_subs = sorted(x for f in expect for x in rows_lookup(rows, fids[f]))
        assert sorted(x for x in subs[i] if x >= 0) == exp_subs
        total_deliv += len(exp_subs)
        # src carries the matched filter id per gathered slot
        exp_pairs = sorted((fids[f], x) for f in expect
                           for x in rows_lookup(rows, fids[f]))
        got_pairs = sorted((int(s), int(x))
                           for s, x in zip(src[i], subs[i]) if x >= 0)
        assert got_pairs == exp_pairs, (t, got_pairs, exp_pairs)
    assert int(stats["matches"]) == total_matches
    assert int(stats["deliveries"]) == total_deliv
    assert int(stats["overflows"]) == 0


def rows_lookup(rows, fid):
    for shard_rows in rows:
        if fid in shard_rows:
            return shard_rows[fid]
    return []


# -- product integration: Router on a mesh (VERDICT round-1 item 7) ---------

def test_router_sharded_match_parity():
    """Router(mesh=...) matches through publish_step with exact
    oracle parity — BASELINE config 5's product path on the virtual
    8-device mesh."""
    import random

    from emqx_tpu.oracle import TrieOracle
    from emqx_tpu.parallel.mesh import default_mesh
    from emqx_tpu.router import MatcherConfig, Router

    rng = random.Random(3)
    mesh = default_mesh(8)
    r = Router(MatcherConfig(mesh=mesh), node="n1")
    oracle = TrieOracle()
    words = ["a", "b", "c", "dd", "s"]
    filters = set()
    while len(filters) < 60:
        depth = rng.randint(1, 4)
        ws = [rng.choice(words + ["+"]) for _ in range(depth)]
        if rng.random() < 0.2:
            ws[-1] = "#"
        filters.add("/".join(ws))
    for f in filters:
        r.add_route(f)
        oracle.insert(f)
    topics = ["/".join(rng.choice(words) for _ in range(rng.randint(1, 4)))
              for _ in range(40)]
    got = r.match_filters(topics)
    for t, g in zip(topics, got):
        assert sorted(g) == sorted(oracle.match(t)), t


def test_router_sharded_mutation_patches_not_rebuilds():
    """Mesh-mode route churn is O(delta): a mutation patches its
    shard's row of the stacked automaton (per-shard AutoPatcher) —
    no re-flatten (VERDICT r2 weak #5)."""
    from emqx_tpu.parallel.mesh import default_mesh
    from emqx_tpu.router import MatcherConfig, Router

    r = Router(MatcherConfig(mesh=default_mesh(8)), node="n1")
    r.add_route("a/+")
    assert [f for [f] in [r.match_filters(["a/x"])[0]]] == ["a/+"]
    base = r.stats()["rebuilds"]
    patches = r.stats()["patches"]
    r.add_route("b/#")
    assert sorted(r.match_filters(["b/z/q"])[0]) == ["b/#"]
    assert r.stats()["rebuilds"] == base  # patched, not re-flattened
    assert r.stats()["patches"] > patches
    r.delete_route("a/+")
    assert r.match_filters(["a/x"])[0] == []
    assert r.stats()["rebuilds"] == base


def test_router_sharded_churn_parity_vs_oracle():
    """Sustained mesh churn (inserts + deletes across many shards)
    keeps exact oracle parity through the per-shard patch path."""
    import random

    from emqx_tpu.oracle import TrieOracle
    from emqx_tpu.parallel.mesh import default_mesh
    from emqx_tpu.router import MatcherConfig, Router

    rng = random.Random(7)
    words = ["a", "b", "c", "d", "e"]
    r = Router(MatcherConfig(mesh=default_mesh(8)), node="n1")
    oracle = TrieOracle()
    live = set()
    while len(live) < 40:
        depth = rng.randint(1, 4)
        ws = [rng.choice(words + ["+"]) for _ in range(depth)]
        f = "/".join(ws)
        if f not in live:
            live.add(f)
            r.add_route(f)
            oracle.insert(f)
    r.match_filters(["a/b"])  # initial flatten
    base = r.stats()["rebuilds"]
    for step in range(30):
        if rng.random() < 0.5 and live:
            f = rng.choice(sorted(live))
            live.discard(f)
            r.delete_route(f)
            oracle.delete(f)
        else:
            f = "/".join(rng.choice(words + ["+"])
                         for _ in range(rng.randint(1, 4)))
            if f not in live:
                live.add(f)
                r.add_route(f)
                oracle.insert(f)
        if step % 5 == 4:
            topics = ["/".join(rng.choice(words)
                               for _ in range(rng.randint(1, 4)))
                      for _ in range(16)]
            got = r.match_filters(topics)
            for t, g in zip(topics, got):
                assert sorted(g) == sorted(oracle.match(t)), (step, t)
    assert r.stats()["rebuilds"] == base  # zero re-flattens at churn


def test_broker_on_mesh_end_to_end():
    """Full product stack on the mesh: Broker.publish fans out via
    the sharded match + the real FanoutManager tables."""
    from emqx_tpu.broker import Broker
    from emqx_tpu.parallel.mesh import default_mesh
    from emqx_tpu.router import MatcherConfig, Router
    from emqx_tpu.types import Message

    class Rec:
        def __init__(self):
            self.got = []

        def deliver(self, topic, msg):
            self.got.append((topic, msg.payload))

    mesh = default_mesh(8)
    b = Broker(router=Router(MatcherConfig(mesh=mesh), node="local"))
    subs = [Rec() for _ in range(12)]
    for i, s in enumerate(subs):
        b.subscribe(s, f"room/{i}/+")
    everyone = Rec()
    b.subscribe(everyone, "room/#")
    n = b.publish(Message(topic="room/3/temp", payload=b"hot"))
    assert n == 2  # room/3/+ and room/#
    assert subs[3].got == [("room/3/+", b"hot")]
    assert all(not s.got for j, s in enumerate(subs) if j != 3)
    assert everyone.got == [("room/#", b"hot")]


def test_mesh_use_device_false_is_honored():
    """MatcherConfig(mesh=..., use_device=False) must stay on the
    host trie walk — the debugging escape hatch wins over the mesh."""
    from emqx_tpu.parallel.mesh import default_mesh
    from emqx_tpu.router import MatcherConfig, Router

    r = Router(MatcherConfig(mesh=default_mesh(8), use_device=False),
               node="n1")
    r.add_route("esc/+")
    assert not r.use_device_now()
    assert r.match_filters(["esc/x"]) == [["esc/+"]]
    assert r.stats()["rebuilds"] == 0  # never flattened for a device


def test_distributed_init_single_process_noop():
    from emqx_tpu.parallel import distributed

    assert distributed.initialize() is False
    assert distributed.initialize(num_processes=1, process_id=0) is False
    import pytest
    with pytest.raises(ValueError):
        distributed.initialize(num_processes=2, process_id=0)


def test_distributed_global_mesh_factors():
    from emqx_tpu.parallel import distributed

    m = distributed.global_mesh()          # 8 virtual CPU devices
    assert m.shape["data"] * m.shape["trie"] == 8
    m2 = distributed.global_mesh(n_trie=4)
    assert m2.shape == {"data": 2, "trie": 4}
    m3 = distributed.global_mesh(n_data=8)
    assert m3.shape == {"data": 8, "trie": 1}


def test_broker_on_mesh_fanout_parity_with_big_filter():
    """Mesh broker delivers through the device per-shard gather with
    exact parity vs host expectations — including a filter whose
    membership exceeds the d bound (excluded from the gather,
    delivered via the host tail from sh_big)."""
    import random

    from emqx_tpu.broker import Broker
    from emqx_tpu.parallel.mesh import default_mesh
    from emqx_tpu.router import MatcherConfig, Router
    from emqx_tpu.types import Message

    class Rec:
        def __init__(self, i):
            self.i = i
            self.got = []

        def deliver(self, topic, msg):
            self.got.append((topic, msg.topic))

    rng = random.Random(11)
    mesh = default_mesh(8)
    b = Broker(router=Router(
        MatcherConfig(mesh=mesh, fanout_d=16), node="local"))
    subs = [Rec(i) for i in range(40)]
    words = ["u", "v", "w"]
    filters = set()
    while len(filters) < 25:
        depth = rng.randint(1, 3)
        ws = [rng.choice(words + ["+"]) for _ in range(depth)]
        if rng.random() < 0.2:
            ws[-1] = "#"
        filters.add("/".join(ws))
    for f in sorted(filters):
        for s in rng.sample(subs, rng.randint(1, 4)):
            b.subscribe(s, f)
    # one BIG filter: 30 members > fanout_d=16 → host-tail delivery
    for s in subs[:30]:
        b.subscribe(s, "big/#")
    from emqx_tpu.oracle import TrieOracle
    oracle = TrieOracle()
    for f in filters | {"big/#"}:
        oracle.insert(f)
    topics = ["/".join(rng.choice(words)
                       for _ in range(rng.randint(1, 3)))
              for _ in range(30)] + ["big/x", "big/y/z"]
    for t in topics:
        for s in subs:
            s.got.clear()
        n = b.publish(Message(topic=t, payload=b"p"))
        matched = oracle.match(t)
        exp_n = 0
        for f in matched:
            for s in subs:
                if f in b.subscriptions(s):
                    exp_n += 1
        assert n == exp_n, (t, n, exp_n)
        for s in subs:
            got_filters = sorted(f for f, _ in s.got)
            exp_filters = sorted(f for f in matched
                                 if f in b.subscriptions(s))
            assert got_filters == exp_filters, (t, s.i)


def test_mesh_fan_overflow_boosts_d_not_k():
    """A fan-only overflow (per-topic deliveries past the d bound,
    match within k) must grow the learned d — never k, whose
    recompile could not reduce fan-out overflow."""
    from emqx_tpu.broker import Broker
    from emqx_tpu.parallel.mesh import make_mesh
    from emqx_tpu.router import MatcherConfig, Router
    from emqx_tpu.types import Message

    class S:
        def deliver(self, flt, msg):
            pass

    mesh = make_mesh(8, 1)  # one trie shard: all fan rows sum per topic
    b = Broker(router=Router(
        MatcherConfig(mesh=mesh, fanout_d=2), node="local"))
    for f in ("m/+", "m/#", "m/a"):
        b.subscribe(S(), f)
    k0 = b.router.effective_k()
    assert b.router.effective_d() == 2
    # 3 deliveries > d=2 -> fan overflow, host fallback, d boost
    assert b.publish(Message(topic="m/a")) == 3
    assert b.router.effective_d() > 2
    assert b.router.effective_k() == k0  # k untouched
    # the grown d fits the workload: delivered via the device gather
    assert b.publish(Message(topic="m/a")) == 3


def test_sharded_shared_pick_parity():
    """shared_pick_step picks seed % group_size from each matched
    group's member row — exact host parity across shard layouts."""
    from emqx_tpu.parallel.mesh import make_mesh
    from emqx_tpu.parallel.sharded import (build_sharded,
                                           build_sharded_fanout,
                                           place_batch, place_sharded,
                                           shard_filters, shard_of,
                                           shared_pick_step)

    rng = random.Random(5)
    words = ["g1", "g2", "g3", "q"]
    filters = sorted({"/".join(rng.choice(words)
                               for _ in range(rng.randint(1, 3)))
                      for _ in range(30)})
    fids = {f: i for i, f in enumerate(filters)}
    table = WordTable()
    oracle = TrieOracle()
    for f in filters:
        oracle.insert(f)
        for w in f.split("/"):
            table.intern(w)
    from emqx_tpu.ops.match import walk_params

    for n_data, n_trie in [(4, 2), (2, 4)]:
        mesh = make_mesh(n_data, n_trie)
        shards = shard_filters(filters, n_trie)
        auto, parts = build_sharded(shards, fids, table,
                                    return_parts=True)
        wp = walk_params(parts[0], 8)
        members = {f: [fids[f] * 100 + j
                       for j in range(rng.randint(1, 5))]
                   for f in filters}
        rows = [{} for _ in range(n_trie)]
        for f in filters:
            rows[shard_of(f, n_trie)][fids[f]] = members[f]
        gfan = build_sharded_fanout(rows, len(filters))
        B = 8 * n_data
        topics = ["/".join(rng.choice(words)
                           for _ in range(rng.randint(1, 3)))
                  for _ in range(B)]
        seeds = np.arange(B, dtype=np.int32) * 7 + 3
        ids_np, n_np, sys_np = encode_batch(table, topics, 8)
        auto_d = place_sharded(mesh, auto)
        gfan_d = place_sharded(mesh, gfan)
        b = place_batch(mesh, ids_np, n_np, sys_np)
        seeds_d = jax.device_put(
            seeds, jax.sharding.NamedSharding(
                mesh, jax.sharding.PartitionSpec("data")))
        picks, mids, ovf = shared_pick_step(
            mesh, auto_d, gfan_d, *b, seeds_d, k=16, m=16, **wp)
        picks, mids = np.asarray(picks), np.asarray(mids)
        assert not np.asarray(ovf).any()
        for i, t in enumerate(topics):
            got = sorted(int(p) for p in picks[i] if p >= 0)
            expect = sorted(
                members[f][seeds[i] % len(members[f])]
                for f in oracle.match(t))
            assert got == expect, (t, got, expect)


def _pick_family(n_trie, mb, want_spread):
    """Find a topic family whose three matching filters (exact, +, #)
    spread over >1 trie shard with ≤ mb per shard (want_spread=True),
    or all collide in ONE shard with count > mb (False)."""
    from emqx_tpu.parallel.sharded import shard_of

    for i in range(1000):
        fam = f"w{i}"
        filters = [f"{fam}/x", f"{fam}/+", f"{fam}/#"]
        shards = [shard_of(f, n_trie) for f in filters]
        counts = [shards.count(t) for t in range(n_trie)]
        if want_spread:
            if max(counts) <= mb and len(set(shards)) > 1:
                return fam, filters
        else:
            if max(counts) > mb:
                return fam, [f for f, s in zip(filters, shards)
                             if s == max(range(n_trie),
                                         key=counts.__getitem__)]
    raise AssertionError("no suitable family found")


def test_sharded_bitmap_multi_big_union_across_shards():
    """Mesh bitmap path with big filters spread over BOTH trie
    shards: per-shard ORs combine over ICI into one union; the
    multi-big tail delivers each (filter, member) pair exactly."""
    from emqx_tpu.broker import Broker
    from emqx_tpu.parallel.mesh import make_mesh
    from emqx_tpu.router import MatcherConfig, Router
    from emqx_tpu.types import Message

    class S:
        def __init__(self, i):
            self.i = i
            self.got = []

        def deliver(self, flt, msg):
            self.got.append(flt)

    fam, filters = _pick_family(2, mb=2, want_spread=True)
    mesh = make_mesh(4, 2)
    b = Broker(router=Router(
        MatcherConfig(mesh=mesh, fanout_d=4, fanout_mb=2),
        node="local"))
    subs = [S(i) for i in range(30)]
    slices = [subs[:20], subs[5:25], subs[10:30]]
    big_members = dict(zip(filters, slices))
    for f, ms in big_members.items():
        for s in ms:
            b.subscribe(s, f)
    n = b.publish(Message(topic=f"{fam}/x"))
    assert n == 60  # per-subscription delivery: 20 per filter
    for i, s in enumerate(subs):
        exp = sorted(f for f, ms in big_members.items() if s in ms)
        assert sorted(s.got) == exp, (i, s.got, exp)
    assert b.metrics.val("messages.delivered") == 60
    # the device stat counts UNIQUE union members once (not once per
    # trie shard — regression: the OR-reduced union is replicated);
    # no truncation happened (≤ mb big rows per shard)
    st = b.router.drain_device_stats()
    assert st["overflows"] == 0, st
    assert st["deliveries"] == 30, st


def test_sharded_bitmap_mb_truncation_falls_back_exact():
    """More big matches than mb on ONE shard: bovf flags the row and
    the host loop delivers — exact despite the truncated union."""
    from emqx_tpu.broker import Broker
    from emqx_tpu.parallel.mesh import make_mesh
    from emqx_tpu.router import MatcherConfig, Router
    from emqx_tpu.types import Message

    class S:
        def __init__(self):
            self.got = []

        def deliver(self, flt, msg):
            self.got.append(flt)

    fam, colliding = _pick_family(2, mb=1, want_spread=False)
    assert len(colliding) >= 2
    mesh = make_mesh(4, 2)
    b = Broker(router=Router(
        MatcherConfig(mesh=mesh, fanout_d=2, fanout_mb=1),
        node="local"))
    subs = [S() for _ in range(8)]
    for f in colliding:
        for s in subs:
            b.subscribe(s, f)  # 8 > d=2: all big, same shard, > mb=1
    n = b.publish(Message(topic=f"{fam}/x"))
    assert n == 8 * len(colliding)
    for s in subs:
        assert sorted(s.got) == sorted(colliding)


def test_finalize_parts_demotes_all_shards_on_wide_guard():
    """ADVICE r5: a shard whose trie trips compress_automaton's
    wide-mode fallback guard (depth > 31) stays narrow even under
    force_mode="wide"; finalize_parts must then demote EVERY shard to
    narrow instead of stacking mismatched row widths."""
    from emqx_tpu.ops.csr import build_automaton
    from emqx_tpu.parallel.sharded import finalize_parts

    table = WordTable()

    def raw(filters):
        trie = TrieOracle()
        fids = {}
        for f in filters:
            trie.insert(f)
            fids[f] = len(fids)
            for w in f.split("/"):
                table.intern(w)
        return build_automaton(trie, fids, table, skip_hash=True)

    # shard 0: a long literal chain below depth 32 -> wants wide
    deep_ok = "/".join(f"w{i}" for i in range(10))
    # shard 1: depth 33 -> the guard forces narrow regardless
    too_deep = "/".join(f"v{i}" for i in range(33))
    parts = finalize_parts([raw([deep_ok]), raw([too_deep])])
    assert len({p.wt_slots for p in parts}) == 1
    assert all(p.wt_take == 1 for p in parts)  # demoted to narrow


def test_sharded_fanout_is_built_once_at_the_shards_common_capacities():
    """Every shard's table at the largest shard's capacities, floors
    honoured: what two passes of build_fanout chose before."""
    from emqx_tpu.ops.fanout import build_fanout
    from emqx_tpu.parallel.sharded import build_sharded_fanout

    rows = [{0: [1, 2], 5: [3]}, {1: list(range(40))}, {}]
    fan = build_sharded_fanout(rows, 20)
    assert fan.row_ptr.shape == (3, 33) and fan.sub_ids.shape == (3, 64)
    for i, r in enumerate(rows):
        one = build_fanout(r, 20, filter_capacity=32, entry_capacity=64)
        assert (fan.row_ptr[i] == one.row_ptr).all()
        assert (fan.sub_ids[i] == one.sub_ids).all()
        assert (fan.row_pairs[i] == one.row_pairs).all()
    floored = build_sharded_fanout(rows, 20, filter_capacity=128,
                                   entry_capacity=16)
    assert floored.row_ptr.shape == (3, 129)
    assert floored.sub_ids.shape == (3, 64)
