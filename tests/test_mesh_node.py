"""A four-chip mesh node as a deployment of the product: ``[matcher]
mesh = { data = 2, trie = 2 }`` through ``parse_config`` /
``build_node`` alone, and the SERVED path on it (sockets →
``Connection.run`` → ``IngressBatcher`` → ``publish_begin`` → executor
fetch → plan → wire runs → flush) held, delivery by delivery, to an
independent oracle (``TrieOracle`` + ``topic.match``) and to a
single-chip node under the same seeded traffic. Runs on conftest's 8
virtual CPU devices; the chip's run is ``benchmark/`` cell
``fleet_10m_mesh.flood``."""

import asyncio
import collections
import os
import random
import sys

import jax
import pytest

from emqx_tpu import topic as topic_mod
from emqx_tpu.config import ConfigError, build_node, parse_config
from emqx_tpu.node import Node
from emqx_tpu.oracle import TrieOracle
from emqx_tpu.parallel.mesh import mesh_axes
from emqx_tpu.reload import diff_config
from tests.indie_mqtt import IndieClient

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (os.path.join(_ROOT, "benchmark"),
           os.path.join(_ROOT, "benchmark", "tests")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import test_node_loader  # noqa: E402  (benchmark/tests: FIELDS)
import test_planes  # noqa: E402  (benchmark/tests: tracefile.cell_chips)

MESH = {"data": 2, "trie": 2}
#: both nodes take the same [matcher] table but for the mesh; a small
#: threshold puts one filter on the bitmap path (rows across shards)
MATCHER = {"fanout_threshold": 16}
N_FILTERS = 20_000
LEVELS, WORDS = 5, 12
BURST = 12          # QoS 0 publishes between two QoS 1 fences
BREAKER = ("breaker.failures", "breaker.trips", "breaker.fallback.batches")
MESH_COUNTERS = ("mesh.batches", "mesh.topics", "mesh.steps",
                 "mesh.step.topics", "mesh.fused")
#: the device path's occupancy (PR 37), stamped on the mesh path too
PATH_COUNTERS = ("pipeline.device.ns", "pipeline.device.batch_ns")


# -- the configuration's edges -----------------------------------------------

@pytest.mark.parametrize("table, where", [
    ({"data": 2, "tri": 2}, r"matcher\.mesh\.tri"),
    ({"data": 3, "trie": 1}, r"matcher\.mesh\.data"),
    ({"data": 0, "trie": 2}, r"matcher\.mesh\.data"),
    ({"data": True, "trie": 2}, r"matcher\.mesh\.data"),
    ({"data": 2, "trie": False}, r"matcher\.mesh\.trie"),
    ({"data": 2.0, "trie": 2}, r"matcher\.mesh\.data"),
    ([2, 2], r"matcher\.mesh must be a table"),
    (4, r"matcher\.mesh must be a table"),
])
def test_a_malformed_mesh_table_is_a_config_error(table, where):
    with pytest.raises(ConfigError, match=where):
        parse_config({"matcher": {"mesh": table}})


def test_more_chips_than_devices_ends_start_up():
    n = jax.device_count()
    cfg = parse_config({"matcher": {"mesh": {"data": 2 * n, "trie": 2}}})
    with pytest.raises(ConfigError, match=r"matcher\.mesh.*need "
                       rf"{4 * n} devices, have {n}"):
        build_node(cfg)


@pytest.mark.parametrize("table", [
    {}, {"matcher": {}}, {"matcher": {"mesh": {}}},
    {"matcher": {"mesh": {"data": 1}}},
    {"matcher": {"mesh": {"data": 1, "trie": 1}}}])
def test_one_by_one_is_no_mesh(table):
    cfg = parse_config(table)
    assert cfg.matcher is None or cfg.matcher.mesh is None
    node = build_node(cfg)
    bare = Node(boot_listeners=False)
    assert node.router.config == bare.router.config
    assert type(node.router._native) is type(bare.router._native)


def test_a_mesh_table_builds_the_mesh_with_the_node():
    cfg = parse_config({"matcher": {"mesh": MESH}})
    assert cfg.matcher.mesh == MESH          # parsing touches no device
    node = build_node(cfg)
    mesh = node.router.config.mesh
    assert dict(mesh.shape) == MESH and mesh_axes(mesh) == MESH
    assert list(mesh.devices.flat) == jax.devices()[:4]
    assert node.boot_config.matcher.mesh == MESH
    assert node.router._delta_active is False   # C6: the delta stays off


def test_reload_reports_a_changed_mesh_as_restart_only():
    node = build_node(parse_config({"matcher": {"mesh": MESH}}))
    same = diff_config(node, parse_config({"matcher": {"mesh": MESH}}))
    assert [c.knob for c in same] == []
    for table, new in (({"mesh": {"data": 4, "trie": 2}},
                        {"data": 4, "trie": 2}),
                       ({"mesh": {"data": 1, "trie": 1}}, None),
                       ({}, None)):
        changes = diff_config(node, parse_config({"matcher": table}))
        assert [(c.knob, c.kind, c.old, c.new) for c in changes] == [
            ("matcher.mesh", "boot_only", MESH, new)]
    plain = build_node(parse_config({}))
    changes = diff_config(plain, parse_config({"matcher": {"mesh": MESH}}))
    assert [(c.knob, c.kind, c.old, c.new) for c in changes] == [
        ("matcher.mesh", "boot_only", None, MESH)]
    assert diff_config(plain, parse_config(
        {"matcher": {"mesh": {"data": 1, "trie": 1}}})) == []


@pytest.mark.parametrize("section", ["matchr", "Matcher", "mesh", "zone",
                                     "listener", "telemetery"])
def test_an_unknown_top_level_section_is_refused(section):
    with pytest.raises(ConfigError, match=rf"unknown config section: "
                       rf"{section}"):
        parse_config({section: {}})


def test_the_example_file_still_loads():
    from emqx_tpu.config import load_config

    load_config(os.path.join(_ROOT, "etc", "emqx_tpu.toml"))


# -- the two guards PERF.md asked of the next PR that may ------------------

@pytest.fixture(scope="module")
def default_nodes():
    return build_node(parse_config({})), Node(boot_listeners=False)


@pytest.mark.parametrize("field", sorted(test_node_loader.FIELDS))
def test_the_loader_builds_the_default_node(default_nodes, field):
    """What the benchmark's one way to a node rests on
    (benchmark/tests/test_node_loader.py, which no gate runs)."""
    loaded, bare = default_nodes
    get = test_node_loader.FIELDS[field]
    assert get(loaded) == get(bare)


@pytest.mark.parametrize("case", sorted(test_planes.CASES))
def test_a_trace_is_read_on_the_cells_planes(case):
    """``tracefile.cell_chips``: which planes of a trace are a cell's,
    the busiest chip for the reducers and the mean for the driver
    (benchmark/tests/test_planes.py's cases, which no gate runs)."""
    test_planes.test_cell_chips(case)


# -- the served path -------------------------------------------------------

def _population(seed: int):
    rng = random.Random(seed)
    vocab = [[f"w{lvl}_{i}" for i in range(WORDS)] for lvl in range(LEVELS)]
    filters: set = set()
    while len(filters) < N_FILTERS:
        depth = rng.randint(2, LEVELS)
        ws = [rng.choice(vocab[i]) for i in range(depth)]
        r = rng.random()
        if r < 0.25:
            ws[rng.randrange(depth)] = "+"
        elif r < 0.40:
            ws = ws[:rng.randint(1, depth)] + ["#"]
        filters.add("/".join(ws))
    return sorted(filters), vocab


def _topics(seed: int, vocab, n: int):
    rng = random.Random(seed ^ 0x70B1C5)

    def zipf(items):
        while True:
            k = int(rng.paretovariate(1.3)) - 1
            if k < len(items):
                return items[k]

    return ["/".join(zipf(vocab[lvl]) for lvl in range(rng.randint(2, LEVELS)))
            for _ in range(n)]


DEEP = "deep/" + "/".join(f"l{i}" for i in range(18))   # > max_levels
SPECIAL = ["$SYS/test/x", DEEP, "big/7/x", "big/8/x", "nobody/home"]
#: socket subscribers: (client id, [(filter, qos)])
SOCKETS = [
    ("s0", [("w0_0/#", 0), ("fence/+", 1)]),
    ("s1", [("+/w1_1/#", 1), ("fence/+", 1)]),
    ("s2", [("#", 0)]),            # never sees $SYS/...; fences too
    ("s3", [("$SYS/test/+", 0), ("deep/#", 1), ("fence/+", 1)]),
    ("s4", [("w0_1/+/w2_0", 1), ("big/+/x", 0), ("fence/+", 1)]),
]
BIG_FILTER, BIG_SUBS = "big/+/x", 24           # > fanout_threshold


class _Sink:
    def __init__(self):
        self.got = collections.Counter()

    def deliver(self, topic_filter, msg):
        self.got[(topic_filter, msg.topic, bytes(msg.payload))] += 1


async def _burst(pub, name, msgs):
    """QoS 0 publishes ended by a QoS 1 fence: the next burst waits for
    its PUBACK, so an ingress batch holds at most two bursts."""
    for t, payload, qos in msgs:
        await pub.publish(t, payload, qos=qos)
    await pub.publish(f"fence/{name}", b"f", qos=1)


async def _serve(matcher: dict, seed: int):
    """Builds the node from a table, seeds it, runs the traffic over
    real sockets and returns everything that was observed."""
    node = build_node(parse_config({"matcher": matcher}))
    lst = node.add_listener(host="127.0.0.1", port=0)
    filters, vocab = _population(seed)
    sink = _Sink()
    for f in filters:
        node.broker.subscribe(sink, f)
    await node.start()
    spans = []
    tel = node.telemetry
    finish = tel.finish

    def record(span):
        if not span.closed:
            finish(span)
            spans.append({"path": span.path, "bucket": span.bucket,
                          "miss": span.cache_miss,
                          "fallbacks": span.fallbacks})
    tel.finish = record
    m0 = node.metrics.all()
    subs = []
    pubs = [IndieClient(f"p{i}") for i in range(2)]
    bigs = [_Sink() for _ in range(BIG_SUBS)]
    try:
        assert node.router.use_device_now()
        for cid, flts in SOCKETS:
            c = IndieClient(cid)
            await c.connect(port=lst.port)
            await c.subscribe(*flts)
            subs.append(c)
        for p in pubs:
            await p.connect(port=lst.port)
        topics = _topics(seed, vocab, 2_200)
        rng = random.Random(seed ^ 0xABCDEF)
        msgs = []
        for i, t in enumerate(topics):
            if i % 40 == 7:
                t = SPECIAL[(i // 40) % len(SPECIAL)]
            msgs.append((t, b"%06d" % i, 1 if rng.random() < 0.2 else 0))
        half = len(msgs) // 2

        async def publisher(k, part):
            for lo in range(0, len(part), BURST):
                await _burst(pubs[k], f"p{k}", part[lo:lo + BURST])

        # phase A: no big filter, so repeat topics ride the sharded
        # match cache and only misses take the collective step
        await asyncio.gather(publisher(0, msgs[0:half:2]),
                             publisher(1, msgs[1:half:2]))
        phase_a = {"spans": list(spans),
                   "counters": {k: node.metrics.val(k) - m0.get(k, 0)
                                for k in MESH_COUNTERS + PATH_COUNTERS}}
        # phase B: one filter over fanout_threshold (bitmap rows)
        for b in bigs:
            node.broker.subscribe(b, BIG_FILTER)
        await asyncio.gather(publisher(0, msgs[half::2]),
                             publisher(1, msgs[half + 1::2]))
        # the fences are the last message of each publisher and the
        # delivery tail is ordered: a socket that saw both has all
        want = {f"fence/p{k}": want_fences(msgs, half, k) for k in range(2)}
        got = {}
        for (cid, _flts), c in zip(SOCKETS, subs):
            seen = collections.Counter()
            fences = collections.Counter()
            while fences != want:
                p = await c.recv(timeout=60.0)
                seen[(p.topic, bytes(p.payload), p.qos)] += 1
                if p.topic in want:
                    fences[p.topic] += 1
            got[cid] = seen
        m1 = node.metrics.all()
        return {
            "sockets": got, "sink": sink.got,
            "bigs": [b.got for b in bigs],
            "spans": spans, "phase_a": phase_a, "msgs": msgs, "half": half,
            "filters": filters,
            "counters": {k: m1[k] - m0.get(k, 0) for k in m1},
            "breaker": node.broker.breaker.STATE_NAMES[
                node.broker.breaker.state],
        }
    finally:
        tel.finish = finish
        for c in subs + pubs:
            await c.close()
        await node.stop()


def want_fences(msgs, half, k):
    """How many fences publisher ``k`` sends over both phases."""
    a = len(msgs[k:half:2])
    b = len(msgs[half + k::2])
    return -(-a // BURST) + -(-b // BURST)


def _expected(run):
    """The independent oracle: per socket the multiset of (topic,
    payload, qos) by ``topic.match`` alone; for the in-process sink
    ``TrieOracle`` over the whole population."""
    msgs, half = run["msgs"], run["half"]
    fences = [(f"fence/p{k}", b"f", 1) for k in range(2)
              for _ in range(want_fences(msgs, half, k))]
    sockets = {}
    for cid, flts in SOCKETS:
        want = collections.Counter()
        for t, payload, qos in msgs + fences:
            hit = [q for f, q in flts if topic_mod.match(t, f)]
            for q in hit:   # one delivery per matching subscription
                want[(t, payload, min(q, qos))] += 1
        sockets[cid] = want
    trie = TrieOracle()
    for f in run["filters"]:
        trie.insert(f)
    sink = collections.Counter()
    for t, payload, _qos in msgs + fences:
        for f in trie.match(t):
            sink[(f, t, payload)] += 1
    big = collections.Counter()
    for t, payload, _qos in msgs[half:]:
        if topic_mod.match(t, BIG_FILTER):
            big[(BIG_FILTER, t, payload)] += 1
    return sockets, sink, big


@pytest.fixture(scope="module")
def runs():
    seed = 20_270_927
    mesh = asyncio.run(_serve(dict(MATCHER, mesh=MESH), seed))
    one = asyncio.run(_serve(dict(MATCHER), seed))
    return mesh, one


def test_served_mesh_deliveries_equal_the_oracles(runs):
    mesh, _one = runs
    sockets, sink, big = _expected(mesh)
    assert len(mesh["msgs"]) >= 2_000 and len(mesh["filters"]) >= 20_000
    for cid, _f in SOCKETS:
        assert mesh["sockets"][cid] == sockets[cid], cid
        assert sum(sockets[cid].values()) > 0, cid
    assert mesh["sink"] == sink and sum(sink.values()) > 2_000
    assert sum(big.values()) > 0
    for got in mesh["bigs"]:
        assert got == big
    # what the cases were there for
    s3 = mesh["sockets"]["s3"]
    assert any(t == "$SYS/test/x" for t, _p, _q in s3)
    assert any(t == DEEP for t, _p, _q in s3)
    assert not any(t.startswith("$SYS") for t, _p, _q in mesh["sockets"]["s2"])
    assert {q for _t, _p, q in mesh["sockets"]["s1"]} == {0, 1}


def test_served_mesh_deliveries_equal_a_single_chip_nodes(runs):
    mesh, one = runs
    assert mesh["sockets"] == one["sockets"]
    assert mesh["sink"] == one["sink"]
    assert mesh["bigs"] == one["bigs"]
    assert {s["path"] for s in one["spans"]} == {"device"}
    assert not any(one["counters"][k] for k in MESH_COUNTERS)


def test_served_mesh_spans_and_breaker(runs):
    mesh, one = runs
    assert len(mesh["spans"]) > 50
    assert all(s["path"] == "mesh" and s["bucket"] for s in mesh["spans"])
    # the deep topic is the one exact host overflow, on both nodes
    assert sum(s["fallbacks"] for s in mesh["spans"]) > 0
    for run in (mesh, one):
        assert [run["counters"][k] for k in BREAKER] == [0, 0, 0]
        assert run["breaker"] == "closed"


def test_served_mesh_counters(runs):
    mesh, _one = runs
    a = mesh["phase_a"]
    ca = a["counters"]
    # cached regime: a batch takes at most one collective step, for
    # its first-seen topics alone
    assert 0 < ca["mesh.steps"] <= ca["mesh.batches"] == len(a["spans"])
    assert ca["mesh.step.topics"] == sum(s["miss"] for s in a["spans"])
    assert 0 < ca["mesh.step.topics"] < ca["mesh.topics"]
    # no filter over fanout_threshold yet: every batch left as one
    # transfer and two or three programs (Router._dispatch_fused)
    assert ca["mesh.fused"] == ca["mesh.batches"]
    # with a bitmap filter live every batch walks whole (uncached)
    c = mesh["counters"]
    assert c["mesh.batches"] == len(mesh["spans"])
    b = {k: c[k] - ca[k] for k in MESH_COUNTERS}
    assert b["mesh.steps"] == b["mesh.batches"] > 0
    assert b["mesh.step.topics"] == b["mesh.topics"]
    # ... through the legacy whole dispatch
    assert b["mesh.fused"] == 0 and c["mesh.fused"] <= c["mesh.batches"]


def test_served_mesh_holds_the_device_path(runs):
    """``pipeline.device.*`` on the mesh: the fused dispatch stamps
    ``t_enq`` at its one transfer (phase A), the legacy whole dispatch
    at its placement (phase B); a one-chip node's batches count alike."""
    mesh, one = runs
    ca, c = mesh["phase_a"]["counters"], mesh["counters"]
    for lo, hi in (({k: 0 for k in PATH_COUNTERS}, ca), (ca, c),
                   ({k: 0 for k in PATH_COUNTERS}, one["counters"])):
        ns, batch_ns = (hi[k] - lo[k] for k in PATH_COUNTERS)
        # every batch held the path for a while; overlap counts once
        assert 0 < ns <= batch_ns <= 4 * ns
    assert c["pipeline.device.ns"] <= c["loop.wall.ns"] + 20_000_000
    assert c["loop.select.device.ns"] > 0
    assert sum(c[f"loop.select.{k}.ns"]
               for k in ("poll", "device", "clients")) <= c["loop.select.ns"]


def test_mesh_device_counters_reach_the_registry():
    """``device.*`` on the mesh: the step's psums, drained by the stats
    flush in one transfer."""
    node = build_node(parse_config({"matcher": {"mesh": MESH}}))
    sink = _Sink()
    for i in range(8):
        node.broker.subscribe(sink, f"a/{i}/+")
    from emqx_tpu.types import Message

    node.broker.publish_batch(
        [Message(topic=f"a/{i}/x", payload=b"") for i in range(8)])
    dev = node.router.drain_device_stats()
    assert dev == {"matches": 8, "deliveries": 8, "overflows": 0}
    assert node.router.drain_device_stats() == {
        "matches": 0, "deliveries": 0, "overflows": 0}
    node.metrics.fold_device_stats(dev)
    assert node.metrics.val("device.matches") == 8
