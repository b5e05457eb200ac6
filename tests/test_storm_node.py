"""A node whose fleet comes back (benchmark cell
``fleet_1m_storm.subscribe_storm`` at a size a test can hold): 8
gateways subscribe new wildcard filters a few packets a second and
drop a quarter of them, beside flood publishers and subscriber sockets
over real TCP and a seeded ``mixed_tree`` population in an in-process
sink, all driven by the benchmark's own ``subscribe_storm`` loop
through ``loadgen.py``'s ``Publishers``, against a node whose
``[matcher] delta_max_filters`` is small enough that the delta
automaton is folded into the main tables many times while traffic
flows. Every socket's and the sink's deliveries are held to
``benchmark/reference.py``'s plain trie before, during and after every
swap, all three probes of every packet behave, the counters this PR
adds add up to what the loop counted, the fan-out tables went over
every swap without a whole build, and ids came back at the merges and
were taken again. Runs on the CPU backend; the chip's run is the
cell."""

import asyncio
import collections
import importlib
import json
import os
import sys
import tempfile
import time

import pytest

from emqx_tpu.node import Node
from emqx_tpu.router import MatcherConfig
from emqx_tpu.telemetry import REBUILD_STAGES, TelemetryConfig
from tests.indie_mqtt import IndieClient

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_BENCH = os.path.join(_ROOT, "benchmark")

GATEWAYS = 8
SOCKETS = 6
PUBS = 2
DELTA_MAX = 40
_CFG = json.load(open(os.path.join(_BENCH, "configs",
                                   "fleet_1m_storm.json")))
CONFIG = {
    "population": {"kind": "mixed_tree", "filters": 3000, "levels": 5,
                   "words_per_level": 12,
                   "mix": {"literal": 0.6, "plus": 0.25, "hash": 0.15}},
    "sockets": [{"count": SOCKETS, "filters": ["w0_{i}/#"]}],
    "gateways": dict(_CFG["gateways"], count=GATEWAYS),
    "publish_topics": {"law": "zipf_levels", "a": 1.3, "depth": [2, 5],
                       "pool": 2048},
    "payload_bytes": 64,
    "guarantees": {"deliver_qos": 0},
}
SEED = 2147483999
PHASE_S = 1.5
MAX_PHASES = 24


def _traffic():
    with open(os.path.join(_BENCH, "traffic", "subscribe_storm.json")) as f:
        tr = json.load(f)
    # 128 filters a second for this population: a packet every second
    # a gateway, a merge every ~third of a second. (3,000 filters in
    # tables of 4,096: the few hundred adds of a run stay inside them)
    tr.update(publishers=PUBS, burst=16, wait_limit_s=60,
              cold_wait_limit_s=120,
              subscribe_rate_fleet=tr["subscribe_rate"] * 3000 // 128)
    return tr


class _Bench:
    """``benchmark/`` on the import path, as ``loadgen.py`` has it."""

    def __enter__(self):
        sys.path.insert(0, _BENCH)
        return (importlib.import_module("loadgen"),
                importlib.import_module("reference"),
                importlib.import_module("populations.mixed_tree"))

    def __exit__(self, *exc):
        sys.path.remove(_BENCH)


class _Sink:
    def __init__(self):
        self.got = collections.Counter()

    def deliver(self, topic_filter, msg):
        self.got[(topic_filter, msg.topic, bytes(msg.payload[:20]))] += 1


async def _serve() -> dict:
    with _Bench() as (loadgen, reference, population):
        return await _serve_with(loadgen, reference, population)


async def _serve_with(loadgen, reference, population) -> dict:
    filters, _vocab = population.build(CONFIG["population"], SEED)
    node = Node(boot_listeners=False,
                matcher=MatcherConfig(delta_max_filters=DELTA_MAX),
                telemetry=TelemetryConfig(enabled=True))
    lst = node.add_listener(host="127.0.0.1", port=0)
    sink = _Sink()
    for f in filters:
        node.broker.subscribe(sink, f)
    await node.start()
    tmp = tempfile.mkdtemp(prefix="storm-node-")
    plan = loadgen.Plan({"seed": SEED, "config": CONFIG,
                         "traffic": _traffic(), "dir": tmp})
    pubs = loadgen.Publishers(plan)
    pool = plan.pool()
    subs = [IndieClient(f"bench-sub-{i}") for i in range(SOCKETS)]
    router, helper = node.router, node.broker.helper
    try:
        assert router.use_device_now()
        m0 = node.metrics.all()
        for i, c in enumerate(subs):
            await c.connect(port=lst.port)
            await c.subscribe(f"w0_{i}/#")
        assert (await pubs.connect(lst.port)) == {
            "connected": PUBS, "refused": 0}
        start = [0] * PUBS
        sent = {}            # (phase, publisher, sequence) -> topic
        merges_by_phase = []
        for phase in range(1, MAX_PHASES + 1):
            done = await pubs.run_phase(
                {"phase": phase, "t0": time.monotonic() + 0.1,
                 "seconds": PHASE_S, "start": list(start)}, tmp)
            assert done["errors"] == 0 and min(done["sent"]) >= 0, done
            for p, n in enumerate(done["sent"]):
                base = plan.base(p, start)
                for seq in range(n):
                    sent[(phase, p, seq)] = pool[(base + seq) % plan.n_pool]
                start[p] += n
            merges_by_phase.append(router.delta_info()["merges"])
            # merges in at least three different rounds of traffic
            if len(set(merges_by_phase)) >= 4 and min(start) >= 64 \
                    and min(pubs.storm.arrivals) >= 64:
                break
        storm = pubs.storm
        # the plain reference over sockets and sink
        socket_trie = reference.Trie()
        for i in range(SOCKETS):
            socket_trie.insert(f"w0_{i}/#")
        resident = reference.Trie()
        for f in filters:
            resident.insert(f)
        want_sockets = [collections.Counter() for _ in range(SOCKETS)]
        want_sink = collections.Counter()
        for (phase, p, seq), topic in sent.items():
            for f in socket_trie.match(topic):
                want_sockets[int(f[3:-2])][(phase, p, seq)] += 1
            for f in resident.match(topic):
                want_sink[(f, topic, (phase, p, seq))] += 1
        got_sockets = []
        for c, want in zip(subs, want_sockets):
            got = collections.Counter()
            for _ in range(sum(want.values())):
                pkt = await c.recv(timeout=60.0)
                phase, _i, p, seq, _due = loadgen.HEADER.unpack_from(
                    pkt.payload)
                got[(phase, p, seq)] += 1
            got_sockets.append(got)
        await asyncio.sleep(0.3)  # a surplus delivery would come now
        surplus = sum(c.inbox.qsize() for c in subs)
        got_sink = collections.Counter()
        probes_in_sink = 0
        for (f, topic, head), n in sink.got.items():
            phase, _i, p, seq, _due = loadgen.HEADER.unpack(head)
            if phase == 0xFFFF:
                probes_in_sink += n
            else:
                got_sink[(f, topic, (phase, p, seq))] += n
        node._fold_stats(node.stats)
        m1 = node.metrics.all()
        live = [f for q in storm.live for f, _t in q]
        tel = node.telemetry
        return {
            "sockets": got_sockets, "want_sockets": want_sockets,
            "sink": got_sink, "want_sink": want_sink, "surplus": surplus,
            "probes_in_sink": probes_in_sink,
            "counters": {k: m1[k] - m0.get(k, 0) for k in m1},
            "storm": storm, "published": len(sent),
            "merges_by_phase": merges_by_phase,
            "helper": (helper.rebuilds, helper.patches, helper.carries),
            "delta": router.delta_info(),
            "flattens": router.stats()["rebuilds"],
            "routes": [router.has_route(f) for f in live],
            "members": [len(helper.members(f)) for f in live],
            "ids": len(router._id_to_filter),
            "n_filters": len(filters),
            "rebuild_stages": {s: tel.hists["rebuild." + s].count
                               for s in REBUILD_STAGES},
            "rebuild_count": tel.hists["rebuild"].count,
        }
    finally:
        for c in subs:
            await c.close()
        for _r, w in pubs.conns:
            w.close()
        st = getattr(pubs, "storm", None)
        for conn in (st.conns if st else ()):
            if conn is not None:
                conn[1].close()
        await node.stop()


@pytest.fixture(scope="module")
def served():
    return asyncio.run(_serve())


def test_every_socket_gets_its_messages_across_every_swap(served):
    assert served["published"] >= PUBS * 64
    for i, (got, want) in enumerate(zip(served["sockets"],
                                        served["want_sockets"])):
        assert got == want, i
    assert any(served["want_sockets"]) and served["surplus"] == 0
    # merges ended in at least three different rounds of the traffic
    assert len(set(served["merges_by_phase"])) >= 4
    assert served["delta"]["merges"] >= 3


def test_the_sinks_filters_are_the_plain_tries(served):
    assert served["sink"] == served["want_sink"]
    assert served["want_sink"]
    # no resident filter takes a probe: the root cmd is outside the tree
    assert served["probes_in_sink"] == 0


def test_all_three_probes_of_every_packet(served):
    st = served["storm"]
    packets = sum(st.arrivals) // st.per_packet
    assert st.first_failed is None and packets >= GATEWAYS * 4
    assert st.subscribed == sum(st.arrivals) == packets * 16
    assert st.unsubscribed == packets * 4
    # the newest filter's and the oldest live one's came back, the
    # removed filter's did not (the next PUBLISH on the connection was
    # the second probe, every time)
    assert st.answered == 2 * packets and st.withheld == packets
    assert sum(st.probes) == 3 * packets
    assert all(served["routes"]) and set(served["members"]) == {1}
    assert len(served["routes"]) == packets * 12


def test_the_counters_add_up_to_what_the_loop_counted(served):
    c, st = served["counters"], served["storm"]
    packets = sum(st.arrivals) // st.per_packet
    assert c["loop.subscribe.filters"] == st.subscribed + SOCKETS
    assert c["loop.subscribe.calls"] == packets + SOCKETS
    assert c["loop.unsubscribe.filters"] == st.unsubscribed
    assert c["loop.unsubscribe.calls"] == packets
    assert c["loop.subscribe.ns"] > 0 and c["loop.unsubscribe.ns"] > 0
    # a subscription costs microseconds to a few milliseconds (a cold
    # node), not the read chunk that brought it
    assert c["loop.subscribe.ns"] < c["loop.subscribe.filters"] * 20e6
    assert c["client.subscribe"] == c["loop.subscribe.calls"]
    d = served["delta"]
    assert c["automaton.delta.merges"] == d["merges"] >= 3
    # every unsubscribe dropped a route: a pending add retracted or a
    # filter of the main tables masked
    assert c["automaton.delta.retracts"] \
        + c["automaton.delta.tombstones"] == st.unsubscribed
    assert c["automaton.delta.tombstones"] > 0
    assert c["automaton.compaction.ns"] > 0
    assert c["automaton.delta.grows"] >= 0  # 0 on an idle host
    assert c["automaton.freeze.deferred"] >= 0
    assert c["breaker.failures"] == c["breaker.trips"] \
        == c["breaker.fallback.batches"] == 0
    # the compaction's stages, one sample each a merge
    assert set(served["rebuild_stages"].values()) == {d["merges"]}
    assert served["rebuild_count"] >= d["merges"]


def test_the_fan_out_tables_went_over_every_swap(served):
    rebuilds, patches, carries = served["helper"]
    c = served["counters"]
    assert carries == served["delta"]["merges"]
    # one whole build, the first flatten's; never one a merge
    assert rebuilds == 1
    assert c["fanout.rebuilds"] == rebuilds
    assert c["fanout.patches"] == patches > carries


def test_ids_came_back_at_the_merges(served):
    st = served["storm"]
    # without recycling the id space would have grown by every add
    grown = served["ids"] - served["n_filters"] - SOCKETS
    assert grown < st.subscribed
    assert grown <= st.subscribed - st.unsubscribed + 4 * DELTA_MAX
