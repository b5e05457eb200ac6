"""A node whose clients' links flap (benchmark cell
``p2p_flap_2k.churn`` at a size a test can hold): 64 devices and 64
consumers over real sockets beside a seeded ``mixed_tree`` population
in an in-process sink, the devices driven by the benchmark's own
``churn`` loop through ``loadgen.py``'s ``Publishers`` with sessions
of half a second: every device subscribes to its command filter,
probes it, publishes, probes it again and reconnects, every other time
over its open old connection. Every socket's and the sink's deliveries
are held to ``benchmark/reference.py``'s plain trie, both probes of
every session come back, the counters this PR adds add up, and a
takeover whose old channel is torn down after the new session has
subscribed leaves the new session subscribed. Runs on the CPU backend;
the chip's run is the cell."""

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
from emqx_tpu.telemetry import TelemetryConfig
from tests.indie_mqtt import IndieClient

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_BENCH = os.path.join(_ROOT, "benchmark")

PAIRS = 64
SECTOR = 64         # pool positions a device
CONFIG = {
    "population": {"kind": "mixed_tree", "filters": 4000, "levels": 5,
                   "words_per_level": 12,
                   "mix": {"literal": 0.6, "plus": 0.25, "hash": 0.15}},
    "sockets": [{"count": PAIRS, "filters": ["dev/{i}/state/#"]}],
    "devices": {"filters": ["dev/{i}/cmd/#"], "probe": "dev/{i}/cmd/probe",
                "qos": 0},
    "publish_topics": {
        "law": "interleave", "every": 4, "pool": PAIRS * SECTOR,
        "main": {"law": "own_topic", "owners": PAIRS,
                 "topic": "dev/{i}/state"},
        "background": {"law": "zipf_levels", "a": 1.3, "depth": [2, 5]}},
    "payload_bytes": 64,
    "guarantees": {"deliver_qos": 0},
}
SEED = 2147483999
PHASE_S = 1.5       # a round of traffic
MAX_PHASES = 20


def _traffic():
    with open(os.path.join(_BENCH, "traffic", "churn.json")) as f:
        tr = json.load(f)
    tr.update(publishers=PAIRS, burst=4, session_s=0.5, wait_limit_s=60,
              cold_wait_limit_s=120)
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
                telemetry=TelemetryConfig(enabled=True))
    lst = node.add_listener(host="127.0.0.1", port=0)
    sink = _Sink()
    for f in filters:
        node.broker.subscribe(sink, f)
    await node.start()
    tmp = tempfile.mkdtemp(prefix="flap-node-")
    plan = loadgen.Plan({"seed": SEED, "config": CONFIG,
                         "traffic": _traffic(), "dir": tmp})
    pubs = loadgen.Publishers(plan)
    pool = plan.pool()
    subs = [IndieClient(f"bench-sub-{i}") for i in range(PAIRS)]
    try:
        assert node.router.use_device_now()
        m0 = node.metrics.all()
        for i, c in enumerate(subs):
            await c.connect(port=lst.port)
            await c.subscribe(f"dev/{i}/state/#")
        assert (await pubs.connect(lst.port)) == {
            "connected": PAIRS, "refused": 0}
        start = [0] * PAIRS
        sent = {}            # (phase, device, sequence) -> topic
        flattens = {node.router.stats()["rebuilds"]}
        # rounds of the cell's traffic until every device has sent a
        # few bursts and reconnected both ways (a cold node compiles
        # through its first rounds, and a loaded host is slow: the
        # count of rounds is not the test's business)
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
            flattens.add(node.router.stats()["rebuilds"])
            if min(start) >= 8 and min(pubs.fleet.reconnects) >= 2:
                break
        fleet = pubs.fleet
        # the plain reference: which consumer's filter and which
        # resident filters match each message
        socket_trie = reference.Trie()
        for i in range(PAIRS):
            socket_trie.insert(f"dev/{i}/state/#")
        resident = reference.Trie()
        for f in filters:
            resident.insert(f)
        want_sockets = [collections.Counter() for _ in range(PAIRS)]
        want_sink = collections.Counter()
        for (phase, p, seq), topic in sent.items():
            for f in socket_trie.match(topic):
                want_sockets[int(f.split("/")[1])][(phase, p, seq)] += 1
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
                assert pkt.qos == 0
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
        # the automaton's counters wait for the stats flush
        node._fold_stats(node.stats)
        m1 = node.metrics.all()
        helper = node.broker.helper
        return {
            "sockets": got_sockets, "want_sockets": want_sockets,
            "sink": got_sink, "want_sink": want_sink, "surplus": surplus,
            "probes_in_sink": probes_in_sink,
            "counters": {k: m1[k] - m0.get(k, 0) for k in m1},
            "fleet": fleet, "published": len(sent),
            "flattens": len(flattens),
            "helper": (helper.rebuilds, helper.patches,
                       helper.rows_patched),
            "delta": node.router.delta_info(),
            "routes": [node.router.has_route(f"dev/{i}/cmd/#")
                       for i in range(PAIRS)],
            "members": [len(helper.members(f"dev/{i}/cmd/#"))
                        for i in range(PAIRS)],
            "channels": node.cm.connection_count(),
        }
    finally:
        for c in subs:
            await c.close()
        for _r, w in pubs.conns:
            w.close()
        await node.stop()


@pytest.fixture(scope="module")
def served():
    return asyncio.run(_serve())


def test_every_consumer_gets_its_devices_messages_and_no_other(served):
    assert served["published"] >= PAIRS * 8
    for i, (got, want) in enumerate(zip(served["sockets"],
                                        served["want_sockets"])):
        assert got == want, i
        assert want and all(n == 1 for n in want.values())
    assert served["surplus"] == 0


def test_the_sinks_filters_are_the_plain_tries(served):
    assert served["sink"] == served["want_sink"]
    assert served["want_sink"]  # the trickle reaches resident filters


def test_both_probes_of_every_session_came_back(served):
    fleet = served["fleet"]
    reconnects = sum(fleet.reconnects)
    # half a second a session over five seconds of phases, less what
    # a cold node's compiles took of them (one reconnect for all that
    # is overdue): both kinds of reconnect, on every device
    assert reconnects >= PAIRS * 2 and min(fleet.reconnects) >= 2
    assert not any(fleet.dead) and fleet.first_failed is None
    assert fleet.opened == reconnects
    # the first session's one probe, then two a reconnect: the old
    # session's last and the new one's first
    assert sum(fleet.probes) == fleet.answered == PAIRS + 2 * reconnects
    # every other one over the open old connection
    assert fleet.takeovers == sum(r // 2 for r in fleet.reconnects)
    assert fleet.takeovers >= PAIRS
    # a probe goes to its device alone: what a resident filter takes of
    # it is the population's business, no consumer's
    assert all(served["routes"]) and served["members"] == [1] * PAIRS


def test_the_sessions_are_counted_where_they_open_and_close(served):
    c = served["counters"]
    reconnects = sum(served["fleet"].reconnects)
    assert c["client.connected"] == 2 * PAIRS + reconnects
    assert c["loop.session.open.calls"] == c["client.connected"]
    assert c["client.disconnected"] == reconnects
    assert c["loop.session.close.calls"] == c["client.disconnected"]
    assert c["loop.session.open.ns"] > 0 and c["loop.session.close.ns"] > 0
    # a session costs microseconds to milliseconds, not the read chunk
    # that brought it
    assert c["loop.session.open.ns"] < c["client.connected"] * 50e6
    assert c["session.created"] == c["client.connected"]
    assert served["channels"] == 2 * PAIRS


def test_the_fan_out_tables_were_patched_and_rebuilt_with_the_epoch(served):
    c = served["counters"]
    rebuilds, patches, rows = served["helper"]
    # whole builds: one an automaton epoch, none for a membership change
    assert 1 <= rebuilds <= served["flattens"]
    assert c["fanout.rebuilds"] == rebuilds
    assert c["fanout.patches"] == patches > 0
    reconnects = sum(served["fleet"].reconnects)
    # every session wrote its row and cleared it (the first sessions'
    # rows may have gone into the first build)
    assert 2 * reconnects <= rows <= 2 * (reconnects + PAIRS)
    assert c["fanout.sync.ns"] > 0


def test_the_delta_took_the_route_changes(served):
    c, delta = served["counters"], served["delta"]
    reconnects = sum(served["fleet"].reconnects)
    assert delta["active"] and delta["pending"] > 0
    assert delta["merges"] == 0
    # every session's end dropped its route: a pending add retracted,
    # or a filter of the main tables masked
    assert c["automaton.delta.retracts"] \
        + c["automaton.delta.tombstones"] == reconnects
    assert c["automaton.delta.tombstones"] == delta["tombstones"] <= PAIRS
    assert c["automaton.delta.filters"] >= reconnects
    assert c["automaton.delta.probes"] > 0
    assert c["breaker.failures"] == c["breaker.trips"] \
        == c["breaker.fallback.batches"] == 0


def test_a_takeover_torn_down_late_leaves_the_new_session_subscribed():
    """The old channel's clean-up (its unsubscribe of the device's
    filter) runs after the new session's SUBACK: the route's reference
    count and the fan-out row come out with the new session in them."""
    async def go():
        node = Node(boot_listeners=False)
        lst = node.add_listener(host="127.0.0.1", port=0)
        await node.start()
        flt, probe = "dev/9/cmd/#", "dev/9/cmd/probe"
        old, new = IndieClient("bench-pub-9"), IndieClient("bench-pub-9")
        late = []
        kick = node.cm._kick
        node.cm._kick = lambda chan, discard: late.append((chan, discard))
        try:
            await old.connect(port=lst.port)
            await old.subscribe(flt)
            old_chan = node.cm.lookup_channel("bench-pub-9")
            await new.connect(port=lst.port)      # the takeover
            assert len(late) == 1 and late[0][0] is old_chan
            await new.subscribe(flt)
            new_chan = node.cm.lookup_channel("bench-pub-9")
            assert new_chan is not old_chan
            helper = node.broker.helper
            assert len(helper.members(flt)) == 2  # both, for now
            kick(*late[0])                        # the late tear-down
            await asyncio.sleep(0.05)
            assert node.cm.lookup_channel("bench-pub-9") is new_chan
            assert node.router.has_route(flt)
            assert helper.members(flt) == {
                helper.registry.sid(new_chan.session)}
            assert node.router.route_refs(flt, node.router.node) == 1
            await new.publish(probe, b"ping")
            got = await new.recv(timeout=10.0)
            assert (got.topic, bytes(got.payload)) == (probe, b"ping")
            await asyncio.sleep(0.2)
            assert new.inbox.qsize() == 0         # once, not twice
            return True
        finally:
            node.cm._kick = kick
            for c in (old, new):
                await c.close()
            await node.stop()

    assert asyncio.run(go())
