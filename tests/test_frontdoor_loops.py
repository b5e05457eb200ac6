"""Multi-loop front door: cross-loop delivery parity + invariants
(docs/DISPATCH.md "Multi-loop front door").

The pinned contract: a node with ``loops = N`` delivers EXACTLY what
the single-loop node delivers — per-connection wire content (topic,
payload, qos, retain, dup, properties), per-session packet-id
sequences, delivery counts, and metric deltas — across QoS0 broadcast,
QoS1/2 per-subscriber frames, shared groups, and session takeover,
including takeover of a session owned by a *different* loop. On top of
parity, the ring's own invariants: at most one cross-loop handoff per
loop per batch, deterministic round-robin placement, and the egress
pre-serialization staying off-loop (``delivery.serialize.onloop`` 0)
across the ring.
"""

import asyncio

import pytest

from emqx_tpu.broker import DispatchConfig
from emqx_tpu.mqtt import constants as C
from emqx_tpu.router import MatcherConfig

from helpers import PathGate, broker_node, node_port, until
from mqtt_client import TestClient

#: metric keys whose deltas are timing-dependent (wakeup coalescing,
#: handoff counts scale with how publishes landed in batch ticks) —
#: excluded from the equality dict; the xloop ones get their own
#: invariant assertions below
_TIMING_KEYS = ("delivery.wakeups", "delivery.xloop.handoffs",
                "delivery.xloop.deliveries",
                # which QoS0 publishes share a batch tick decides
                # which groups form a wire run (tests/test_wire_run.py
                # pins the frames and counters either way)
                "delivery.wire_runs", "delivery.wire_run.frames",
                # ... and how often a filter recurs inside one group
                # (tests/test_delivery_walk.py pins the count)
                "delivery.plan.resolves")


async def _workload(loops: int):
    """The parity workload: mixed-QoS fan-out + shared group through
    a ``loops``-sharded node; returns (comparable, xstats)."""
    async with broker_node(
            loops=loops,
            matcher=MatcherConfig(device_min_filters=0),
            dispatch_config=DispatchConfig()) as node:
        port = node_port(node)
        a0 = TestClient("a0")                     # v4 qos0
        a1 = TestClient("a1")                     # v4 qos1
        a2 = TestClient("a2", version=C.MQTT_V5)  # v5 qos2
        a3 = TestClient("a3", version=C.MQTT_V5)  # v5 subid slow path
        a4 = TestClient("a4")                     # v4 qos1 literal
        g1 = TestClient("g1")                     # shared group
        g2 = TestClient("g2")
        pub = TestClient("wp")
        clients = [a0, a1, a2, a3, a4, g1, g2, pub]
        # sequential connects => deterministic round-robin placement
        for cli in clients:
            await cli.connect(port=port)
        await a0.subscribe("L/+", qos=0)
        await a1.subscribe("L/#", qos=1)
        await a2.subscribe("L/t", qos=2)
        await a3.subscribe("L/+", qos=1,
                           props={"Subscription-Identifier": 7})
        await a4.subscribe("L/t", qos=1)
        await g1.subscribe("$share/g/L/t", qos=1)
        await g2.subscribe("$share/g/L/t", qos=1)
        on_t = [a0, a1, a2, a3, a4]   # subscribers matching L/t
        on_x = [a0, a1, a3]           # subscribers matching L/x
        expect = {c: 0 for c in on_t}
        for i in range(3):
            await pub.publish("L/t", payload=b"q0-%d" % i, qos=0)
            for c in on_t:
                expect[c] += 1
        for i in range(4):
            await pub.publish("L/t", payload=b"q1-%d" % i, qos=1)
            for c in on_t:
                expect[c] += 1
        await pub.publish("L/x", payload=b"q1-x", qos=1)
        for c in on_x:
            expect[c] += 1
        for i in range(2):
            await pub.publish("L/t", payload=b"q2-%d" % i, qos=2)
            for c in on_t:
                expect[c] += 1
        await pub.publish("L/t", payload=b"rt", qos=1, retain=True)
        for c in on_t:
            expect[c] += 1
        got = []
        for cli in on_t:
            pkts = []
            for _ in range(expect[cli]):
                p = await cli.recv(timeout=5.0)
                pkts.append((p.topic, bytes(p.payload), p.qos,
                             p.retain, p.dup, p.packet_id,
                             dict(p.properties or {})))
            # batch-tick grouping may interleave topics; per-payload
            # identity (incl. the pid the session assigned it) is the
            # contract
            pkts.sort(key=lambda t: t[1])
            got.append(pkts)
        shared_total = 0
        for cli in (g1, g2):
            try:
                while True:
                    await asyncio.wait_for(cli.inbox.get(), 0.5)
                    shared_total += 1
            except asyncio.TimeoutError:
                pass
        got.append(shared_total)
        got.append({k: v for k, v in node.metrics.all().items()
                    if v and k.startswith(("messages.", "delivery.",
                                           "packets.publish"))
                    and k not in _TIMING_KEYS
                    and k != "delivery.serialize.onloop"})
        xstats = {
            "handoffs": node.metrics.val("delivery.xloop.handoffs"),
            "xdeliveries": node.metrics.val(
                "delivery.xloop.deliveries"),
            "onloop": node.metrics.val("delivery.serialize.onloop"),
            "flushes": node.ingress.flushes,
            "loop_conns_seen": (node.listeners[0].loop_connections()
                                if loops > 1 else []),
        }
        for cli in clients:
            await cli.close()
        return got, xstats


@pytest.mark.parametrize("loops", [2, 4])
async def test_delivery_parity_vs_single_loop(loops):
    # a throw-away pass pays the process's cold compiles: their loop
    # lag raises an overload alarm, and the alarm's $SYS publish would
    # count into one side's messages.* deltas only
    await _workload(1)
    base, base_x = await _workload(1)
    multi, multi_x = await _workload(loops)
    # wire content, pid sequences, delivery counts, metric deltas —
    # identical whatever loop each session landed on
    assert multi == base
    # single-loop control: the ring never engaged
    assert base_x["handoffs"] == 0 and base_x["xdeliveries"] == 0
    # multi-loop: the ring actually carried deliveries, with at most
    # one handoff per loop per batch, and the on-loop serialize count
    # (the workload's deliberate slow-path subscribers: subid, shared
    # redispatch state) unchanged by the sharding
    assert multi_x["xdeliveries"] > 0
    assert 0 < multi_x["handoffs"] <= multi_x["flushes"] * (loops - 1)
    assert multi_x["onloop"] == base_x["onloop"], (base_x, multi_x)


async def test_onloop_stays_zero_for_eligible_traffic_across_ring():
    """The PR 5 invariant survives the ring: eligible QoS1 fan-out
    patches pre-built templates on the OWNING loop — zero on-loop
    serializes with loops=2, exactly as with loops=1."""
    async with broker_node(
            loops=2,
            matcher=MatcherConfig(device_min_filters=0)) as node:
        port = node_port(node)
        subs = [TestClient(f"z{i}") for i in range(4)]
        pub = TestClient("zp")
        for cli in subs + [pub]:
            await cli.connect(port=port)
        for cli in subs:
            await cli.subscribe("z/+", qos=1)
        for i in range(6):
            await pub.publish("z/t", payload=b"%d" % i, qos=1)
        for cli in subs:
            for _ in range(6):
                await cli.recv(timeout=5.0)
        assert node.metrics.val("delivery.serialize.onloop") == 0
        assert node.metrics.val("delivery.xloop.deliveries") > 0
        for cli in subs + [pub]:
            await cli.close()


async def test_a_held_flush_is_released_on_a_multi_loop_node():
    """ISSUE 41 across the ring: a peer loop's submits that find a
    batch on the device path are held on the home loop (the kick's
    flush begins nothing), leave as one batch when that batch lands,
    and are delivered and acknowledged in order."""
    async with broker_node(
            loops=2,
            matcher=MatcherConfig(device_min_filters=0)) as node:
        port = node_port(node)
        sub, pub = TestClient("hs"), TestClient("hp")
        await sub.connect(port=port)          # conn 1 -> loop 0 (home)
        await pub.connect(port=port)          # conn 2 -> loop 1 (peer)
        assert node.listeners[0].loop_connections() == [1, 1]
        await sub.subscribe("h/+", qos=1)
        await pub.publish("h/warm", qos=1)    # compiles, makes the pool
        assert (await sub.recv(timeout=30.0)).topic == "h/warm"
        p, ing = PathGate(node), node.ingress
        try:
            await pub.publish("h/0", qos=0)
            await p.on_the_path()
            await pub.publish("h/1", qos=0)
            await pub.publish("h/2", qos=0)
            ack = asyncio.get_running_loop().create_task(
                pub.publish("h/3", qos=1, timeout=30.0))
            await until(lambda: len(ing._pending) == 3)
            await asyncio.sleep(0.05)
            # the peer loop's kick reached the home loop and was held
            assert p.began == [["h/0"]] and not ack.done()
            assert ing._inflight == 1
            assert p.held == p.counted() >= 1
        finally:
            p.land()
        await asyncio.wait_for(ack, 30.0)
        got = [(await sub.recv(timeout=5.0)).topic for _ in range(4)]
        assert got == ["h/0", "h/1", "h/2", "h/3"]
        assert p.began == [["h/0"], ["h/1", "h/2", "h/3"]]
        await until(lambda: ing._on_path == 0 and ing._inflight == 0)
        for cli in (sub, pub):
            await cli.close()


async def test_round_robin_placement_is_deterministic():
    async with broker_node(loops=3) as node:
        port = node_port(node)
        clients = [TestClient(f"rr{i}") for i in range(7)]
        for cli in clients:
            await cli.connect(port=port)
        # conn k lands on loop k % 3: 7 conns -> [3, 2, 2]
        assert node.listeners[0].loop_connections() == [3, 2, 2]
        for cli in clients:
            await cli.close()
        for _ in range(100):
            if node.listeners[0].loop_connections() == [0, 0, 0]:
                break
            await asyncio.sleep(0.02)
        assert node.listeners[0].loop_connections() == [0, 0, 0]


async def test_cross_loop_takeover():
    """A reconnecting client accepted by a DIFFERENT loop takes over
    the live session: the takeover marshals onto the old owning loop,
    the session resumes with its inflight/pid state, and subsequent
    deliveries route to the new owning loop."""
    async with broker_node(
            loops=2,
            matcher=MatcherConfig(device_min_filters=0)) as node:
        port = node_port(node)
        tk1 = TestClient("tk", version=C.MQTT_V5, clean_start=False,
                         properties={"Session-Expiry-Interval": 300})
        await tk1.connect(port=port)          # conn 1 -> loop 0
        await tk1.subscribe("tk/t", qos=1)
        pub = TestClient("tkp")
        await pub.connect(port=port)          # conn 2 -> loop 1
        await pub.publish("tk/t", payload=b"before", qos=1)
        p = await tk1.recv(timeout=5.0)
        assert p.payload == b"before"
        assert node.listeners[0].loop_connections() == [1, 1]
        filler = TestClient("fill")
        await filler.connect(port=port)       # conn 3 -> loop 0
        tk2 = TestClient("tk", version=C.MQTT_V5, clean_start=False,
                         properties={"Session-Expiry-Interval": 300})
        await tk2.connect(port=port)          # conn 4 -> loop 1 (!)
        assert tk2.connack.session_present
        assert node.metrics.val("session.takeovered") == 1
        # the old owner was told why, on ITS loop
        d = await asyncio.wait_for(tk1.acks.get(), 5.0)
        assert getattr(d, "reason_code", None) == 0x8E, d
        # deliveries now route to the session's NEW owning loop
        await pub.publish("tk/t", payload=b"after", qos=1)
        p2 = await tk2.recv(timeout=5.0)
        assert p2.payload == b"after"
        # pid sequence continued from the taken-over session state
        assert p2.packet_id > p.packet_id
        for cli in (tk1, tk2, pub, filler):
            await cli.close()


async def test_loops1_is_the_single_loop_build():
    """loops = 1 constructs no LoopGroup: classic asyncio server,
    lock-free metrics, no ring — byte-for-byte the pre-multi-loop
    node."""
    async with broker_node(loops=1) as node:
        assert node.loop_group is None
        assert node.broker.loop_group is None
        lst = node.listeners[0]
        assert lst._accept_task is None and lst._server is not None
        assert node.metrics._lock is None
        assert node.ingress.accepts_threadsafe() is False
        c = TestClient("one")
        await c.connect(port=node_port(node))
        await c.subscribe("o/t", qos=0)
        await c.publish("o/t", payload=b"hi")
        assert (await c.recv(timeout=5.0)).payload == b"hi"
        assert node.metrics.val("delivery.xloop.handoffs") == 0
        await c.close()


def test_loops_validation():
    from emqx_tpu.config import ConfigError, parse_config
    from emqx_tpu.node import Node

    with pytest.raises(ValueError):
        Node(boot_listeners=False, loops=0)
    with pytest.raises(ConfigError):
        parse_config({"node": {"loops": 0}})
    with pytest.raises(ConfigError):
        parse_config({"node": {"loops": True}})
    assert parse_config({"node": {"loops": 4}}).loops == 4
