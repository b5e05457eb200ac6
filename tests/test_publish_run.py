"""The publish run (ISSUE 30): a read chunk's plain PUBLISH packets
go to the channel as one list.

**Differential.** The same byte stream goes through one
``Connection.run`` twice, on two fresh nodes: once as the product
takes it (runs engage wherever the packets allow), once with the
channel reporting that no run may start, so every packet takes
``Connection._process``. The product has no switch for this: the test
overrides ``handle_publish_run`` on the one channel it drives. Both
legs must hand the broker the same batches (topics, payloads, flags,
headers, order, list for list), write the same bytes, end with the
same disconnect reason and move every counter alike, bar the run's
own. A plain account of what must be queued, made by the generator
without the product's admission code, stands beside both.

Then: ``submit_many`` against N × ``submit``; how fast an ACL change
bites inside a run; message ids.
"""

import asyncio
import json
import os
import random
import types

import pytest

from emqx_tpu.access_control import DENY
from emqx_tpu.connection import Connection
from emqx_tpu.hooks import STOP
from emqx_tpu.metrics import ALL_METRICS, Metrics
from emqx_tpu.modules.acl_file import AclFileModule
from emqx_tpu.mqtt import constants as C
from emqx_tpu.mqtt.frame import serialize
from emqx_tpu.mqtt.packet import (Connect, Pingreq, Publish, Subscribe)
from emqx_tpu.node import Node
from emqx_tpu.telemetry import TelemetryConfig
from emqx_tpu.types import Message
from emqx_tpu.zone import Zone

from helpers import Wire

RUN = "channel.publish_run.msgs"


class Leg:
    """One node, one connection, and a record of every batch the
    ingress handed to ``Broker.publish_begin``."""

    def __init__(self, node, zone, runs: bool):
        self.node = node
        self.batches = []
        begin = node.broker.publish_begin

        def record(msgs, *a, **kw):
            mine = [m for m in msgs if not m.topic.startswith("$SYS/")]
            if mine:
                self.batches.append(mine)
            return begin(msgs, *a, **kw)
        node.broker.publish_begin = record
        self.reader = asyncio.StreamReader()
        self.wire = Wire()
        self.conn = Connection(self.reader, self.wire, node.broker,
                               node.cm, zone=zone)
        if not runs:
            self.conn.channel.handle_publish_run = \
                lambda pkts, start, stop: (0, [])
        self.task = asyncio.get_running_loop().create_task(
            self.conn.run())

    @property
    def msgs(self):
        return [m for b in self.batches for m in b]

    async def feed(self, data: bytes, settle: bool = True):
        self.reader.feed_data(data)
        if settle:
            await self.settle()

    async def settle(self):
        for _ in range(200):
            await asyncio.sleep(0)
            if not self.reader._buffer and not self.node.broker.ingress._pending:
                break
        await self.node.broker.ingress.drain()
        await asyncio.sleep(0)

    async def finish(self):
        self.reader.feed_eof()
        await asyncio.wait_for(self.task, 10)
        await self.node.broker.ingress.drain()

    def seen(self):
        """What both legs must agree on, to the byte."""
        return {
            "batches": [[(m.topic, m.payload, m.qos, m.from_,
                          dict(m.flags), dict(m.headers))
                         for m in b] for b in self.batches],
            "out": bytes(self.wire.out),
            "reason": self.conn.channel.disconnect_reason,
            "metrics": {k: v for k, v in self.node.metrics.all().items()
                        if k != RUN},
        }


async def _node(name, batch_size=256):
    node = Node(name=name, boot_listeners=False, batch_size=batch_size,
                telemetry=TelemetryConfig(enabled=False))
    await node.start()
    return node


def _connect(ver=C.MQTT_V4, cid="pr-pub", username="u1"):
    return serialize(Connect(proto_ver=ver, client_id=cid,
                             username=username, keepalive=0), ver)


# -- the streams ------------------------------------------------------------

WORDS = [f"w{i}" for i in range(12)]


def _topic(rnd, depth=None):
    return "/".join(rnd.choice(WORDS)
                    for _ in range(depth or rnd.randint(2, 5)))


def _mix(rnd, n, ver, plain=0.85, bad=None):
    """``n`` packets as (packet, queued?) pairs: mostly plain QoS 0
    publishes, with QoS 1 / QoS 2 publishes, retained ones,
    SUBSCRIBEs and PINGREQs between them; ``bad(rnd)`` (a packet and
    whether the stream ends there) takes the place of one packet in
    twenty."""
    pid = 0
    for i in range(n):
        r = rnd.random()
        if bad is not None and r < 0.05 and i > 3:
            pkt, queued, ends = bad(rnd)
            if not ends or i > n // 2:
                yield pkt, queued
                if ends:
                    return
                continue
        if r < plain:
            yield Publish(topic=_topic(rnd), qos=0,
                          payload=rnd.randbytes(rnd.randint(0, 40))), True
            continue
        pid = pid % 60000 + 1
        kind = rnd.randint(0, 4)
        if kind == 0:
            yield Publish(topic=_topic(rnd), qos=1, packet_id=pid,
                          payload=b"q1"), True
        elif kind == 1:
            yield Publish(topic=_topic(rnd), qos=2, packet_id=pid,
                          payload=b"q2"), True
        elif kind == 2:
            yield Publish(topic=_topic(rnd), qos=0, retain=True,
                          payload=b"r"), True
        elif kind == 3:
            opts = {"qos": 0} if ver != C.MQTT_V5 else \
                {"qos": 0, "nl": 0, "rap": 0, "rh": 0}
            yield Subscribe(packet_id=pid,
                            topic_filters=[(f"side/{pid}/#", opts)]), False
        else:
            yield Pingreq(), False


def _wild(rnd):
    return Publish(topic="w1/+/w2", qos=0, payload=b"x"), False, True


def _deep(rnd):
    qos = rnd.randint(0, 1)
    return Publish(topic=_topic(rnd, 7), qos=qos, payload=b"deep",
                   packet_id=77 if qos else None), False, False


def _denied(rnd):
    qos = rnd.randint(0, 1)
    return Publish(topic=f"secret/{rnd.choice(WORDS)}", qos=qos,
                   payload=b"no", packet_id=78 if qos else None), \
        False, False


def _denied_fatal(rnd):
    return Publish(topic="secret/x", qos=0, payload=b"no"), False, True


def _retained(rnd):
    return Publish(topic=_topic(rnd), qos=0, retain=True,
                   payload=b"r"), False, False


class _Alias:
    """v5 PUBLISHes that set a Topic-Alias and ones that use it."""

    def __init__(self):
        self.known = {}

    def __call__(self, rnd):
        alias = rnd.randint(1, 4)
        if alias in self.known and rnd.random() < 0.6:
            pkt = Publish(topic="", qos=0, payload=b"by-alias",
                          properties={"Topic-Alias": alias})
        else:
            self.known[alias] = _topic(rnd)
            pkt = Publish(topic=self.known[alias], qos=0, payload=b"set",
                          properties={"Topic-Alias": alias,
                                      "User-Property": [("k", "v")]})
        return pkt, True, False


ACL_RULES = {"rules": [("deny", "all", "publish", ["secret/#"]),
                       ("allow", "all", "pubsub", ["#"])]}

#: name → (protocol version, zone overrides, the odd packet, ACL module?)
CASES = {
    "v4_mix": (C.MQTT_V4, {}, None, False),
    "v5_mix": (C.MQTT_V5, {}, None, False),
    "v4_wildcard_topic": (C.MQTT_V4, {}, _wild, False),
    "v5_wildcard_topic": (C.MQTT_V5, {}, _wild, False),
    "over_deep_topic": (C.MQTT_V5, {"max_topic_levels": 6}, _deep, False),
    "v5_topic_alias": (C.MQTT_V5, {}, "alias", False),
    "acl_file_deny": (C.MQTT_V5, {}, _denied, True),
    "acl_file_deny_disconnect": (C.MQTT_V4,
                                 {"acl_deny_action": "disconnect"},
                                 _denied_fatal, True),
    "acl_nomatch_deny": (C.MQTT_V4, {"acl_nomatch": "deny"}, None, False),
    "quota": (C.MQTT_V5, {"quota_conn_messages": (1e9, 1e9)}, None, False),
    "mountpoint": (C.MQTT_V4, {"mountpoint": "fleet/%c/"}, None, False),
    "retain_unavailable": (C.MQTT_V5, {"retain_available": False},
                           _retained, False),
    "acl_off": (C.MQTT_V4, {"enable_acl": False}, None, False),
}


async def _both(case, seed, n=400):
    ver, zone_kw, bad, acl = CASES[case]
    rnd = random.Random(seed)
    if bad == "alias":
        bad = _Alias()
    pairs = list(_mix(rnd, n, ver, bad=bad))
    if zone_kw.get("retain_available") is False \
            or zone_kw.get("acl_nomatch") == "deny":
        pairs = [(p, q and not (zone_kw.get("acl_nomatch") == "deny"
                                or getattr(p, "retain", False)))
                 for p, q in pairs]
    stream = b"".join(serialize(p, ver) for p, _ in pairs)
    want = [p for p, q in pairs if q and isinstance(p, Publish)]
    legs = []
    for runs in (True, False):
        # 24: the batch_size boundary falls inside runs, not on the
        # 32-packet line where the loop would flush anyway
        node = await _node(f"pr-{case}-{seed}-{int(runs)}@test",
                           batch_size=24)
        try:
            if acl:
                node.modules.load(AclFileModule, ACL_RULES)
            zone = Zone(name=f"z-{case}", **zone_kw)
            leg = Leg(node, zone, runs)
            await leg.feed(_connect(ver))
            assert leg.conn.channel.state == "connected"
            # one chunk and a few uneven ones: runs of every length
            cut = rnd.randint(1, len(stream) - 1)
            await leg.feed(stream[:cut], settle=False)
            await leg.feed(stream[cut:])
            await leg.finish()
            legs.append((leg, leg.seen(),
                         node.metrics.val(RUN)))
        finally:
            await node.stop()
    return want, legs


@pytest.mark.parametrize("seed", [3, 2900000011])
@pytest.mark.parametrize("case", sorted(CASES))
async def test_run_and_per_packet_agree(case, seed):
    want, ((run_leg, ran, run_n), (pp_leg, per, pp_n)) = \
        await _both(case, seed)
    assert pp_n == 0, "the reference leg must not have formed a run"
    if case in ("quota", "acl_nomatch_deny"):
        # a quota bucket keeps a channel off the run by itself; where
        # every topic is denied a run queues nothing
        assert run_n == 0
    else:
        assert run_n > 0, "the traffic formed no run"
    assert ran["reason"] == per["reason"]
    assert ran["out"] == per["out"]
    assert [len(b) for b in ran["batches"]] == \
        [len(b) for b in per["batches"]]
    assert ran["batches"] == per["batches"]
    assert ran["metrics"] == per["metrics"]
    # the generator's own account of what had to be queued
    mp = CASES[case][1].get("mountpoint")
    got = [(m.topic, m.payload, m.qos, m.flags["retain"])
           for m in run_leg.msgs]
    prefix = mp.replace("%c", "pr-pub") if mp else ""
    alias = {}
    exp = []
    for p in want:
        a = p.properties.get("Topic-Alias")
        if a is not None and p.topic:
            alias[a] = p.topic
        exp.append((prefix + (p.topic or alias.get(a, "")), p.payload,
                    p.qos, p.retain))
    assert got == exp
    # ids: strictly increasing in queue order, whichever path built them
    for leg in (run_leg, pp_leg):
        ids = [m.id for m in leg.msgs]
        assert all(a < b for a, b in zip(ids, ids[1:]))
    hdrs = run_leg.msgs[0].headers if run_leg.msgs else {}
    if hdrs:
        assert hdrs["peerhost"] == "10.1.2.3" and hdrs["username"] == "u1"
        assert hdrs is not run_leg.msgs[-1].headers or len(run_leg.msgs) == 1


async def test_counters_a_run_moves_are_the_ones_ctl_metrics_shows():
    """512 plain publishes and a fence: the five totals the issue
    names read what they read packet by packet, and the run carried
    all but the fence."""
    node = await _node("pr-count@test")
    try:
        leg = Leg(node, Zone(name="z-count"), True)
        await leg.feed(_connect())
        base = node.metrics.all()
        rnd = random.Random(5)
        burst = [Publish(topic=_topic(rnd), qos=0, payload=b"p" * 256)
                 for _ in range(512)]
        burst.append(Publish(topic="fence/0", qos=1, packet_id=9,
                             payload=b"f"))
        data = b"".join(serialize(p, 4) for p in burst)
        await leg.feed(data)
        now = node.metrics.all()
        moved = {k: now[k] - base[k] for k in now if now[k] != base[k]}
        assert moved["packets.received"] == 513
        assert moved["packets.publish.received"] == 513
        assert moved["messages.received"] == 513
        assert moved["messages.qos0.received"] == 512
        assert moved["bytes.received"] == len(data)
        assert moved[RUN] == 512
        assert moved["packets.puback.sent"] == 1
        # the fence's PUBACK says everything before it is in: it was
        # queued after the run that preceded it
        assert [m.topic for m in leg.msgs][-1] == "fence/0"
        await leg.finish()
    finally:
        await node.stop()


# -- submit_many against N x submit ------------------------------------------


async def _batches(how, sizes, backlog_at=None, batch_size=16,
                   inflight=None, multi=False):
    """The batches that ``sizes`` runs form, submitted as runs
    (``many``) or a message at a time. At run ``backlog_at`` the
    pipeline reads ``inflight`` batches (default: every slot busy, so
    arrivals stand in the accumulator past the boundary; 1 = a batch
    in the pipeline, so the size trigger is twice ``batch_size``)."""
    node = await _node(f"pr-sm-{how}@test", batch_size=batch_size)
    try:
        ing = node.broker.ingress
        if multi:
            node.metrics.enable_threadsafe()
            ing.bind_multiloop(types.SimpleNamespace(
                home=asyncio.get_running_loop()))
        got = []
        begin = node.broker.publish_begin
        appended, calls = ing._appended, [0]

        def record(msgs, *a, **kw):
            got.append([m.topic for m in msgs
                        if not m.topic.startswith("$SYS/")])
            return begin(msgs, *a, **kw)

        def counted(*a):
            calls[0] += 1
            appended(*a)
        node.broker.publish_begin = record
        ing._appended = counted
        k = 0
        for r, size in enumerate(sizes):
            if r == backlog_at:
                ing._inflight = ing.max_inflight if inflight is None \
                    else inflight
            run = [Message(topic=f"t/{k + i}") for i in range(size)]
            k += size
            if how == "many":
                assert ing.submit_many(run) is True
            else:
                for m in run:
                    assert ing.submit(m, want_result=False) is ing._DONE
            if r == backlog_at:
                if inflight is None:
                    assert len(ing._pending) >= batch_size
                ing._inflight = 0
            if r % 3 == 2:
                await asyncio.sleep(0)   # the call_soon'd flush
        await ing.drain()
        return [b for b in got if b], ing.flushes, ing.submitted, \
            ing.max_queue, calls[0]
    finally:
        await node.stop()


@pytest.mark.parametrize("multi", [False, True])
@pytest.mark.parametrize("sizes,backlog_at,inflight", [
    ([32] * 6, None, None),
    ([1, 15, 16, 17, 31, 32, 3], None, None),
    ([5, 32, 32, 7, 1, 1, 30], None, None),
    ([16, 16, 16], None, None),
    ([32, 20, 9, 32], 1, None),
    ([7, 32, 32, 32, 2], 2, None),
    # a batch in the pipeline at run ``backlog_at``: the boundary is
    # twice batch_size there, and batch_size again after it
    ([3, 100, 20], 1, 1),
    ([16, 70, 5, 40], 1, 1),
    ([9, 9, 31, 33, 64], 3, 1),
    ([200], 0, 2),
])
async def test_submit_many_flushes_where_n_submits_would(
        sizes, backlog_at, inflight, multi):
    many = await _batches("many", sizes, backlog_at, inflight=inflight,
                          multi=multi)
    one = await _batches("one", sizes, backlog_at, inflight=inflight,
                         multi=multi)
    assert many[0] == one[0]          # the batches, list for list
    assert many[1:4] == one[1:4]      # flushes, submitted, max_queue
    assert sum(len(b) for b in many[0]) == sum(sizes)
    if backlog_at is None:
        assert max(len(b) for b in many[0]) <= 16
    elif inflight is not None:
        assert max(len(b) for b in many[0]) == 32
    if multi:
        return  # a peer loop's appends interleave: one at a time
    # ``_appended`` once a boundary and once for what a run leaves
    # short of it, not once a message (over a standing backlog a
    # message at a time, as submit() does)
    assert one[4] == sum(sizes)
    if inflight is not None or backlog_at is None:
        assert many[4] <= len(many[0]) + len(sizes)


def test_submit_many_without_a_loop_queues_nothing():
    node = Node(name="pr-noloop@test", boot_listeners=False)
    ing = node.broker.ingress
    assert ing.submit_many([Message(topic="a/b")]) is False
    assert not ing._pending


# -- how fast an ACL change bites inside a run -------------------------------


def _plain(topic, n):
    return b"".join(serialize(Publish(topic=topic, qos=0, payload=b"x"), 4)
                    for _ in range(n))


@pytest.mark.parametrize("runs", [True, False])
@pytest.mark.parametrize("change", ["callback_where_none_was",
                                    "rule_and_drain", "rule_and_ttl",
                                    "callback_removed", "acl_enabled"])
async def test_acl_change_bites_no_later_than_packet_by_packet(change,
                                                               runs):
    node = await _node(f"pr-acl-{change}-{int(runs)}@test")
    try:
        denied = set()

        def acl(clientinfo, pubsub, topic, acc):
            return (STOP, DENY) if topic in denied else None

        def deny_all(clientinfo, pubsub, topic, acc):
            return (STOP, DENY)
        hooks = node.broker.hooks
        zone = Zone(name=f"z-{change}",
                    enable_acl=change != "acl_enabled")
        if change in ("rule_and_drain", "rule_and_ttl"):
            hooks.add("client.check_acl", acl)
        if change == "callback_removed":
            hooks.add("client.check_acl", deny_all)
        leg = Leg(node, zone, runs)
        ch = leg.conn.channel
        await leg.feed(_connect())
        await leg.feed(_plain("hot/topic", 40))
        before = len(leg.msgs)
        if change == "callback_removed":
            assert before == 0
            hooks.delete("client.check_acl", deny_all)
            await leg.feed(_plain("hot/topic", 40))
            assert len(leg.msgs) == 40      # allowed from the next packet
            return
        assert before == 40
        denied.add("hot/topic")
        if change == "callback_where_none_was":
            hooks.add("client.check_acl", acl)
        elif change == "rule_and_drain":
            ch.acl_cache.drain()
        elif change == "acl_enabled":
            hooks.add("client.check_acl", acl)
            zone.enable_acl = True
        else:
            # an allow lives in the AclCache, and no longer than its ttl
            await leg.feed(_plain("hot/topic", 5))
            assert len(leg.msgs) == 45
            ch.acl_cache.ttl = 0.05
            await asyncio.sleep(0.08)
        deny0 = node.metrics.val("client.acl.deny")
        await leg.feed(_plain("hot/topic", 40))
        assert len(leg.msgs) == (45 if change == "rule_and_ttl" else 40)
        assert node.metrics.val("client.acl.deny") - deny0 == 40
        await leg.finish()
    finally:
        await node.stop()


@pytest.mark.parametrize("runs", [True, False])
async def test_deny_for_a_topic_the_acl_cache_evicted_bites_at_once(runs):
    """A publisher that cycles through more topics than the AclCache
    holds (32, oldest out) finds none of them there, so every PUBLISH
    is taken to the ``client.check_acl`` callbacks: a rule revoked
    mid-stream denies the very next packet. Nothing between the run
    and the callbacks may remember an allow the cache has let go."""
    node = await _node(f"pr-evict-{int(runs)}@test")
    try:
        denied = set()
        asked = []

        def acl(clientinfo, pubsub, topic, acc):
            asked.append(topic)
            return (STOP, DENY) if topic in denied else None
        node.broker.hooks.add("client.check_acl", acl)
        leg = Leg(node, Zone(name="z-evict"), runs)
        ch = leg.conn.channel
        await leg.feed(_connect())
        cycle = b"".join(_plain(f"cyc/{i}", 1) for i in range(40))
        await leg.feed(cycle * 3)
        assert len(leg.msgs) == 120 and len(asked) == 120
        assert len(ch.acl_cache) == 32
        assert node.metrics.val("client.acl.cache_hit") == 0
        denied.add("cyc/7")
        await leg.feed(cycle)
        assert [m.topic for m in leg.msgs[120:]] == \
            [f"cyc/{i}" for i in range(40) if i != 7]
        assert node.metrics.val("client.acl.deny") == 1
        if runs:
            assert node.metrics.val(RUN) == 159
        await leg.finish()
    finally:
        await node.stop()


@pytest.mark.parametrize("runs", [True, False])
async def test_without_a_callback_the_acl_is_the_zone_default(runs):
    """No ACL module, no plugin: each PUBLISH counts one
    ``client.check_acl``, reads ``zone.acl_nomatch`` as it stands
    then, and leaves the AclCache empty (a constant is not cached)."""
    node = await _node(f"pr-nocb-{int(runs)}@test")
    try:
        zone = Zone(name="z-nocb")
        leg = Leg(node, zone, runs)
        await leg.feed(_connect())
        m = node.metrics
        await leg.feed(_plain("a/b", 10))
        assert m.val("client.check_acl") == 10
        assert m.val("client.acl.cache_hit") == 0
        assert len(leg.conn.channel.acl_cache) == 0
        zone.acl_nomatch = "deny"
        await leg.feed(_plain("a/b", 10))
        assert len(leg.msgs) == 10 and m.val("client.acl.deny") == 10
        await leg.finish()
    finally:
        await node.stop()


# -- the benchmark's reading of the counter ----------------------------------


def test_publish_run_share_file_matches_its_benchmark_entry():
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "BENCHMARK.json"), encoding="utf-8") as f:
        spec = json.load(f)
    with open(os.path.join(root, "benchmark", "layer_metrics",
                           "publish_run_share.json"),
              encoding="utf-8") as f:
        entry = json.load(f)
    listed = [m for m in spec["per_layer"]
              if m["name"] == "publish_run_share"]
    assert len(listed) == 1
    # appended at PR 30, after everything PR 29's benchmark had (later
    # PRs append after it: a cell's metrics bear its suffix)
    names = [m["name"] for m in spec["per_layer"]]
    assert names.index("publish_run_share") > names.index("warmers_s.mesh")
    for key in ("unit", "better", "source", "layer", "moves"):
        assert listed[0][key] == entry[key], key
    assert listed[0]["workloads"] == ["fleet_1m.flood", "fanout_1k.flood"]
    assert os.path.exists(os.path.join(
        root, "benchmark", "reducers", entry["reducer"] + ".py"))
    names = set(Metrics().names())
    assert {"channel.publish_run.msgs", "messages.received"} <= names
    assert set(entry["args"]["counters"]) <= set(ALL_METRICS)
    assert entry["args"]["per"] == "counter:messages.received"
    # the read layer's other metric keeps the layer's name, letter for letter
    read = next(m for m in spec["per_layer"]
                if m["name"] == "read_us_per_msg")
    assert read["layer"] == entry["layer"]
    # the mesh cell's per-layer list is pinned elsewhere: not touched
    assert not any(w.startswith("fleet_10m_mesh")
                   for w in listed[0]["workloads"])
