"""Egress pre-serialization (ops/dispatch_plan.preserialize_plan +
Channel._wire_template + mqtt.frame.publish_template, docs/DISPATCH.md
"Egress pre-serialization"): golden-byte pid-patch fuzz against
``wire_serialize`` with the independent ``tests/indie_mqtt.py`` codec
as the second opinion, preserialize-on vs -off parity (wire bytes,
pid sequences, inflight, metric deltas) across QoS0/1/2 × v3/v4/v5 ×
retain/dup/subid/shared cases, the effective-QoS-in-key regression
for the shared wire image cache, the on-loop serialize counter, and
the ``[dispatch] preserialize`` config schema."""

import asyncio
import random

import pytest

from tests import indie_mqtt as im
from emqx_tpu.broker import Broker, DispatchConfig
from emqx_tpu.channel import Channel
from emqx_tpu.cm import ConnectionManager
from emqx_tpu.config import ConfigError, parse_config
from emqx_tpu.mqtt import constants as C
from emqx_tpu.mqtt.frame import FrameError, publish_template
from emqx_tpu.mqtt.frame import serialize as wire_serialize
from emqx_tpu.mqtt.packet import Connect, Publish
from emqx_tpu.router import MatcherConfig, Router
from emqx_tpu.session import Session
from emqx_tpu.types import Message, SubOpts

VERSIONS = (C.MQTT_V3, C.MQTT_V4, C.MQTT_V5)

# v5 property sets a template may legally carry (per-delivery rewrites
# — Message-Expiry-Interval, Subscription-Identifier — are routed to
# the slow path by the planner and never enter a template; the codec
# itself doesn't care, so the fuzz includes an expiry case too)
PROP_SETS = (
    {},
    {"Content-Type": "application/json"},
    {"User-Property": [("a", "b"), ("c", "d")]},
    {"Payload-Format-Indicator": 1, "Response-Topic": "r/t"},
    {"Correlation-Data": b"\x00\xffcorr"},
    {"Message-Expiry-Interval": 30},
)

PIDS = (1, 0x7F, 0x80, 0xFF, 0x100, 0x1234, 0x7FFF, 0x8000, 0xFFFF)


def _indie_decode(frame: bytes, version: int):
    """Split a serialized frame with the INDEPENDENT codec's own
    primitives and decode the body — no emqx_tpu parser involved."""
    rl, boff = im.dec_varint(frame, 1)
    body = bytes(frame[boff:])
    assert len(body) == rl
    return im.decode(frame[0] >> 4, frame[0] & 0x0F, body,
                     5 if version == C.MQTT_V5 else 4)


# -- golden-byte template fuzz --------------------------------------------


def test_template_pid_patch_matches_serialize_fuzz():
    rng = random.Random(0xE5)
    alphabet = "abcdefg/μτ0"
    for _ in range(150):
        ver = rng.choice(VERSIONS)
        qos = rng.choice((1, 2))
        retain = bool(rng.randrange(2))
        dup = bool(rng.randrange(2))
        topic = "".join(rng.choice(alphabet)
                        for _ in range(rng.randint(1, 60)))
        payload = rng.randbytes(rng.randrange(0, 200))
        props = dict(rng.choice(PROP_SETS)) if ver == C.MQTT_V5 else {}
        tpl, off = publish_template(
            Publish(topic=topic, payload=payload, qos=qos,
                    retain=retain, dup=dup, packet_id=0x0B0B,
                    properties=dict(props)), ver)
        for pid in rng.sample(PIDS, 4):
            buf = bytearray(tpl)
            buf[off] = (pid >> 8) & 0xFF
            buf[off + 1] = pid & 0xFF
            patched = bytes(buf)
            assert patched == wire_serialize(
                Publish(topic=topic, payload=payload, qos=qos,
                        retain=retain, dup=dup, packet_id=pid,
                        properties=dict(props)), ver)
            # second opinion: the independent codec must read back
            # exactly what the template claims to carry
            p = _indie_decode(patched, ver)
            assert (p.ptype, p.topic, p.payload, p.qos, p.retain,
                    p.dup, p.pkt_id) == (im.PUBLISH, topic, payload,
                                         qos, retain, dup, pid)
            if ver == C.MQTT_V5:
                assert p.props == props


def test_template_alias_variant_empty_topic():
    # v5 outbound topic alias: empty topic + Topic-Alias property —
    # the pid offset derivation must hold at topic length 0
    tpl, off = publish_template(
        Publish(topic="", payload=b"x", qos=1, packet_id=0,
                properties={"Topic-Alias": 5}), C.MQTT_V5)
    buf = bytearray(tpl)
    buf[off:off + 2] = (0xBEEF).to_bytes(2, "big")
    p = _indie_decode(bytes(buf), C.MQTT_V5)
    assert p.topic == "" and p.pkt_id == 0xBEEF
    assert p.props == {"Topic-Alias": 5}


def test_template_refuses_qos0():
    with pytest.raises(FrameError):
        publish_template(Publish(topic="t", qos=0), C.MQTT_V4)


# -- preserialize_plan: what gets primed, what stays slow -----------------


def _hinted_session(broker, cid, ver=C.MQTT_V4, upgrade=False):
    s = Session(cid, broker=broker, upgrade_qos=upgrade)
    s.proto_ver = ver
    s.wire_fast_hint = True
    return s


def _device_broker(preserialize=True, **mk):
    mk.setdefault("device_min_filters", 0)
    return Broker(router=Router(MatcherConfig(**mk), node="n1"),
                  dispatch_config=DispatchConfig(
                      preserialize=preserialize))


def test_preserialize_primes_templates_and_images():
    b = _device_broker()
    s1 = _hinted_session(b, "t1")                    # qos1 template
    s0 = _hinted_session(b, "t0")                    # downgrade to 0
    s5 = _hinted_session(b, "t5", ver=C.MQTT_V5)     # v5 template
    s1.subscribe("p/t", SubOpts(qos=1))
    s0.subscribe("p/t", SubOpts(qos=0))
    s5.subscribe("p/t", SubOpts(qos=2))
    msg = Message(topic="p/t", payload=b"pay", qos=1, from_="pub")
    pb = b.publish_begin([msg])
    assert not pb.done
    b.publish_fetch(pb)
    assert pb.plan is not None
    tpl = msg.headers["_wiretpl"]
    wire = msg.headers["_wire"]
    # qos1 v4 template, qos1 v5 template (granted 2 caps at msg qos 1)
    assert set(tpl) == {(C.MQTT_V4, 1, False, False),
                        (C.MQTT_V5, 1, False, False)}
    # the downgraded-to-QoS0 copy's image keys with qos 0 — the
    # effective-QoS-in-key rule: it can never serve the QoS1 bytes
    assert set(wire) == {(C.MQTT_V4, 0, False, False)}
    data, off = tpl[(C.MQTT_V4, 1, False, False)]
    buf = bytearray(data)
    buf[off:off + 2] = (42).to_bytes(2, "big")
    assert bytes(buf) == wire_serialize(
        Publish(topic="p/t", payload=b"pay", qos=1, packet_id=42),
        C.MQTT_V4)
    assert wire[(C.MQTT_V4, 0, False, False)] == wire_serialize(
        Publish(topic="p/t", payload=b"pay", qos=0), C.MQTT_V4)
    assert wire[(C.MQTT_V4, 0, False, False)] != bytes(data)
    # finish still delivers normally
    assert b.publish_finish(pb) == [3]
    assert [pid for pid, _ in s1.outbox] == [1]
    assert [pid for pid, _ in s0.outbox] == [None]


def test_preserialize_skips_per_session_rewrites():
    b = _device_broker()
    s_subid = _hinted_session(b, "sid", ver=C.MQTT_V5)
    s_share = _hinted_session(b, "shr")
    s_nohint = Session("noh", broker=b)   # no channel hints
    s_subid.subscribe("q/t", SubOpts(qos=1, subid=9))
    s_share.subscribe("$share/g/q/t", SubOpts(qos=1))
    s_nohint.subscribe("q/t", SubOpts(qos=1))
    msg = Message(topic="q/t", qos=1, from_="pub")
    pb = b.publish_begin([msg])
    b.publish_fetch(pb)
    assert pb.plan is not None
    # nothing eligible: subid and shared are per-delivery rewrites,
    # the hintless session might need a mountpoint/alias rewrite
    assert not msg.headers.get("_wiretpl")
    assert not msg.headers.get("_wire")
    b.publish_finish(pb)


def test_preserialize_skips_expiry_messages():
    b = _device_broker()
    s = _hinted_session(b, "e1")
    s.subscribe("x/t", SubOpts(qos=1))
    msg = Message(topic="x/t", qos=1, from_="pub")
    msg.set_header("properties", {"Message-Expiry-Interval": 60})
    pb = b.publish_begin([msg])
    b.publish_fetch(pb)
    assert "_wiretpl" not in msg.headers
    b.publish_finish(pb)


# -- session-state parity: preserialize must not perturb delivery ---------


def _metric_deltas(broker):
    return {k: v for k, v in broker.metrics.all().items()
            if v and (k.startswith("messages.")
                      or k.startswith("delivery."))
            and k != "delivery.serialize.onloop"
            # wire runs are built by the serialize stage: none with
            # preserialize off, by design (tests/test_wire_run.py)
            and not k.startswith("delivery.wire_run")}


def test_session_state_parity_preser_on_off():
    outs = []
    for preser in (True, False):
        b = _device_broker(preserialize=preser)
        sess = [_hinted_session(b, f"s{i}") for i in range(3)]
        sess[0].subscribe("m/+", SubOpts(qos=1))
        sess[1].subscribe("m/a", SubOpts(qos=2))
        sess[2].subscribe("m/#", SubOpts(qos=0))
        for _ in range(3):
            b.publish_batch([Message(topic="m/a", qos=2, from_="p"),
                             Message(topic="m/b", qos=1, from_="p"),
                             Message(topic="m/a", qos=0, from_="p")])
        outs.append((
            [[(pid, m.topic, m.qos, m.flags.get("dup", False))
              for pid, m in s.outbox] for s in sess],
            [sorted(pid for pid, _ in s.inflight.to_list())
             for s in sess],
            _metric_deltas(b)))
    assert outs[0] == outs[1]


# -- wire-level parity through real connections ---------------------------


async def _egress_run(preserialize: bool):
    from helpers import broker_node, node_port
    from mqtt_client import TestClient

    async with broker_node(
            matcher=MatcherConfig(device_min_filters=0),
            dispatch_config=DispatchConfig(
                preserialize=preserialize)) as node:
        port = node_port(node)
        a0 = TestClient("a0")                     # v4 qos0
        a1 = TestClient("a1")                     # v4 qos1
        a2 = TestClient("a2", version=C.MQTT_V5)  # v5 qos2
        a3 = TestClient("a3", version=C.MQTT_V5)  # v5 subid slow path
        g1 = TestClient("g1")                     # shared group
        g2 = TestClient("g2")
        pub = TestClient("wp")
        pub5 = TestClient("wp5", version=C.MQTT_V5)
        clients = [a0, a1, a2, a3, g1, g2, pub, pub5]
        for cli in clients:
            await cli.connect(port=port)
        await a0.subscribe("e/+", qos=0)
        await a1.subscribe("e/#", qos=1)
        await a2.subscribe("e/t", qos=2)
        await a3.subscribe("e/+", qos=1,
                           props={"Subscription-Identifier": 7})
        await g1.subscribe("$share/g/e/t", qos=1)
        await g2.subscribe("$share/g/e/t", qos=1)
        expect = {a0: 0, a1: 0, a2: 0, a3: 0}
        for i in range(3):
            await pub.publish("e/t", payload=b"q0-%d" % i, qos=0)
            expect[a0] += 1
            expect[a1] += 1
            expect[a2] += 1
            expect[a3] += 1
        for i in range(4):
            await pub.publish("e/t", payload=b"q1-%d" % i, qos=1)
        await pub.publish("e/x", payload=b"q1-x", qos=1)
        expect[a0] += 5
        expect[a1] += 5
        expect[a2] += 4
        expect[a3] += 5
        for i in range(2):
            await pub.publish("e/t", payload=b"q2-%d" % i, qos=2)
        await pub.publish("e/t", payload=b"rt", qos=1, retain=True)
        expect[a0] += 3
        expect[a1] += 3
        expect[a2] += 3
        expect[a3] += 3
        # v5 publisher: pass-through properties + per-delivery expiry
        await pub5.publish("e/t", payload=b"v5p", qos=1,
                           props={"User-Property": [("k", "v")],
                                  "Payload-Format-Indicator": 1})
        await pub5.publish("e/t", payload=b"v5e", qos=1,
                           props={"Message-Expiry-Interval": 120})
        for cli in (a0, a1, a2, a3):
            expect[cli] += 2
        got = []
        for cli in (a0, a1, a2, a3):
            pkts = []
            for _ in range(expect[cli]):
                p = await cli.recv(timeout=5.0)
                props = {k: v for k, v in (p.properties or {}).items()
                         if k != "Message-Expiry-Interval"}
                pkts.append((p.topic, bytes(p.payload), p.qos,
                             p.retain, p.dup, p.packet_id, props))
            pkts.sort(key=lambda t: t[1])  # batch tick grouping may
            # interleave topics; per-payload identity is the contract
            got.append(pkts)
        # shared group: totals must match even if the pick rotates
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
                    if v and (k.startswith(("messages.", "delivery.",
                                            "packets.publish")))
                    and k != "delivery.serialize.onloop"
                    and not k.startswith("delivery.wire_run")
                    # which publishes share a batch tick decides how
                    # often a filter recurs inside one group
                    and k != "delivery.plan.resolves"})
        onloop = node.metrics.val("delivery.serialize.onloop")
        for cli in clients:
            await cli.close()
        return got, onloop


async def test_wire_parity_preser_on_vs_off():
    on, onloop_on = await _egress_run(True)
    off, onloop_off = await _egress_run(False)
    assert on == off
    # the A/B signal: pre-serialization moved the eligible serializes
    # off the loop; the legacy pass did every one of them on-loop
    assert onloop_on < onloop_off
    # subid subscriber saw its Subscription-Identifier (slow path)
    a3_pkts = on[3]
    assert all(p[6].get("Subscription-Identifier") == 7
               for p in a3_pkts)


async def test_onloop_counter_zero_for_eligible_qos1_fanout():
    from helpers import broker_node, node_port
    from mqtt_client import TestClient

    for preser, expect_zero in ((True, True), (False, False)):
        async with broker_node(
                matcher=MatcherConfig(device_min_filters=0),
                dispatch_config=DispatchConfig(
                    preserialize=preser)) as node:
            port = node_port(node)
            subs = [TestClient(f"k{i}") for i in range(2)]
            pub = TestClient("kp")
            for cli in subs + [pub]:
                await cli.connect(port=port)
            for cli in subs:
                await cli.subscribe("k/+", qos=1)
            for i in range(6):
                await pub.publish("k/t", payload=b"%d" % i, qos=1)
            for cli in subs:
                for _ in range(6):
                    await cli.recv(timeout=5.0)
            onloop = node.metrics.val("delivery.serialize.onloop")
            if expect_zero:
                assert onloop == 0, onloop
            else:
                assert onloop == 12, onloop  # every delivery
            for cli in subs + [pub]:
                await cli.close()


# -- effective-QoS key regression (satellite) ------------------------------


def _mk_channel(broker, cid, ver=C.MQTT_V4):
    cm = ConnectionManager()
    ch = Channel(broker, cm)
    ch.wire_fast = True
    out = ch.handle_in(Connect(client_id=cid, proto_ver=ver,
                               proto_name=C.PROTOCOL_NAMES[ver]))
    assert out and out[0].type == C.CONNACK
    return ch


def test_wire_cache_keys_by_effective_qos():
    b = Broker()  # host path is fine: the cache is channel-side
    ch = _mk_channel(b, "wc")
    ch.session.subscribe("z/t", SubOpts(qos=0))
    orig = Message(topic="z/t", payload=b"zz", qos=1, from_="p")
    orig.headers["_wire"] = {}
    # a hostile prior: a QoS1 frame somehow cached under qos byte 1
    q1_frame = wire_serialize(
        Publish(topic="z/t", payload=b"zz", qos=1, packet_id=7),
        C.MQTT_V4)
    orig.headers["_wire"][(C.MQTT_V4, 1, False, False)] = q1_frame
    # deliver: downgraded-to-QoS0 copy shares the dict but must key
    # (and build) under qos 0 — never serve the QoS1 bytes
    ch.session.deliver("z/t", orig)
    out = ch.handle_deliver()
    assert len(out) == 1 and type(out[0]) is bytes
    assert out[0] != q1_frame
    assert out[0] == wire_serialize(
        Publish(topic="z/t", payload=b"zz", qos=0), C.MQTT_V4)
    assert orig.headers["_wire"][(C.MQTT_V4, 0, False, False)] \
        == out[0]


def test_template_variant_miss_builds_on_loop_and_caches():
    b = Broker()
    ch = _mk_channel(b, "tm")
    ch.session.subscribe("y/t", SubOpts(qos=1))
    msg = Message(topic="y/t", payload=b"yy", qos=1, from_="p")
    msg.headers["_wiretpl"] = {}  # primed dict, but no variant yet
    base = b.metrics.val("delivery.serialize.onloop")
    ch.session.deliver("y/t", msg)
    out = ch.handle_deliver()
    assert len(out) == 1 and type(out[0]) is bytes
    pid = ch.session.inflight.to_list()[0][0]
    assert out[0] == wire_serialize(
        Publish(topic="y/t", payload=b"yy", qos=1, packet_id=pid),
        C.MQTT_V4)
    # the miss built (and counted) ONE on-loop serialize, then cached
    assert b.metrics.val("delivery.serialize.onloop") == base + 1
    assert (C.MQTT_V4, 1, False, False) in msg.headers["_wiretpl"]


# -- [dispatch] config schema ---------------------------------------------


def test_dispatch_preserialize_config_schema():
    cfg = parse_config({"dispatch": {"preserialize": False}})
    assert cfg.dispatch is not None
    assert cfg.dispatch.preserialize is False
    assert cfg.dispatch.planner is True
    assert DispatchConfig().preserialize is True
    with pytest.raises(ConfigError, match="unknown dispatch setting"):
        parse_config({"dispatch": {"preserialise": False}})
    with pytest.raises(ConfigError, match="must be a boolean"):
        parse_config({"dispatch": {"preserialize": 1}})
