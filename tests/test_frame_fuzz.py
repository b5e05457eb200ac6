"""Deep serialize∘parse property fuzzing over the full packet space.

The reference runs PropEr generators over every packet type × proto
version (test/props/prop_emqx_frame.erl:26-55). This suite is that
generator by hand: all 15 control packet types, valid v5 properties
drawn from the property table per packet type, wills, unicode
topics, QoS variants — roundtripped across v3.1 / v3.1.1 / v5 — plus
an adversarial pass: random byte corruption must surface as
FrameError/FrameTooLarge (or a clean parse), never a crash.
"""

import random

import pytest

from emqx_tpu.mqtt import constants as C
from emqx_tpu.mqtt.frame import (FrameError, FrameTooLarge, Parser,
                                 serialize)
from emqx_tpu.mqtt.packet import (Auth, Connack, Connect, Disconnect,
                                  Pingreq, Pingresp, PubAck, Publish,
                                  Suback, Subscribe, Unsuback,
                                  Unsubscribe)
from emqx_tpu.mqtt.props import (BINARY, BYTE, FOUR_BYTE, PROPS, TWO_BYTE,
                                 UTF8, UTF8_PAIR, VARINT)

VERSIONS = (C.MQTT_V3, C.MQTT_V4, C.MQTT_V5)

_TOPIC_WORDS = ["a", "b", "sensor", "温度", "x-y_z", "0", "ß"]


def _topic(rng, wild=False):
    words = [rng.choice(_TOPIC_WORDS)
             for _ in range(rng.randint(1, 6))]
    if wild and rng.random() < 0.4:
        words[rng.randrange(len(words))] = "+"
    if wild and rng.random() < 0.2:
        words[-1] = "#"
    return "/".join(words)


def _prop_value(rng, wire_type):
    if wire_type == BYTE:
        return rng.randint(0, 1)
    if wire_type == TWO_BYTE:
        return rng.randint(1, 0xFFFF)
    if wire_type == FOUR_BYTE:
        return rng.randint(1, 0xFFFFFFFF)
    if wire_type == VARINT:
        return rng.randint(1, 0x0FFFFFFF)
    if wire_type == BINARY:
        return rng.randbytes(rng.randint(0, 16))
    if wire_type == UTF8:
        return _topic(rng)
    if wire_type == UTF8_PAIR:
        return [(f"k{i}", f"v{i}") for i in range(rng.randint(1, 3))]
    raise AssertionError(wire_type)


# properties the codec normalizes rather than echoing verbatim
_SKIP_PROPS = {"Subscription-Identifier"}


def _props_for(rng, pkt_type):
    """Random VALID property dict for a packet type."""
    out = {}
    for pid, (name, wt, allowed) in PROPS.items():
        if name in _SKIP_PROPS:
            continue
        if allowed is not None and pkt_type not in allowed:
            continue
        if rng.random() < 0.35:
            out[name] = _prop_value(rng, wt)
    return out


def gen_packet(rng, version):
    v5 = version == C.MQTT_V5
    t = rng.choice(["connect", "connack", "publish", "ack", "subscribe",
                    "suback", "unsubscribe", "unsuback", "pingreq",
                    "pingresp", "disconnect", "auth"])
    if t == "connect":
        will = rng.random() < 0.5
        return Connect(
            proto_ver=version,
            proto_name=C.PROTOCOL_NAMES[version],
            client_id="cli-%d" % rng.randint(0, 999),
            clean_start=bool(rng.randint(0, 1)),
            keepalive=rng.randint(0, 0xFFFF),
            username=rng.choice([None, "user"]),
            password=rng.choice([None, b"pw\x00\xff"]),
            will_flag=will,
            will_qos=rng.randint(0, 2) if will else 0,
            will_retain=bool(rng.randint(0, 1)) if will else False,
            will_topic=_topic(rng) if will else None,
            will_payload=rng.randbytes(rng.randint(0, 32))
            if will else b"",
            will_props=_props_for(rng, C.PUBLISH)
            if (will and v5) else {},
            properties=_props_for(rng, C.CONNECT) if v5 else {},
        )
    if t == "connack":
        return Connack(
            session_present=bool(rng.randint(0, 1)),
            reason_code=rng.choice([0, 0x80, 0x85, 0x87]),
            properties=_props_for(rng, C.CONNACK) if v5 else {})
    if t == "publish":
        qos = rng.randint(0, 2)
        props = _props_for(rng, C.PUBLISH) if v5 else {}
        props.pop("Topic-Alias", None)  # alias0 is a protocol error
        if v5 and rng.random() < 0.5:
            props["Topic-Alias"] = rng.randint(1, 0xFFFF)
        return Publish(
            topic=_topic(rng), qos=qos,
            retain=bool(rng.randint(0, 1)),
            dup=bool(rng.randint(0, 1)) if qos else False,
            packet_id=rng.randint(1, 0xFFFF) if qos else None,
            payload=rng.randbytes(rng.randint(0, 64)),
            properties=props)
    if t == "ack":
        ptype = rng.choice([C.PUBACK, C.PUBREC, C.PUBREL, C.PUBCOMP])
        return PubAck(
            type=ptype, packet_id=rng.randint(1, 0xFFFF),
            reason_code=rng.choice([0, 0x10, 0x80]) if v5 else 0,
            properties={"Reason-String": "r"}
            if (v5 and rng.random() < 0.3) else {})
    if t == "subscribe":
        props = {}
        if v5 and rng.random() < 0.5:
            props["Subscription-Identifier"] = rng.randint(1, 1000)
        return Subscribe(
            packet_id=rng.randint(1, 0xFFFF),
            topic_filters=[
                (_topic(rng, wild=True),
                 {"qos": rng.randint(0, 2), "nl": rng.randint(0, 1),
                  "rap": rng.randint(0, 1), "rh": rng.randint(0, 2)})
                for _ in range(rng.randint(1, 5))],
            properties=props)
    if t == "suback":
        return Suback(
            packet_id=rng.randint(1, 0xFFFF),
            reason_codes=[rng.choice([0, 1, 2, 0x80])
                          for _ in range(rng.randint(1, 5))],
            properties=_props_for(rng, C.SUBACK) if v5 else {})
    if t == "unsubscribe":
        return Unsubscribe(
            packet_id=rng.randint(1, 0xFFFF),
            topic_filters=[_topic(rng, wild=True)
                           for _ in range(rng.randint(1, 5))])
    if t == "unsuback":
        return Unsuback(
            packet_id=rng.randint(1, 0xFFFF),
            reason_codes=[rng.choice([0, 0x11, 0x80])
                          for _ in range(rng.randint(1, 5))]
            if v5 else [],
            properties=_props_for(rng, C.UNSUBACK) if v5 else {})
    if t == "pingreq":
        return Pingreq()
    if t == "pingresp":
        return Pingresp()
    if t == "disconnect":
        return Disconnect(
            reason_code=rng.choice([0, 0x04, 0x81, 0x9C]) if v5 else 0,
            properties=_props_for(rng, C.DISCONNECT) if v5 else {})
    return Auth(reason_code=rng.choice([0, 0x18, 0x19]),
                properties=_props_for(rng, C.AUTH) if v5 else {})


def _normalize(pkt, version):
    """Fields the wire legitimately does not carry for a version."""
    v5 = version == C.MQTT_V5
    if not v5:
        pkt.properties = {}
        if isinstance(pkt, Connect):
            pkt.will_props = {}
        if isinstance(pkt, (PubAck, Disconnect, Auth)):
            pkt.reason_code = 0
        if isinstance(pkt, Unsuback):
            pkt.reason_codes = []
        if isinstance(pkt, Subscribe):
            # v3/v4 carry only (filter, qos)
            pkt.topic_filters = [
                (f, {"qos": o["qos"], "nl": 0, "rap": 0, "rh": 0})
                for f, o in pkt.topic_filters]
    return pkt


@pytest.mark.parametrize("version", VERSIONS)
def test_exhaustive_roundtrip(version):
    """serialize∘parse == id for every packet type with randomized
    valid contents (2000 packets per protocol version)."""
    rng = random.Random(1000 + version)
    parser = Parser(version=version)
    for i in range(2000):
        pkt = gen_packet(rng, version)
        if isinstance(pkt, (Auth,)) and version != C.MQTT_V5:
            continue  # AUTH exists only in v5
        data = serialize(pkt, version)
        if isinstance(pkt, Connect):
            parser = Parser()  # fresh parser negotiates on CONNECT
        got = parser.feed(data)
        assert len(got) == 1, (i, pkt)
        want = _normalize(pkt, version)
        assert got[0] == want, (i, version, want, got[0])


def test_roundtrip_stream_interleaved_versions_fragmented():
    """A long stream of random packets split at random byte
    boundaries parses identically to whole-packet feeds."""
    rng = random.Random(77)
    for version in VERSIONS:
        pkts = [gen_packet(rng, version) for _ in range(100)]
        pkts = [p for p in pkts
                if not (isinstance(p, Auth) and version != C.MQTT_V5)
                and not isinstance(p, Connect)]
        blob = b"".join(serialize(p, version) for p in pkts)
        parser = Parser(version=version)
        got = []
        i = 0
        while i < len(blob):
            n = rng.randint(1, 40)
            got.extend(parser.feed(blob[i:i + n]))
            i += n
        assert [type(g) for g in got] == [type(p) for p in pkts]
        assert got == [_normalize(p, version) for p in pkts]


def test_corruption_never_crashes_parser():
    """Adversarial bytes: flip/truncate/extend random packets — the
    parser must either parse cleanly or raise its own error types,
    never IndexError/KeyError/UnicodeDecodeError."""
    rng = random.Random(31337)
    for version in VERSIONS:
        for _ in range(1500):
            pkt = gen_packet(rng, version)
            if isinstance(pkt, Auth) and version != C.MQTT_V5:
                continue
            data = bytearray(serialize(pkt, version))
            mode = rng.random()
            if mode < 0.4 and data:      # flip 1-4 bytes
                for _ in range(rng.randint(1, 4)):
                    k = rng.randrange(len(data))
                    data[k] ^= rng.randint(1, 255)
            elif mode < 0.7:             # truncate
                data = data[:rng.randrange(max(1, len(data)))]
            else:                        # append garbage
                data += rng.randbytes(rng.randint(1, 16))
            parser = Parser(version=version, max_size=1 << 20)
            try:
                parser.feed(bytes(data))
            except (FrameError, FrameTooLarge):
                pass  # the contract: typed errors only


def test_pure_garbage_streams():
    rng = random.Random(4242)
    for _ in range(300):
        parser = Parser(version=C.MQTT_V5, max_size=1 << 16)
        try:
            parser.feed(rng.randbytes(rng.randint(1, 512)))
        except (FrameError, FrameTooLarge):
            pass


# -- 3-way differential: NativeParser vs Parser vs the indie codec ---------
#
# Three independent implementations of the same wire format: the C++
# incremental parser (native/emqx_native.cpp through NativeParser),
# the pure-Python Parser, and tests/indie_mqtt.py (a from-scratch
# codec with its own reading of the spec). A mirrored misreading
# between the two in-tree engines fails against indie; a native-port
# bug fails against Python. Compared: parsed packets on valid
# streams, error CLASS + message + retained-buffer length on
# malformed input, and resume behavior at EVERY byte split.

from emqx_tpu.mqtt.frame import NativeParser
from emqx_tpu.ops import native as _nat

needs_native_parser = pytest.mark.skipif(
    not _nat.has_frame_parser(),
    reason="native frame parser not built")


def _feed_outcome(parser, chunks):
    """(\"ok\", packets) or (error class name, message, pending bytes)
    — the full observable surface of a feed sequence."""
    got = []
    try:
        for c in chunks:
            got.extend(parser.feed(c))
    except (FrameError, FrameTooLarge) as e:
        return (type(e).__name__, str(e), parser.pending())
    return ("ok", got)


def _pending(parser):
    return parser.pending()


@needs_native_parser
@pytest.mark.parametrize("version", [4, 5])
def test_differential_indie_built_stream(version):
    """Client→server stream built by the INDIE codec: both in-tree
    parsers must agree with each other AND with indie's intent."""
    from tests import indie_mqtt as im

    rng = random.Random(505 + version)
    parts = [im.build_connect("diff", version=version)]
    intents = []  # (topic, payload, qos, pkt_id) per PUBLISH, in order
    for i in range(120):
        r = rng.random()
        if r < 0.5:
            qos = rng.choice([0, 0, 1, 2])
            topic = f"d/{i}/{rng.choice(_TOPIC_WORDS)}"
            payload = rng.randbytes(rng.randrange(96))
            pid = i + 1 if qos else None
            parts.append(im.build_publish(
                topic, payload, qos=qos, pkt_id=pid, version=version,
                retain=bool(rng.random() < 0.2)))
            intents.append((topic, payload, qos, pid))
        elif r < 0.7:
            parts.append(im.build_subscribe(
                i + 1, [(f"d/{i}/+", rng.randint(0, 2))],
                version=version))
        elif r < 0.8:
            parts.append(im.build_puback_like(
                C.PUBACK, i + 1, version=version))
        elif r < 0.9:
            parts.append(im.build_pingreq())
        else:
            parts.append(im.build_unsubscribe(
                i + 1, [f"d/{i}/#"], version=version))
    stream = b"".join(parts)

    for chunk in (1, 3, 17, 256, len(stream)):
        py = Parser()
        nat = NativeParser()
        chunks = [stream[o:o + chunk]
                  for o in range(0, len(stream), chunk)]
        op, on = _feed_outcome(py, chunks), _feed_outcome(nat, chunks)
        assert op == on, (version, chunk)
        assert op[0] == "ok"
        pubs = [p for p in op[1] if isinstance(p, Publish)]
        got_intents = [(p.topic, p.payload, p.qos, p.packet_id)
                       for p in pubs]
        assert got_intents == intents, (version, chunk)


@needs_native_parser
def test_differential_resume_at_every_byte_split():
    """One stream, split at EVERY byte boundary into two feeds: both
    parsers must return the whole-feed reference packet list from
    every resume point."""
    rng = random.Random(808)
    pkts = []
    for i in range(12):
        pkts.append(gen_packet(rng, C.MQTT_V4))
    pkts = [p for p in pkts if not isinstance(p, (Connect, Auth))]
    pkts.append(Publish(topic="r/s", qos=1, packet_id=7,
                        payload=b"tail" * 20))
    stream = b"".join(serialize(p, C.MQTT_V4) for p in pkts)
    ref = Parser(version=C.MQTT_V4).feed(stream)
    assert len(ref) == len(pkts)
    for i in range(len(stream) + 1):
        py = Parser(version=C.MQTT_V4)
        nat = NativeParser(version=C.MQTT_V4)
        gp = py.feed(stream[:i]) + py.feed(stream[i:])
        gn = nat.feed(stream[:i]) + nat.feed(stream[i:])
        assert gp == ref, i
        assert gn == ref, i
        assert _pending(py) == _pending(nat) == 0, i


@needs_native_parser
@pytest.mark.parametrize("version", VERSIONS)
def test_differential_error_classes_on_malformed(version):
    """Corrupted streams: both engines must agree on the FULL
    outcome — packets when clean, else error class, error message,
    and how many bytes stay buffered (raise-before-consume)."""
    rng = random.Random(31991 + version)
    for trial in range(600):
        good = [gen_packet(rng, version) for _ in range(2)]
        good = [p for p in good
                if not isinstance(p, (Connect, Auth))]
        victim = gen_packet(rng, version)
        if isinstance(victim, (Connect, Auth)):
            victim = Publish(topic="v/t", payload=b"x")
        data = bytearray(serialize(victim, version))
        mode = rng.random()
        if mode < 0.4 and data:
            for _ in range(rng.randint(1, 4)):
                k = rng.randrange(len(data))
                data[k] ^= rng.randint(1, 255)
        elif mode < 0.7:
            data = data[:rng.randrange(max(1, len(data)))]
        else:
            data += rng.randbytes(rng.randint(1, 16))
        blob = (b"".join(serialize(p, version) for p in good)
                + bytes(data))
        py = Parser(version=version, max_size=1 << 20)
        nat = NativeParser(version=version, max_size=1 << 20)
        op = _feed_outcome(py, [blob])
        on = _feed_outcome(nat, [blob])
        if op[0] == "ok":
            assert on == op, (trial, op, on)
        else:
            # class + message must match; buffered remainder too
            assert on[0] == op[0], (trial, op, on)
            assert on[1] == op[1], (trial, op, on)
            assert on[2] == op[2], (trial, op, on)


@needs_native_parser
def test_differential_server_to_client_against_indie():
    """Server→client frames serialized by the repo: both in-tree
    parsers and the indie decoder must extract the same fields."""
    from tests import indie_mqtt as im

    rng = random.Random(2718)
    for version in (C.MQTT_V4, C.MQTT_V5):
        pkts = []
        for _ in range(60):
            p = gen_packet(rng, version)
            if isinstance(p, (Connect, Subscribe, Unsubscribe,
                              Pingreq)):
                continue
            if isinstance(p, Auth) and version != C.MQTT_V5:
                continue
            pkts.append(p)
        blob = b"".join(serialize(p, version) for p in pkts)
        got_py = Parser(version=version).feed(blob)
        got_nat = NativeParser(version=version).feed(blob)
        assert got_py == got_nat
        # indie's framing + decode over the same bytes
        iv = 5 if version == C.MQTT_V5 else 4
        off, got_indie = 0, []
        while off < len(blob):
            ptype, flags = blob[off] >> 4, blob[off] & 0x0F
            rl, noff = im.dec_varint(blob, off + 1)
            body = blob[noff:noff + rl]
            got_indie.append(im.decode(ptype, flags, body, iv))
            off = noff + rl
        assert len(got_indie) == len(got_py)
        for mine, theirs in zip(got_py, got_indie):
            if isinstance(mine, Publish):
                assert (mine.topic, mine.payload, mine.qos,
                        mine.retain) == (theirs.topic, theirs.payload,
                                         theirs.qos, theirs.retain)
                if mine.qos:
                    assert mine.packet_id == theirs.pkt_id
            elif isinstance(mine, Connack):
                assert (mine.session_present, mine.reason_code) == \
                    (theirs.session_present, theirs.rc)
            elif isinstance(mine, PubAck):
                assert mine.packet_id == theirs.pkt_id
                if version == C.MQTT_V5:
                    assert mine.reason_code == theirs.rc
            elif isinstance(mine, (Suback, Unsuback)):
                assert mine.packet_id == theirs.pkt_id
                assert list(mine.reason_codes) == theirs.rcs
            elif isinstance(mine, Disconnect) and version == C.MQTT_V5:
                assert mine.reason_code == theirs.rc
