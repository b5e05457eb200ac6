"""Wire runs (ops/dispatch_plan.WireRun, docs/DISPATCH.md "Wire
runs"): a planned batch's QoS0 broadcast leaves each socket as one
pre-joined write. The contract pinned here: every socket's byte
stream and every delivery counter are IDENTICAL with runs and with
runs expanded into the per-message path — across protocol versions
and every session shape a run can meet — with ``tests/indie_mqtt.py``
as the second opinion on the bytes; one run object and one join per
protocol version however many groups share it; and every reader of
the outbox (overload's queue length, the session snapshot, takeover)
sees the frames a run stands for."""

import asyncio
import functools
import time

import pytest

from tests import indie_mqtt as im
from emqx_tpu.broker import Broker, DispatchConfig
from emqx_tpu.cm import ConnectionManager
from emqx_tpu.connection import Connection
from emqx_tpu.mqtt import constants as C
from emqx_tpu.mqtt.frame import WireBlob
from emqx_tpu.mqtt.packet import Connect
from emqx_tpu.ops import dispatch_plan
from emqx_tpu.ops.dispatch_plan import WireRun
from emqx_tpu.router import MatcherConfig, Router
from emqx_tpu.session import WIRE_RUN, Session, expand_outbox
from emqx_tpu.tracing import Tracing, TracingConfig
from emqx_tpu.types import Message, SubOpts
from emqx_tpu.ws_connection import WsConnection
from emqx_tpu.zone import Zone

VERSIONS = (C.MQTT_V3, C.MQTT_V4, C.MQTT_V5)

#: the delivery counters both paths must agree on
COUNTERS = ("packets.sent", "bytes.sent", "packets.publish.sent",
            "messages.sent", "messages.qos0.sent", "messages.qos1.sent",
            "messages.delivered", "delivery.dropped",
            "delivery.dropped.too_large", "delivery.dropped.no_local",
            "delivery.dropped.expired", "delivery.dropped.qos0_msg")


class FakeWriter:
    """What ``Connection`` needs of a ``StreamWriter``; keeps every
    piece handed to the transport, in order."""

    transport = None

    def __init__(self):
        self.pieces = []

    def get_extra_info(self, name, default=None):
        return default

    def write(self, data):
        self.pieces.append(data)

    def writelines(self, frames):
        self.pieces.extend(frames)

    def close(self):
        pass

    def stream(self) -> bytes:
        return b"".join(self.pieces)

    def joined_frames(self) -> int:
        return sum(p.frames for p in self.pieces
                   if type(p) is WireBlob)


def _broker() -> Broker:
    return Broker(router=Router(MatcherConfig(device_min_filters=0),
                                node="n1"),
                  dispatch_config=DispatchConfig())


def _connect(broker, cm, cid, ver, props=None, zone=None, cls=Connection,
             **connect_kw):
    """One connection on a fake socket. Deliveries stay in the outbox
    until :func:`_flush` — the gap a live loop has between the
    planner's enqueue and the flush wake-up."""
    w = FakeWriter()
    conn = cls(None, w, broker, cm, zone=zone)
    out = conn.channel.handle_in(Connect(
        client_id=cid, proto_ver=ver, proto_name=C.PROTOCOL_NAMES[ver],
        properties=dict(props or {}), **connect_kw))
    assert out and out[0].type == C.CONNACK and out[0].reason_code == 0
    conn.channel.on_deliver = None
    # the CONNACK, and whatever a resumed session had waiting
    conn._send_packets(out)
    conn.after_connack = w.pieces[1:]
    w.pieces = []
    conn.send_pkts = conn.send_bytes = 0
    return conn


def _flush(conn) -> None:
    conn._flush_deliver()


def _indie_frames(stream: bytes, ver: int):
    """The stream split and decoded by the independent codec alone."""
    out = []
    i = 0
    while i < len(stream):
        rl, boff = im.dec_varint(stream, i + 1)
        body = bytes(stream[boff:boff + rl])
        assert len(body) == rl
        p = im.decode(stream[i] >> 4, stream[i] & 0x0F, body,
                      5 if ver == C.MQTT_V5 else 4)
        props = {k: v for k, v in (p.props or {}).items()
                 if k != "Message-Expiry-Interval"} \
            if ver == C.MQTT_V5 else {}
        out.append((p.ptype, p.topic, bytes(p.payload), p.qos, p.retain,
                    p.dup, props))
        i = boff + rl
    return out


def _batch(prefix="t", n=6, big=2):
    """``n`` QoS0 messages on three topics; message ``big`` carries a
    300-byte payload (the frame a small Maximum-Packet-Size drops)."""
    return [Message(topic=f"{prefix}/{k % 3}",
                    payload=(b"B" * 300 if k == big else b"p%d" % k),
                    qos=0, from_="pub") for k in range(n)]


#: case -> (v5 only?, frames that leave socket "a" inside a joined
#: piece, the plain peers' outbox entries: is each one a run?)
ONE_RUN = [True]
CASES = {
    "plain": (False, 6, ONE_RUN),
    "mountpoint": (False, 0, ONE_RUN),
    # message 1 counts down its expiry per delivery: a lone frame,
    # the countdown's, then a run of four
    "expiry": (False, 4, [False, False, True]),
    "nl": (False, 0, ONE_RUN),
    "traced": (False, 0, [False] * 6),
    "trace_on_late": (False, 0, ONE_RUN),
    # message 4 is QoS1: a run of four, the QoS1 frame, a lone frame
    "mixed_qos": (False, 4, [True, False, False]),
    "disconnected": (False, 0, ONE_RUN),
    "alias": (True, 0, ONE_RUN),
    "maxpkt_above": (True, 6, ONE_RUN),
    "maxpkt_below": (True, 0, ONE_RUN),
    "subid": (True, 0, ONE_RUN),
}
PARAMS = [(ver, case) for case, (v5, _j, _k) in CASES.items()
          for ver in (VERSIONS if not v5 else (C.MQTT_V5,))]


@functools.lru_cache(maxsize=None)
def _scenario(ver: int, case: str, runs: bool):
    """Deliver one planned batch to three sockets — ``a`` (the case's
    subject, protocol ``ver``), ``b`` (plain, ``ver``), ``c`` (plain,
    another version) — with runs or, ``MIN_RUN_FRAMES`` out of reach,
    with every run expanded. Returns what a socket and a counter can
    show."""
    saved = dispatch_plan.MIN_RUN_FRAMES
    dispatch_plan.MIN_RUN_FRAMES = saved if runs else 1 << 30
    try:
        return _run_scenario(ver, case)
    finally:
        dispatch_plan.MIN_RUN_FRAMES = saved


def _run_scenario(ver: int, case: str):
    b = _broker()
    cm = ConnectionManager()
    prefix = "mp/t" if case == "mountpoint" else "t"
    a_props, a_zone, a_opts = {}, None, SubOpts(qos=0)
    a_filter = f"{prefix}/#"
    if case == "mountpoint":
        a_zone = Zone(name="mp", mountpoint="mp/")
    elif case == "alias":
        a_props = {"Topic-Alias-Maximum": 2}
    elif case == "maxpkt_above":
        a_props = {"Maximum-Packet-Size": 10_000}
    elif case == "maxpkt_below":
        a_props = {"Maximum-Packet-Size": 200}
    elif case == "subid":
        a_opts = SubOpts(qos=0, subid=7)
    elif case == "nl":
        a_opts = SubOpts(qos=0, nl=True)
    elif case == "mixed_qos":
        a_opts = SubOpts(qos=1)
    if case == "traced":
        b.tracing = Tracing(TracingConfig(enabled=True, sample_rate=1.0))
    elif case == "trace_on_late":
        b.tracing = Tracing(TracingConfig(enabled=False, sample_rate=1.0))
    other = C.MQTT_V4 if ver != C.MQTT_V4 else C.MQTT_V5
    conns = {
        "a": _connect(b, cm, "a", ver, props=a_props, zone=a_zone),
        "b": _connect(b, cm, "b", ver),
        "c": _connect(b, cm, "c", other),
    }
    # a run reaches a session it does not fit only through a stale
    # hint (a session resumed by another kind of channel): force it,
    # so that the flush — not the planner — has to turn the run away
    conns["a"].channel.session.wire_fast_hint = True
    conns["a"].channel.session.subscribe(a_filter, a_opts)
    conns["b"].channel.session.subscribe(f"{prefix}/#", SubOpts(qos=0))
    conns["c"].channel.session.subscribe(f"{prefix}/+", SubOpts(qos=0))
    msgs = _batch(prefix)
    if case == "expiry":
        msgs[1].set_header("properties", {"Message-Expiry-Interval": 60})
    elif case == "nl":
        msgs[3].from_ = "a"
    elif case == "mixed_qos":
        msgs[4].qos = 1
    elif case == "disconnected":
        conns["a"].channel.session.connected = False
    counts = b.publish_batch(msgs)
    outbox_kinds = {k: [pid is WIRE_RUN for pid, _ in
                        c.channel.session.outbox]
                    for k, c in conns.items()}
    if case == "trace_on_late":
        b.tracing.config.enabled = True
    for c in conns.values():
        _flush(c)
    streams = {k: c.writer.stream() for k, c in conns.items()}
    sess_a = conns["a"].channel.session
    return {
        "counts": counts,
        "streams": streams,
        "vers": {"a": ver, "b": ver, "c": other},
        "pieces": {k: len(c.writer.pieces) for k, c in conns.items()},
        "joined": {k: c.writer.joined_frames()
                   for k, c in conns.items()},
        "outbox_kinds": outbox_kinds,
        "conn": {k: (c.send_pkts, c.send_bytes)
                 for k, c in conns.items()},
        "metrics": {k: b.metrics.val(k) for k in COUNTERS},
        "wire_run_frames": b.metrics.val("delivery.wire_run.frames"),
        "wire_runs": b.metrics.val("delivery.wire_runs"),
        "a_queue": (len(sess_a.mqueue), len(sess_a.inflight)),
        "aliases": dict(conns["a"].channel.alias_out),
    }


# -- (1) byte identity ----------------------------------------------------


@pytest.mark.parametrize("ver,case", PARAMS)
def test_byte_identity_runs_vs_expanded(ver, case):
    on = _scenario(ver, case, True)
    off = _scenario(ver, case, False)
    assert on["counts"] == off["counts"]
    # every socket: the same bytes in the same order
    assert on["streams"] == off["streams"]
    assert on["a_queue"] == off["a_queue"]
    assert on["aliases"] == off["aliases"]
    # the expanded side really is the per-message path …
    assert off["wire_run_frames"] == 0
    assert not any(any(k) for k in off["outbox_kinds"].values())
    # … and the run side really ran: each plain peer's outbox held the
    # batch as its maximal runs, and every run left as ONE piece
    _v5, a_joined, kinds = CASES[case]
    peer_joined = 0 if case in ("traced", "trace_on_late") \
        else (6 if kinds == ONE_RUN else 4)
    for k in ("b", "c"):
        assert on["outbox_kinds"][k] == kinds
        assert on["joined"][k] == peer_joined
        if peer_joined:
            assert on["pieces"][k] == len(kinds)
    assert on["joined"]["a"] == a_joined
    # second opinion: an independent codec reads the same PUBLISHes
    # out of both, and for the plain peers exactly the batch
    for k, stream in on["streams"].items():
        got = _indie_frames(stream, on["vers"][k])
        assert got == _indie_frames(off["streams"][k], on["vers"][k])
        assert all(p[0] == im.PUBLISH for p in got)
    want = [(m.topic, bytes(m.payload)) for m in _batch(
        "mp/t" if case == "mountpoint" else "t")]
    for k in ("b", "c"):
        got = _indie_frames(on["streams"][k], on["vers"][k])
        assert [(p[1], p[2]) for p in got] == want


def test_case_specifics_hold_on_the_run_side():
    """What each awkward session must see, read from the run side."""
    v5 = C.MQTT_V5
    # mountpoint: unmounted topics, all six
    got = _indie_frames(_scenario(v5, "mountpoint", True)["streams"]["a"],
                        v5)
    assert [p[1] for p in got] == [f"t/{k % 3}" for k in range(6)]
    # outbound alias: three topics, two aliases assigned, repeats empty
    s = _scenario(v5, "alias", True)
    got = _indie_frames(s["streams"]["a"], v5)
    assert len(got) == 6 and len(s["aliases"]) == 2
    assert sum(1 for p in got if p[1] == "") == 2
    # Maximum-Packet-Size under the largest frame: exactly that frame
    # is dropped, the other five arrive in order
    s = _scenario(v5, "maxpkt_below", True)
    got = _indie_frames(s["streams"]["a"], v5)
    assert [p[2] for p in got] == [b"p0", b"p1", b"p3", b"p4", b"p5"]
    assert s["metrics"]["delivery.dropped.too_large"] == 1
    # … and above it the run goes out joined
    s = _scenario(v5, "maxpkt_above", True)
    assert s["joined"]["a"] == 6 and s["pieces"]["a"] == 1
    # subid rides on every frame of that session only
    s = _scenario(v5, "subid", True)
    got = _indie_frames(s["streams"]["a"], v5)
    assert all(p[6].get("Subscription-Identifier") == [7] for p in got)
    assert s["joined"] == {"a": 0, "b": 6, "c": 6}
    # no-local: the session's own message is withheld, the group is no
    # longer the run's and goes per message
    s = _scenario(v5, "nl", True)
    got = _indie_frames(s["streams"]["a"], v5)
    assert [p[2] for p in got] == [b"p0", b"p1", b"B" * 300, b"p4",
                                   b"p5"]
    assert s["outbox_kinds"]["a"] == [False] * 5
    # a disconnected session's QoS0 goes to its queue policy, not to
    # the socket; its peers are untouched
    s = _scenario(v5, "disconnected", True)
    assert s["streams"]["a"] == b"" and s["joined"]["b"] == 6
    # tracing switched on between enqueue and flush: the run expands
    s = _scenario(v5, "trace_on_late", True)
    assert s["outbox_kinds"]["b"] == [True] and s["joined"]["b"] == 0
    assert s["pieces"]["b"] == 6


# -- (2) counter parity ---------------------------------------------------


@pytest.mark.parametrize("ver,case", PARAMS)
def test_counter_parity_runs_vs_expanded(ver, case):
    on = _scenario(ver, case, True)
    off = _scenario(ver, case, False)
    assert on["metrics"] == off["metrics"]
    assert on["conn"] == off["conn"]
    # the engagement counter counts exactly the frames that went out
    # inside a joined piece, and the runs written
    assert on["wire_run_frames"] == sum(on["joined"].values())
    assert on["wire_runs"] == sum(1 for v in on["joined"].values() if v)
    # per-connection counters against the socket itself
    for k, stream in on["streams"].items():
        assert on["conn"][k] == (
            len(_indie_frames(stream, on["vers"][k])), len(stream))


# -- (3) one shared object, one join per protocol version -----------------


def _hinted(broker, cid, ver):
    s = Session(cid, broker=broker)
    s.proto_ver = ver
    s.wire_fast_hint = True
    return s


def test_thousand_equal_groups_share_one_run_and_one_join(monkeypatch):
    b = _broker()
    sess = [_hinted(b, f"k{i}", C.MQTT_V5 if i % 4 == 0 else C.MQTT_V4)
            for i in range(1000)]
    for s in sess:
        s.subscribe("f/g", SubOpts(qos=0))
    odd = _hinted(b, "odd", C.MQTT_V4)   # another slice of the rows
    odd.subscribe("f/g", SubOpts(qos=0))
    odd.subscribe("f/h", SubOpts(qos=0))
    joins = []
    real_join = WireRun.join

    def counting_join(self, ver):
        joins.append((id(self), ver))
        return real_join(self, ver)

    monkeypatch.setattr(WireRun, "join", counting_join)
    msgs = [Message(topic="f/g", payload=b"%d" % k, qos=0, from_="p")
            for k in range(5)] \
        + [Message(topic="f/h", payload=b"h", qos=0, from_="p")]
    pb = b.publish_begin(msgs)
    b.publish_fetch(pb)
    # every group is one whole-batch segment; a thousand of them are
    # the SAME tuple holding the same run
    assert all(len(segs) == 1 and segs[0][0] == 0
               for segs in pb.plan.g_runs)
    runs = {id(segs[0][2]): segs[0][2] for segs in pb.plan.g_runs}
    assert len(runs) == 2               # the thousand's, and odd's
    assert len({id(segs) for segs in pb.plan.g_runs}) == 2
    shared = next(r for r in runs.values() if r.n == 5)
    assert shared.n == 5
    # one join per (run, hinted version): v4 and v5 for the thousand,
    # v4 for odd's — not one per group
    assert sorted(v for _i, v in joins) == [C.MQTT_V4, C.MQTT_V4,
                                            C.MQTT_V5]
    assert b.publish_finish(pb) == [1001] * 5 + [1]
    # every session holds the SAME object as its one outbox entry
    assert all(len(s.outbox) == 1 and s.outbox[0][0] is WIRE_RUN
               and s.outbox[0][1] is shared for s in sess)
    assert odd.outbox[0][1] is not shared and odd.outbox[0][1].n == 6
    # and both versions' bytes are the shared blobs, joined once
    blob4, blob5 = shared.joined(C.MQTT_V4), shared.joined(C.MQTT_V5)
    assert blob4.frames == blob5.frames == 5
    assert len(blob5) == len(blob4) + 5   # a property-length byte each
    assert len(joins) == 3                # the finish joined nothing


def test_late_version_joins_on_loop_once_and_is_counted():
    b = _broker()
    cm = ConnectionManager()
    conns = [_connect(b, cm, f"l{i}", C.MQTT_V5) for i in range(3)]
    for c in conns:
        c.channel.session.subscribe("lv/#", SubOpts(qos=0))
        # the planner sees v4 sessions; the channels speak v5 — a
        # session resumed on another protocol version
        c.channel.session.proto_ver = C.MQTT_V4
    b.publish_batch([Message(topic="lv/x", payload=b"%d" % k, qos=0,
                             from_="p") for k in range(4)])
    run = conns[0].channel.session.outbox[0][1]
    assert run.joined(C.MQTT_V4) is not None
    assert run.joined(C.MQTT_V5) is None
    for c in conns:
        _flush(c)
    # four v5 images built on the loop, by the first flush alone
    assert b.metrics.val("delivery.serialize.onloop") == 4
    assert all(c.writer.pieces == [run.joined(C.MQTT_V5)] for c in conns)
    got = _indie_frames(conns[2].writer.stream(), C.MQTT_V5)
    assert [p[2] for p in got] == [b"0", b"1", b"2", b"3"]


def test_run_segments_are_the_maximal_eligible_stretches():
    msgs = [Message(topic="s/t", payload=b"%d" % k, qos=0, from_="p")
            for k in range(9)]
    msgs[2].qos = 1                       # a fence
    msgs[5].set_flag("retain", True)
    msgs[6].headers["_trace"] = {"tid": 1, "t0": 0.0}
    live = list(enumerate(msgs))
    runs = {}
    ok = bytearray(len(live))
    #        0 1 | 2 | 3 4 | 5 6 | 7 | 8 8(a duplicate row: two filters)
    rkey = (0, 1, 2, 3, 4, 5, 6, 7, 8, 8)
    segs = dispatch_plan._run_segments(rkey, live, ok, runs)
    assert [(a, b) for a, b, _r in segs] == [(0, 2), (3, 5), (7, 10)]
    assert [[m.payload for m in r.msgs] for _a, _b, r in segs] \
        == [[b"0", b"1"], [b"3", b"4"], [b"7", b"8", b"8"]]
    assert bytes(ok) == bytes([1, 1, 2, 1, 1, 2, 2, 1, 1])
    # another slice holding the same stretch shares the run object
    again = dispatch_plan._run_segments((3, 4, 5), live, ok, runs)
    assert again == ((0, 2, segs[1][2]),)
    # a lone eligible frame between two others forms none
    assert dispatch_plan._run_segments((2, 7, 5), live, ok, runs) == ()


def test_flood_shaped_batch_goes_out_as_runs_around_its_fences():
    """The benchmark's fan-out shape: each publisher's burst of QoS0
    ends in a QoS1 fence on the same topics, so nearly every batch
    holds one — the stretches between them are the runs."""
    b = _broker()
    cm = ConnectionManager()
    conns = [_connect(b, cm, f"fl{i}", C.MQTT_V4) for i in range(4)]
    for c in conns:
        for g in range(3):
            c.channel.session.subscribe(f"fan/g{g}", SubOpts(qos=0))
    msgs = []
    for k in range(36):
        fence = k in (11, 30)
        msgs.append(Message(topic=f"fan/g{k % 3}", payload=b"%d" % k,
                            qos=1 if fence else 0, from_="p"))
    b.publish_batch(msgs)
    for c in conns:
        assert [pid is WIRE_RUN for pid, _ in c.channel.session.outbox] \
            == [True, False, True, False, True]
        _flush(c)
        assert [getattr(p, "frames", 1) for p in c.writer.pieces] \
            == [11, 1, 18, 1, 5]
        got = _indie_frames(c.writer.stream(), C.MQTT_V4)
        assert [p[2] for p in got] == [b"%d" % k for k in range(36)]
        assert all(p[3] == 0 for p in got)
    assert b.metrics.val("delivery.wire_run.frames") == 4 * 34
    assert b.metrics.val("messages.sent") == 4 * 36
    # four sockets, one join per run: the pieces are the SAME objects
    assert all(c.writer.pieces[0] is conns[0].writer.pieces[0]
               for c in conns)


def test_single_frame_group_forms_no_run():
    b = _broker()
    s = _hinted(b, "one", C.MQTT_V4)
    s.subscribe("o/t", SubOpts(qos=0))
    b.publish_batch([Message(topic="o/t", qos=0, from_="p")])
    assert [pid for pid, _ in s.outbox] == [None]


# -- (4) the outbox's other readers ---------------------------------------


def _session_with_run(b, cid="r1", n=4):
    s = _hinted(b, cid, C.MQTT_V4)
    s.subscribe("ob/#", SubOpts(qos=0))
    b.publish_batch([Message(topic="ob/x", payload=b"%d" % k, qos=0,
                             from_="p") for k in range(n)])
    assert [pid for pid, _ in s.outbox] == [WIRE_RUN]
    return s


def test_outbox_frames_counts_a_run_as_its_frames():
    b = _broker()
    s = _session_with_run(b, n=4)
    s.outbox.append(("pubrel", 9))
    assert len(s.outbox) == 2 and s.outbox_frames() == 5
    assert [pid for pid, _ in expand_outbox(s.outbox)] \
        == [None] * 4 + ["pubrel"]


def test_overload_force_shutdown_counts_frames():
    from emqx_tpu.node import Node
    from emqx_tpu.overload import OverloadConfig

    node = Node(boot_listeners=False,
                matcher=MatcherConfig(device_min_filters=0),
                overload=OverloadConfig(force_shutdown_queue_len=5))
    s = _session_with_run(node.broker, "big", n=8)
    small = _session_with_run(node.broker, "small", n=3)
    kicked = []

    class Chan:
        def __init__(self, session):
            self.session = session

    node.cm._channels["big"] = Chan(s)
    node.cm._channels["small"] = Chan(small)
    node.cm.kick_session = kicked.append
    node.overload._sweep_force_shutdown()
    # one outbox entry, eight frames: over the policy of five
    assert kicked == ["big"]
    assert node.metrics.val("overload.force_shutdown") == 1


def test_snapshot_restore_persists_a_run_as_its_messages():
    from emqx_tpu import wire

    b = _broker()
    s = _session_with_run(b, n=4)
    d = s.to_wire()
    assert [pid for pid, _ in d["outbox"]] == [None] * 4
    assert [m.payload for _pid, m in d["outbox"]] \
        == [b"0", b"1", b"2", b"3"]
    # the snapshot is pure data: it crosses the cluster wire codec
    back = wire.loads(wire.dumps(s))
    assert [(pid, m.topic, m.payload) for pid, m in back.outbox] \
        == [(None, "ob/x", b"%d" % k) for k in range(4)]
    assert s.outbox[0][0] is WIRE_RUN      # the live session keeps it
    # a restored session flushes them per message: nothing lost,
    # nothing twice
    cm = ConnectionManager()
    conn = _connect(b, cm, "restored", C.MQTT_V4)
    conn.channel.session.outbox = list(back.outbox)
    _flush(conn)
    got = _indie_frames(conn.writer.stream(), C.MQTT_V4)
    assert [p[2] for p in got] == [b"0", b"1", b"2", b"3"]


def test_takeover_with_a_run_in_the_outbox_loses_and_duplicates_nothing():
    b = _broker()
    cm = ConnectionManager()
    old = _connect(b, cm, "tk", C.MQTT_V5, clean_start=False,
                   props={"Session-Expiry-Interval": 300})
    old.channel.session.subscribe("tk/#", SubOpts(qos=0))
    b.publish_batch([Message(topic="tk/x", payload=b"%d" % k, qos=0,
                             from_="p") for k in range(5)])
    sess = old.channel.session
    assert [pid for pid, _ in sess.outbox] == [WIRE_RUN]
    # the same client id reconnects, on v4, before the old
    # connection's flush ran
    new = _connect(b, cm, "tk", C.MQTT_V4, clean_start=False)
    assert new.channel.session is sess and sess.outbox == []
    _flush(old)
    _flush(new)
    # the old socket sent none of it (only its v5 DISCONNECT, session
    # taken over); the new one got the five frames behind its CONNACK
    # — as a v4 run, joined where it was flushed
    assert old.writer.pieces == [b"\xe0\x02\x8e\x00"]
    assert new.writer.pieces == []
    assert [type(p) for p in new.after_connack] == [WireBlob]
    got = _indie_frames(b"".join(new.after_connack), C.MQTT_V4)
    assert [(p[0], p[2]) for p in got] \
        == [(im.PUBLISH, b"%d" % k) for k in range(5)]
    assert b.metrics.val("messages.sent") == 5


# -- (5) WebSocket and the two-loop node ----------------------------------


def _ws_payloads(stream: bytes):
    """Server→client WS frames are unmasked: split them by hand."""
    out = []
    i = 0
    while i < len(stream):
        assert stream[i] == 0x82      # FIN | binary
        n = stream[i + 1] & 0x7F
        pos = i + 2
        if n == 126:
            n = int.from_bytes(stream[pos:pos + 2], "big")
            pos += 2
        elif n == 127:
            n = int.from_bytes(stream[pos:pos + 8], "big")
            pos += 8
        out.append(bytes(stream[pos:pos + n]))
        i = pos + n
    return out


@pytest.mark.parametrize("ver", VERSIONS)
def test_ws_carries_the_same_mqtt_bytes(ver, monkeypatch):
    streams = {}
    for runs in (True, False):
        monkeypatch.setattr(dispatch_plan, "MIN_RUN_FRAMES",
                            2 if runs else 1 << 30)
        b = _broker()
        cm = ConnectionManager()
        ws = _connect(b, cm, "ws", ver, cls=WsConnection)
        tcp = _connect(b, cm, "tcp", ver)
        for c in (ws, tcp):
            c.channel.session.subscribe("t/#", SubOpts(qos=0))
        b.publish_batch(_batch())
        _flush(ws)
        _flush(tcp)
        payloads = _ws_payloads(ws.writer.stream())
        # the run is ONE binary frame holding the six control packets
        # (MQTT-6.0.0-2); expanded, six frames of one packet each
        assert len(payloads) == (1 if runs else 6)
        assert b"".join(payloads) == tcp.writer.stream()
        assert ws.send_pkts == tcp.send_pkts == 6
        streams[runs] = b"".join(payloads)
        assert b.metrics.val("delivery.wire_run.frames") \
            == (12 if runs else 0)
    assert streams[True] == streams[False]
    got = _indie_frames(streams[True], ver)
    assert [(p[1], p[2]) for p in got] \
        == [(m.topic, bytes(m.payload)) for m in _batch()]


async def _fan_node(loops: int):
    """Eight raw sockets on one filter through a ``loops``-sharded
    node, bursts of QoS0 from a ninth; returns each subscriber's
    PUBLISH stream as bytes, and the engagement counters."""
    from helpers import broker_node, node_port

    async def sub(port, cid):
        r, w = await asyncio.open_connection("127.0.0.1", port)
        w.write(im.build_connect(cid, version=4))
        await r.readexactly(4)
        w.write(im.build_subscribe(1, [("fan/#", 0)], version=4))
        await r.readexactly(5)
        return r, w

    async with broker_node(
            loops=loops,
            matcher=MatcherConfig(device_min_filters=0)) as node:
        port = node_port(node)
        subs = [await sub(port, f"f{i}") for i in range(8)]
        pr, pw = await asyncio.open_connection("127.0.0.1", port)
        pw.write(im.build_connect("fp", version=4))
        await pr.readexactly(4)
        n_msgs = 0
        for burst in range(4):
            for k in range(12):
                pw.write(im.build_publish(f"fan/{k % 3}",
                                          b"b%d-%d" % (burst, k)))
                n_msgs += 1
            # a QoS1 fence ON THE SAME TOPICS ends the burst, as in
            # the benchmark's flood: it reaches the QoS0 subscribers
            # as a downgraded copy, between two runs
            pw.write(im.build_publish("fan/0", b"fence-%d" % burst,
                                      qos=1, pkt_id=burst + 1))
            n_msgs += 1
            await pw.drain()
            await asyncio.wait_for(pr.readexactly(4), 20.0)
        want = None
        streams = []
        for r, _w in subs:
            buf = b""
            deadline = time.monotonic() + 20.0
            while True:
                frames = _indie_frames_partial(buf)
                if len(frames) >= n_msgs:
                    break
                assert time.monotonic() < deadline
                buf += await asyncio.wait_for(r.read(1 << 16), 20.0)
            streams.append(buf)
            want = want or buf
        stats = {k: node.metrics.val(k) for k in (
            "delivery.wire_run.frames", "messages.sent",
            "delivery.xloop.deliveries", "packets.sent")}
        for _r, w in subs + [(pr, pw)]:
            w.close()
        return streams, stats, n_msgs


def _indie_frames_partial(buf: bytes):
    """Whole frames at the head of ``buf`` (a socket read may end
    inside one)."""
    out = []
    i = 0
    while i + 2 <= len(buf):
        try:
            rl, boff = im.dec_varint(buf, i + 1)
        except im.MQTTError:
            break
        if boff + rl > len(buf):
            break
        out.append(bytes(buf[i:boff + rl]))
        i = boff + rl
    return out


async def test_two_loop_node_delivers_the_same_frames():
    one, one_stats, n = await _fan_node(1)
    two, two_stats, _n = await _fan_node(2)
    # every socket of both nodes: the same frames in the same order
    assert len(set(one)) == 1 and set(two) == set(one)
    got = _indie_frames(one[0], C.MQTT_V4)
    assert [p[2] for p in got] == [
        x for burst in range(4)
        for x in [b"b%d-%d" % (burst, k) for k in range(12)]
        + [b"fence-%d" % burst]]
    assert all(p[3] == 0 for p in got)      # the fences too, as QoS0
    for stats in (one_stats, two_stats):
        assert stats["messages.sent"] == 8 * n
        # bursts arrive as batches: most frames left inside a run,
        # the fences and what a batch boundary left alone did not
        assert stats["delivery.wire_run.frames"] >= 0.5 * 8 * n
    assert one_stats["delivery.xloop.deliveries"] == 0
    assert two_stats["delivery.xloop.deliveries"] > 0
