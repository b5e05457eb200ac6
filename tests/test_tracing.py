"""End-to-end message tracing (emqx_tpu/tracing.py): deterministic
sampling, span lifecycle across the publish seams, the disabled-mode
byte-identity pin, ring overflow accounting, slow-subscriber
ranking/expiry/alarm, trace-context continuity across loops and a
2-node cluster forward, Chrome trace-event export, the per-loop lag
gauges, and the observability satellites (tracer topic stamping,
profile-stop error handling, [tracing] config schema + reload
classification)."""

import asyncio
import json

import pytest

from emqx_tpu.alarm import AlarmManager
from emqx_tpu.broker import Broker
from emqx_tpu.config import ConfigError, parse_config
from emqx_tpu.metrics import Metrics
from emqx_tpu.monitors import SysMon
from emqx_tpu.node import Node
from emqx_tpu.router import MatcherConfig, Router
from emqx_tpu.tracer import Tracer
from emqx_tpu.tracing import (TRACE_HEADER, SlowSubs, Tracing,
                              TracingConfig)
from emqx_tpu.types import Message

from helpers import broker_node, node_port
from mqtt_client import TestClient


class Q:
    def __init__(self, client_id="c"):
        self.client_id = client_id
        self.inbox = []

    def deliver(self, topic, msg):
        self.inbox.append((topic, msg))


def _wire(broker: Broker, cfg: TracingConfig = None,
          **trc_kw) -> Tracing:
    """Manual Node-style wiring for standalone Broker tests."""
    trc = Tracing(cfg or TracingConfig(sample_rate=1.0), **trc_kw)
    broker.tracing = trc
    return trc


def _device_broker(**mk) -> Broker:
    mk.setdefault("device_min_filters", 0)
    return Broker(router=Router(MatcherConfig(**mk), node="node1"))


# -- deterministic sampling -----------------------------------------------


def test_sampling_is_deterministic_and_rate_shaped():
    t0 = Tracing(TracingConfig(sample_rate=0.5))
    t1 = Tracing(TracingConfig(sample_rate=0.5))
    mids = list(range(10_000))
    picks = [m for m in mids if t0.sampled(m)]
    # every instance (== every node of a cluster) picks the same set
    assert picks == [m for m in mids if t1.sampled(m)]
    assert 0.4 < len(picks) / len(mids) < 0.6
    # the rate endpoints are exact
    assert not any(Tracing(TracingConfig(sample_rate=0.0)).sampled(m)
                   for m in mids)
    assert all(Tracing(TracingConfig(sample_rate=1.0)).sampled(m)
               for m in mids)


def test_sample_rate_is_live_reloadable():
    trc = Tracing(TracingConfig(sample_rate=0.0))
    assert not trc.active and not trc.sampled(7)
    trc.config.sample_rate = 1.0  # what apply_reload does
    assert trc.active and trc.sampled(7)
    from emqx_tpu.reload import classification

    table = classification()["tracing"]
    assert table["sample_rate"] == "reloadable"
    assert table["slow_subs_threshold_ms"] == "reloadable"
    assert table["ring_size"] == "boot_only"
    assert table["enabled"] == "boot_only"


def test_stamp_is_idempotent_and_keeps_foreign_context():
    trc = Tracing(TracingConfig(sample_rate=1.0), node="here")
    msg = Message(topic="t")
    ctx = trc.stamp(msg)
    assert ctx is not None and ctx["tid"] == msg.id
    assert msg.headers[TRACE_HEADER] is ctx
    # a context that arrived with the message (cluster forward) wins
    assert trc.stamp(msg) is ctx
    foreign = {"tid": 99, "t0": 1.0, "node": "there"}
    msg2 = Message(topic="t", headers={TRACE_HEADER: foreign})
    assert trc.stamp(msg2) is foreign


# -- disabled mode: byte-identical dispatch, zero span allocations --------


def _run_workload(broker):
    subs = [Q(f"c{i}") for i in range(3)]
    broker.subscribe(subs[0], "w/+/x")
    broker.subscribe(subs[1], "w/1/x")
    broker.subscribe(subs[2], "w/#")
    out = []
    for _ in range(3):
        out.append(broker.publish_batch(
            [Message(topic="w/1/x"), Message(topic="w/2/x"),
             Message(topic="other")]))
    return out, [[t for t, _ in s.inbox] for s in subs]


def test_sample_rate_zero_is_byte_identical_and_allocates_nothing():
    b_off = _device_broker(match_cache_slots=64)
    trc = _wire(b_off, TracingConfig(sample_rate=0.0))
    b_ref = _device_broker(match_cache_slots=64)  # tracing = None
    got_off = _run_workload(b_off)
    got_ref = _run_workload(b_ref)
    assert got_off == got_ref  # results AND per-sub delivery streams
    # zero span allocations: no ring was ever registered, no batch
    # ever carried trace state, no message was ever stamped
    assert trc._rings == []
    assert trc.drain_tick() == 0 and trc.spans_total == 0
    pb = b_off.publish_begin([Message(topic="w/1/x")])
    assert pb.tbatch is None
    b_off.publish_fetch(pb)
    b_off.publish_finish(pb)


def test_sampled_mode_same_dispatch_results_as_reference():
    b_on = _device_broker(match_cache_slots=64)
    trc = _wire(b_on, TracingConfig(sample_rate=1.0))
    b_ref = _device_broker(match_cache_slots=64)
    assert _run_workload(b_on) == _run_workload(b_ref)
    assert trc.drain_tick() > 0  # and the spans actually recorded


# -- span lifecycle on the broker seams -----------------------------------


def test_host_path_records_the_batch_span_chain():
    b = Broker()  # default config: few filters -> host regime
    trc = _wire(b)
    s = Q()
    b.subscribe(s, "a/+")
    assert b.publish_batch([Message(topic="a/x"),
                            Message(topic="a/y")]) == [1, 1]
    trc.drain_tick()
    stages = [rec[1] for rec in trc._export]
    for stage in ("ingress", "match", "dispatch", "publish"):
        assert stages.count(stage) == 1, (stage, stages)
    # batch spans carry every sampled message's trace id
    tids_per = {rec[1]: rec[0] for rec in trc._export}
    assert len(tids_per["publish"]) == 2


def test_device_path_chunked_finish_closes_trace_batch_once():
    b = _device_broker(match_cache=False)
    trc = _wire(b)
    s = Q()
    b.subscribe(s, "t/+")
    msgs = [Message(topic=f"t/{i}") for i in range(8)]
    pb = b.publish_begin(msgs)
    assert pb.tbatch is not None
    b.publish_fetch(pb)
    for lo in range(0, len(pb.live), 3):
        b.publish_finish_chunk(pb, lo, min(lo + 3, len(pb.live)))
    pb.done = True
    assert pb.results == [1] * 8
    assert pb.tbatch is None  # closed exactly at the last chunk
    trc.drain_tick()
    stages = [rec[1] for rec in trc._export]
    assert stages.count("publish") == 1
    assert stages.count("dispatch") == 1
    assert stages.count("serialize") <= 1


def test_ring_overflow_drops_and_counts_instead_of_blocking():
    m = Metrics()
    b = Broker()
    trc = _wire(b, TracingConfig(sample_rate=1.0, ring_size=2),
                metrics=m)
    s = Q()
    b.subscribe(s, "r")
    for _ in range(5):  # 4 spans per batch >> ring_size 2
        b.publish_batch([Message(topic="r")])
    assert trc.drain_tick() == 2  # the ring never grew past cap
    assert trc.dropped_total > 0
    assert m.val("tracing.dropped") == trc.dropped_total
    assert m.val("tracing.spans") == 2


# -- slow subscribers -----------------------------------------------------


def test_slow_subs_ranking_ewma_and_expiry():
    cfg = TracingConfig(slow_subs_top=2, slow_subs_expiry_s=10.0)
    ss = SlowSubs(cfg)
    ss.fold("fast", 1.0, now_w=100.0)
    for lat in (800.0, 900.0):
        ss.fold("slow1", lat, now_w=100.0)
    ss.fold("slow2", 400.0, now_w=100.0)
    rows = ss.top()
    assert len(rows) == 2  # bounded by slow_subs_top
    assert rows[0][0] == "slow1" and rows[1][0] == "slow2"
    assert rows[0][2] == 900.0 and rows[0][3] == 2  # max, count
    # EWMA: the average moved toward the second sample
    assert 800.0 < rows[0][1] < 900.0
    # expiry: an idle clientid drops off the next tick
    ss.fold("slow2", 400.0, now_w=111.0)
    ss.tick(now_w=111.0)  # 100.0 + 10s < 111 -> fast/slow1 expire
    assert set(ss.clients) == {"slow2"}


def test_slow_subs_table_is_bounded_under_clientid_fanin():
    cfg = TracingConfig(slow_subs_top=10)
    ss = SlowSubs(cfg)
    for i in range(1000):
        ss.fold(f"c{i}", float(i), now_w=5.0)
    ss.tick(now_w=5.0)
    assert len(ss.clients) <= max(64, cfg.slow_subs_top * 8)
    # the worst averages survived the bound
    assert ss.top(1)[0][0] == "c999"


def test_slow_subs_sustained_breach_alarm_and_clear():
    alarms = AlarmManager(node="t@test")
    cfg = TracingConfig(slow_subs_threshold_ms=100.0,
                        slow_subs_alarm_ticks=2)
    ss = SlowSubs(cfg, alarms=alarms)
    ss.fold("laggard", 500.0, now_w=1.0)
    ss.tick(now_w=1.0)
    assert not alarms.get_alarms("activated")  # streak 1 < 2
    ss.fold("laggard", 500.0, now_w=2.0)
    ss.tick(now_w=2.0)
    active = alarms.get_alarms("activated")
    assert [a.name for a in active] == ["slow_subs"]
    assert active[0].details["clientid"] == "laggard"
    # recovery: the table empties (expiry) -> streak 0 -> deactivate
    ss.reset()
    ss.tick(now_w=3.0)
    assert not alarms.get_alarms("activated")
    assert [a.name for a in alarms.get_alarms("deactivated")] \
        == ["slow_subs"]


def test_drain_folds_flush_spans_into_slow_subs_and_stats():
    from emqx_tpu.stats import Stats

    m, stats = Metrics(), Stats()
    trc = Tracing(TracingConfig(sample_rate=1.0,
                                slow_subs_threshold_ms=0.0),
                  metrics=m)
    msg = Message(topic="t")
    ctx = trc.stamp(msg)
    trc.flush_mark(ctx, "c-slow")
    trc.drain_tick(stats)
    assert [r[0] for r in trc.slow.top()] == ["c-slow"]
    assert m.val("slow_subs.flushes") == 1
    assert m.val("slow_subs.breaches") == 1  # threshold 0: any flush
    assert stats.getstat("slow_subs.tracked") == 1
    assert stats.getstat("tracing.spans.pending") == 1


# -- Chrome trace-event export --------------------------------------------


def test_export_writes_valid_chrome_trace_json(tmp_path):
    b = Broker()
    trc = _wire(b)
    s = Q()
    b.subscribe(s, "e/+")
    b.publish_batch([Message(topic="e/1")])
    trc.drain_tick()
    path = str(tmp_path / "trace.json")
    n = trc.export(path)
    doc = json.load(open(path))
    assert set(doc) == {"traceEvents", "displayTimeUnit", "otherData"}
    xs = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    ms = [e for e in doc["traceEvents"] if e["ph"] == "M"]
    assert n == len(xs) + len(ms)
    assert {e["name"] for e in xs} == {"ingress", "match", "dispatch",
                                       "publish"}
    for e in xs:
        assert e["ts"] >= 0 and e["dur"] >= 0  # µs, rebased
        assert e["args"]["trace"]
    # writer threads are named via metadata events
    assert {e["name"] for e in ms} == {"thread_name"}
    assert trc.reset() is None and trc._export == []


# -- satellites: tracer topic stamping, profile stop ----------------------


class _Pkt:
    def __init__(self, topic=None):
        self.topic = topic

    def __repr__(self):
        return f"PUBLISH({self.topic})"


def test_trace_packet_stamps_topic_when_packet_has_one():
    tr = Tracer()
    by_topic = tr.start_trace("topic", "tp/#")
    by_client = tr.start_trace("clientid", "c7")
    # a PUBLISH packet carries its topic -> the topic filter sees it
    tr.trace_packet("SEND", "c7", _Pkt(topic="tp/1"))
    assert len(by_topic) == 1 and len(by_client) == 1
    # a topic-less packet (CONNECT/PINGREQ) still hits clientid traces
    tr.trace_packet("RECV", "c7", "PINGREQ")
    assert len(by_topic) == 1 and len(by_client) == 2


class _Reg:
    def __init__(self, node=None):
        self.cmds = {}
        self.node = node

    def register_command(self, name, fn, usage=""):
        self.cmds[name] = fn


def test_profile_stop_failure_returns_text_not_traceback(monkeypatch):
    import jax

    from emqx_tpu import profiling

    class _N:
        tracing = Tracing(TracingConfig())

    reg = _Reg(node=_N())
    profiling.register_ctl(reg)
    # a stop whose underlying trace jax never started must come back
    # as operator text with the registry cleared, not a traceback
    profiling._active["dir"] = "/tmp/ghost"

    def _boom():
        raise RuntimeError("No profile session active")

    monkeypatch.setattr(jax.profiler, "stop_trace", _boom)
    out = reg.cmds["profile"](["stop"])
    assert "profile stop failed" in out
    assert profiling._active["dir"] is None
    assert reg.cmds["profile"](["stop"]) == "not tracing"


def test_profile_loops_subcommands_drive_the_sampler():
    from emqx_tpu import profiling

    class _N:
        tracing = Tracing(TracingConfig(profile_interval_ms=1.0))

    reg = _Reg(node=_N())
    profiling.register_ctl(reg)
    p = reg.cmds["profile"]
    assert p(["loops", "stop"]) == "loop profiler not running"
    assert "sampling every" in p(["loops", "start"])
    assert "already running" in p(["loops", "start"])
    import time as _t
    _t.sleep(0.05)
    assert "stopped" in p(["loops", "stop"])
    assert "loops: off" in p([])
    prof = _N.tracing.profiler
    assert prof.samples > 0
    # the sampler saw the main thread (this test's own frames)
    text = prof.collapsed()
    assert "MainThread;" in text


# -- per-loop lag gauges (monitors.SysMon) --------------------------------


def test_sysmon_bind_loops_sizes_and_probe_records_lag():
    class _LG:
        n = 3

    sm = SysMon()
    assert sm.loop_lags == [0.0]
    sm.bind_loops(_LG())
    assert sm.loop_lags == [0.0] * 3
    import time as _t
    sm._probe_loop(1, _t.perf_counter() - 0.25)
    assert 200.0 < sm.loop_lags[1] < 5000.0
    assert sm._probe_seq[1] == 1 and sm.loop_lags[2] == 0.0


# -- [tracing] config schema ----------------------------------------------


def test_config_tracing_section_parses():
    cfg = parse_config({"tracing": {
        "sample_rate": 0.25, "ring_size": 128, "export_keep": 500,
        "slow_subs_top": 5, "slow_subs_threshold_ms": 50,
        "profile_interval_ms": 5}})
    t = cfg.tracing
    assert t is not None and t.sample_rate == 0.25
    assert t.ring_size == 128 and t.export_keep == 500
    assert t.slow_subs_top == 5
    assert t.slow_subs_threshold_ms == 50.0  # int coerced to float
    assert parse_config({}).tracing is None  # defaults at Node


def test_config_tracing_rejects_typos_and_bad_values():
    with pytest.raises(ConfigError):
        parse_config({"tracing": {"sample_rte": 0.5}})
    with pytest.raises(ConfigError):
        parse_config({"tracing": {"sample_rate": 1.5}})
    with pytest.raises(ConfigError):
        parse_config({"tracing": {"sample_rate": True}})
    with pytest.raises(ConfigError):
        parse_config({"tracing": {"ring_size": 0}})
    with pytest.raises(ConfigError):
        parse_config({"tracing": {"slow_subs_alarm_ticks": 0}})
    with pytest.raises(ConfigError):
        parse_config({"tracing": {"profile_interval_ms": 0}})
    with pytest.raises(ConfigError):
        parse_config({"tracing": ["not", "a", "table"]})


# -- node integration: loops=2 continuity, ctl, $SYS ----------------------


async def test_trace_chain_is_continuous_across_two_loops():
    """The acceptance chain: a sampled publish through a loops=2 node
    yields one trace id whose spans cover ingress → match → dispatch
    → xloop → flush, with the flush attributed to the subscriber's
    clientid — and `ctl trace export` writes it as loadable JSON."""
    async with broker_node(
            loops=2, matcher=MatcherConfig(device_min_filters=0),
            tracing=TracingConfig(sample_rate=1.0)) as node:
        port = node_port(node)
        s1, s2, pub = (TestClient("ts1"), TestClient("ts2"),
                       TestClient("tpub"))
        for c in (s1, s2, pub):
            await c.connect(port=port)  # round-robin across 2 loops
        await s1.subscribe("tr/+", qos=1)
        await s2.subscribe("tr/t", qos=0)
        for i in range(4):
            # the first publish pays this node's cold compiles on the
            # loop (4–5 s beside five other test workers): its PUBACK
            # gets the time, the chain below is what is tested
            await pub.publish("tr/t", payload=b"%d" % i, qos=1,
                              timeout=30.0 if i == 0 else 5.0)
        for c in (s1, s2):
            for _ in range(4):
                await c.recv(timeout=5.0)
        out = node.ctl.run(["trace", "export", "/tmp/_trace_t.json"])
        assert "exported" in out
        doc = json.load(open("/tmp/_trace_t.json"))
        bytid = {}
        for e in doc["traceEvents"]:
            if e["ph"] == "X":
                bytid.setdefault(e["args"]["trace"],
                                 set()).add(e["name"])
        full = [t for t, st in bytid.items()
                if {"ingress", "match", "dispatch", "publish",
                    "flush"} <= st]
        assert full, bytid
        # the ring actually carried deliveries cross-loop, traced
        assert any("xloop" in st for st in bytid.values())
        flushed = {e["args"]["clientid"]
                   for e in doc["traceEvents"]
                   if e["ph"] == "X" and e["name"] == "flush"}
        assert {"ts1", "ts2"} <= flushed
        # slow_subs saw the same flushes, by clientid
        table = node.ctl.run(["slow_subs"])
        assert "ts1" in table and "ts2" in table
        # $SYS heartbeat carries the ranking
        sysq = Q("sysq")
        node.broker.subscribe(
            sysq, f"$SYS/brokers/{node.name}/slow_subs")
        node.sys.heartbeat()
        rows = json.loads(sysq.inbox[-1][1].payload)
        assert {"ts1", "ts2"} <= {r["clientid"] for r in rows}
        # per-loop lag gauges: one row per front-door loop
        node._update_stats(node.stats)
        all_stats = node.stats.all()
        assert "loop.0.lag_ms" in all_stats
        assert "loop.1.lag_ms" in all_stats
        for c in (s1, s2, pub):
            await c.close()


async def test_node_with_tracing_off_has_no_trace_surface():
    async with broker_node() as node:  # default: sample_rate 0
        port = node_port(node)
        c = TestClient("off1")
        await c.connect(port=port)
        await c.subscribe("o/t", qos=0)
        await c.publish("o/t", payload=b"x")
        assert (await c.recv(timeout=5.0)).payload == b"x"
        assert not node.tracing.active
        assert node.tracing._rings == []  # nothing ever recorded
        assert node.metrics.val("tracing.spans") == 0
        assert "none traced" in node.ctl.run(["slow_subs"])
        await c.close()


# -- cluster forward continuity -------------------------------------------


async def test_trace_context_survives_cluster_forward():
    """Deterministic sampling + header carriage: a message sampled on
    the publishing node arrives at the remote subscriber still
    carrying the ORIGIN node's trace context, so the remote flush
    span completes the origin's trace id."""
    from emqx_tpu.cluster import ClusterConfig

    def _fast():
        return ClusterConfig(heartbeat_interval_s=0.1,
                             suspect_after=2, down_after=5)

    n1 = Node(name="trc1@local", boot_listeners=False,
              tracing=TracingConfig(sample_rate=1.0))
    n2 = Node(name="trc2@local", boot_listeners=False,
              tracing=TracingConfig(sample_rate=1.0))
    for n in (n1, n2):
        n.enable_cluster(port=0, cookie="trace-ck", config=_fast())
    await n1.start()
    await n2.start()
    try:
        n1.cluster.join_remote("127.0.0.1",
                               n2.cluster.transport.port)

        class Rec:
            client_id = "remote-sub"

            def __init__(self):
                self.got = asyncio.Queue()

            def deliver(self, topic, msg):
                self.got.put_nowait(msg)

        r = Rec()
        n2.broker.subscribe(r, "x/+")
        deadline = asyncio.get_running_loop().time() + 20
        while not n1.router.has_dest("x/+", "trc2@local"):
            assert asyncio.get_running_loop().time() < deadline
            await asyncio.sleep(0.1)
        sent = Message(topic="x/1", payload=b"hop")
        n1.broker.publish(sent)
        got = await asyncio.wait_for(r.got.get(), 20)
        ctx = got.headers.get(TRACE_HEADER)
        assert ctx is not None
        assert ctx["tid"] == sent.id and ctx["node"] == "trc1@local"
        # the remote flush completes the ORIGIN's trace id, and its
        # wall-clock latency is sane cross-node (clamped >= 0)
        n2.tracing.flush_mark(ctx, r.client_id)
        n2.tracing.drain_tick(n2.stats)
        flush = [rec for rec in n2.tracing._export
                 if rec[1] == "flush"]
        assert flush and flush[-1][0] == (sent.id,)
        assert flush[-1][3] >= 0.0
        assert flush[-1][4]["clientid"] == "remote-sub"
        # ...and the origin recorded the publish-side spans under the
        # same trace id
        n1.tracing.drain_tick(n1.stats)
        pub_tids = {tid for rec in n1.tracing._export
                    for tid in rec[0] if rec[1] == "publish"}
        assert sent.id in pub_tids
    finally:
        await n1.stop()
        await n2.stop()
