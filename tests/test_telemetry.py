"""Publish-path telemetry (emqx_tpu/telemetry.py): histogram bucket
math vs numpy, span lifecycle across real publish_batch calls (host /
device / mesh-1×1 paths, cache hit/miss tags), disabled-mode zero-
cost + byte-identical dispatch, the slow-publish log + sustained-
breach alarm, Prometheus histogram exposition, and the observability
satellites (tracer sink failure, profiler start failure, [telemetry]
config schema)."""

import logging

import numpy as np
import pytest

from emqx_tpu.alarm import AlarmManager
from emqx_tpu.broker import Broker
from emqx_tpu.metrics import GAUGE_METRICS
from emqx_tpu.modules.prometheus import render
from emqx_tpu.router import MatcherConfig, Router
from emqx_tpu.telemetry import (BUCKETS_MS, STAGES, Histogram,
                                Telemetry, TelemetryConfig)
from emqx_tpu.tracer import Tracer
from emqx_tpu.types import Message

from emqx_tpu.config import ConfigError, parse_config
from emqx_tpu.node import Node


class Q:
    def __init__(self, client_id="c"):
        self.client_id = client_id
        self.inbox = []

    def deliver(self, topic, msg):
        self.inbox.append((topic, msg))


def _wire(broker: Broker, cfg: TelemetryConfig = None,
          **tel_kw) -> Telemetry:
    """Manual Node-style wiring for standalone Broker tests."""
    tel = Telemetry(cfg or TelemetryConfig(), **tel_kw)
    broker.telemetry = tel
    broker.router.telemetry = tel
    return tel


def _device_broker(**mk) -> Broker:
    mk.setdefault("device_min_filters", 0)
    return Broker(router=Router(MatcherConfig(**mk), node="node1"))


# -- Histogram ------------------------------------------------------------


def test_histogram_percentiles_match_numpy():
    rng = np.random.default_rng(7)
    xs = rng.lognormal(mean=0.0, sigma=2.0, size=1500)
    h = Histogram(ring_size=4096)
    for x in xs:
        h.observe(float(x))
    for q in (50, 95, 99):
        got = h.percentile(q)
        lo = float(np.percentile(xs, q, method="lower"))
        hi = float(np.percentile(xs, q, method="higher"))
        assert lo <= got <= hi or got == pytest.approx(lo), (q, got)
    assert h.count == 1500
    assert h.sum == pytest.approx(float(xs.sum()), rel=1e-9)


def test_histogram_bucket_counts_are_exact_and_cumulative():
    h = Histogram(ring_size=64)
    xs = [0.005, 0.05, 0.05, 3.0, 40.0, 9999.0]  # last is > max bound
    for x in xs:
        h.observe(x)
    snap = h.snapshot()
    bounds = [b for b, _ in snap["buckets"]]
    assert bounds == list(BUCKETS_MS)
    # cumulative counts per le, computed independently
    expect = [int(sum(1 for x in xs if x <= b)) for b in bounds]
    assert [c for _, c in snap["buckets"]] == expect
    assert snap["count"] == len(xs)          # +Inf bucket == count
    assert snap["buckets"][-1][1] == 5       # 9999 only in +Inf
    # cumulative sequence never decreases
    cums = [c for _, c in snap["buckets"]]
    assert cums == sorted(cums)


def test_histogram_ring_is_bounded_but_counts_are_total():
    h = Histogram(ring_size=8)
    for i in range(100):
        h.observe(float(i))
    assert h.count == 100
    assert len(h.ring) == 8
    assert list(h.ring) == [float(i) for i in range(92, 100)]
    h.reset()
    assert h.count == 0 and not h.ring and h.sum == 0.0


# -- span lifecycle: host path --------------------------------------------


def test_host_path_span_records_match_dispatch_e2e():
    b = Broker()  # default config: few filters -> host regime
    tel = _wire(b)
    s = Q()
    b.subscribe(s, "a/+")
    assert b.publish_batch([Message(topic="a/x"),
                            Message(topic="a/y")]) == [1, 1]
    assert tel.spans_total == 1
    st = tel.stage_stats()
    for stage in ("match", "dispatch", "end_to_end"):
        assert st[stage]["count"] == 1, stage
    assert st["end_to_end"]["p50_ms"] > 0
    # device-only stages never fired on the host path
    assert st["fetch"]["count"] == 0
    assert st["cache_gather"]["count"] == 0


def test_vetoed_out_batch_still_closes_its_span():
    b = Broker()
    tel = _wire(b)
    b.hooks.add("message.publish", lambda msg: None)  # veto all
    assert b.publish_batch([Message(topic="t")]) == [0]
    assert tel.spans_total == 1
    assert tel.stage_stats()["end_to_end"]["count"] == 1


# -- span lifecycle: device path + cache tags -----------------------------


def test_device_path_span_stages_and_cache_tags():
    b = _device_broker(match_cache_slots=256)
    # threshold 0: every batch lands in the slow ring, exposing tags
    tel = _wire(b, TelemetryConfig(slow_threshold_ms=0.0,
                                   slow_alarm_after=10**9))
    s1, s2 = Q("c1"), Q("c2")
    b.subscribe(s1, "s/+/a")
    b.subscribe(s2, "s/1/a")
    msgs = [Message(topic="s/1/a"), Message(topic="s/2/a"),
            Message(topic="s/1/a")]
    assert b.publish_batch(msgs) == [2, 1, 2]
    assert b.publish_batch(msgs) == [2, 1, 2]
    assert tel.spans_total == 2
    st = tel.stage_stats()
    for stage in ("match", "cache_gather", "pack", "fetch",
                  "dispatch", "end_to_end"):
        assert st[stage]["count"] == 2, stage
    first, second = tel.slow_records()
    assert first["path"] == "device"
    assert first["n_uniq"] == 2 and first["batch"] == 3
    assert first["bucket"] >= 2
    assert first["cache_miss"] == 2 and first["cache_hit"] == 0
    # identical repeat batch: pure cache hits
    assert second["cache_hit"] == 2 and second["cache_miss"] == 0
    assert "stages_ms" in first and "match" in first["stages_ms"]


def test_mesh_1x1_span_path_tag():
    from emqx_tpu.parallel.mesh import make_mesh

    b = Broker(router=Router(
        MatcherConfig(mesh=make_mesh(1, 1), fanout_d=8,
                      match_cache_slots=128), node="local"))
    tel = _wire(b, TelemetryConfig(slow_threshold_ms=0.0,
                                   slow_alarm_after=10**9))
    s1 = Q("c1")
    b.subscribe(s1, "a/+")
    assert b.publish_batch([Message(topic="a/b")]) == [1]
    assert tel.spans_total == 1
    rec = tel.slow_records()[0]
    assert rec["path"] == "mesh"
    st = tel.stage_stats()
    assert st["match"]["count"] == 1
    assert st["fetch"]["count"] == 1


def test_chunked_finish_closes_span_once():
    b = _device_broker(match_cache=False)
    tel = _wire(b)
    s = Q()
    b.subscribe(s, "t/+")
    msgs = [Message(topic=f"t/{i}") for i in range(8)]
    pb = b.publish_begin(msgs)
    assert not pb.done
    b.publish_fetch(pb)
    # the streaming ingress form: chunked delivery tail
    for lo in range(0, len(pb.live), 3):
        b.publish_finish_chunk(pb, lo, min(lo + 3, len(pb.live)))
    pb.done = True
    assert pb.results == [1] * 8
    assert tel.spans_total == 1
    st = tel.stage_stats()
    assert st["end_to_end"]["count"] == 1
    # dispatch accumulated over 3 chunks but folded ONCE
    assert st["dispatch"]["count"] == 1


# -- disabled mode: zero samples, byte-identical dispatch -----------------


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


def test_disabled_mode_records_nothing_and_dispatch_is_identical():
    b_off = _device_broker(match_cache_slots=64)
    tel = _wire(b_off, TelemetryConfig(enabled=False))
    b_ref = _device_broker(match_cache_slots=64)  # telemetry = None
    got_off = _run_workload(b_off)
    got_ref = _run_workload(b_ref)
    assert got_off == got_ref  # results AND per-sub delivery streams
    assert tel.spans_total == 0 and tel.slow_total == 0
    assert all(h.count == 0 for h in tel.hists.values())
    assert tel.begin(4) is None  # the broker-facing contract
    # none of the loop counters of the host timeline moves either, and
    # the device.* totals are fed the same with or without telemetry
    from emqx_tpu.metrics import (DEVICE_METRICS, LOOP_METRICS,
                                  PIPELINE_METRICS)
    assert tel.loop_clock() is None
    assert not any(b_off.metrics.val(k)
                   for k in LOOP_METRICS + PIPELINE_METRICS)
    assert [b_off.metrics.val(k) for k in DEVICE_METRICS] == \
        [b_ref.metrics.val(k) for k in DEVICE_METRICS]
    assert b_off.metrics.val("device.matches") > 0
    # no span was ever attached to a batch
    pb = b_off.publish_begin([Message(topic="w/1/x")])
    assert pb.span is None
    b_off.publish_fetch(pb)
    b_off.publish_finish(pb)


def test_enabled_mode_same_dispatch_results_as_reference():
    b_on = _device_broker(match_cache_slots=64)
    _wire(b_on)
    b_ref = _device_broker(match_cache_slots=64)
    assert _run_workload(b_on) == _run_workload(b_ref)


def test_disabled_mode_ab_guard_covers_dispatch_planner():
    """The disabled-mode byte-identity guard, on BOTH delivery tails:
    planner-on (the default, its dispatch_plan stage silent) and the
    [dispatch] planner=false legacy walk."""
    from emqx_tpu.broker import DispatchConfig

    assert "dispatch_plan" in STAGES
    for planner in (True, False):
        dc = DispatchConfig(planner=planner)
        b_off = Broker(router=Router(
            MatcherConfig(device_min_filters=0, match_cache_slots=64),
            node="node1"), dispatch_config=dc)
        tel = _wire(b_off, TelemetryConfig(enabled=False))
        b_ref = Broker(router=Router(
            MatcherConfig(device_min_filters=0, match_cache_slots=64),
            node="node1"), dispatch_config=dc)
        assert _run_workload(b_off) == _run_workload(b_ref), planner
        assert tel.spans_total == 0
        assert all(h.count == 0 for h in tel.hists.values())


# -- slow-publish log + alarm ---------------------------------------------


def test_slow_publish_log_line_and_sustained_alarm(caplog):
    alarms = AlarmManager(node="t@test")
    b = Broker()
    tel = _wire(b, TelemetryConfig(slow_threshold_ms=0.0,
                                   slow_alarm_after=2),
                alarms=alarms)
    s = Q()
    b.subscribe(s, "a")
    with caplog.at_level(logging.WARNING, logger="emqx_tpu.telemetry"):
        b.publish(Message(topic="a"))
        assert not [a for a in alarms.get_alarms("activated")]
        b.publish(Message(topic="a"))  # streak hits 2 -> alarm
    assert tel.slow_total == 2
    active = alarms.get_alarms("activated")
    assert [a.name for a in active] == ["slow_publish"]
    assert active[0].details["streak"] == 2
    lines = [r.message for r in caplog.records
             if "slow publish batch" in r.message]
    assert len(lines) == 2
    assert '"end_to_end_ms"' in lines[0]
    # a fast batch clears the streak AND the alarm
    tel.config.slow_threshold_ms = 1e9
    b.publish(Message(topic="a"))
    assert not alarms.get_alarms("activated")
    assert [a.name for a in alarms.get_alarms("deactivated")] \
        == ["slow_publish"]
    # the ring keeps the slow records for ctl telemetry slow
    assert len(tel.slow_records()) == 2
    tel.reset()
    assert tel.slow_records() == [] and tel.spans_total == 0


def test_slow_record_tees_through_tracer():
    tr = Tracer()
    sink = tr.start_trace("topic", "hot/#")
    tel = Telemetry(TelemetryConfig(slow_threshold_ms=0.0),
                    tracer=tr)
    sp = tel.begin(1)
    sp.topic = "hot/t"
    tel.finish(sp)
    assert len(sink) == 1 and "SLOW PUBLISH" in sink[0]
    # a non-matching topic trace captures nothing
    tr2 = Tracer()
    sink2 = tr2.start_trace("topic", "cold/#")
    tel2 = Telemetry(TelemetryConfig(slow_threshold_ms=0.0),
                     tracer=tr2)
    sp2 = tel2.begin(1)
    sp2.topic = "hot/t"
    tel2.finish(sp2)
    assert sink2 == []


# -- Prometheus exposition ------------------------------------------------


def test_prometheus_histogram_line_format():
    tel = Telemetry(TelemetryConfig())
    tel.hists["match"].observe(0.3)
    tel.hists["match"].observe(7.0)
    tel.hists["match"].observe(99999.0)  # past the last bound
    doc = render({}, {}, tel.histograms())
    lines = doc.splitlines()
    fam = "emqx_tpu_publish_stage_match_ms"
    assert f"# TYPE {fam} histogram" in lines
    assert f'{fam}_bucket{{le="0.5"}} 1' in lines
    assert f'{fam}_bucket{{le="10"}} 2' in lines
    assert f'{fam}_bucket{{le="5000"}} 2' in lines
    assert f'{fam}_bucket{{le="+Inf"}} 3' in lines
    assert f"{fam}_count 3" in lines
    assert any(l.startswith(f"{fam}_sum ") for l in lines)
    # every stage family is present even before any traffic
    for stage in STAGES:
        assert (f"# TYPE emqx_tpu_publish_stage_{stage}_ms histogram"
                in lines), stage


def test_prometheus_gauge_audit_for_dec_counters():
    # retained.count is dec'd by the retainer (GAUGE_METRICS): the
    # exposition must say gauge, not counter — a scraper rate()s
    # counters and reads any decrease as a restart
    assert "retained.count" in GAUGE_METRICS
    doc = render({"retained.count": 5, "messages.received": 9}, {})
    lines = doc.splitlines()
    assert "# TYPE emqx_retained_count gauge" in lines
    assert "# TYPE emqx_messages_received counter" in lines
    assert "emqx_retained_count 5" in lines


# -- tracer satellites ----------------------------------------------------


class _BoomSink:
    def __init__(self):
        self.wrote = 0

    def write(self, line):
        raise OSError("closed")


def test_trace_handler_sink_failure_detaches_cleanly():
    tr = Tracer()
    tr.start_trace("topic", "a/#", sink=_BoomSink())
    ok_sink = tr.start_trace("topic", "a/b")
    # must not raise out of the logging call on the publish path
    tr.trace_publish(Message(topic="a/b", payload=b"x"))
    # broken handler detached; healthy one captured the line
    assert tr.lookup_traces() == [("topic", "a/b")]
    assert len(ok_sink) == 1
    # and the detached sink stays gone on the next publish
    tr.trace_publish(Message(topic="a/b", payload=b"y"))
    assert len(ok_sink) == 2


def test_stop_trace_flushes_file_like_sinks():
    class _FileSink:
        def __init__(self):
            self.lines = []
            self.flushed = False

        def write(self, line):
            self.lines.append(line)

        def flush(self):
            self.flushed = True

    tr = Tracer()
    fs = _FileSink()
    tr.start_trace("clientid", "c9", sink=fs)
    tr.trace_packet("RECV", "c9", "CONNECT")
    assert tr.stop_trace("clientid", "c9")
    assert fs.flushed and len(fs.lines) == 1


# -- profiling satellites -------------------------------------------------


class _Reg:
    def __init__(self):
        self.cmds = {}

    def register_command(self, name, fn, usage=""):
        self.cmds[name] = fn


def test_profile_start_failure_keeps_state_consistent(monkeypatch):
    import jax

    from emqx_tpu import profiling

    reg = _Reg()
    profiling.register_ctl(reg)

    def _boom(logdir, **_options):
        raise RuntimeError("unwritable: " + logdir)

    monkeypatch.setattr(jax.profiler, "start_trace", _boom)
    stopped = []
    monkeypatch.setattr(jax.profiler, "stop_trace",
                        lambda: stopped.append(True))
    out = reg.cmds["profile"](["start", "/nope/dir"])
    assert "profile start failed" in out and "unwritable" in out
    assert profiling._active["dir"] is None  # no trace-running ghost
    assert stopped  # best-effort cleanup of a partial trace
    assert "off" in reg.cmds["profile"]([])


# -- [telemetry] config schema --------------------------------------------


def test_config_telemetry_section_parses():
    cfg = parse_config({"telemetry": {
        "enabled": False, "slow_threshold_ms": 5,
        "ring_size": 128, "slow_log_size": 8, "slow_alarm_after": 3}})
    t = cfg.telemetry
    assert t is not None and t.enabled is False
    assert t.slow_threshold_ms == 5.0 and t.ring_size == 128
    assert t.slow_log_size == 8 and t.slow_alarm_after == 3
    assert parse_config({}).telemetry is None  # defaults at Node


def test_config_telemetry_rejects_typos_and_bad_types():
    with pytest.raises(ConfigError):
        parse_config({"telemetry": {"enabld": True}})
    with pytest.raises(ConfigError):
        parse_config({"telemetry": {"enabled": "yes"}})
    with pytest.raises(ConfigError):
        parse_config({"telemetry": {"ring_size": 2.5}})
    with pytest.raises(ConfigError):
        parse_config({"telemetry": {"slow_threshold_ms": -1}})
    with pytest.raises(ConfigError):
        parse_config({"telemetry": ["not", "a", "table"]})


# -- node integration: wiring, ctl, $SYS ----------------------------------


async def test_node_wiring_ctl_and_sys_heartbeat():
    node = Node(name="tel@test", boot_listeners=False,
                batch_ingress=False)
    await node.start()
    try:
        assert node.broker.telemetry is node.telemetry
        assert node.router.telemetry is node.telemetry
        s = Q()
        node.broker.subscribe(s, "a/b")
        node.publish(Message(topic="a/b"))
        assert node.telemetry.spans_total >= 1
        out = node.ctl.run(["telemetry"])
        assert "match" in out and "end_to_end" in out
        assert "p50_ms" in out and "p99_ms" in out
        assert node.ctl.run(["telemetry", "slow"]) == "(none)"
        # $SYS heartbeat publishes the per-stage summary
        sysq = Q("sysq")
        node.broker.subscribe(
            sysq, "$SYS/brokers/tel@test/telemetry/stages")
        node.sys.heartbeat()
        assert any("end_to_end" in m.payload.decode()
                   for _, m in sysq.inbox)
        # stats gauges ride the registered update fun
        node.stats.tick()
        assert node.stats.getstat("publish.spans.count") >= 1
        assert node.ctl.run(["telemetry", "reset"]) == "ok"
        assert node.telemetry.spans_total == 0
    finally:
        await node.stop()


async def test_node_disabled_telemetry_ctl_reports_it():
    node = Node(name="teloff@test", boot_listeners=False,
                telemetry=TelemetryConfig(enabled=False))
    await node.start()
    try:
        s = Q()
        node.broker.subscribe(s, "x")
        node.publish(Message(topic="x"))
        assert node.telemetry.spans_total == 0
        assert "disabled" in node.ctl.run(["telemetry"])
    finally:
        await node.stop()


async def test_ingress_pipelined_batches_close_spans():
    """The real async ingress path: executor-thread fetch + chunked
    delivery tail must still close every span exactly once."""
    import asyncio

    node = Node(name="telin@test", boot_listeners=False,
                batch_ingress=True)
    await node.start()
    try:
        s = Q()
        node.broker.subscribe(s, "p/+")
        futs = [node.broker.ingress.submit(Message(topic=f"p/{i % 4}"))
                for i in range(32)]
        res = await asyncio.gather(*futs)
        assert res == [1] * 32
        await node.broker.ingress.drain()
        tel = node.telemetry
        assert tel.spans_total >= 1
        st = tel.stage_stats()
        assert st["end_to_end"]["count"] == tel.spans_total
        assert st["dispatch"]["count"] == tel.spans_total
    finally:
        await node.stop()
