"""One host timeline for the publish path (ISSUE 24): the span's
waits and its completeness check through the real async ingress, the
loop counters outside publish batches (read chunks, flush wake-ups,
collections) and their telemetry gate, the heartbeat's stall record
with the loop's stack, the profiler annotations on the CPU backend,
``ctl telemetry stalls`` / ``ctl profile report``, and the
single-chip ``device.*`` counters."""

import asyncio
import gc
import json
import time

import pytest

from emqx_tpu import profiling
from emqx_tpu.broker import Broker, DispatchConfig
from emqx_tpu.hooks import Hooks
from emqx_tpu.metrics import LOOP_METRICS, PIPELINE_METRICS, Metrics
from emqx_tpu.monitors import SysMon
from emqx_tpu.node import Node
from emqx_tpu.router import MatcherConfig, Router
from emqx_tpu.telemetry import (STAGES, STALL_S, PublishSpan, Telemetry,
                                TelemetryConfig)
from emqx_tpu.types import Message

from helpers import Inbox as Q
from helpers import Wire
from helpers import device_node as _device_node
from helpers import record_spans as _record_spans
from indie_mqtt import IndieClient

#: the stages ISSUE 24 added to the span
NEW_STAGES = ("ingress_wait", "prepare", "executor_wait", "chain_wait",
              "loop_wait", "tail_yield", "unattributed")


# -- part 1: the span records the whole life of a batch ---------------------


@pytest.mark.parametrize("planner", [True, False])
@pytest.mark.parametrize("linger_ms", [0.0, 2.0])
async def test_ingress_span_names_every_wait(planner, linger_ms):
    """Batches through IngressBatcher with a full pipeline: every new
    stage is there and ≥ 0, the intervals cover the span."""
    node = await _device_node(
        "tl@test", batch_size=16, batch_linger_ms=linger_ms,
        dispatch_config=DispatchConfig(planner=planner))
    try:
        subs = [Q(f"c{i}") for i in range(96)]
        for s in subs:
            node.broker.subscribe(s, "p/+")
        ing = node.broker.ingress
        ing.finish_chunk = 32       # 96 groups: the tail yields twice
        # warm the programs: a first-use compile is no steady state
        await asyncio.gather(*[ing.submit(Message(topic=f"p/{i % 4}"))
                               for i in range(16)])
        await ing.drain()
        spans = _record_spans(node.telemetry)
        futs = []
        for _ in range(6):          # more batches than pipeline slots
            futs += [ing.submit(Message(topic=f"p/{i % 4}"))
                     for i in range(16)]
            await asyncio.sleep(0)
        res = await asyncio.gather(*futs)
        assert res == [96] * len(futs)
        await ing.drain()
        # (a $SYS alarm publish may ride along under a loaded CPU)
        spans = [s for s in spans if s.topic.startswith("p/")]
        # with every slot busy, arrivals flush as one bigger batch
        assert len(spans) >= 4 and sum(s.batch for s in spans) == 96
        assert len({s.seq for s in spans}) == len(spans)
        for sp in spans:
            assert sp.path == "device" and sp.open is None
            st = sp.stages
            for stage in NEW_STAGES:
                assert st.get(stage, 0.0) >= 0.0, (stage, st)
            for stage in ("ingress_wait", "prepare", "match", "pack",
                          "executor_wait", "fetch", "loop_wait",
                          "dispatch", "unattributed", "end_to_end"):
                assert stage in st, (stage, st)
            assert st["unattributed"] < 0.05 * st["end_to_end"], st
            # every interval lies inside the span and names a stage
            for stage, a, b, tid in sp.ivs:
                assert stage in STAGES and sp.t0 <= a <= b
            where = {stage: tid for stage, _a, _b, tid in sp.ivs}
            assert where["fetch"] > 0          # executor thread
            assert where["dispatch"] == 0      # the loop
            assert where["executor_wait"] == -1
        if planner:
            # chunked over 96 groups: the tail gave the loop back
            assert any("tail_yield" in s.stages for s in spans)
        # six flushes against four slots: someone waited on the chain
        # or for a slot
        assert any(s.inflight > 0 for s in spans)
        rec = spans[-1].record()
        assert rec["seq"] == spans[-1].seq and rec["intervals"]
        assert {iv[3] for iv in rec["intervals"]} <= {
            "loop", "wait", "executor"}
    finally:
        await node.stop()


def test_sync_publish_batch_span_is_complete_without_waits():
    b = Broker(router=Router(MatcherConfig(device_min_filters=0),
                             node="n1"))
    tel = Telemetry(TelemetryConfig())
    b.telemetry = tel
    b.router.telemetry = tel
    b.subscribe(Q(), "s/+")
    b.publish_batch([Message(topic="s/1")])   # compile
    spans = _record_spans(tel)
    assert b.publish_batch([Message(topic="s/1"),
                            Message(topic="s/2")]) == [1, 1]
    (sp,) = spans
    assert "ingress_wait" not in sp.stages
    assert sp.stages["prepare"] > 0.0
    assert sp.stages["unattributed"] < 0.05 * sp.stages["end_to_end"]
    assert all(tid == 0 for _s, _a, _b, tid in sp.ivs
               if _s != "executor_wait")


def test_span_union_and_carved_cache_gather():
    class _R:
        _last_dispatch = {"hit": 3, "miss": 1, "cache_gather_ms": 1.0}

    sp = PublishSpan(4, seq=9)
    sp.start("match")
    time.sleep(0.004)
    sp.stop_match(_R())
    assert _R._last_dispatch is None or sp.cache_hit == 3
    assert sp.stages["cache_gather"] == pytest.approx(1.0)
    assert sp.stages["match"] > 2.0
    (m, a0, a1, _), (g, b0, b1, _) = sp.ivs
    assert (m, g) == ("match", "cache_gather") and a1 == b0
    # overlapping and disjoint intervals count once
    sp.wait("loop_wait", a0, b1)
    sp.wait("chain_wait", b1 + 1.0, b1 + 1.5)
    assert sp.covered_s() == pytest.approx((b1 - a0) + 0.5)


def test_start_closes_a_stage_left_open():
    sp = PublishSpan(1)
    sp.start("prepare")
    sp.start("match")       # closes prepare where match begins
    sp.stop()
    sp.stop()               # idempotent
    assert [iv[0] for iv in sp.ivs] == ["prepare", "match"]
    assert sp.ivs[0][2] <= sp.ivs[1][1]


# -- part 2: the loop outside batches, and the telemetry gate ---------------


@pytest.mark.parametrize("enabled", [True, False])
async def test_loop_counters_follow_the_telemetry_gate(enabled):
    """Real sockets: enabled, reads and flushes are timed; disabled,
    none of the new counters moves and the byte stream is the same."""
    node = Node(name="gate@test", boot_listeners=False,
                telemetry=TelemetryConfig(enabled=enabled))
    lst = node.add_listener(port=0)
    await node.start()
    try:
        port = lst.port
        sub = IndieClient("tl-sub")
        pub = IndieClient("tl-pub")
        await sub.connect(port=port)
        await pub.connect(port=port)
        await sub.subscribe("t/#")
        for i in range(40):
            await pub.publish(f"t/{i % 3}", b"x" * 16)
        got = [await sub.recv() for _ in range(40)]
        assert [p.topic for p in got] == [f"t/{i % 3}"
                                          for i in range(40)]
        gc.collect()
        await asyncio.sleep(3 * SysMon.BEAT_S)
        m = node.metrics
        vals = {k: m.val(k) for k in LOOP_METRICS + PIPELINE_METRICS}
        if not enabled:
            assert not any(vals.values()), vals
            assert node.telemetry.spans_total == 0
        else:
            # two CONNECTs, a SUBSCRIBE, then publishes as TCP chunks them
            assert vals["loop.read.calls"] >= 4
            assert vals["loop.read.ns"] > 0
            assert vals["loop.flush.calls"] >= 1
            assert vals["loop.flush.ns"] > 0
            assert vals["loop.flush.wait_ns"] > 0
            assert vals["gc.collections.gen2"] >= 1
            assert vals["gc.ns.gen2"] > 0
            # the loop waited in its selector for most of this test
            assert vals["loop.select.calls"] >= 10
            assert vals["loop.select.ns"] > vals["loop.read.ns"]
            # the heartbeat's wall clock covers the sections it frames
            assert vals["loop.wall.ns"] > vals["loop.read.ns"]
        await pub.disconnect()
        await sub.disconnect()
    finally:
        await node.stop()


async def _read_chunk(node, n_pubs, runs=True, topic="rs/t"):
    """One connection, a CONNECT, then ``n_pubs`` plain QoS 0
    publishes fed as ONE chunk; returns what the chunk moved."""
    from emqx_tpu.connection import Connection
    from emqx_tpu.mqtt.frame import serialize
    from emqx_tpu.mqtt.packet import Connect, Publish

    reader = asyncio.StreamReader()
    conn = Connection(reader, Wire(), node.broker, node.cm)
    if not runs:
        conn.channel.handle_publish_run = lambda pkts, i, stop: (0, [])
    task = asyncio.get_running_loop().create_task(conn.run())
    reader.feed_data(serialize(Connect(client_id="rs", keepalive=0), 4))
    for _ in range(5):
        await asyncio.sleep(0)
    assert conn.channel.state == "connected"
    m = node.metrics
    base = m.all()
    reader.feed_data(b"".join(
        serialize(Publish(topic=topic, qos=0, payload=b"x"), 4)
        for _ in range(n_pubs)))
    for _ in range(20):
        await asyncio.sleep(0)
    await node.broker.ingress.drain()
    moved = {k: v - base[k] for k, v in m.all().items()}
    reader.feed_eof()
    await asyncio.wait_for(task, 5)
    return moved


@pytest.mark.parametrize("runs", [True, False])
async def test_read_slices_close_at_the_32_packet_yield(runs):
    """100 packets in one chunk: the handler gives the loop back
    after packets 32, 64 and 96, runs engaged or not, and each slice
    is one ``loop.read.calls``."""
    node = Node(name=f"slices{int(runs)}@test", boot_listeners=False)
    await node.start()
    try:
        moved = await _read_chunk(node, 100, runs)
        assert moved["loop.read.calls"] == 4
        assert moved["loop.read.ns"] > 0
        assert moved["packets.received"] == 100
        assert moved["channel.publish_run.msgs"] == (100 if runs else 0)
        moved = await _read_chunk(node, 64, runs)
        # packets 32 and 64 each end a slice; the chunk's end a third
        assert moved["loop.read.calls"] == 3
    finally:
        await node.stop()


@pytest.mark.parametrize("runs", [True, False])
async def test_batch_flushed_inside_a_read_is_not_read_time(runs):
    """A batch that fills at the ``batch_size`` boundary is flushed
    from inside the read chunk — from ``submit_many`` inside a run as
    from ``submit`` packet by packet — and its stages are no part of
    ``loop.read.ns``."""
    node = Node(name=f"nest{int(runs)}@test", boot_listeners=False,
                batch_size=8)
    await node.start()
    try:
        slept = [0]

        def slow(msg):
            if msg.topic == "rs/slow":
                time.sleep(0.004)       # inside the span's `prepare`
                slept[0] += 1
            return msg
        node.broker.hooks.add("message.publish", slow)
        flushes0 = node.broker.ingress.flushes
        moved = await _read_chunk(node, 31, runs, topic="rs/slow")
        assert slept[0] == 31
        # 24 of them slept inside flushes taken at the boundary,
        # inside the read chunk: ≥ 96 ms that the read does not own
        assert node.broker.ingress.flushes - flushes0 >= 4
        assert moved["loop.read.calls"] == 1
        assert moved["loop.read.ns"] < 40_000_000, moved["loop.read.ns"]
        assert moved["channel.publish_run.msgs"] == (31 if runs else 0)
    finally:
        await node.stop()


def test_loop_leave_is_exclusive_of_what_nested():
    m = Metrics()
    tel = Telemetry(TelemetryConfig(), metrics=m)
    assert tel.loop_clock() is tel
    i_read = m._index["loop.read.ns"]
    t0, n0 = time.perf_counter(), tel.inner
    time.sleep(0.01)
    tel.gc_done(2, 0.004)            # a collection nested in the read
    tel.loop_leave(i_read, t0, n0)
    assert m.val("gc.ns.gen2") == 4_000_000
    assert m.val("gc.collections.gen2") == 1
    assert m.val("loop.read.calls") == 1
    own = m.val("loop.read.ns")
    assert 5_000_000 < own < 50_000_000
    # the read handed its whole length up to whatever encloses it
    assert tel.inner == pytest.approx(n0 + own * 1e-9 + 0.004)
    assert Telemetry(TelemetryConfig(enabled=False),
                     metrics=m).loop_clock() is None
    assert Telemetry(TelemetryConfig()).loop_clock() is None


def test_loop_leave_loses_no_count_across_threads():
    """Multi-loop nodes arm the Metrics lock: sections closed from
    several loop threads at once must each be counted."""
    import sys
    import threading

    m = Metrics()
    m.enable_threadsafe()
    tel = Telemetry(TelemetryConfig(), metrics=m)
    i_flush = m._index["loop.flush.ns"]
    n_threads, per = 8, 2000

    def work():
        for _ in range(per):
            tel.loop_leave(i_flush, time.perf_counter(), tel.inner, 1e-6)

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=work)
                   for _ in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(30.0)
            assert not t.is_alive()
    finally:
        sys.setswitchinterval(old)
    assert m.val("loop.flush.calls") == n_threads * per
    assert m.val("loop.flush.wait_ns") == n_threads * per * 1000


def test_forced_collection_moves_gc_gen2_and_span_gc_inside():
    m = Metrics()
    tel = Telemetry(TelemetryConfig(), metrics=m)
    mon = SysMon(metrics=m, telemetry=tel)
    mon.install_gc_hook()
    try:
        sp = tel.begin(1)
        sp.start("dispatch")
        gc.collect()
        sp.stop()
    finally:
        mon.remove_gc_hook()
    assert m.val("gc.collections.gen2") == 1
    ns = m.val("gc.ns.gen2")
    assert ns > 0 and tel.gc_s == pytest.approx(ns * 1e-9, rel=1e-3)
    # the stage stays inclusive; the collection inside it is named
    assert sp.stages["gc_inside"] == pytest.approx(ns * 1e-6, rel=1e-3)
    assert sp.stages["dispatch"] >= sp.stages["gc_inside"]


def test_gc_hook_is_silent_when_telemetry_is_disabled():
    m = Metrics()
    tel = Telemetry(TelemetryConfig(enabled=False), metrics=m)
    mon = SysMon(metrics=m, telemetry=tel, long_gc_ms=1e9)
    mon.install_gc_hook()
    try:
        gc.collect()
    finally:
        mon.remove_gc_hook()
    assert m.val("gc.collections.gen2") == 0 and tel.gc_s == 0.0


# -- part 3: a stall has a culprit -------------------------------------------


def _the_sleeping_function():
    time.sleep(0.2)


async def test_stall_record_names_the_sleeping_function(caplog):
    m = Metrics()
    tel = Telemetry(TelemetryConfig(), metrics=m)
    hooks = Hooks()
    events = []
    hooks.add("sysmon.long_schedule", lambda ms: events.append(ms))
    mon = SysMon(metrics=m, hooks=hooks, telemetry=tel,
                 long_schedule_ms=100.0)
    task = asyncio.get_running_loop().create_task(mon.run())
    import threading
    done = threading.Event()

    def _spin_beside_the_loop():
        while not done.is_set():
            sum(range(200))

    busy = threading.Thread(target=_spin_beside_the_loop, daemon=True)
    parked = threading.Thread(target=done.wait, name="parked",
                              daemon=True)
    busy.start()
    parked.start()
    try:
        await asyncio.sleep(0.1)      # a few quiet beats
        assert tel.stall_records() == []
        with caplog.at_level("WARNING", logger="emqx_tpu.monitors"):
            _the_sleeping_function()
            await asyncio.sleep(0.1)
        recs = tel.stall_records()
        assert len(recs) == 1, recs
        rec = recs[0]
        assert 150.0 < rec["ms"] < 400.0
        assert any("_the_sleeping_function" in f
                   for f in rec["frames"]), rec
        assert rec["rebuild"] is False and rec["gc_ms"] >= 0.0
        # the busy thread beside the loop is named, parked ones are not
        assert any("_spin_beside_the_loop" in f
                   for fs in rec["others"].values() for f in fs), rec
        assert not any("loop-watch" in k or "parked" in k
                       for k in rec["others"])
        assert abs(rec["ts"] - time.time()) < 5.0
        assert m.val("loop.stalls") == 1
        assert m.val("loop.stall.ns") == int(rec["ms"] * 1e6)
        # SysMon reads the same heartbeat: threshold under the stall
        assert mon.long_schedule_count == 1
        assert m.val("sysmon.long_schedule") == 1
        assert len(events) == 1 and events[0] > 100.0
        assert any("long_schedule" in r.getMessage()
                   and "_the_sleeping_function" in r.getMessage()
                   for r in caplog.records)
        await asyncio.sleep(1.1)      # one tick: the gauge saw it
        assert mon.loop_lags[0] >= 0.0
    finally:
        done.set()
        busy.join(5.0)
        parked.join(5.0)
        task.cancel()
        try:
            await task
        except asyncio.CancelledError:
            pass
    assert not busy.is_alive() and not parked.is_alive()
    assert mon._watch_thread is None and mon._beat_h is None


async def test_short_hiccup_is_no_stall_and_disabled_keeps_no_ring():
    tel = Telemetry(TelemetryConfig(enabled=False), metrics=Metrics())
    mon = SysMon(telemetry=tel, long_schedule_ms=100.0)
    mon.start_heartbeat(asyncio.get_running_loop())
    try:
        assert mon._watch_thread is None   # no watcher when disabled
        time.sleep(STALL_S / 2)            # under the stall mark
        await asyncio.sleep(0.05)
        assert mon.long_schedule_count == 0
        time.sleep(0.15)                   # a stall, telemetry off
        await asyncio.sleep(0.05)
        assert mon.long_schedule_count == 1
        assert tel.stall_records() == []
        assert tel.metrics.val("loop.stalls") == 0
    finally:
        mon.stop_heartbeat()


async def test_stall_overlapping_a_rebuild_says_so():
    tel = Telemetry(TelemetryConfig(), metrics=Metrics())
    mon = SysMon(telemetry=tel)
    mon.start_heartbeat(asyncio.get_running_loop())
    try:
        await asyncio.sleep(0.05)
        ann = tel.rebuild_begin()
        time.sleep(0.12)
        tel.rebuild_done(ann)
        await asyncio.sleep(0.05)
        (rec,) = tel.stall_records()
        assert rec["rebuild"] is True
    finally:
        mon.stop_heartbeat()


# -- part 4: one clock with the device ----------------------------------------


async def test_profiler_trace_holds_stage_annotations(tmp_path):
    """A trace taken on the CPU backend holds emqx/match, emqx/fetch
    and emqx/dispatch events of one batch under one sequence number,
    and `ctl profile report` renders it."""
    node = await _device_node("ann@test")
    try:
        node.broker.subscribe(Q(), "p/+")
        ing = node.broker.ingress
        await asyncio.gather(*[ing.submit(Message(topic="p/1"))
                               for _ in range(4)])
        await ing.drain()
        logdir = str(tmp_path / "trace")
        assert "tracing to" in node.ctl.run(["profile", "start", logdir])
        try:
            spans = _record_spans(node.telemetry)
            await asyncio.gather(*[ing.submit(Message(topic="p/2"))
                                   for _ in range(4)])
            await ing.drain()
            gc.collect()
        finally:
            assert "trace written" in node.ctl.run(["profile", "stop"])
        ops, anns = profiling.read_trace(logdir)
        by_name = {}
        for _a, _b, name, seq in anns:
            by_name.setdefault(name, set()).add(seq)
        seqs = {s.seq for s in spans}
        assert seqs
        for name in ("emqx/match", "emqx/fetch", "emqx/dispatch",
                     "emqx/prepare", "emqx/pack"):
            assert by_name.get(name) == seqs, (name, by_name)
        assert "emqx/gc" in by_name
        one = sorted(seqs)[0]
        order = [n for a, _b, n, s in sorted(anns) if s == one]
        assert order.index("emqx/match") < order.index("emqx/fetch") \
            < order.index("emqx/dispatch")
        out = node.ctl.run(["profile", "report", logdir])
        assert "host annotations" in out
        # the CPU backend's trace has no /device: plane
        assert "device ops" in out
        assert "profile report failed" in node.ctl.run(
            ["profile", "report", str(tmp_path / "nope")])
    finally:
        await node.stop()


def test_attribute_names_what_the_host_did_in_each_gap():
    # device ops at 0-1, 5-6, 6.5-7 (seconds); the 4 s gap holds a
    # dispatch of batch 7 (3 s), a fetch of batch 8 on another thread
    # overlapping its last second, and 0.5 s of nothing named
    ops = [(0.0, 1.0, "%while.2 = s32[] while(...)"),
           (5.0, 6.0, "%fusion.5 = s32[] fusion(...)"),
           (6.5, 7.0, "%copy.1 = s32[] copy(...)")]
    anns = [(0.5, 4.0, "emqx/dispatch", 7),
            (3.0, 4.5, "emqx/fetch", 8),
            (6.0, 6.4, "emqx/match", 9)]
    rep = profiling.attribute(ops, anns, top=5)
    assert rep["window_s"] == 7.0 and rep["device_ops"] == 3
    assert rep["device_busy_s"] == pytest.approx(2.5)
    assert rep["device_busy_share"] == pytest.approx(2.5 / 7.0)
    g1, g2 = rep["gaps"]
    assert g1["seconds"] == pytest.approx(4.0)
    assert g1["start_s"] == pytest.approx(1.0)
    assert g1["before"] == "%fusion.5"
    assert g1["host"][0] == ["emqx/dispatch", 7, pytest.approx(0.75)]
    assert g1["host"][1] == ["emqx/fetch", 8, pytest.approx(0.375)]
    assert g1["host"][-1] == [profiling.OUTSIDE, None,
                              pytest.approx(0.125)]
    assert g2["seconds"] == pytest.approx(0.5)
    assert g2["host"][0] == ["emqx/match", 9, pytest.approx(0.8)]
    text = profiling.render_report(rep)
    assert "gap 1: 4000.000ms" in text and "seq=7" in text
    assert text.count(profiling.OUTSIDE) == 2   # once per gap
    with pytest.raises(ValueError):
        profiling.attribute([], [])
    no_dev = profiling.attribute([], anns)
    assert no_dev["device_busy_share"] is None
    assert "no device plane" in profiling.render_report(no_dev)


# -- ctl -----------------------------------------------------------------------


async def test_ctl_telemetry_stalls_and_profile_status_render():
    node = Node(name="ctl24@test", boot_listeners=False)
    await node.start()
    try:
        assert node.ctl.run(["telemetry", "stalls"]) == "(none)"
        node.telemetry.note_stall(
            {"ts": 1.0, "ms": 250.0, "frames": ["a.py:f", "b.py:g"],
             "others": {}, "gc_ms": 0.0, "rebuild": False})
        out = json.loads(node.ctl.run(["telemetry", "stalls"]))
        assert out[0]["frames"][-1] == "b.py:g"
        assert node.metrics.val("loop.stalls") == 1
        assert node.metrics.val("loop.stall.ns") == 250_000_000
        table = node.ctl.run(["telemetry"])
        for stage in NEW_STAGES:
            assert stage in table
        # `ctl profile` alone: trace state + the rebuild stage
        node.telemetry.observe_stage("rebuild", 12.5)
        out = node.ctl.run(["profile"])
        assert "profiling: off" in out and "rebuild: 1" in out
        assert "error" in node.ctl.run(["profile", "kernels"])
        assert node.ctl.run(["telemetry", "reset"]) == "ok"
        assert node.ctl.run(["telemetry", "stalls"]) == "(none)"
    finally:
        await node.stop()


# -- device.* on the single-chip served path ------------------------------------


@pytest.mark.parametrize("planner", [True, False])
def test_single_chip_batches_feed_device_counters(planner):
    b = Broker(router=Router(MatcherConfig(device_min_filters=0),
                             node="n1"),
               dispatch_config=DispatchConfig(planner=planner))
    subs = [Q(f"c{i}") for i in range(3)]
    b.subscribe(subs[0], "d/+/x")
    b.subscribe(subs[1], "d/1/x")
    b.subscribe(subs[2], "d/#")
    res = b.publish_batch([Message(topic="d/1/x"),
                           Message(topic="d/2/x"),
                           Message(topic="other")])
    assert res == [3, 2, 0]
    m = b.metrics
    # per UNIQUE topic: 3 + 2 matched filters, one subscriber each
    assert m.val("device.matches") == 5
    assert m.val("device.deliveries") == 5
    assert m.val("device.overflows") == 0
    b.publish_batch([Message(topic="d/1/x")] * 4)   # one unique row
    assert m.val("device.matches") == 8
    assert m.val("device.deliveries") == 8
