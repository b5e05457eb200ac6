"""BENCH_MODE=live — the socket-to-deliver benchmark.

Round-1's bench only timed the compiled kernels; this mode measures
the LIVE path the reference's own load tests exercise: real MQTT
clients over loopback TCP → frame parse → channel FSM → ingress
batcher → device match+fan-out → session → serialize → socket.
Reference shape: emqtt-driven client suites
(/root/reference/test/emqx_client_SUITE.erl) at benchmark scale.

Publishers pipeline QoS0 PUBLISHes whose payload carries the send
timestamp; each delivery received by a subscriber yields one latency
sample. Reports end-to-end deliveries/sec plus p50/p99
socket-to-deliver latency.

Env knobs: LIVE_PUBS, LIVE_SUBS, LIVE_TOPICS, LIVE_SECS,
LIVE_PIPELINE (outstanding publishes per publisher), LIVE_RATE
(publishes/sec per publisher; 0 = saturate — percentiles then
measure queue depth, use a paced rate for meaningful latency),
LIVE_FILTERS (extra background subscriptions; push it past
device_min_filters to measure the DEVICE live regime — default
leaves the route table small, i.e. the host-match regime),
LIVE_PLANNER (0 = legacy per-delivery tail instead of the batch
dispatch planner, docs/DISPATCH.md), LIVE_AB (0 = skip the
planner-off comparison pass the record's planner_off_* columns come
from), LIVE_QOS (publish/subscribe QoS, default 0 — at 1 every
delivery is a per-subscriber frame with its own packet id, the
egress pre-serialization target), LIVE_PRESER (0 = per-delivery
on-loop serialization instead of the pre-built templates),
LIVE_PRESER_AB (0 = skip the QoS1 preserialize on/off pair the
record's qos1_* columns come from), LIVE_LOOPS (front-door event
loops inside the node — [node] loops, docs/DISPATCH.md "Multi-loop
front door"; >1 shards connections over loop threads and routes the
delivery tail through the cross-loop ring), LIVE_LOOPS_AB (0 = skip
the loops=1 comparison pass the record's loops1_* columns come
from; only runs when LIVE_LOOPS > 1), LIVE_TRACE_RATE ([tracing]
sample_rate for the pass — default 0, tracing cold),
LIVE_TRACE_AB (0 = skip the traced comparison pass the record's
traced_* / trace_overhead_frac columns come from; the pass reruns
the workload at LIVE_TRACE_AB_RATE, default 0.01 — the
docs/OBSERVABILITY.md "Tracing" ≤3%-overhead budget's measurement).

On a single-core host the loop threads time-share with the harness
clients — the multi-loop row there documents ring overhead; the
harness is ready for a many-core run where it measures scaling.
"""

from __future__ import annotations

import asyncio
import json
import os
import struct
import time

import numpy as np

from emqx_tpu.mqtt import constants as C
from emqx_tpu.mqtt.frame import Parser, serialize
from emqx_tpu.mqtt.packet import (Connect, Pingreq, PubAck, Publish,
                                  Subscribe)


def _bind_addr():
    """Optional (ip, 0) source binding for outbound bench sockets.
    Loopback connections burn one ephemeral port per (src, dst)
    address pair (~28K), so a fleet past that size must spread its
    SOURCE addresses — each fleet driver claims its own 127/8 ip via
    FLEET_BIND_IP."""
    ip = os.environ.get("FLEET_BIND_IP")
    return (ip, 0) if ip else None


class _Peer:
    """Tiny single-purpose client (the package must not import
    tests/); only what the bench needs: CONNECT, SUBSCRIBE, pipelined
    QoS0 PUBLISH, and a receive loop that timestamps deliveries."""

    def __init__(self, cid: str) -> None:
        self.cid = cid
        self.parser = Parser(version=C.MQTT_V4)
        self.reader = None
        self.writer = None
        self.latencies: list = []
        self.received = 0

    async def connect(self, port: int) -> None:
        self.reader, self.writer = await asyncio.open_connection(
            "127.0.0.1", port, local_addr=_bind_addr())
        # keepalive 0: a fleet-scale setup can take minutes, and the
        # traffic core must not be expired before the window starts
        await self._send(Connect(client_id=self.cid, clean_start=True,
                                 keepalive=0, proto_ver=C.MQTT_V4))
        await self._read_packet()  # CONNACK

    async def _send(self, pkt) -> None:
        self.writer.write(serialize(pkt, C.MQTT_V4))
        await self.writer.drain()

    async def _read_packet(self):
        while True:
            pkts = self.parser.feed(await self.reader.read(65536))
            if pkts:
                return pkts[0]

    async def subscribe(self, flt: str, qos: int = 0) -> None:
        await self._send(Subscribe(packet_id=1,
                                   topic_filters=[(flt, {"qos": qos})]))
        await self._read_packet()  # SUBACK

    async def recv_loop(self) -> None:
        """Count deliveries + record socket-to-deliver latency from
        the embedded send timestamp; QoS1 deliveries are PUBACKed so
        the broker-side inflight window keeps draining."""
        try:
            while True:
                data = await self.reader.read(65536)
                if not data:
                    return
                now = time.perf_counter_ns()
                acked = False
                for pkt in self.parser.feed(data):
                    if isinstance(pkt, Publish):
                        self.received += 1
                        (ts,) = struct.unpack_from("<q", pkt.payload)
                        self.latencies.append((now - ts) / 1e6)
                        if pkt.qos == 1:
                            self.writer.write(serialize(
                                PubAck(type=C.PUBACK,
                                       packet_id=pkt.packet_id),
                                C.MQTT_V4))
                            acked = True
                if acked:
                    await self.writer.drain()
        except (asyncio.CancelledError, ConnectionResetError):
            return

    async def drain_loop(self) -> None:
        """QoS1 publishers: read and discard the broker's PUBACK
        stream so it neither backs up the socket nor trips the
        slow-consumer guard."""
        try:
            while True:
                data = await self.reader.read(65536)
                if not data:
                    return
                self.parser.feed(data)
        except (asyncio.CancelledError, ConnectionResetError):
            return

    async def publish_loop(self, topics, stop, pipeline: int,
                           rate: float = 0.0, qos: int = 0) -> int:
        """Pipelined QoS0 publishing until ``stop`` is set; drains
        the socket buffer every ``pipeline`` sends so the OS buffer
        (not this coroutine) is the limiter.

        ``rate`` > 0 paces to that many publishes/sec instead of
        saturating: under saturation the latency percentiles measure
        QUEUE DEPTH, not service time — the paced mode is the one
        whose p50/p99 mean anything."""
        sent = 0
        i = 0
        next_t = time.perf_counter()
        while not stop.is_set():
            topic = topics[i % len(topics)]
            i += 1
            payload = struct.pack("<q", time.perf_counter_ns())
            self.writer.write(serialize(
                Publish(topic=topic, payload=payload, qos=qos,
                        packet_id=(i - 1) % 0xFFFF + 1 if qos
                        else None),
                C.MQTT_V4))
            sent += 1
            if rate > 0:
                await self.writer.drain()
                next_t += 1.0 / rate
                now = time.perf_counter()
                if next_t < now:
                    # fell behind (a stall, or rate > achievable):
                    # re-anchor rather than burst full-speed to catch
                    # up — a catch-up burst puts the samples right
                    # back into the queue-depth regime this mode
                    # exists to avoid
                    next_t = now
                pause = next_t - now
                if pause > 0:
                    try:
                        # stop-aware: a low rate (long pause) must not
                        # overshoot the timed window by up to 1/rate
                        await asyncio.wait_for(stop.wait(), pause)
                    except asyncio.TimeoutError:
                        pass
                else:
                    await asyncio.sleep(0)
            elif sent % pipeline == 0:
                await self.writer.drain()
                # drain() does not yield below the high-water mark;
                # yield explicitly so the broker/receivers run
                await asyncio.sleep(0)
        await self.writer.drain()
        return sent

    def close(self) -> None:
        try:
            self.writer.close()
        except Exception:
            pass


async def _run() -> dict:
    from emqx_tpu.broker import DispatchConfig
    from emqx_tpu.node import Node

    n_pubs = int(os.environ.get("LIVE_PUBS", "8"))
    n_subs = int(os.environ.get("LIVE_SUBS", "8"))
    n_topics = int(os.environ.get("LIVE_TOPICS", "64"))
    secs = float(os.environ.get("LIVE_SECS", "5"))
    pipeline = int(os.environ.get("LIVE_PIPELINE", "64"))
    # per-publisher publishes/sec; 0 = saturate (latency then
    # measures queue depth, not service time)
    rate = float(os.environ.get("LIVE_RATE", "0"))

    # >0: subscribe a sink to this many extra filters so the route
    # table crosses the device threshold — the live device regime
    n_filters = int(os.environ.get("LIVE_FILTERS", "0"))

    # delivery QoS: at 1 every delivery is a per-subscriber frame
    # with its own packet id — the egress pre-serialization target
    qos = int(os.environ.get("LIVE_QOS", "0"))

    planner = os.environ.get("LIVE_PLANNER", "1") != "0"
    preser = os.environ.get("LIVE_PRESER", "1") != "0"
    loops = int(os.environ.get("LIVE_LOOPS", "1"))
    # [tracing] sample_rate for this pass; 0 leaves the node on the
    # default (tracing cold — the disabled-mode branch only)
    trace_rate = float(os.environ.get("LIVE_TRACE_RATE", "0"))
    trace_cfg = None
    if trace_rate > 0:
        from emqx_tpu.tracing import TracingConfig
        trace_cfg = TracingConfig(sample_rate=trace_rate)
    zone = None
    if qos:
        # QoS>0 saturation needs a wide send window: the default
        # 32-deep inflight caps throughput at the harness's ack
        # round-trip, and the bench would measure the window, not
        # the broker (pids wrap at 65535 — stay well below)
        from emqx_tpu.zone import Zone
        zone = Zone(name="default",
                    max_inflight=int(os.environ.get(
                        "LIVE_INFLIGHT", "8192")),
                    max_mqueue_len=50000)
    node = Node(boot_listeners=False, batch_linger_ms=1.0, zone=zone,
                loops=loops, tracing=trace_cfg,
                dispatch_config=DispatchConfig(planner=planner,
                                               preserialize=preser))
    lst = node.add_listener(port=0)
    await node.start()

    if n_filters:
        class _Sink:
            client_id = "bench-sink"

            def deliver(self, f, m):
                pass

        sink = _Sink()
        for i in range(n_filters):
            node.broker.subscribe(sink, f"bg/{i // 100}/f{i}/+")

    # paced probe: one publisher at a gentle rate on its own topic,
    # one dedicated subscriber. Under saturation the bulk percentiles
    # measure standing-queue depth AND the harness's own client-side
    # parse lag; the probe's samples measure what a compliant
    # (paced) client actually experiences through the loaded broker —
    # the operator's tail-latency number (0 disables)
    probe_rate = float(os.environ.get("LIVE_PROBE_RATE", "100"))

    topics = [f"bench/t{i}/v" for i in range(n_topics)]
    subs = []
    for i in range(n_subs):
        s = _Peer(f"sub{i}")
        await s.connect(lst.port)
        # mixed literal/wildcard subscription shapes
        await s.subscribe("bench/+/v" if i % 2 else f"bench/t{i}/#",
                          qos=qos)
        subs.append(s)
    probe_sub = probe_pub = None
    if probe_rate > 0:
        probe_sub = _Peer("probe-sub")
        await probe_sub.connect(lst.port)
        await probe_sub.subscribe("probe/t")
        probe_pub = _Peer("probe-pub")
        await probe_pub.connect(lst.port)
    recv_tasks = [asyncio.ensure_future(s.recv_loop()) for s in subs]
    if probe_sub is not None:
        recv_tasks.append(asyncio.ensure_future(probe_sub.recv_loop()))

    pubs = []
    for i in range(n_pubs):
        p = _Peer(f"pub{i}")
        await p.connect(lst.port)
        pubs.append(p)
    if qos:
        # QoS>0 publishers must drain their PUBACK stream
        recv_tasks += [asyncio.ensure_future(p.drain_loop())
                       for p in pubs]

    # warmup: force the jit compiles outside the timed window. In the
    # device regime every pow2 padding bucket the capped ingress can
    # hit must be compiled up front — an un-warmed bucket mid-window
    # is a tens-of-seconds stall (once per machine with the
    # persistent compile cache, but never inside the measurement)
    if node.broker.router.use_device_now():
        from emqx_tpu.types import Message as _Msg
        bsz = 8
        while True:
            # publish every bucket TWICE: the first batch takes the
            # match-cache MISS path, the second the HIT path — each
            # compiles different kernels per bucket, and an un-warmed
            # hit-path compile used to stall the timed window (a
            # multi-second in-window backend_compile)
            for _ in range(2):
                node.broker.publish_batch(
                    [_Msg(topic=topics[i % len(topics)],
                          payload=struct.pack("<q", 0))
                     for i in range(bsz)])
            if bsz >= node.ingress.batch_cap:
                break
            bsz *= 2
    warm_stop = asyncio.Event()
    warm = [asyncio.ensure_future(
        p.publish_loop(topics, warm_stop, pipeline, rate, qos))
        for p in pubs]
    await asyncio.sleep(0.5)
    warm_stop.set()
    await asyncio.gather(*warm)
    await asyncio.sleep(0.5)
    for s in subs:
        s.latencies.clear()
        s.received = 0
    if probe_sub is not None:
        probe_sub.latencies.clear()
        probe_sub.received = 0
    base_flushes = node.ingress.flushes
    base_submitted = node.ingress.submitted
    base_wakeups = node.metrics.val("delivery.wakeups")
    base_onloop = node.metrics.val("delivery.serialize.onloop")
    base_xhand = node.metrics.val("delivery.xloop.handoffs")
    base_xdeliv = node.metrics.val("delivery.xloop.deliveries")
    base_delivered = node.metrics.val("messages.delivered")

    stop = asyncio.Event()
    t0 = time.perf_counter()
    pub_tasks = [asyncio.ensure_future(
        p.publish_loop(topics, stop, pipeline, rate, qos))
        for p in pubs]
    if probe_pub is not None:
        pub_tasks.append(asyncio.ensure_future(probe_pub.publish_loop(
            ["probe/t"], stop, 1, probe_rate)))
    await asyncio.sleep(secs)
    stop.set()
    sent = sum(await asyncio.gather(*pub_tasks))
    await asyncio.sleep(0.5)  # drain in-flight deliveries
    elapsed = time.perf_counter() - t0

    received = sum(s.received for s in subs)
    lats = np.concatenate([np.asarray(s.latencies, dtype=np.float64)
                           for s in subs if s.latencies]) \
        if any(s.latencies for s in subs) else np.zeros(1)
    flushes = node.ingress.flushes - base_flushes
    submitted = node.ingress.submitted - base_submitted
    wakeups = node.metrics.val("delivery.wakeups") - base_wakeups
    onloop = node.metrics.val("delivery.serialize.onloop") - base_onloop
    xhand = node.metrics.val("delivery.xloop.handoffs") - base_xhand
    xdeliv = node.metrics.val("delivery.xloop.deliveries") - base_xdeliv
    delivered_srv = node.metrics.val("messages.delivered") \
        - base_delivered

    probe_lats = (np.asarray(probe_sub.latencies, np.float64)
                  if probe_sub is not None and probe_sub.latencies
                  else None)

    for t in recv_tasks:
        t.cancel()
    for peer in subs + pubs + [p for p in (probe_sub, probe_pub)
                               if p is not None]:
        peer.close()
    node.tracing.drain_tick()  # spans still buffered in the rings
    trace_spans = node.tracing.spans_total
    await node.stop()

    out = {
        "sent": sent,
        "received": received,
        "elapsed_s": round(elapsed, 3),
        "deliveries_per_s": received / elapsed,
        "publishes_per_s": sent / elapsed,
        "p50_ms": float(np.percentile(lats, 50)),
        "p99_ms": float(np.percentile(lats, 99)),
        "avg_device_batch": round(submitted / flushes, 2) if flushes else 0,
        # delivery-tail wakeup pressure: scheduled connection flushes
        # per ingress batch (the planner targets ≤1 per connection)
        "wakeups_per_batch": round(wakeups / flushes, 2) if flushes else 0,
        "planner": planner,
        "preserialize": preser,
        "qos": qos,
        # frames serialized ON the loop per delivered frame: ~0 when
        # pre-serialization covers the traffic, ~1 when every frame
        # pays a full serialize() on the event loop
        "serialize_onloop": onloop,
        "onloop_per_delivery": round(onloop / received, 4)
        if received else 0.0,
        "pubs": n_pubs, "subs": n_subs,
        "paced_rate_per_pub": rate,
        "bg_filters": n_filters,
        "regime": ("device" if node.broker.router.use_device_now()
                   else "host"),
        # multi-loop front door: ring traffic during the timed window
        # (one handoff per loop per batch; fraction = the share of
        # the delivery tail the ring carried to non-home loops)
        "loops": loops,
        "xloop_handoffs_per_batch": round(xhand / flushes, 2)
        if flushes else 0,
        "xloop_fraction": round(xdeliv / delivered_srv, 3)
        if delivered_srv else 0.0,
        "trace_rate": trace_rate,
        "trace_spans": trace_spans,
    }
    if probe_lats is not None:
        out["probe_rate"] = probe_rate
        out["probe_samples"] = int(probe_lats.size)
        out["probe_p50_ms"] = float(np.percentile(probe_lats, 50))
        out["probe_p99_ms"] = float(np.percentile(probe_lats, 99))
    tel = getattr(node, "telemetry", None)
    if tel is not None and tel.enabled:
        # per-stage breakdown from the publish-path telemetry spans
        # (docs/OBSERVABILITY.md): where a batch's latency went —
        # match dispatch vs transfer wait vs delivery tail
        out["stages"] = {
            s: {"count": st["count"],
                "p50_ms": round(st["p50_ms"], 3),
                "p99_ms": round(st["p99_ms"], 3)}
            for s, st in tel.stage_stats().items() if st["count"]}
    return out


def live(emit=None) -> None:
    import sys

    from emqx_tpu.profiling import enable_compile_cache

    enable_compile_cache()
    info = asyncio.run(_run())
    print(json.dumps(info), file=sys.stderr, flush=True)
    # planner A/B: a second pass with the legacy per-delivery tail
    # ([dispatch] planner = false) so the record carries the pair —
    # msgs/sec and wakeups/batch for both tails (docs/DISPATCH.md).
    # Skipped when the headline pass itself ran planner-off (the
    # comparison would be off-vs-off) or LIVE_AB=0.
    info_off = None
    if info.get("planner") and os.environ.get("LIVE_AB", "1") != "0":
        os.environ["LIVE_PLANNER"] = "0"
        try:
            info_off = asyncio.run(_run())
        finally:
            del os.environ["LIVE_PLANNER"]
        print(json.dumps(info_off), file=sys.stderr, flush=True)
    # egress pre-serialization A/B: a QoS1 fan-out pair (preserialize
    # on vs off) — QoS1 is where the template lane matters, every
    # delivery being a per-subscriber frame with its own packet id
    # (the QoS0 bulk already shares one wire image per message). The
    # on-loop serialize counter is the mechanism check: ~0 per
    # delivery with templates, ~1 without (docs/DISPATCH.md).
    # Host-regime batches never plan, so there are no templates to
    # A/B — the pair only runs where the serialize stage engages.
    info_q1 = info_q1_off = None
    if info.get("preserialize") and info.get("regime") == "device" \
            and os.environ.get("LIVE_PRESER_AB", "1") != "0":
        saved_qos = os.environ.get("LIVE_QOS")
        os.environ["LIVE_QOS"] = "1"
        try:
            info_q1 = asyncio.run(_run())
            print(json.dumps(info_q1), file=sys.stderr, flush=True)
            os.environ["LIVE_PRESER"] = "0"
            try:
                info_q1_off = asyncio.run(_run())
            finally:
                del os.environ["LIVE_PRESER"]
            print(json.dumps(info_q1_off), file=sys.stderr,
                  flush=True)
        finally:
            if saved_qos is None:
                del os.environ["LIVE_QOS"]
            else:
                os.environ["LIVE_QOS"] = saved_qos
    # multi-loop A/B: the LIVE_LOOPS > 1 headline vs the same
    # workload on one loop — the front-door sharding pair
    # (docs/DISPATCH.md "Multi-loop front door"). On a single-core
    # host this documents ring overhead; on a many-core host it is
    # the scaling row.
    info_l1 = None
    if info.get("loops", 1) > 1 \
            and os.environ.get("LIVE_LOOPS_AB", "1") != "0":
        saved_loops = os.environ.get("LIVE_LOOPS")
        os.environ["LIVE_LOOPS"] = "1"
        try:
            info_l1 = asyncio.run(_run())
        finally:
            if saved_loops is None:
                del os.environ["LIVE_LOOPS"]
            else:
                os.environ["LIVE_LOOPS"] = saved_loops
        print(json.dumps(info_l1), file=sys.stderr, flush=True)
    # tracing A/B: the same workload with [tracing] sample_rate at a
    # production-plausible 1% vs the untraced headline — the
    # traced_* / trace_overhead_frac columns the ≤3%-overhead budget
    # is gated on (docs/OBSERVABILITY.md "Tracing"). Skipped when the
    # headline pass itself ran traced (the comparison would be
    # on-vs-on) or LIVE_TRACE_AB=0.
    info_tr = None
    if not info.get("trace_rate") \
            and os.environ.get("LIVE_TRACE_AB", "1") != "0":
        saved_tr = os.environ.get("LIVE_TRACE_RATE")
        os.environ["LIVE_TRACE_RATE"] = os.environ.get(
            "LIVE_TRACE_AB_RATE", "0.01")
        try:
            info_tr = asyncio.run(_run())
        finally:
            if saved_tr is None:
                del os.environ["LIVE_TRACE_RATE"]
            else:
                os.environ["LIVE_TRACE_RATE"] = saved_tr
        print(json.dumps(info_tr), file=sys.stderr, flush=True)
    rec = {
        "metric": "live_socket_throughput",
        # r5: ingest backpressure + paced service-latency probe
        "workload": "probe_v1",
        "value": round(info["deliveries_per_s"], 1),
        "unit": "msgs/sec",
        "vs_baseline": round(info["deliveries_per_s"] / 1_000_000, 3),
        "planner": info.get("planner", True),
        "wakeups_per_batch": info.get("wakeups_per_batch", 0),
        "preserialize": info.get("preserialize", True),
        "onloop_per_delivery": info.get("onloop_per_delivery", 0.0),
        "loops": info.get("loops", 1),
    }
    if rec["loops"] > 1:
        rec["xloop_handoffs_per_batch"] = info.get(
            "xloop_handoffs_per_batch", 0)
        rec["xloop_fraction"] = info.get("xloop_fraction", 0.0)
    if info_l1 is not None:
        rec["loops1_msgs_per_s"] = round(
            info_l1["deliveries_per_s"], 1)
        rec["loops1_p99_ms"] = round(info_l1["p99_ms"], 3)
        if info_l1["deliveries_per_s"] > 0:
            rec["loops_speedup"] = round(
                info["deliveries_per_s"]
                / info_l1["deliveries_per_s"], 3)
    if info_q1 is not None:
        # the QoS1 fan-out row: per-subscriber pid-stamped frames —
        # the pre-serialization target traffic
        rec["qos1_msgs_per_s"] = round(info_q1["deliveries_per_s"], 1)
        rec["qos1_saturated_p99_ms"] = round(info_q1["p99_ms"], 3)
        rec["qos1_onloop_per_delivery"] = \
            info_q1.get("onloop_per_delivery", 0.0)
        if "probe_p99_ms" in info_q1:
            rec["qos1_probe_p99_ms"] = round(
                info_q1["probe_p99_ms"], 3)
    if info_q1_off is not None:
        rec["qos1_preser_off_msgs_per_s"] = round(
            info_q1_off["deliveries_per_s"], 1)
        rec["qos1_preser_off_saturated_p99_ms"] = round(
            info_q1_off["p99_ms"], 3)
        rec["qos1_preser_off_onloop_per_delivery"] = \
            info_q1_off.get("onloop_per_delivery", 0.0)
        if info_q1 is not None and info_q1_off["deliveries_per_s"] > 0:
            rec["preser_speedup"] = round(
                info_q1["deliveries_per_s"]
                / info_q1_off["deliveries_per_s"], 3)
    if info_tr is not None:
        rec["traced_msgs_per_s"] = round(
            info_tr["deliveries_per_s"], 1)
        rec["traced_p99_ms"] = round(info_tr["p99_ms"], 3)
        rec["trace_sample_rate"] = info_tr.get("trace_rate", 0.0)
        rec["trace_spans"] = info_tr.get("trace_spans", 0)
        if info["deliveries_per_s"] > 0:
            # fraction of untraced throughput the traced pass gives
            # up (negative = noise in the traced pass's favor)
            rec["trace_overhead_frac"] = round(
                1.0 - info_tr["deliveries_per_s"]
                / info["deliveries_per_s"], 3)
    if info_off is not None:
        rec["planner_off_msgs_per_s"] = round(
            info_off["deliveries_per_s"], 1)
        rec["planner_off_wakeups_per_batch"] = \
            info_off.get("wakeups_per_batch", 0)
        if info_off["deliveries_per_s"] > 0:
            rec["planner_speedup"] = round(
                info["deliveries_per_s"]
                / info_off["deliveries_per_s"], 3)
    if "probe_p99_ms" in info:
        # per-message socket-to-deliver latency: the PACED PROBE's
        # samples (service latency through the loaded broker — what a
        # compliant client experiences while the bulk saturates it).
        # The saturating bulk's own percentiles move to saturated_*:
        # with ingest backpressure the standing queue lives in the
        # publishers' kernel socket buffers, so those numbers measure
        # offered-load excess + kernel buffering, not the broker.
        rec["p50_batch_ms"] = round(info["probe_p50_ms"], 3)
        rec["p99_batch_ms"] = round(info["probe_p99_ms"], 3)
        rec["p99_deliver_ms"] = round(info["probe_p99_ms"], 3)
        rec["p50_deliver_ms"] = round(info["probe_p50_ms"], 3)
        rec["deliver_probe_rate"] = info["probe_rate"]
        rec["saturated_p50_ms"] = round(info["p50_ms"], 3)
        rec["saturated_p99_ms"] = round(info["p99_ms"], 3)
    else:
        rec["p50_batch_ms"] = round(info["p50_ms"], 3)
        rec["p99_batch_ms"] = round(info["p99_ms"], 3)
        rec["p99_deliver_ms"] = round(info["p99_ms"], 3)
        rec["p50_deliver_ms"] = round(info["p50_ms"], 3)
    if "stages" in info:
        # per-stage breakdown columns (telemetry spans): a latency
        # regression in this row is attributable to a stage, not a
        # vibe (ISSUE 2)
        rec["stage_p50_ms"] = {s: v["p50_ms"]
                               for s, v in info["stages"].items()}
        rec["stage_p99_ms"] = {s: v["p99_ms"]
                               for s, v in info["stages"].items()}
    if emit is not None:
        # the repo-root bench entry passes its _emit so the record
        # stages through the last-good-TPU artifact path
        emit(rec)
    else:
        print(json.dumps(rec), flush=True)


async def _run_overload() -> dict:
    """BENCH_MODE=overload body — the degradation curve: a loopback
    node with the overload monitor on tight thresholds, a stepped
    offered-load sweep, and per-step delivered-rate + shed-fraction
    accounting (docs/ROBUSTNESS.md). A detached persistent session
    rides along so warn-level QoS0 mqueue shedding has a queue to
    bite (live sockets' QoS0 goes straight to the outbox)."""
    from emqx_tpu.node import Node
    from emqx_tpu.overload import LEVEL_NAMES, OverloadConfig
    from emqx_tpu.session import Session

    n_subs = int(os.environ.get("OVERLOAD_SUBS", "4"))
    step_secs = float(os.environ.get("OVERLOAD_STEP_SECS", "2"))
    rates = [float(x) for x in os.environ.get(
        "OVERLOAD_RATES", "500,2000,8000,32000").split(",")]

    node = Node(boot_listeners=False, batch_size=64,
                overload=OverloadConfig(
                    interval_s=0.2, queue_warn=1.0,
                    queue_critical=4.0, clear_ticks=2))
    node.add_listener(port=0)
    await node.start()
    node.ingress.queue_hiwater = 64
    port = node.listeners[0].port
    loop = asyncio.get_running_loop()
    subs = []
    tasks = []
    for i in range(n_subs):
        p = _Peer(f"ovs{i}")
        await p.connect(port)
        await p.subscribe("ov/t", 0)
        tasks.append(loop.create_task(p.recv_loop()))
        subs.append(p)
    ghost = Session("ovghost", broker=node.broker, max_mqueue_len=256,
                    mqueue_store_qos0=True)
    ghost.connected = False
    node.broker.subscribe(ghost, "ov/t")
    pub = _Peer("ovpub")
    await pub.connect(port)
    frame = serialize(Publish(topic="ov/t", payload=b"\x00" * 16,
                              qos=0), C.MQTT_V4)
    m = node.metrics
    keys = ("messages.delivered", "delivery.dropped",
            "overload.shed.qos0", "overload.shed.ingress_timeout",
            "overload.shed.connect", "messages.dropped")
    curve = []
    for rate in rates:
        base = {k: m.val(k) for k in keys}
        lvl_peak = node.overload.level
        sent = 0
        burst = max(1, int(rate // 100))
        t0 = time.perf_counter()
        next_t = t0
        while time.perf_counter() - t0 < step_secs:
            for _ in range(burst):
                pub.writer.write(frame)
            sent += burst
            await pub.writer.drain()
            lvl_peak = max(lvl_peak, node.overload.level)
            next_t += burst / rate
            pause = next_t - time.perf_counter()
            if pause > 0:
                await asyncio.sleep(pause)
            else:
                next_t = time.perf_counter()
                await asyncio.sleep(0)
        # settle: the step's counters must include its own backlog
        ing = node.ingress
        deadline = time.perf_counter() + 5.0
        while (ing._pending or ing._inflight) \
                and time.perf_counter() < deadline:
            await asyncio.sleep(0.01)
        wall = time.perf_counter() - t0
        d = {k: m.val(k) - base[k] for k in keys}
        delivered = d["messages.delivered"]
        shed = d["delivery.dropped"] + d["messages.dropped"]
        curve.append({
            "offered_msgs_per_s": round(sent / wall, 1),
            "delivered_msgs_per_s": round(delivered / wall, 1),
            "deliver_ratio": round(
                delivered / max(1.0, sent * (n_subs + 1)), 4),
            "shed_fraction": round(
                shed / max(1, delivered + shed), 4),
            "shed_qos0": d["overload.shed.qos0"],
            "level_peak": LEVEL_NAMES[lvl_peak],
        })
        lvl_peak = max(lvl_peak, node.overload.level)
    for t in tasks:
        t.cancel()
    pub.close()
    for p in subs:
        p.close()
    await node.stop()
    return {
        "mode": "overload", "subs": n_subs,
        "ghost_mqueue": 256, "step_secs": step_secs,
        "hiwater": 64, "curve": curve,
        "transitions": m.val("overload.transitions"),
    }


def _run_devloss() -> dict:
    """BENCH_MODE=devloss body — the device-loss recovery window,
    measured (docs/ROBUSTNESS.md "Device-loss recovery"): a
    device-regime node under continuous batch traffic loses its
    backend mid-batch (`device.lost` armed times=0), every batch
    rides the exact host oracle, the backend returns, and the
    recovery rebuilds HBM state + re-warms the kernels until the
    half-open probe closes the breaker. Records the host-fallback
    throughput during the outage, `rebuild_s`, time-to-breaker-
    closed after the backend returns, and the p99 of the first
    post-recovery batches (the kernel-rewarm-stayed-off-the-hot-path
    proof). Direct ``publish_batch`` driving — per-batch latency is
    the quantity under test, sockets would only blur it."""
    from emqx_tpu import faults
    from emqx_tpu.node import Node
    from emqx_tpu.overload import DeviceBreaker, OverloadConfig
    from emqx_tpu.ops.warmup import stamp_first_batch
    from emqx_tpu.router import MatcherConfig
    from emqx_tpu.types import Message

    n_filters = int(os.environ.get("DEVLOSS_FILTERS", "600"))
    n_topics = int(os.environ.get("DEVLOSS_TOPICS", "16"))
    batch = int(os.environ.get("DEVLOSS_BATCH", "64"))
    secs = float(os.environ.get("DEVLOSS_SECS", "2"))
    outage = float(os.environ.get("DEVLOSS_OUTAGE_SECS", "2"))

    node = Node(boot_listeners=False,
                matcher=MatcherConfig(device_min_filters=0),
                overload=OverloadConfig(
                    breaker_failures=2, breaker_cooldown_s=60.0,
                    rebuild_backoff_s=0.1, sentinel_timeout_s=1.0))

    class _Sink:
        __slots__ = ("n",)

        def __init__(self):
            self.n = 0

        def deliver(self, flt, msg):
            self.n += 1

    sink = _Sink()
    topics = [f"dv/t{i}" for i in range(n_topics)]
    for t in topics:
        node.broker.subscribe(sink, t)
    # a deep (16-level) bucket rides along: its level shape is its
    # own compile family, and the rewarm must cover it too — the
    # first_deep_batch_p99_ms column is that proof (ISSUE 16)
    deep_topics = ["/".join(["dv", "deep", str(i)] + ["d"] * 13)
                   for i in range(min(4, n_topics))]
    for t in deep_topics:
        node.broker.subscribe(sink, t)
    pad = _Sink()
    for i in range(n_filters):
        node.broker.subscribe(pad, f"dvbg/{i}/x")
    msgs = [Message(topic=topics[i % n_topics], payload=b"\x00" * 16)
            for i in range(batch)]
    deep_msgs = [Message(topic=deep_topics[i % len(deep_topics)],
                         payload=b"\x00" * 16)
                 for i in range(batch)]

    def drive(seconds, latencies=None):
        sent = 0
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < seconds:
            tb = time.perf_counter()
            node.broker.publish_batch(msgs)
            if latencies is not None:
                latencies.append((time.perf_counter() - tb) * 1000.0)
            sent += batch
        return sent / (time.perf_counter() - t0)

    br = node.broker.breaker
    rec = br.recovery
    drive(1.0)  # compile every kernel pre-outage
    node.broker.publish_batch(deep_msgs)  # incl. the deep bucket
    steady_lat = []
    steady = drive(secs, steady_lat)
    # the outage: the backend dies mid-traffic; batches host-match
    out_lat = []
    faults.arm("device.lost", times=0)
    try:
        fallback_rate = drive(outage, out_lat)
        rebuilding = br.state == DeviceBreaker.REBUILDING
    finally:
        faults.disarm("device.lost")
    t_back = time.perf_counter()
    # the backend is back: publish until the probe closes the breaker
    closed = False
    while time.perf_counter() - t_back < 60.0:
        node.broker.publish_batch(msgs)
        if br.state == DeviceBreaker.CLOSED:
            closed = True
            break
        time.sleep(0.02)
    time_to_closed = time.perf_counter() - t_back
    # first post-recovery batches: the rewarm proof (no compile tail)
    post_lat = []
    for _ in range(20):
        tb = time.perf_counter()
        node.broker.publish_batch(msgs)
        post_lat.append((time.perf_counter() - tb) * 1000.0)
    # the deep bucket's own first batches: the rewarm must have
    # compiled the 16-level shape too, off the hot path
    post_deep_lat = []
    for _ in range(10):
        tb = time.perf_counter()
        node.broker.publish_batch(deep_msgs)
        post_deep_lat.append((time.perf_counter() - tb) * 1000.0)
    info = {
        "mode": "devloss", "filters": n_filters,
        "topics": n_topics, "batch": batch,
        "steady_msgs_per_s": round(steady, 1),
        "steady_p99_ms": round(
            float(np.percentile(steady_lat, 99)), 3),
        "fallback_msgs_per_s": round(fallback_rate, 1),
        "outage_p99_ms": round(float(np.percentile(out_lat, 99)), 3),
        "classified_lost_during_outage": rebuilding,
        "rebuild_s": rec.last_rebuild_s,
        "rebuilds": rec.rebuilds,
        "rebuild_failures": rec.rebuild_failures,
        "time_to_closed_s": round(time_to_closed, 3),
        "breaker_closed": closed,
        "first_batch_ms": round(post_lat[0], 3),
        "first_deep_batch_ms": round(post_deep_lat[0], 3),
        "first_deep_batch_p99_ms": round(
            float(np.percentile(post_deep_lat, 99)), 3),
        "deliveries": sink.n,
    }
    stamp_first_batch(info, float(np.percentile(post_lat, 99)))
    return info


def devloss(emit=None) -> None:
    """BENCH_MODE=devloss — the device-loss recovery row: host-
    fallback msgs/s during the outage (`value`; vs_baseline = the
    fraction of steady device throughput the oracle window retains),
    `rebuild_s`, `time_to_closed_s` after the backend returns, and
    `first_batch_p99_ms` (scripts/ci.sh gates a toy-scale run)."""
    import sys

    from emqx_tpu.profiling import enable_compile_cache

    enable_compile_cache()
    info = _run_devloss()
    print(json.dumps(info), file=sys.stderr, flush=True)
    rec = {
        "metric": "devloss_host_fallback_msgs_per_s",
        "workload": "devloss_v2_deep",
        "value": info["fallback_msgs_per_s"],
        "unit": "msgs/sec",
        "vs_baseline": round(
            info["fallback_msgs_per_s"]
            / max(info["steady_msgs_per_s"], 1.0), 3),
    }
    for k in ("steady_msgs_per_s", "steady_p99_ms", "outage_p99_ms",
              "classified_lost_during_outage", "rebuild_s",
              "rebuilds", "rebuild_failures", "time_to_closed_s",
              "breaker_closed", "first_batch_ms",
              "first_batch_p99_ms", "first_deep_batch_ms",
              "first_deep_batch_p99_ms"):
        rec[k] = info[k]
    if emit is not None:
        emit(rec)
    else:
        print(json.dumps(rec), flush=True)


def overload_curve(emit=None) -> None:
    """BENCH_MODE=overload — offered load vs delivered msgs/s vs shed
    fraction, one JSON row with the whole curve (scripts/ci.sh gates
    a toy-scale run of this as the overload smoke)."""
    import sys

    from emqx_tpu.profiling import enable_compile_cache

    enable_compile_cache()
    info = asyncio.run(_run_overload())
    print(json.dumps(info), file=sys.stderr, flush=True)
    curve = info["curve"]
    peak = max(c["delivered_msgs_per_s"] for c in curve)
    last = curve[-1]
    rec = {
        "metric": "overload_delivered_msgs_per_s",
        "workload": "overload_curve_v1",
        "value": peak,
        "unit": "msgs/sec",
        # retention at the top offered step: delivered there vs the
        # curve's peak — 1.0 means saturation degrades gracefully
        # (shedding + backpressure, no collapse)
        "vs_baseline": round(
            last["delivered_msgs_per_s"] / max(peak, 1.0), 3),
        "curve": curve,
        "shed_fraction_peak": max(c["shed_fraction"] for c in curve),
        "level_peak": curve[-1]["level_peak"],
        "overload_transitions": info["transitions"],
    }
    if emit is not None:
        emit(rec)
    else:
        print(json.dumps(rec), flush=True)


async def _run_drain() -> dict:
    """BENCH_MODE=drain body — the zero-downtime operation, measured
    (docs/OPERATIONS.md): a 2-node socket cluster, ``DRAIN_SESSIONS``
    detached persistent sessions (subscription + queued QoS1 state)
    plus ``DRAIN_LIVE`` real socket clients on the draining node;
    `ctl drain start --target` redirects the live clients in paced
    waves and hands every session's custody to the peer. Records
    sessions drained/s, the redirect-wave p99, time-to-empty, and
    the zero-RPO booleans (digest-verified hand-off, every session
    on the target, exactly-one-holder)."""
    import tempfile

    from emqx_tpu.cluster import ClusterConfig
    from emqx_tpu.drain import DrainConfig
    from emqx_tpu.durability import DurabilityConfig
    from emqx_tpu.node import Node
    from emqx_tpu.replication import sessions_digest
    from emqx_tpu.session import Session
    from emqx_tpu.types import Message, SubOpts
    from tests.mqtt_client import TestClient

    n_sessions = int(os.environ.get("DRAIN_SESSIONS", "5000"))
    n_live = int(os.environ.get("DRAIN_LIVE", "50"))
    wave_size = int(os.environ.get("DRAIN_WAVE", "200"))
    tmp = tempfile.mkdtemp(prefix="bench-drain-")
    ccfg = ClusterConfig(heartbeat_interval_s=0.2,
                         heartbeat_timeout_s=2.0, suspect_after=4,
                         down_after=100, ok_after=1,
                         anti_entropy_interval_s=5.0)
    nodes = []
    for i in range(2):
        node = Node(
            name=f"bd{i}", boot_listeners=False,
            durability=DurabilityConfig(
                enabled=True, dir=os.path.join(tmp, f"d{i}"),
                fsync=False, standbys=(f"bd{1 - i}",), ack_quorum=1,
                quorum_timeout_ms=500.0, repl_ack_timeout_s=5.0),
            drain=DrainConfig(wave_size=wave_size,
                              wave_interval_s=0.1,
                              handoff_timeout_s=60.0))
        node.add_listener(port=0)
        node.enable_cluster(port=0, cookie="bench-drain",
                            config=ccfg)
        await node.start()
        nodes.append(node)
    n0, n1 = nodes
    loop = asyncio.get_running_loop()
    await loop.run_in_executor(None, n1.cluster.join_remote,
                               "127.0.0.1",
                               n0.cluster.transport.port)
    # the detached persistent-session population with real state
    cids = [f"bench-d{i}" for i in range(n_sessions)]
    now = time.time()
    for i, cid in enumerate(cids):
        s = Session(cid, broker=n0.broker, clean_start=False)
        n0.durability.session_opened(s, 3600.0)
        s.subscribe(f"bench/{i % 97}/+", SubOpts(qos=1))
        n0.cm._detached[cid] = (s, now, 3600.0)
    # registry population batched (ONE call, not 5k broadcast casts
    # that would starve the heartbeats at setup time)
    with n0.cluster._lock:
        for cid in cids:
            n0.cluster._registry[cid] = "bd0"
    n0.cluster.transport.call("bd1", "registry_sync", "bd0", cids)
    n0.broker.publish(Message(topic="bench/13/x", payload=b"queued",
                              qos=1))
    n0.durability.on_batch()
    pre_digest = sessions_digest(n0, cids)
    # the live population (v5, redirect targets)
    clients = []
    from emqx_tpu.mqtt import constants as C
    for i in range(n_live):
        c = TestClient(f"bench-l{i}", version=C.MQTT_V5)
        await c.connect(port=n0.listeners[0].port, timeout=10.0)
        clients.append(c)
    # the measured operation
    t0 = time.perf_counter()
    n0.drain.start(target="bd1")
    while n0.drain.time_to_empty_s is None:
        await asyncio.sleep(0.02)
        if time.perf_counter() - t0 > 120:
            break
    info = n0.drain.info()
    on_target = sum(1 for cid in cids if cid in n1.cm._detached)
    digest_ok = sessions_digest(n1, cids) == pre_digest
    one_holder = not any(cid in n0.cm._detached for cid in cids)
    tte = info["time_to_empty_s"] or (time.perf_counter() - t0)
    out = {
        "sessions": n_sessions,
        "live_clients": n_live,
        "time_to_empty_s": round(tte, 3),
        "sessions_drained_per_s": round(
            info["handed_off"] / max(tte, 1e-6), 1),
        "redirect_wave_p99_ms": info["wave_p99_ms"],
        "redirected": info["redirected"],
        "handed_off": info["handed_off"],
        "handoff_digest_ok": bool(digest_ok),
        "sessions_on_target": on_target,
        "exactly_one_holder": bool(one_holder),
        "rpo_records": 0 if (digest_ok and on_target == n_sessions
                             and one_holder) else None,
    }
    for c in clients:
        try:
            await c.close()
        except Exception:
            pass
    for node in nodes:
        await node.stop()
    return out


def drain(emit=None) -> None:
    """BENCH_MODE=drain — graceful-drain operation metrics: sessions
    drained/s, redirect wave p99, time-to-empty at DRAIN_SESSIONS
    persistent sessions, and the zero-RPO boolean (scripts/ci.sh
    gates a toy-scale run)."""
    import sys

    from emqx_tpu.profiling import enable_compile_cache

    enable_compile_cache()
    info = asyncio.run(_run_drain())
    print(json.dumps(info), file=sys.stderr, flush=True)
    rec = {
        "metric": "drain_time_to_empty_s",
        "workload": "drain_v1",
        "value": info["time_to_empty_s"],
        "unit": "s",
        "vs_baseline": None,
    }
    rec.update({k: v for k, v in info.items()
                if k != "time_to_empty_s"})
    if emit is not None:
        emit(rec)
    else:
        print(json.dumps(rec), flush=True)


# -- BENCH_MODE=fleet ------------------------------------------------------
#
# The million-user claim, measured with real sockets (ISSUE 18): a
# connection FLEET — mostly-idle devices with wills, persistent
# sessions, and keepalive pings — around a mixed-traffic core
# (QoS0/1, retained, a shared-sub group) plus a reconnect-churn pool,
# against one node (FLEET_LOOPS event loops), an SO_REUSEPORT worker
# pool (FLEET_WORKERS processes), or an in-process socket cluster
# (FLEET_NODES). Reports delivered msgs/s, delivery p99, RSS per 10K
# connections, and a counted QoS1 blast whose zero-lost boolean is
# the CI gate. FLEET_DRIVERS > 1 shards the CLIENT side over that
# many subprocesses too — required past ~hard_nofile/2 connections,
# since one harness process pays 2 fds per loopback conn. Env:
# FLEET_CONNS, FLEET_SECS, FLEET_LOOPS, FLEET_WORKERS, FLEET_NODES,
# FLEET_DRIVERS, FLEET_SUBS, FLEET_PUBS, FLEET_CHURN, FLEET_TOPICS,
# FLEET_PIPELINE, FLEET_BLAST, FLEET_BLAST_TIMEOUT; the frame engine
# follows EMQX_TPU_FRAME like any broker.


def _raise_nofile(conns: int) -> None:
    """Lift RLIMIT_NOFILE toward what the fleet needs (2 fds per
    loopback connection: client end + server end)."""
    try:
        import resource
    except ImportError:
        return
    need = conns * 2 + 8192
    soft, hard = resource.getrlimit(resource.RLIMIT_NOFILE)
    if soft >= need:
        return
    if hard != resource.RLIM_INFINITY and hard < need:
        # privileged processes may lift the hard cap too (bounded by
        # the kernel's fs.nr_open); a 100K-connection fleet needs it
        try:
            resource.setrlimit(resource.RLIMIT_NOFILE, (need, need))
            return
        except (ValueError, OSError):
            pass
    new_soft = (need if hard == resource.RLIM_INFINITY
                else min(need, hard))
    try:
        resource.setrlimit(resource.RLIMIT_NOFILE, (new_soft, hard))
    except (ValueError, OSError):
        pass


def _rss_mb(pid="self") -> float:
    try:
        with open(f"/proc/{pid}/status") as f:
            for line in f:
                if line.startswith("VmRSS:"):
                    return int(line.split()[1]) / 1024.0
    except OSError:
        pass
    return 0.0


async def _count_recv(peer: _Peer) -> None:
    """Receive loop that counts deliveries WITHOUT latency samples
    (for subscribers whose payloads are not timestamps: wills, the
    counted blast)."""
    try:
        while True:
            data = await peer.reader.read(65536)
            if not data:
                return
            acked = False
            for pkt in peer.parser.feed(data):
                if isinstance(pkt, Publish):
                    peer.received += 1
                    if pkt.qos == 1:
                        peer.writer.write(serialize(
                            PubAck(type=C.PUBACK,
                                   packet_id=pkt.packet_id),
                            C.MQTT_V4))
                        acked = True
            if acked:
                await peer.writer.drain()
    except (asyncio.CancelledError, ConnectionResetError):
        return


async def _idle_connect(port: int, cid: str, clean: bool = True,
                        will_topic: str = None, sub: str = None,
                        sub_qos: int = 0):
    """One fleet idler: CONNECT (keepalive 0 — no ping obligation),
    optionally a will and one quiet subscription, then the socket
    just sits there. No per-connection task: CONNACK (4 bytes) and
    SUBACK (5 bytes) are fixed-size in v4, so the setup reads are
    exact and nothing ever needs parsing again."""
    reader, writer = await asyncio.open_connection(
        "127.0.0.1", port, local_addr=_bind_addr())
    kw = {}
    if will_topic is not None:
        kw = dict(will_flag=True, will_qos=0, will_topic=will_topic,
                  will_payload=struct.pack("<q", 0))
    writer.write(serialize(Connect(client_id=cid, clean_start=clean,
                                   keepalive=0, proto_ver=C.MQTT_V4,
                                   **kw), C.MQTT_V4))
    await writer.drain()
    await reader.readexactly(4)          # CONNACK
    if sub is not None:
        writer.write(serialize(Subscribe(
            packet_id=1, topic_filters=[(sub, {"qos": sub_qos})]),
            C.MQTT_V4))
        await writer.drain()
        await reader.readexactly(5)      # SUBACK (1 filter)
    return reader, writer


async def _churn_loop(ports, cid: str, stop: asyncio.Event,
                      counter: list, wills_root: str) -> None:
    """Reconnect churn: connect (with a will), linger briefly, drop
    the socket WITHOUT a DISCONNECT — the will fires, the session
    cleans up, and the fleet's accept path stays warm."""
    k = 0
    while not stop.is_set():
        try:
            r, w = await _idle_connect(
                ports[k % len(ports)], cid,
                will_topic=f"{wills_root}/{cid}")
        except (OSError, asyncio.IncompleteReadError):
            await asyncio.sleep(0.1)
            continue
        k += 1
        try:
            await asyncio.wait_for(stop.wait(), 0.05 + (k % 7) * 0.05)
        except asyncio.TimeoutError:
            pass
        try:
            w.transport.abort()          # abrupt: fires the will
        except Exception:
            w.close()
        counter[0] += 1


async def _run_fleet(ports, delivered_fn, conns_fn) -> dict:
    conns = int(os.environ.get("FLEET_CONNS", "2000"))
    secs = float(os.environ.get("FLEET_SECS", "5"))
    n_topics = int(os.environ.get("FLEET_TOPICS", "32"))
    pipeline = int(os.environ.get("FLEET_PIPELINE", "64"))
    blast_n = int(os.environ.get("FLEET_BLAST", "2000"))
    n_subs = int(os.environ.get(
        "FLEET_SUBS", str(min(64, max(4, conns // 16)))))
    n_pubs = int(os.environ.get(
        "FLEET_PUBS", str(min(16, max(2, conns // 64)))))
    n_churn = int(os.environ.get("FLEET_CHURN", str(conns // 20)))
    # sharded-driver runs give each driver its own client-id prefix
    # (same-cid sessions across drivers would take each other over)
    # and its own will/blast namespaces so per-driver counts stay
    # exact
    prefix = os.environ.get("FLEET_CID_PREFIX", "fl")
    wills_root = f"fleet/wills/{prefix}"
    blast_topic = f"fleet/blast/{prefix}"

    _raise_nofile(conns)
    rss0 = _rss_mb()
    topics = [f"fl/t{i}/v" for i in range(n_topics)]
    recv_tasks = []

    # traffic core: subscribers over literal/wildcard/shared shapes,
    # mixed delivery QoS
    subs = []
    for i in range(n_subs):
        s = _Peer(f"{prefix}-sub{i}")
        await s.connect(ports[i % len(ports)])
        if i % 8 == 0:
            flt = f"$share/flg/fl/t{i % n_topics}/#"
        elif i % 4 == 0:
            flt = "fl/+/v"
        else:
            flt = f"fl/t{i % n_topics}/#"
        await s.subscribe(flt, qos=1 if i % 2 else 0)
        recv_tasks.append(asyncio.ensure_future(s.recv_loop()))
        subs.append(s)

    # wills witness + the counted-blast pair (subscribed up front so
    # the blast needs no route churn mid-measurement)
    will_sub = _Peer(f"{prefix}-wills")
    await will_sub.connect(ports[0])
    await will_sub.subscribe(f"{wills_root}/#", qos=0)
    recv_tasks.append(asyncio.ensure_future(_count_recv(will_sub)))
    blast_sub = _Peer(f"{prefix}-blast-sub")
    await blast_sub.connect(ports[0])
    await blast_sub.subscribe(blast_topic, qos=1)
    recv_tasks.append(asyncio.ensure_future(_count_recv(blast_sub)))
    blast_pub = _Peer(f"{prefix}-blast-pub")
    await blast_pub.connect(ports[0])
    recv_tasks.append(asyncio.ensure_future(blast_pub.drain_loop()))

    if delivered_fn is None:
        # sharded-driver mode: this process can't see server
        # counters, so deliveries are counted at the client edge —
        # stricter, if anything (only frames that made it all the
        # way back over the wire count)
        def delivered_fn():
            return (sum(s.received for s in subs)
                    + will_sub.received + blast_sub.received)
    if conns_fn is None:
        def conns_fn():
            return len(idlers) + len(subs) + len(pubs) + 4

    pubs = []
    for i in range(n_pubs):
        p = _Peer(f"{prefix}-pub{i}")
        await p.connect(ports[i % len(ports)])
        recv_tasks.append(asyncio.ensure_future(p.drain_loop()))
        pubs.append(p)

    # the fleet: mostly-idle device connections. 30% carry wills,
    # 30% are persistent sessions holding a quiet QoS1 subscription,
    # the rest are plain keepalive-0 connections.
    n_idle = max(0, conns - n_subs - n_pubs - n_churn - 3)
    idlers = []
    n_wills = n_persist = 0
    sem = asyncio.Semaphore(256)

    async def _one_idler(i: int):
        nonlocal n_wills, n_persist
        async with sem:
            port = ports[i % len(ports)]
            try:
                if i % 10 < 3:
                    rw = await _idle_connect(
                        port, f"{prefix}-idle{i}",
                        will_topic=f"{wills_root}/idle{i}")
                    n_wills += 1
                elif i % 10 < 6:
                    rw = await _idle_connect(
                        port, f"{prefix}-idle{i}", clean=False,
                        sub=f"fleet/persist/{prefix}/{i}", sub_qos=1)
                    n_persist += 1
                else:
                    rw = await _idle_connect(port, f"{prefix}-idle{i}")
            except (OSError, asyncio.IncompleteReadError) as e:
                return e
            idlers.append(rw)
            return None

    setup_errs = [e for e in await asyncio.gather(
        *(_one_idler(i) for i in range(n_idle))) if e is not None]

    # rotating keepalive driver: PINGREQ over a moving slice of the
    # fleet each tick (the 2-byte PINGRESPs pool harmlessly in each
    # idler's stream buffer — nobody reads them, nobody needs to)
    ping_stop = asyncio.Event()
    pinged = [0]

    async def _ping_driver():
        pos = 0
        ping = serialize(Pingreq(), C.MQTT_V4)
        while not ping_stop.is_set():
            step = max(1, len(idlers) // 50) if idlers else 1
            for _ in range(step):
                if not idlers:
                    break
                _, w = idlers[pos % len(idlers)]
                try:
                    w.write(ping)
                    pinged[0] += 1
                except Exception:
                    pass
                pos += 1
            try:
                await asyncio.wait_for(ping_stop.wait(), 0.2)
            except asyncio.TimeoutError:
                pass

    ping_task = asyncio.ensure_future(_ping_driver())

    # reconnect churn
    churn_stop = asyncio.Event()
    churned = [0]
    churn_tasks = [asyncio.ensure_future(
        _churn_loop(ports, f"{prefix}-churn{i}", churn_stop, churned,
                    wills_root))
        for i in range(n_churn)]

    # a retained drip rides along: one retained set per tick on a
    # core topic (matches subscriber 0's filter), so the retain path
    # is in the measured mix
    retain_stop = asyncio.Event()
    retain_pub = _Peer(f"{prefix}-retain")
    await retain_pub.connect(ports[0])

    async def _retain_drip():
        j = 0
        while not retain_stop.is_set():
            retain_pub.writer.write(serialize(Publish(
                topic="fl/t0/v",
                payload=struct.pack("<q", time.perf_counter_ns()),
                retain=True), C.MQTT_V4))
            try:
                await retain_pub.writer.drain()
                await asyncio.wait_for(retain_stop.wait(), 0.1)
            except asyncio.TimeoutError:
                pass
            except Exception:
                return
            j += 1

    retain_task = asyncio.ensure_future(_retain_drip())

    await asyncio.sleep(1.0)  # settle: routes, churn steady-state

    # warm pass (compiles/caches outside the window)
    warm_stop = asyncio.Event()
    warm = [asyncio.ensure_future(p.publish_loop(
        topics, warm_stop, pipeline, 0.0, 1 if i % 2 else 0))
        for i, p in enumerate(pubs)]
    await asyncio.sleep(0.5)
    warm_stop.set()
    await asyncio.gather(*warm, return_exceptions=True)
    await asyncio.sleep(0.5)
    for s in subs:
        s.latencies.clear()
        s.received = 0

    # the timed window: mixed QoS0/QoS1 publish load (a publisher
    # reset mid-window costs its remaining sends, not the whole run)
    base_delivered = delivered_fn()
    stop = asyncio.Event()
    t0 = time.perf_counter()
    pub_tasks = [asyncio.ensure_future(p.publish_loop(
        topics, stop, pipeline, 0.0, 1 if i % 2 else 0))
        for i, p in enumerate(pubs)]
    await asyncio.sleep(secs)
    stop.set()
    sent = sum(r for r in
               await asyncio.gather(*pub_tasks, return_exceptions=True)
               if isinstance(r, int))
    elapsed = time.perf_counter() - t0
    await asyncio.sleep(0.5)
    delivered = delivered_fn() - base_delivered
    conns_now = conns_fn()

    received = sum(s.received for s in subs)
    lats = np.concatenate([np.asarray(s.latencies, np.float64)
                           for s in subs if s.latencies]) \
        if any(s.latencies for s in subs) else np.zeros(1)
    rss1 = _rss_mb()

    # counted QoS1 blast: every delivery individually owed, so
    # expected == received is a hard zero-lost check, not a rate
    churn_stop.set()     # quiesce churn first: no takeover noise
    await asyncio.gather(*churn_tasks, return_exceptions=True)
    # let the window's delivery backlog drain before counting: on an
    # oversubscribed host the standing queue can be tens of seconds
    # deep, and the blast must not race it
    prev = delivered_fn()
    quiet_deadline = time.perf_counter() + 60.0
    while time.perf_counter() < quiet_deadline:
        await asyncio.sleep(0.5)
        cur = delivered_fn()
        if cur == prev:
            break
        prev = cur
    base_blast = blast_sub.received
    for i in range(blast_n):
        blast_pub.writer.write(serialize(Publish(
            topic=blast_topic, payload=struct.pack("<q", i),
            qos=1, packet_id=i % 0xFFFF + 1), C.MQTT_V4))
        if (i + 1) % 128 == 0:
            await blast_pub.writer.drain()
            await asyncio.sleep(0)
    await blast_pub.writer.drain()
    deadline = time.perf_counter() + float(
        os.environ.get("FLEET_BLAST_TIMEOUT", "60"))
    while (blast_sub.received - base_blast) < blast_n \
            and time.perf_counter() < deadline:
        await asyncio.sleep(0.05)
    blast_got = blast_sub.received - base_blast

    # reconnect-storm retained replay (docs/DISPATCH.md "Retained
    # replay"): seed FLEET_RETAINED retained topics, then
    # FLEET_RETAINED_CONNS fresh connections subscribe the covering
    # wildcard at once — each is owed exactly the full set, so
    # expected == received is the zero-lost-replay check and the
    # elapsed window is the storm's replay rate. Exercises the
    # batched subscribe-time match + planner-egress replay end to
    # end (requires the server to run the retainer module — the
    # in-process/worker fleet servers load it).
    ret_n = int(os.environ.get("FLEET_RETAINED", "64"))
    ret_conns = int(os.environ.get("FLEET_RETAINED_CONNS", "32"))
    ret_expected = ret_got = 0
    ret_elapsed = 0.0
    if ret_n and ret_conns:
        ret_root = f"fleet/ret/{prefix}"
        for i in range(ret_n):
            retain_pub.writer.write(serialize(Publish(
                topic=f"{ret_root}/{i}/s", payload=b"r",
                retain=True), C.MQTT_V4))
        await retain_pub.writer.drain()
        await asyncio.sleep(0.5)  # stores land before the storm
        storm = [_Peer(f"{prefix}-ret{i}") for i in range(ret_conns)]
        await asyncio.gather(*(p.connect(ports[i % len(ports)])
                               for i, p in enumerate(storm)))
        storm_tasks = []
        t0r = time.perf_counter()
        for p in storm:
            # SUBSCRIBE without awaiting the SUBACK: replayed frames
            # can share a read with the ack and every one must count
            p.writer.write(serialize(Subscribe(
                packet_id=1,
                topic_filters=[(f"{ret_root}/#", {"qos": 0})]),
                C.MQTT_V4))
            storm_tasks.append(asyncio.ensure_future(_count_recv(p)))
        await asyncio.gather(*(p.writer.drain() for p in storm))
        ret_expected = ret_n * ret_conns
        ret_deadline = time.perf_counter() + float(
            os.environ.get("FLEET_RETAINED_TIMEOUT", "30"))
        while sum(p.received for p in storm) < ret_expected \
                and time.perf_counter() < ret_deadline:
            await asyncio.sleep(0.05)
        ret_elapsed = time.perf_counter() - t0r
        ret_got = sum(p.received for p in storm)
        for t in storm_tasks:
            t.cancel()
        for p in storm:
            p.close()

    ping_stop.set()
    retain_stop.set()
    await asyncio.gather(ping_task, retain_task,
                         return_exceptions=True)
    for t in recv_tasks:
        t.cancel()
    for peer in subs + pubs + [will_sub, blast_sub, blast_pub,
                               retain_pub]:
        peer.close()
    for _, w in idlers:
        try:
            w.close()
        except Exception:
            pass
    await asyncio.sleep(0)

    return {
        "conns_target": conns,
        "conns_live": conns_now,
        "idlers": len(idlers),
        "idler_connect_errors": len(setup_errs),
        "idlers_with_wills": n_wills,
        "persistent_sessions": n_persist,
        "keepalive_pings": pinged[0],
        "churn_conns": n_churn,
        "churn_reconnects": churned[0],
        "wills_fired": will_sub.received,
        "subs": n_subs, "pubs": n_pubs,
        "sent": sent,
        "delivered": delivered,
        "received_client": received,
        "elapsed_s": round(elapsed, 3),
        "delivered_per_s": round(delivered / elapsed, 1),
        "p50_ms": float(np.percentile(lats, 50)),
        "p99_ms": float(np.percentile(lats, 99)),
        "blast_expected": blast_n,
        "blast_received": blast_got,
        "blast_lost": blast_n - blast_got,
        "retained_storm_conns": ret_conns,
        "retained_storm_topics": ret_n,
        "retained_storm_expected": ret_expected,
        "retained_storm_replayed": ret_got,
        "retained_storm_lost": ret_expected - ret_got,
        "retained_storm_s": round(ret_elapsed, 3),
        "retained_storm_replays_per_s": round(
            ret_got / ret_elapsed, 1) if ret_elapsed else 0.0,
        "rss_mb": round(rss1, 1),
        "rss_setup_mb": round(rss0, 1),
        "rss_per_10k_conns_mb": round(
            (rss1 - rss0) / max(1, conns) * 10000, 1),
    }


async def _run_fleet_inproc() -> dict:
    """One process: FLEET_NODES in-process nodes (socket cluster when
    >1), each with FLEET_LOOPS front-door event loops."""
    from emqx_tpu.node import Node
    from emqx_tpu.zone import Zone

    loops = int(os.environ.get("FLEET_LOOPS", "1"))
    nnodes = int(os.environ.get("FLEET_NODES", "1"))
    zone = Zone(name="default", max_inflight=8192,
                max_mqueue_len=50000)
    nodes = []
    from emqx_tpu.modules.retainer import RetainerModule

    for i in range(nnodes):
        node = Node(name=f"fleet{i}", boot_listeners=False,
                    loops=loops, zone=zone, batch_linger_ms=1.0)
        # the reconnect-storm retained-replay column needs the
        # retainer serving replays
        node.modules.load(RetainerModule)
        node.add_listener(port=0)
        if nnodes > 1:
            node.enable_cluster(port=0, cookie="bench-fleet")
        await node.start()
        nodes.append(node)
    if nnodes > 1:
        loop = asyncio.get_running_loop()
        for node in nodes[1:]:
            await loop.run_in_executor(
                None, node.cluster.join_remote, "127.0.0.1",
                nodes[0].cluster.transport.port)
        await asyncio.sleep(0.5)
    ports = [n.listeners[0].port for n in nodes]
    try:
        res = await _run_fleet(
            ports,
            delivered_fn=lambda: sum(
                n.metrics.val("messages.delivered") for n in nodes),
            conns_fn=lambda: sum(
                n.cm.connection_count() for n in nodes))
        res["loops"] = loops
        res["nodes"] = nnodes
        res["workers"] = 1
        res["rss_includes_harness"] = True
        for key in ("frame.native.frames", "frame.fallback",
                    "frame.oversize", "messages.retained"):
            res[key.replace(".", "_")] = sum(
                n.metrics.val(key) for n in nodes)
        res["frame_mode"] = nodes[0].listeners[0].frame
    finally:
        for node in nodes:
            await node.stop()
    return res


def _run_fleet_workers(n_workers: int) -> dict:
    """FLEET_WORKERS SO_REUSEPORT worker PROCESSES share one port;
    worker RSS is pure server-side (the harness lives elsewhere)."""
    from emqx_tpu.workers import WorkerPool

    # one process per chip: the bench parent holds it, so the
    # front-door worker processes match on the host
    with WorkerPool(n_workers, port=0, platform="cpu") as pool:
        res = asyncio.run(_run_fleet(
            [pool.port],
            delivered_fn=lambda: sum(d for _, d in pool.stats()),
            conns_fn=lambda: sum(c for c, _ in pool.stats())))
        worker_rss = sum(_rss_mb(p.pid) for p in pool.procs)
    res["loops"] = 1
    res["nodes"] = 1
    res["workers"] = n_workers
    res["rss_includes_harness"] = False
    res["rss_mb"] = round(worker_rss, 1)
    res["rss_per_10k_conns_mb"] = round(
        worker_rss / max(1, res["conns_target"]) * 10000, 1)
    res["frame_mode"] = os.environ.get("EMQX_TPU_FRAME", "py")
    return res


def _fleet_driver_main() -> None:
    """Entry point for one FLEET_DRIVERS subprocess (re-exec'd by
    ``_run_fleet_sharded``): drive this process's slice of the fleet
    against the ports in FLEET_DRIVER_PORTS and report the row JSON
    on stdout. The per-process RLIMIT_NOFILE hard cap is why this
    exists — one harness process tops out near hard_cap/2 loopback
    connections, so a 100K fleet is driven by a pool of these."""
    ports = [int(p) for p in
             os.environ["FLEET_DRIVER_PORTS"].split(",")]
    info = asyncio.run(_run_fleet(ports, None, None))
    info["driver_rss_mb"] = round(_rss_mb(), 1)
    print(json.dumps(info), flush=True)


def _merge_driver_rows(rows: list) -> dict:
    """Sum the additive columns across driver rows. Percentiles are
    merged conservatively — the max across drivers — because raw
    latency samples don't cross the process boundary."""
    out = dict(rows[0])
    out.pop("rss_setup_mb", None)
    for k in ("conns_target", "conns_live", "idlers",
              "idler_connect_errors", "idlers_with_wills",
              "persistent_sessions", "keepalive_pings", "churn_conns",
              "churn_reconnects", "wills_fired", "subs", "pubs",
              "sent", "delivered", "received_client",
              "blast_expected", "blast_received", "blast_lost",
              "retained_storm_conns", "retained_storm_expected",
              "retained_storm_replayed", "retained_storm_lost",
              "driver_rss_mb"):
        out[k] = sum(r.get(k, 0) for r in rows)
    out["retained_storm_s"] = max(
        r.get("retained_storm_s", 0.0) for r in rows)
    out["retained_storm_replays_per_s"] = round(sum(
        r.get("retained_storm_replays_per_s", 0.0) for r in rows), 1)
    out["elapsed_s"] = max(r["elapsed_s"] for r in rows)
    out["delivered_per_s"] = round(
        sum(r["delivered"] / r["elapsed_s"] for r in rows), 1)
    out["p50_ms"] = max(r["p50_ms"] for r in rows)
    out["p99_ms"] = max(r["p99_ms"] for r in rows)
    return out


async def _spawn_drivers(n_drivers: int, ports, conns: int) -> list:
    """Launch the driver pool (each with a distinct cid prefix and a
    proportional slice of every population knob) and collect one row
    dict per driver."""
    import sys

    blast = int(os.environ.get("FLEET_BLAST", "2000"))
    churn = int(os.environ.get("FLEET_CHURN", str(conns // 20)))
    subs = int(os.environ.get(
        "FLEET_SUBS", str(min(64, max(4, conns // 16)))))
    pubs = int(os.environ.get(
        "FLEET_PUBS", str(min(16, max(2, conns // 64)))))
    procs = []
    for d in range(n_drivers):
        env = dict(os.environ)
        env.update({
            "FLEET_DRIVER_PORTS": ",".join(str(p) for p in ports),
            "FLEET_CID_PREFIX": f"fd{d}",
            # one 127/8 source ip per driver: past ~28K conns the
            # shared (src, dst) ephemeral-port space runs dry
            "FLEET_BIND_IP": f"127.0.0.{d % 250 + 2}",
            "FLEET_CONNS": str(conns // n_drivers),
            "FLEET_BLAST": str(max(1, blast // n_drivers)),
            "FLEET_CHURN": str(max(1, churn // n_drivers)),
            "FLEET_SUBS": str(max(2, subs // n_drivers)),
            "FLEET_PUBS": str(max(1, pubs // n_drivers)),
        })
        procs.append(await asyncio.create_subprocess_exec(
            sys.executable, "-c",
            "from emqx_tpu.bench_live import _fleet_driver_main; "
            "_fleet_driver_main()",
            stdout=asyncio.subprocess.PIPE, env=env))
    outs = await asyncio.gather(*(p.communicate() for p in procs))
    rows = []
    for (stdout, _), p in zip(outs, procs):
        if p.returncode == 0 and stdout.strip():
            rows.append(json.loads(
                stdout.decode().splitlines()[-1]))
    if not rows:
        raise RuntimeError("every fleet driver failed")
    return rows


async def _run_fleet_sharded(n_drivers: int) -> dict:
    """FLEET_DRIVERS client subprocesses against either an in-proc
    node (FLEET_LOOPS event loops) or an SO_REUSEPORT worker pool
    (FLEET_WORKERS > 1). Server and harness never share a process,
    so ``rss_mb`` is pure server-side — and no single process has to
    hold the whole fleet's fds, which is what makes a 100K run fit
    under an unraisable RLIMIT_NOFILE hard cap (use enough workers
    AND drivers that each side's per-process share stays under it)."""
    conns = int(os.environ.get("FLEET_CONNS", "2000"))
    loops = int(os.environ.get("FLEET_LOOPS", "1"))
    n_workers = int(os.environ.get("FLEET_WORKERS", "1"))
    if n_workers > 1:
        from emqx_tpu.workers import WorkerPool

        with WorkerPool(n_workers, port=0, platform="cpu") as pool:
            d0 = sum(d for _, d in pool.stats())
            rows = await _spawn_drivers(n_drivers, [pool.port], conns)
            server_delivered = sum(d for _, d in pool.stats()) - d0
            server_rss = sum(_rss_mb(p.pid) for p in pool.procs)
        res = _merge_driver_rows(rows)
        res["loops"] = 1
        res["nodes"] = 1
        res["workers"] = n_workers
        res["frame_mode"] = os.environ.get("EMQX_TPU_FRAME", "py")
    else:
        from emqx_tpu.node import Node
        from emqx_tpu.zone import Zone

        zone = Zone(name="default", max_inflight=8192,
                    max_mqueue_len=50000)
        node = Node(name="fleet0", boot_listeners=False, loops=loops,
                    zone=zone, batch_linger_ms=1.0)
        node.add_listener(port=0)
        await node.start()
        try:
            d0 = node.metrics.val("messages.delivered")
            rows = await _spawn_drivers(
                n_drivers, [node.listeners[0].port], conns)
            server_delivered = node.metrics.val(
                "messages.delivered") - d0
            server_rss = _rss_mb()
            res = _merge_driver_rows(rows)
            for key in ("frame.native.frames", "frame.fallback",
                        "frame.oversize", "messages.retained"):
                res[key.replace(".", "_")] = node.metrics.val(key)
            res["frame_mode"] = node.listeners[0].frame
        finally:
            await node.stop()
        res["loops"] = loops
        res["nodes"] = 1
        res["workers"] = 1
    # client-edge vs server-side delivery accounting, both reported:
    # drivers count what arrived over the wire, the server counts
    # what it dispatched
    res["server_delivered_total"] = server_delivered
    res["drivers"] = n_drivers
    res["rss_mb"] = round(server_rss, 1)
    res["rss_includes_harness"] = False
    res["rss_per_10k_conns_mb"] = round(
        server_rss / max(1, res["conns_live"]) * 10000, 1)
    return res


def fleet(emit=None) -> None:
    """BENCH_MODE=fleet — the connection-fleet row: delivered msgs/s
    + delivery p99 + RSS per 10K conns at FLEET_CONNS real sockets
    with wills, persistent sessions, churn, and mixed traffic, plus
    the counted-blast zero-lost boolean (scripts/ci.sh gates a
    toy-scale run)."""
    import sys

    from emqx_tpu.profiling import enable_compile_cache

    enable_compile_cache()
    n_workers = int(os.environ.get("FLEET_WORKERS", "1"))
    n_drivers = int(os.environ.get("FLEET_DRIVERS", "1"))
    if n_drivers > 1:
        info = asyncio.run(_run_fleet_sharded(n_drivers))
    elif n_workers > 1:
        info = _run_fleet_workers(n_workers)
    else:
        info = asyncio.run(_run_fleet_inproc())
    print(json.dumps(info), file=sys.stderr, flush=True)
    rec = {
        "metric": "fleet_delivered_msgs_per_s",
        "workload": "fleet_v1",
        "value": info["delivered_per_s"],
        "unit": "msgs/sec",
        # the million-user yardstick: live connections vs 1M
        "vs_baseline": round(info["conns_live"] / 1_000_000, 4),
    }
    for k in ("conns_target", "conns_live", "idlers",
              "idlers_with_wills", "persistent_sessions",
              "churn_reconnects", "wills_fired", "p50_ms", "p99_ms",
              "blast_expected", "blast_received", "blast_lost",
              "retained_storm_conns", "retained_storm_topics",
              "retained_storm_expected", "retained_storm_replayed",
              "retained_storm_lost", "retained_storm_s",
              "retained_storm_replays_per_s",
              "rss_mb", "rss_per_10k_conns_mb",
              "rss_includes_harness", "loops", "workers", "nodes",
              "drivers", "driver_rss_mb", "server_delivered_total",
              "frame_mode"):
        if k in info:
            rec[k] = info[k]
    for k in ("frame_native_frames", "frame_fallback",
              "messages_retained"):
        if k in info:
            rec[k] = info[k]
    if emit is not None:
        emit(rec)
    else:
        print(json.dumps(rec), flush=True)


if __name__ == "__main__":
    live()
