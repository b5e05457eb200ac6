"""Chaos suite: every registered fault-injection point (faults.py)
exercised against the shedding/healing behavior it exists to trigger
(docs/ROBUSTNESS.md; ISSUE 8 acceptance).

The pinned contracts:

  - device-step failure/stall trips the circuit breaker to the exact
    host-oracle path with ZERO wrong or lost deliveries, and the
    breaker recovers through a half-open probe;
  - executor death and a crashed compaction flatten self-heal
    (respawn / alarm + backoff-retry);
  - a dead front-door loop's connections close with wills fired and
    the cross-loop join never hangs (handoff loss is bounded +
    counted, not silent);
  - a saturated ingress sheds a parked publisher after the bounded
    submit wait instead of wedging it forever;
  - faults-disabled and ``[overload] enabled = false`` keep the
    broker byte-for-byte the pre-robustness build.
"""

import asyncio
import time

import pytest

from emqx_tpu import faults
from emqx_tpu.config import ConfigError, parse_config
from emqx_tpu.mqtt import constants as C
from emqx_tpu.node import Node
from emqx_tpu.overload import (CRITICAL, OK, WARN, DeviceBreaker,
                               OverloadConfig)
from emqx_tpu.router import MatcherConfig
from emqx_tpu.session import Session
from emqx_tpu.types import Message

from helpers import broker_node, node_port
from mqtt_client import TestClient


@pytest.fixture(autouse=True)
def _clean_faults():
    """The fault registry is process-global: every test starts and
    ends with it empty (and the master switch on, its default)."""
    faults.clear()
    faults.set_master(True)
    yield
    faults.clear()
    faults.set_master(True)


class Sink:
    def __init__(self):
        self.got = []

    def deliver(self, flt, msg):
        self.got.append((flt, msg.topic, bytes(msg.payload)))


def _device_node(**over):
    kw = dict(boot_listeners=False,
              matcher=MatcherConfig(device_min_filters=0))
    kw.update(over)
    return Node(**kw)


# -- fault registry semantics ------------------------------------------------


def test_registry_validation_times_and_determinism():
    with pytest.raises(ValueError):
        faults.arm("no.such.point")
    with pytest.raises(ValueError):
        faults.arm("device.walk", action="explode")
    with pytest.raises(ValueError):
        faults.arm("device.walk", action="stall")  # needs delay_ms
    assert not faults.enabled
    # times accounting: 2 triggers then self-disarm (and the module
    # gate drops with the last arm)
    faults.arm("ingress.saturate", times=2)
    assert faults.enabled
    assert faults.fire("ingress.saturate") is True
    assert faults.fire("ingress.saturate") is True
    assert not faults.enabled
    assert faults.fire("ingress.saturate") is False
    # seeded probability is deterministic
    faults.seed(7)
    faults.arm("ingress.saturate", times=0, prob=0.5)
    seq1 = [faults.fire("ingress.saturate") for _ in range(16)]
    faults.clear()
    faults.seed(7)
    faults.arm("ingress.saturate", times=0, prob=0.5)
    seq2 = [faults.fire("ingress.saturate") for _ in range(16)]
    assert seq1 == seq2 and True in seq1 and False in seq1
    # master off keeps arms inert
    faults.clear()
    faults.arm("ingress.saturate", times=0)
    faults.set_master(False)
    assert not faults.enabled
    # context manager disarms on exit
    faults.set_master(True)
    faults.clear()
    with faults.injected("device.walk", times=0):
        assert faults.enabled
    assert not faults.enabled
    # arm-spec parsing (the TOML/ctl syntax)
    assert faults.parse_arm("device.fetch:raise:3") == \
        ("device.fetch", "raise", 3, 0.0)
    with pytest.raises(ValueError):
        faults.parse_arm("device.fetch:bogus")


def test_config_sections_closed_schema():
    with pytest.raises(ConfigError):
        parse_config({"overload": {"lag_warm_ms": 5}})  # typo'd key
    with pytest.raises(ConfigError):
        parse_config({"overload": {"lag_warn_ms": 100,
                                   "lag_critical_ms": 10}})  # order
    with pytest.raises(ConfigError):
        parse_config({"faults": {"arm": ["no.such.point"]}})
    cfg = parse_config({
        "overload": {"enabled": False},
        "faults": {"enabled": False, "seed": 3,
                   "arm": ["device.fetch:raise:2"]},
    })
    assert cfg.overload.enabled is False
    assert cfg.faults.arm == ["device.fetch:raise:2"]
    # an overload-off node builds NO monitor, breaker, or bounded
    # ingress wait — the hot paths read None (the byte-for-byte pin)
    node = Node(boot_listeners=False, overload=cfg.overload)
    assert node.overload is None
    assert node.broker.overload is None
    assert node.broker.breaker is None
    assert node.ingress.submit_wait_timeout == 0.0


# -- device-path circuit breaker ---------------------------------------------


def test_device_failure_trips_breaker_and_half_open_recovers():
    """The acceptance scenario: injected device-step failures trip
    the breaker to host-oracle matching with zero wrong/lost
    deliveries, and the breaker recovers via a half-open probe."""
    node = _device_node(overload=OverloadConfig(
        breaker_failures=2, breaker_cooldown_s=0.2))
    s = Sink()
    node.subscribe(s, "c/+")
    node.subscribe(s, "c/#")
    br = node.broker.breaker
    # two consecutive fetch failures: each batch falls back to the
    # exact host oracle (both filters still deliver), then the
    # breaker opens
    with faults.injected("device.fetch", times=2):
        for i in range(2):
            got = node.broker.publish_batch(
                [Message(topic="c/t", payload=b"f%d" % i)])
            assert got == [2]
    assert br.state == DeviceBreaker.OPEN
    assert node.metrics.val("breaker.trips") == 1
    assert node.metrics.val("breaker.failures") == 2
    assert any(a.name == "device_path_breaker"
               for a in node.alarms.get_alarms("activated"))
    # open: batches are host-matched without touching the device
    assert node.broker.publish_batch(
        [Message(topic="c/t", payload=b"open")]) == [2]
    assert node.metrics.val("breaker.fallback.batches") >= 1
    # cooldown elapses -> exactly one half-open probe rides the
    # device; success closes the breaker and clears the alarm
    time.sleep(0.25)
    assert node.broker.publish_batch(
        [Message(topic="c/t", payload=b"probe")]) == [2]
    assert br.state == DeviceBreaker.CLOSED
    assert node.metrics.val("breaker.probes") == 1
    assert not any(a.name == "device_path_breaker"
                   for a in node.alarms.get_alarms("activated"))
    # nothing was lost or duplicated across the whole episode
    assert len(s.got) == 2 * 4


def test_device_walk_failure_is_caught_too():
    node = _device_node()
    s = Sink()
    node.subscribe(s, "w/1")
    with faults.injected("device.walk", times=1):
        assert node.broker.publish_batch(
            [Message(topic="w/1", payload=b"x")]) == [1]
    assert node.metrics.val("breaker.failures") == 1
    assert len(s.got) == 1


def test_stalled_device_step_counts_as_failure():
    """A device that answers but too slowly must trip the fallback —
    breaker_slow_ms turns the stall into a recorded failure."""
    node = _device_node(overload=OverloadConfig(
        breaker_failures=1, breaker_cooldown_s=30.0))
    s = Sink()
    node.subscribe(s, "st/1")
    # warm with the latency gate off — the first fetch pays XLA
    # compiles and must not count; then arm a bound the injected
    # stall clearly exceeds but a warm fetch clearly doesn't
    node.broker.publish_batch([Message(topic="st/1", payload=b"warm")])
    assert node.broker.breaker.state == DeviceBreaker.CLOSED
    node.broker.breaker.slow_ms = 400.0
    with faults.injected("device.fetch", action="stall", times=1,
                         delay_ms=600.0):
        assert node.broker.publish_batch(
            [Message(topic="st/1", payload=b"slow")]) == [1]
    assert node.broker.breaker.state == DeviceBreaker.OPEN
    assert len(s.got) == 2


def test_breaker_off_reraises_device_failure():
    """[overload] off: no breaker — a device failure surfaces raw,
    exactly the pre-robustness behavior."""
    node = _device_node(overload=OverloadConfig(enabled=False))
    node.subscribe(Sink(), "r/1")
    with faults.injected("device.fetch", times=1):
        with pytest.raises(faults.FaultInjected):
            node.broker.publish_batch(
                [Message(topic="r/1", payload=b"x")])


# -- executor death / flatten crash supervision ------------------------------


async def test_executor_death_self_heals():
    async with broker_node(
            matcher=MatcherConfig(device_min_filters=0)) as node:
        port = node_port(node)
        sub = TestClient("exsub")
        pub = TestClient("expub")
        await sub.connect(port=port)
        await pub.connect(port=port)
        await sub.subscribe("ex/t", qos=1)
        # warm: the fetch pool is lazily created by the first batch
        await pub.publish("ex/t", payload=b"warm", qos=1)
        assert (await sub.recv()).payload == b"warm"
        with faults.injected("executor.death", times=1):
            await pub.publish("ex/t", payload=b"survives", qos=1)
        msg = await sub.recv()
        assert msg.payload == b"survives"
        assert node.metrics.val("overload.heal.executor") == 1
        await sub.close()
        await pub.close()


def test_flatten_crash_alarms_backoff_then_retries():
    node = _device_node(matcher=MatcherConfig(
        device_min_filters=0, delta_max_filters=4))
    r = node.router
    for i in range(3):
        r.add_route(f"fl/{i}")
    r.match_ids(["fl/0"])  # build the automaton (delta plane live)
    with faults.injected("compaction.flatten", times=1):
        for i in range(3, 12):
            r.add_route(f"fl/{i}")
        deadline = time.time() + 10
        while r._compact_failures == 0 and time.time() < deadline:
            time.sleep(0.01)
    assert r._compact_failures == 1
    # route ops kept landing (the delta carries them) and matching
    # still answers exactly
    assert sorted(r.host_match("fl/7")) == ["fl/7"]
    node.drain_robustness_events()
    assert any(a.name == "router_compaction_failed"
               for a in node.alarms.get_alarms("activated"))
    assert node.metrics.val("overload.heal.flatten") == 1
    # inside the backoff window nothing re-flattens; once it elapses
    # the monitor's retry hook re-kicks the compaction and it heals
    r.retry_compaction()
    assert r._compact_failures == 1
    r._compact_backoff_until = 0.0
    r.retry_compaction()
    deadline = time.time() + 10
    while (r._compacting or r._compact_failures) \
            and time.time() < deadline:
        time.sleep(0.01)
    assert r._compact_failures == 0
    node.drain_robustness_events()
    assert not any(a.name == "router_compaction_failed"
                   for a in node.alarms.get_alarms("activated"))


def test_handover_crash_alarms_backoff_and_the_old_tables_stay():
    """PR 42: the fan-out tables' hand-over at a compaction's swap
    (``Router.on_swap`` -> ``FanoutManager.carry``) fails: the swap is
    not made, the old automaton, its epoch and the old fan-out tables
    stay live and exact, the freeze is lifted, and the failure takes
    the crashed flatten's path: alarm, backoff, retry."""
    class _Sub:
        def deliver(self, topic_filter, msg):
            pass

    node = _device_node(matcher=MatcherConfig(
        device_min_filters=0, delta_max_filters=4))
    r, helper, sub = node.router, node.broker.helper, _Sub()
    for i in range(3):
        node.broker.subscribe(sub, f"fl/{i}")
    r.match_ids(["fl/0"])  # build the automaton (delta plane live)
    auto, id_map, epoch = r.automaton()
    st0 = helper.state(epoch, id_map)
    assert st0 is not None and st0.epoch == epoch
    carry, calls = r.on_swap, []

    def boom(*a):
        calls.append(a)
        if len(calls) == 1:
            raise RuntimeError("hand-over failed")
        return carry(*a)

    r.on_swap = boom
    for i in range(3, 12):
        node.broker.subscribe(sub, f"fl/{i}")
    deadline = time.time() + 10
    while r._compact_failures == 0 and time.time() < deadline:
        time.sleep(0.01)
    assert r._compact_failures == 1 and len(calls) == 1
    # nothing of the swap was made: the old tables serve, exactly
    assert r.automaton()[2] == epoch and r._auto is auto
    assert r._freeze is None and not r._rebuild_inflight
    assert r.delta_info()["merges"] == 0 and helper.carries == 0
    st1 = helper.state(epoch, r.automaton()[1])
    assert st1.epoch == epoch and helper.rebuilds == 1
    assert sorted(r.host_match("fl/7")) == ["fl/7"]
    assert r.match_filters(["fl/7", "fl/1"]) == [["fl/7"], ["fl/1"]]
    node.drain_robustness_events()
    assert any(a.name == "router_compaction_failed"
               for a in node.alarms.get_alarms("activated"))
    assert node.metrics.val("overload.heal.flatten") == 1
    # once the backoff elapses the retry swaps, and the tables go over
    r.retry_compaction()
    assert r._compact_failures == 1
    r._compact_backoff_until = 0.0
    r.retry_compaction()
    deadline = time.time() + 10
    while (r._compacting or r._compact_failures) \
            and time.time() < deadline:
        time.sleep(0.01)
    assert r._compact_failures == 0 and len(calls) == 2
    assert r.automaton()[2] == epoch + 1 and helper.carries == 1
    st2 = helper.state(*r.automaton()[2:0:-1])
    assert st2.epoch == epoch + 1 and helper.rebuilds == 1
    assert r.match_filters(["fl/9"]) == [["fl/9"]]
    node.drain_robustness_events()
    assert not any(a.name == "router_compaction_failed"
                   for a in node.alarms.get_alarms("activated"))


# -- multi-loop: dead loop, dropped handoff, stalled owner -------------------


async def test_dead_loop_heal_closes_connections_and_fires_wills():
    async with broker_node(
            loops=2,
            matcher=MatcherConfig(device_min_filters=0)) as node:
        port = node_port(node)
        obs = TestClient("obs")          # first connect -> loop 0
        await obs.connect(port=port)
        await obs.subscribe("wills/#", qos=1)
        doomed = TestClient("doomed", will_flag=True, will_qos=1,
                            will_topic="wills/loop",
                            will_payload=b"loop died")
        await doomed.connect(port=port)  # second connect -> loop 1
        lg = node.loop_group
        assert node.listeners[0].loop_connections()[1] == 1
        lg.crash(1)
        deadline = time.time() + 5
        while lg.dead_peer_indices() == [] and time.time() < deadline:
            await asyncio.sleep(0.02)
        # the monitor's heal sweep: routes around the dead loop and
        # closes its connections so the will fires
        node.overload.tick(0.0)
        msg = await obs.recv()
        assert msg.topic == "wills/loop" and msg.payload == b"loop died"
        assert node.metrics.val("overload.heal.loop") == 1
        assert 1 in lg._dead
        assert any(a.name == "frontdoor_loop_1_dead"
                   for a in node.alarms.get_alarms("activated"))
        # the node still serves: publish/deliver through loop 0
        pub = TestClient("after")
        await pub.connect(port=port)
        await pub.publish("wills/after", payload=b"alive", qos=1)
        msg = await obs.recv()
        assert msg.payload == b"alive"
        await pub.close()
        await obs.close()
        await doomed.close()


async def test_xloop_handoff_drop_is_bounded_and_counted():
    """An injected handoff loss: the batch's fold waits at most
    XLOOP_JOIN_TIMEOUT, the lost groups are counted as orphaned, and
    the next batch delivers normally — the join never hangs."""
    async with broker_node(
            loops=2,
            matcher=MatcherConfig(device_min_filters=0)) as node:
        node.broker.XLOOP_JOIN_TIMEOUT = 0.5
        port = node_port(node)
        filler = TestClient("filler")    # -> loop 0
        await filler.connect(port=port)
        sub = TestClient("xsub")         # -> loop 1 (cross-loop)
        await sub.connect(port=port)
        await sub.subscribe("xh/t", qos=1)
        pub = TestClient("xpub")         # -> loop 0
        await pub.connect(port=port)
        with faults.injected("xloop.handoff", times=1):
            t0 = time.perf_counter()
            # the PUBACK waits on the bounded join, then arrives
            await pub.publish("xh/t", payload=b"lost", qos=1,
                              timeout=5.0)
            assert time.perf_counter() - t0 < 4.0
        assert node.metrics.val("delivery.xloop.orphaned") >= 1
        # the ring works again on the next batch
        await pub.publish("xh/t", payload=b"found", qos=1)
        msg = await sub.recv()
        assert msg.payload == b"found"
        for cli in (filler, sub, pub):
            await cli.close()


async def test_takeover_timeout_on_stalled_owner_loop():
    """Satellite: the bounded cm takeover wait's timeout arm. The
    owning loop is wedged (chaos stall), so the resume-takeover
    marshal expires; the client gets a FRESH session instead of a
    hung CONNECT, and the timeout is counted."""
    async with broker_node(loops=2) as node:
        node.cm.XLOOP_CALL_TIMEOUT = 0.4
        port = node_port(node)
        filler = TestClient("filler2")   # -> loop 0
        await filler.connect(port=port)
        victim = TestClient("dup", clean_start=False)  # -> loop 1
        ack = await victim.connect(port=port)
        assert ack.reason_code == 0
        node.loop_group.stall(1, 1.5)
        await asyncio.sleep(0.05)  # let the stall land on the loop
        again = TestClient("dup", clean_start=False)   # -> loop 0
        t0 = time.perf_counter()
        ack = await again.connect(port=port, timeout=5.0)
        assert time.perf_counter() - t0 < 1.2
        assert ack.reason_code == 0
        # the wedged owner's session could not be taken over: fresh
        # session, no session_present, timeout counted
        assert not ack.session_present
        assert node.metrics.val("overload.takeover.timeout") == 1
        # the fresh session works
        await again.subscribe("tk/t", qos=1)
        pub = TestClient("tkpub")
        await pub.connect(port=port)
        await pub.publish("tk/t", payload=b"fresh", qos=1)
        msg = await again.recv()
        assert msg.payload == b"fresh"
        await asyncio.sleep(1.3)  # let the stall drain before stop
        for cli in (filler, victim, again, pub):
            await cli.close()


async def test_keepalive_survives_owner_loop_stall():
    """Satellite: a stalled owning loop must not make keepalive kill
    a live client once it unwedges — the byte-delta check sees the
    traffic that queued during the stall."""
    async with broker_node(loops=2) as node:
        port = node_port(node)
        filler = TestClient("kfill")     # -> loop 0
        await filler.connect(port=port)
        cli = TestClient("kal", keepalive=1)  # -> loop 1
        await cli.connect(port=port)
        node.loop_group.stall(1, 1.8)    # > 1.5x the interval
        # traffic sent INTO the stall: queued by the kernel, read
        # when the loop unwedges — proof of life for the check
        await cli.send(__import__("emqx_tpu.mqtt.packet",
                                  fromlist=["Pingreq"]).Pingreq())
        await asyncio.sleep(2.2)
        assert node.cm.lookup_channel("kal") is not None
        await cli.ping()                 # still serviceable
        await cli.close()
        await filler.close()


# -- socket reset, ingress saturation ----------------------------------------


async def test_socket_reset_mid_flush_closes_cleanly_fires_will():
    async with broker_node() as node:
        port = node_port(node)
        obs = TestClient("robs")
        await obs.connect(port=port)
        await obs.subscribe("wills/reset", qos=1)
        vic = TestClient("rvic", will_flag=True, will_qos=1,
                         will_topic="wills/reset",
                         will_payload=b"reset")
        await vic.connect(port=port)
        await vic.subscribe("rs/t")
        # the next flush anywhere is the victim's delivery flush
        # (server-initiated publish: no other connection writes)
        with faults.injected("socket.reset", times=1):
            node.broker.publish(Message(topic="rs/t", payload=b"x"))
            deadline = time.time() + 5
            while node.cm.lookup_channel("rvic") is not None \
                    and time.time() < deadline:
                await asyncio.sleep(0.02)
        assert node.cm.lookup_channel("rvic") is None
        msg = await obs.recv()
        assert msg.payload == b"reset"  # abnormal close -> will
        # broker unharmed: obs still serves
        node.broker.publish(Message(topic="wills/reset",
                                    payload=b"after"))
        msg = await obs.recv()
        assert msg.payload == b"after"
        await obs.close()


async def test_ingress_saturation_sheds_publisher_after_bounded_wait():
    async with broker_node() as node:
        node.ingress.submit_wait_timeout = 0.3
        port = node_port(node)
        pub = TestClient("satpub")
        await pub.connect(port=port)
        with faults.injected("ingress.saturate", times=0):
            await pub.publish("sat/t", payload=b"x", qos=0)
            deadline = time.time() + 5
            while node.cm.lookup_channel("satpub") is not None \
                    and time.time() < deadline:
                await asyncio.sleep(0.02)
        assert node.cm.lookup_channel("satpub") is None
        assert node.metrics.val("overload.shed.ingress_timeout") == 1
        assert any(a.name == "ingress_saturated"
                   for a in node.alarms.get_alarms("activated"))
        # with the saturation gone the monitor clears the alarm
        node.overload.tick(0.0)
        assert not any(a.name == "ingress_saturated"
                       for a in node.alarms.get_alarms("activated"))
        await pub.close()


# -- overload state machine + shedding ---------------------------------------


def test_overload_levels_hysteresis_and_alarm():
    node = _device_node(overload=OverloadConfig(
        lag_warn_ms=50, lag_critical_ms=500, clear_ticks=2))
    ov = node.overload
    assert ov.tick(10.0) == OK
    assert ov.tick(80.0) == WARN
    assert node.metrics.val("overload.transitions") == 1
    alarms = {a.name: a for a in node.alarms.get_alarms("activated")}
    assert alarms["overload"].details["level"] == "warn"
    assert ov.tick(900.0) == CRITICAL
    assert ov.reject_connects()
    # downgrade needs clear_ticks consecutive clean samples
    assert ov.tick(0.0) == CRITICAL
    assert ov.tick(0.0) == OK
    assert not any(a.name == "overload"
                   for a in node.alarms.get_alarms("activated"))


def test_queue_depth_drives_level_and_ingress_pressure():
    node = _device_node(overload=OverloadConfig(
        queue_warn=2.0, queue_critical=4.0, clear_ticks=1))
    ing = node.ingress
    ov = node.overload
    hw = ing.queue_hiwater
    ing._pending.extend([(None, None)] * (hw * 4))
    assert ov.tick(0.0) == CRITICAL
    # critical divides the effective high-water mark: backpressure
    # engages at a fraction of the configured mark
    del ing._pending[hw:]
    assert ing.backlogged()  # hw items >= hw//4 under pressure
    del ing._pending[hw // 8:]
    assert ing.backlogged() is (hw // 8 >= max(1, hw // 4))
    ing._pending.clear()
    assert ov.tick(0.0) == OK
    assert not ing.backlogged()


def test_a_full_accumulator_of_a_healthy_node_stays_ok():
    """The monitor's queue ratio is taken against the mark the
    admission line uses (``2 × batch_size`` by default): the fullest a
    healthy node's accumulator gets, the mark less one and a reader's
    whole 64 KiB chunk of small PUBLISHes, reads under ``queue_warn``
    with the default guard, and no transition is counted."""
    node = _device_node()
    ing, ov = node.ingress, node.overload
    assert ing.queue_hiwater == ing._mark() == 2 * ing.batch_size
    full = ing.queue_hiwater - 1 + 234
    ing._pending.extend([(None, None)] * full)
    assert ing.backlogged()
    for _ in range(4):
        assert ov.tick(0.0) == OK
    assert ov.samples["ingress_hiwater"] == ing._mark()
    assert ov.samples["ingress_queue"] / ov.samples["ingress_hiwater"] \
        < 1.5 < ov.cfg.queue_warn
    assert node.metrics.val("overload.transitions") == 0
    # against batch_size, which the mark was, the same queue would warn
    assert full / ing.batch_size >= ov.cfg.queue_warn
    ing._pending.clear()


def test_warn_sheds_qos0_at_mqueue_pressure():
    node = _device_node()
    sess = Session("shed", broker=node.broker, max_mqueue_len=8,
                   mqueue_store_qos0=True)
    sess.connected = False
    for i in range(6):
        sess.enqueue(Message(topic="q/t", payload=b"%d" % i, qos=0))
    assert len(sess.mqueue) == 6
    node.overload.level = WARN
    sess.enqueue(Message(topic="q/t", payload=b"shed", qos=0))
    assert len(sess.mqueue) == 6  # dropped, not queued
    assert node.metrics.val("overload.shed.qos0") == 1
    # QoS1 still queues — the capacity shedding protects
    sess.enqueue(Message(topic="q/t", payload=b"keep", qos=1))
    assert len(sess.mqueue) == 7


async def test_critical_rejects_new_connects_server_busy():
    async with broker_node() as node:
        node.overload.level = CRITICAL
        v5 = TestClient("busy5", version=C.MQTT_V5)
        ack = await v5.connect(port=node_port(node))
        assert ack.reason_code == 0x89  # ServerBusy
        v3 = TestClient("busy3")
        ack = await v3.connect(port=node_port(node))
        assert ack.reason_code == 3     # compat: server unavailable
        assert node.metrics.val("overload.shed.connect") == 2
        node.overload.level = OK
        ok = TestClient("okc")
        ack = await ok.connect(port=node_port(node))
        assert ack.reason_code == 0
        await ok.close()
        for cli in (v5, v3):
            await cli.close()


def test_force_shutdown_policy_kills_oom_session():
    node = _device_node(overload=OverloadConfig(
        force_shutdown_queue_len=5))

    class Chan:
        def __init__(self, sess):
            self.session = sess
            self.client_id = sess.client_id
            self.kicked = False

        def kick(self, discard=False):
            self.kicked = True

    sess = Session("oom", broker=node.broker, max_mqueue_len=0,
                   mqueue_store_qos0=True)
    sess.connected = False
    for i in range(10):
        sess.enqueue(Message(topic="o/t", payload=b"%d" % i, qos=1))
    chan = Chan(sess)
    node.cm.register_channel("oom", chan)
    node.overload._sweep_force_shutdown()
    assert chan.kicked
    assert node.metrics.val("overload.force_shutdown") == 1
    assert node.cm.lookup_channel("oom") is None


def test_orphaned_counter_on_home_loop_gone_publish():
    """Satellite: the formerly-silent `return 0 # home loop gone`
    path now counts + logs the lost publish."""
    node = _device_node()

    class DeadLG:
        def on_home_thread(self):
            return False

        def post(self, idx, cb, *args):
            raise RuntimeError("loop closed")

    node.broker.loop_group = DeadLG()
    node.broker.ingress = None
    assert node.broker.publish(
        Message(topic="gone/t", payload=b"x")) == 0
    assert node.metrics.val("delivery.xloop.orphaned") == 1


# -- disabled-mode parity ----------------------------------------------------


def test_faults_disabled_sites_never_call_fire(monkeypatch):
    """The zero-cost-off pin: with nothing armed every site's guard
    is a dead branch — faults.fire is never reached."""
    def boom(point):
        raise AssertionError(f"fire({point!r}) called while disabled")

    monkeypatch.setattr(faults, "fire", boom)
    assert not faults.enabled
    node = _device_node()
    s = Sink()
    node.subscribe(s, "p/1")
    assert node.broker.publish_batch(
        [Message(topic="p/1", payload=b"x")]) == [1]
    assert len(s.got) == 1


async def _parity_workload(overload_cfg):
    """Mixed-QoS fan-out; returns (per-client wire tuples, delivery
    metric deltas) — the overload-on/off comparison payload."""
    async with broker_node(
            matcher=MatcherConfig(device_min_filters=0),
            overload=overload_cfg) as node:
        port = node_port(node)
        a = TestClient("pa")
        b = TestClient("pb", version=C.MQTT_V5)
        pub = TestClient("pp")
        for cli in (a, b, pub):
            await cli.connect(port=port)
        await a.subscribe("par/+", qos=1)
        await b.subscribe("par/t", qos=2)
        n = 0
        for i in range(3):
            await pub.publish("par/t", payload=b"m%d" % i, qos=1)
            n += 1
        await pub.publish("par/x", payload=b"x", qos=0)
        got = []
        for cli, want in ((a, n + 1), (b, n)):
            pkts = []
            for _ in range(want):
                p = await cli.recv()
                pkts.append((p.topic, bytes(p.payload), p.qos,
                             p.packet_id))
            pkts.sort(key=lambda t: t[1])
            got.append(pkts)
        metrics = {k: v for k, v in node.metrics.all().items()
                   if v and k.startswith(("messages.", "delivery.",
                                          "overload.", "breaker.",
                                          "faults."))}
        for cli in (a, b, pub):
            await cli.close()
        return got, metrics


async def test_overload_on_off_delivery_parity():
    """[overload] default-on in the OK state vs enabled=false: wire
    content and metric deltas identical — the robustness layer is
    invisible until something actually breaks."""
    on_wire, on_metrics = await _parity_workload(OverloadConfig())
    off_wire, off_metrics = await _parity_workload(
        OverloadConfig(enabled=False))
    assert on_wire == off_wire
    assert on_metrics == off_metrics  # no overload.*/breaker.* moved


# -- ctl surfaces ------------------------------------------------------------


def test_ctl_overload_and_faults_commands():
    import json

    node = _device_node()
    out = json.loads(node.ctl.run(["overload"]))
    assert out["enabled"] and out["level"] == "ok"
    assert out["breaker"]["state"] == "closed"
    assert node.ctl.run(["faults", "arm", "device.fetch:raise:2"]) \
        == "ok"
    info = json.loads(node.ctl.run(["faults"]))
    assert info["armed"]["device.fetch"]["action"] == "raise"
    assert node.ctl.run(["faults", "disarm", "device.fetch"]) == "ok"
    assert "unknown fault point" in node.ctl.run(
        ["faults", "arm", "nope"])
    assert node.ctl.run(["faults", "clear"]) == "ok"
    assert not faults.enabled


# -- device-loss recovery (devloss.py, docs/ROBUSTNESS.md) -------------------
#
# The contract: a LOST backend (every device call raises/hangs, not
# just one slow batch) is classified by the sentinel, the breaker
# enters REBUILDING, publishes ride the exact host oracle with zero
# lost or duplicated deliveries, all device-resident state rebuilds
# from host authority, the kernels re-warm off the hot path, and the
# half-open probe auto-closes the breaker — no process restart.


def _wait_for(cond, timeout=10.0, step=0.01):
    deadline = time.monotonic()
    deadline += timeout
    while time.monotonic() < deadline:
        if cond():
            return True
        time.sleep(step)
    return cond()


def _recovery_cfg(**over):
    kw = dict(breaker_failures=2, breaker_cooldown_s=30.0,
              rebuild_backoff_s=0.05, sentinel_timeout_s=1.0)
    kw.update(over)
    return OverloadConfig(**kw)


def test_device_lost_point_is_persistent():
    """The device.lost contract vs the times-bounded walk/fetch
    points: armed times=0, EVERY device call raises until disarmed
    (the backend is gone, not glitching)."""
    faults.arm("device.lost", times=0)
    for _ in range(5):
        with pytest.raises(faults.FaultInjected):
            faults.fire("device.lost")
    assert faults.enabled
    assert faults.disarm("device.lost")
    assert faults.fire("device.lost") is False
    # config knob validation rides along (closed schema)
    with pytest.raises(ValueError):
        OverloadConfig(rebuild_backoff_s=0.0)
    with pytest.raises(ValueError):
        OverloadConfig(sentinel_timeout_s=-1.0)


def test_device_loss_classifies_rebuilds_and_auto_closes():
    """The tentpole scenario at broker level: a lost backend trips
    the breaker, the sentinel classifies LOST (not transient), the
    breaker enters REBUILDING (cooldown_s=30 — any recovery must
    come through the rebuild, not the cooldown probe), rebuild
    attempts fail while the backend is still gone, and once it
    returns the rebuilt tables + re-warmed kernels admit the probe
    that closes the breaker. Deliveries are exact throughout."""
    node = _device_node(overload=_recovery_cfg())
    s = Sink()
    node.subscribe(s, "dl/+")
    node.subscribe(s, "dl/#")
    br = node.broker.breaker
    rec = br.recovery
    assert rec is not None
    # warm the device path so the loss is a regression, not a boot
    assert node.broker.publish_batch(
        [Message(topic="dl/t", payload=b"warm")]) == [2]
    epoch_before = node.router._rebuilds
    faults.arm("device.lost", times=0)
    try:
        # every batch during the outage host-matches exactly
        for i in range(3):
            assert node.broker.publish_batch(
                [Message(topic="dl/t", payload=b"out%d" % i)]) == [2]
        assert br.state in (DeviceBreaker.OPEN,
                            DeviceBreaker.REBUILDING)
        # classification runs off the hot path; the sentinel cannot
        # answer -> REBUILDING, device matching suspended
        assert _wait_for(lambda: br.state == DeviceBreaker.REBUILDING)
        assert rec.last_classification == "lost"
        # the recovery thread suspends a few lines after the state flips
        assert _wait_for(node.router.device_suspended)
        assert any(a.name == "device_path_lost"
                   for a in node.alarms.get_alarms("activated"))
        # rebuild attempts fail while the backend is still gone
        assert _wait_for(lambda: rec.rebuild_failures >= 1)
        assert node.metrics.val("breaker.rebuild.failures") >= 1
        # publishes still serve, host-only, mid-rebuild
        assert node.broker.publish_batch(
            [Message(topic="dl/t", payload=b"mid")]) == [2]
    finally:
        faults.disarm("device.lost")
    # the backend is back: the next attempt rebuilds + re-warms and
    # arms the half-open window (NOT the 30s cooldown clock)
    assert _wait_for(lambda: br.state == DeviceBreaker.HALF_OPEN)
    assert rec.rebuilds == 1
    assert node.metrics.val("breaker.rebuilds") == 1
    assert rec.last_rebuild_s is not None
    assert not node.router.device_suspended()
    assert node.router._rebuilds > epoch_before  # fresh tables
    # the probe batch rides the rebuilt tables and closes the breaker
    assert node.broker.publish_batch(
        [Message(topic="dl/t", payload=b"probe")]) == [2]
    assert br.state == DeviceBreaker.CLOSED
    assert not any(a.name in ("device_path_lost",
                              "device_path_breaker")
                   for a in node.alarms.get_alarms("activated"))
    # zero lost, zero duplicated across the whole episode
    assert sorted(p for _f, _t, p in s.got) == sorted(
        2 * [b"warm", b"out0", b"out1", b"out2", b"mid", b"probe"])
    # ctl surfaces the recovery fields
    import json as _json
    out = _json.loads(node.ctl.run(["overload"]))
    assert out["breaker"]["state"] == "closed"
    assert out["breaker"]["rebuilds"] == 1
    assert out["breaker"]["classification"] == "lost"
    assert out["breaker"]["last_rebuild_s"] is not None


def test_device_loss_double_loss_mid_rebuild():
    """The device dies AGAIN mid-recovery: after the lost
    classification, the first attempts fail against the still-dead
    backend; then the rebuild itself succeeds but the warmup phase
    dies (device.fetch) — the attempt counts as failed and retries,
    and only a fully clean rebuild+warm admits the probe."""
    node = _device_node(overload=_recovery_cfg(breaker_failures=1))
    s = Sink()
    node.subscribe(s, "dd/1")
    assert node.broker.publish_batch(
        [Message(topic="dd/1", payload=b"warm")]) == [1]
    br = node.broker.breaker
    rec = br.recovery
    faults.arm("device.lost", times=0)
    try:
        assert node.broker.publish_batch(
            [Message(topic="dd/1", payload=b"out")]) == [1]
        assert _wait_for(lambda: rec.rebuild_failures >= 1)
        # the backend returns... but dies again during kernel warmup
        faults.arm("device.fetch", action="raise", times=1)
    finally:
        faults.disarm("device.lost")
    assert _wait_for(lambda: br.state == DeviceBreaker.HALF_OPEN)
    assert rec.rebuild_failures >= 2  # dead-backend + mid-warm death
    assert rec.rebuilds == 1
    assert node.broker.publish_batch(
        [Message(topic="dd/1", payload=b"probe")]) == [1]
    assert br.state == DeviceBreaker.CLOSED
    assert sorted(p for _f, _t, p in s.got) == \
        [b"out", b"probe", b"warm"]


def test_half_open_single_probe_invariant():
    """Satellite pin: concurrent batches arriving during the
    half-open window must not all ride the device — exactly ONE
    probe is admitted; and a stale pre-trip success can neither
    close an OPEN breaker nor preempt a rebuild."""
    import threading

    from emqx_tpu.metrics import Metrics
    br = DeviceBreaker(Metrics(), failures=1, cooldown_s=0.05)
    br.record_failure()
    assert br.state == DeviceBreaker.OPEN
    # a pre-trip in-flight batch completing late must NOT close it
    br.record_success()
    assert br.state == DeviceBreaker.OPEN
    time.sleep(0.06)
    results = []
    barrier = threading.Barrier(8)

    def probe():
        barrier.wait()
        results.append(br.allow_device())

    ts = [threading.Thread(target=probe) for _ in range(8)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert sum(results) == 1  # exactly one probe admitted
    assert br.state == DeviceBreaker.HALF_OPEN
    assert br.allow_device() is False  # probe still in flight
    br.record_success()
    assert br.state == DeviceBreaker.CLOSED
    # REBUILDING admits no probe even past any cooldown, ignores
    # stale successes, and only rebuild_complete re-arms the window
    br2 = DeviceBreaker(Metrics(), failures=1, cooldown_s=0.01)
    br2.record_failure()
    assert br2.enter_rebuilding()
    time.sleep(0.03)
    assert br2.allow_device() is False
    br2.record_success()
    assert br2.state == DeviceBreaker.REBUILDING
    br2.rebuild_complete()
    assert br2.state == DeviceBreaker.HALF_OPEN
    assert br2.allow_device() is True
    br2.record_success()
    assert br2.state == DeviceBreaker.CLOSED


def test_breaker_fallback_never_rides_device():
    """While the breaker is OPEN or REBUILDING the oracle fallback
    must not re-enter the device plane through any seam — with a
    truly lost backend the fallback itself would raise. Pin it by
    making every router device entry explode."""
    node = _device_node(overload=_recovery_cfg(breaker_failures=1))
    s = Sink()
    node.subscribe(s, "ho/1")
    assert node.broker.publish_batch(
        [Message(topic="ho/1", payload=b"warm")]) == [1]

    def boom(*a, **k):
        raise AssertionError("device path entered during fallback")

    faults.arm("device.lost", times=0)
    try:
        assert node.broker.publish_batch(
            [Message(topic="ho/1", payload=b"trip")]) == [1]
        assert _wait_for(
            lambda: node.broker.breaker.state
            == DeviceBreaker.REBUILDING)
        # the recovery thread suspends a few lines after the state flips
        assert _wait_for(node.router.device_suspended)
        node.router.match_dispatch = boom
        node.router.match_ids = boom
        node.router._dispatch_sharded = boom
        # breaker fallback, host regime probe, retained-style lookups
        assert node.broker.publish_batch(
            [Message(topic="ho/1", payload=b"fb")]) == [1]
        assert [r.dest for r in node.router.match_routes("ho/1")] \
            == [node.broker.node]
    finally:
        # restore the seams BEFORE the backend "returns": the
        # background recovery warms through them the moment the
        # fault disarms
        for name in ("match_dispatch", "match_ids",
                     "_dispatch_sharded"):
            node.router.__dict__.pop(name, None)
        faults.disarm("device.lost")
    assert sorted(p for _f, _t, p in s.got) == \
        [b"fb", b"trip", b"warm"]


def test_rebuild_under_route_churn_parity():
    """Route ops arriving DURING the rebuild window complete and the
    rebuilt automaton matches the host oracle byte-exactly on the
    churned filter set (the PR 7 freeze protocol carries them into
    the fresh tables + next delta generation)."""
    node = _device_node(overload=_recovery_cfg(breaker_failures=1))
    sinks = {f"rc/{i}": Sink() for i in range(6)}
    for flt, s in sinks.items():
        node.subscribe(s, flt)
    assert node.broker.publish_batch(
        [Message(topic="rc/0", payload=b"warm")]) == [1]
    br = node.broker.breaker
    rec = br.recovery
    faults.arm("device.lost", times=0)
    late = Sink()
    try:
        assert node.broker.publish_batch(
            [Message(topic="rc/0", payload=b"trip")]) == [1]
        assert _wait_for(lambda: rec.rebuild_failures >= 1)
        # stretch the successful attempt's flatten so churn lands in
        # the freeze window (stall = sleep then proceed normally)
        faults.arm("compaction.flatten", action="stall", times=1,
                   delay_ms=300.0)
    finally:
        faults.disarm("device.lost")
    # churn while the rebuild flatten runs off-lock: adds, deletes,
    # and a brand-new wildcard — all must land in the fresh tables
    t0 = time.monotonic()
    node.subscribe(late, "rc/late/+")
    node.subscribe(late, "rc/0")
    node.broker.unsubscribe(sinks["rc/5"], "rc/5")
    churn_s = time.monotonic() - t0
    assert _wait_for(lambda: br.state == DeviceBreaker.HALF_OPEN,
                     timeout=15.0)
    assert churn_s < 5.0  # route ops did not ride the whole flatten
    assert node.broker.publish_batch(
        [Message(topic="rc/0", payload=b"probe")]) == [2]
    assert br.state == DeviceBreaker.CLOSED
    # parity: device match vs host oracle over the churned set
    topics = [f"rc/{i}" for i in range(6)] + ["rc/late/x", "rc/none"]
    dev = node.router.match_filters(topics)
    host = node.router.match_filters_host(topics)
    assert [sorted(r) for r in dev] == [sorted(r) for r in host]
    assert sorted(dev[0]) == ["rc/0"]
    assert dev[5] == []                     # deleted mid-rebuild
    assert dev[6] == ["rc/late/+"]          # added mid-rebuild
    # the mid-rebuild subscriber actually receives
    assert node.broker.publish_batch(
        [Message(topic="rc/late/x", payload=b"new")]) == [1]
    assert late.got[-1][2] == b"new"


def test_breaker_rebuild_off_is_legacy_open_forever():
    """[overload] breaker_rebuild = false: no recovery manager — a
    lost backend leaves the breaker cycling OPEN exactly as PR 8
    shipped it (the pre-recovery behavior, selectable)."""
    node = _device_node(overload=_recovery_cfg(
        breaker_rebuild=False, breaker_failures=1,
        breaker_cooldown_s=0.1))
    s = Sink()
    node.subscribe(s, "lg/1")
    br = node.broker.breaker
    assert br.recovery is None
    assert node.broker.publish_batch(
        [Message(topic="lg/1", payload=b"warm")]) == [1]
    faults.arm("device.lost", times=0)
    try:
        assert node.broker.publish_batch(
            [Message(topic="lg/1", payload=b"t")]) == [1]
        assert br.state == DeviceBreaker.OPEN
        time.sleep(0.12)
        # the cooldown probe re-executes against the dead backend,
        # fails, and re-opens — forever, by design with rebuild off
        assert node.broker.publish_batch(
            [Message(topic="lg/1", payload=b"p")]) == [1]
        assert br.state == DeviceBreaker.OPEN
        assert br.state != DeviceBreaker.REBUILDING
    finally:
        faults.disarm("device.lost")
    time.sleep(0.12)
    assert node.broker.publish_batch(
        [Message(topic="lg/1", payload=b"ok")]) == [1]
    assert br.state == DeviceBreaker.CLOSED
    assert len(s.got) == 4


async def test_device_loss_qos1_live_zero_lost_or_duplicated(tmp_path):
    """The acceptance scenario over real sockets: kill the device
    mid-stream under DURABLE QoS1 traffic (journal flushing from the
    very fetch seam that is failing), keep publishing through
    fallback -> rebuild -> close, and assert every payload was
    delivered exactly once — zero lost, zero duplicated, no process
    restart."""
    from emqx_tpu.durability import DurabilityConfig
    async with broker_node(
            matcher=MatcherConfig(device_min_filters=0),
            durability=DurabilityConfig(
                enabled=True, dir=str(tmp_path / "dur"), fsync=False),
            overload=_recovery_cfg(breaker_failures=1,
                                   sentinel_timeout_s=0.5)) as node:
        port = node_port(node)
        sub = TestClient("dlsub")
        pub = TestClient("dlpub")
        await sub.connect(port=port)
        await pub.connect(port=port)
        await sub.subscribe("dl/t", qos=1)
        br = node.broker.breaker
        sent = []

        async def send(i):
            payload = b"m%03d" % i
            await pub.publish("dl/t", payload=payload, qos=1)
            sent.append(payload)

        for i in range(5):          # warm device regime
            await send(i)
        faults.arm("device.lost", times=0)
        try:
            for i in range(5, 15):  # the outage window
                await send(i)
            assert _wait_for(
                lambda: br.state == DeviceBreaker.REBUILDING,
                timeout=10.0)
            for i in range(15, 20):  # mid-rebuild traffic
                await send(i)
        finally:
            faults.disarm("device.lost")
        # keep publishing until a probe closes the breaker
        i = 20
        deadline = time.monotonic() + 20.0
        while br.state != DeviceBreaker.CLOSED \
                and time.monotonic() < deadline:
            await send(i)
            i += 1
            await asyncio.sleep(0.05)
        assert br.state == DeviceBreaker.CLOSED
        for j in range(i, i + 3):   # post-recovery device traffic
            await send(j)
        got = []
        for _ in sent:
            got.append(bytes((await sub.recv(timeout=10.0)).payload))
        assert sorted(got) == sorted(sent)  # exact, no loss, no dup
        with pytest.raises(asyncio.TimeoutError):
            await sub.recv(timeout=0.3)     # and nothing extra
        assert node.metrics.val("breaker.rebuilds") == 1
        await sub.close()
        await pub.close()
