"""Ingress batching: per-tick PUBLISH aggregation into one device
call, with QoS acks deferred to the batch flush (SURVEY §2.2 row 1;
accumulator semantics after src/emqx_batch.erl:1-91)."""

import asyncio
import contextlib

import pytest

from emqx_tpu import faults
from emqx_tpu.broker import Broker
from emqx_tpu.ingress import IngressBatcher
from emqx_tpu.node import Node
from emqx_tpu.types import Message
from helpers import PathGate, device_node
from mqtt_client import TestClient


class Rec:
    def __init__(self, cid="r"):
        self.client_id = cid
        self.got = []

    def deliver(self, f, m):
        self.got.append(m.topic)


async def test_tick_aggregation_one_device_call():
    b = Broker()
    s = Rec()
    b.subscribe(s, "t/+")
    bat = IngressBatcher(b, batch_size=100)
    futs = [bat.submit(Message(topic=f"t/{i}")) for i in range(5)]
    assert all(f is not None for f in futs)
    assert bat.flushes == 0  # nothing flushed inside this tick
    await asyncio.sleep(0)   # next loop iteration -> call_soon flush
    counts = [await f for f in futs]
    assert counts == [1] * 5
    assert bat.flushes == 1  # 5 messages, ONE publish_batch
    assert s.got == [f"t/{i}" for i in range(5)]


async def test_size_triggered_flush():
    b = Broker()
    s = Rec()
    b.subscribe(s, "x")
    bat = IngressBatcher(b, batch_size=3)
    f1 = bat.submit(Message(topic="x"))
    f2 = bat.submit(Message(topic="x"))
    f3 = bat.submit(Message(topic="x"))  # cap hit: flush inline
    assert f3.done() and f1.done() and f2.done()
    assert bat.flushes == 1 and bat.max_batch == 3
    assert await f1 == 1 and await f2 == 1 and await f3 == 1


def test_submit_without_loop_falls_back():
    b = Broker()
    bat = IngressBatcher(b)
    assert bat.submit(Message(topic="t")) is None  # sync caller path


async def test_live_batched_acks_all_qos():
    """Real sockets end to end: QoS0/1/2 publishes flow through the
    batcher (Node default), acks complete at flush, deliveries
    arrive."""
    n = Node(boot_listeners=False)
    lst = n.add_listener(port=0)
    await n.start()
    try:
        sub = TestClient("sub", version=5)
        await sub.connect(port=lst.port)
        await sub.subscribe("a/#", qos=2)
        pub = TestClient("pub", version=5)
        await pub.connect(port=lst.port)
        await pub.publish("a/zero", b"0", qos=0)
        await pub.publish("a/one", b"1", qos=1)    # PUBACK deferred
        await pub.publish("a/two", b"2", qos=2)    # PUBREC deferred
        topics = sorted([(await sub.recv()).topic for _ in range(3)])
        assert topics == ["a/one", "a/two", "a/zero"]
        assert n.ingress.submitted == 3
        assert n.ingress.flushes >= 1
        await pub.disconnect()
        await sub.disconnect()
    finally:
        await n.stop()


async def test_live_concurrent_publishers_batch_together():
    """Publishes from many connections in the same tick share one
    flush (the whole point of ingress batching)."""
    n = Node(boot_listeners=False, batch_linger_ms=5.0)
    lst = n.add_listener(port=0)
    await n.start()
    try:
        sub = TestClient("sub")
        await sub.connect(port=lst.port)
        await sub.subscribe("c/+")
        pubs = []
        for i in range(8):
            p = TestClient(f"p{i}")
            await p.connect(port=lst.port)
            pubs.append(p)
        # fire all QoS1 publishes concurrently: acks gate on the flush
        await asyncio.gather(*(
            p.publish(f"c/{i}", b"x", qos=1)
            for i, p in enumerate(pubs)))
        got = sorted([(await sub.recv()).topic for _ in range(8)])
        assert got == sorted(f"c/{i}" for i in range(8))
        assert n.ingress.submitted == 8
        # linger collects across connections: strictly fewer flushes
        # than messages
        assert n.ingress.flushes < 8
        for p in pubs:
            await p.disconnect()
        await sub.disconnect()
    finally:
        await n.stop()


async def test_ack_order_preserved_with_error_acks():
    """MQTT-4.6.0: a rejected PUBLISH's ack must not overtake the
    deferred ack of an earlier accepted one."""
    import asyncio as aio

    from emqx_tpu.mqtt import constants as C
    from emqx_tpu.mqtt.packet import Publish

    n = Node(boot_listeners=False, batch_linger_ms=20.0)
    lst = n.add_listener(port=0)
    await n.start()
    try:
        c = TestClient("c", version=5)
        await c.connect(port=lst.port)
        # pid=7 QoS2 accepted (PUBREC defers to flush); then pid=7
        # again -> PACKET_IDENTIFIER_IN_USE error PUBREC, which must
        # queue BEHIND the first ack despite being ready instantly
        await c.send(Publish(topic="q/t", qos=2, packet_id=7))
        await c.send(Publish(topic="q/t", qos=2, packet_id=7))
        a1 = await aio.wait_for(c.acks.get(), 5)
        a2 = await aio.wait_for(c.acks.get(), 5)
        assert a1.type == C.PUBREC and a2.type == C.PUBREC
        assert a1.reason_code in (0x00, 0x10)   # no-matching-subs ok
        assert a2.reason_code == 0x91           # identifier in use
        c.writer.close()
    finally:
        await n.stop()


async def test_flush_failure_sends_no_ack():
    """A failed device batch must NOT be acked — the QoS1 client's
    retransmit is the recovery path (at-least-once)."""
    import asyncio as aio

    from emqx_tpu.mqtt.packet import Publish

    n = Node(boot_listeners=False)
    lst = n.add_listener(port=0)
    await n.start()
    try:
        c = TestClient("c", version=4)
        await c.connect(port=lst.port)

        def boom(msgs, defer_host=False, span=None):
            raise RuntimeError("device gone")

        orig = n.broker.publish_begin
        n.broker.publish_begin = boom
        await c.send(Publish(topic="a/b", qos=1, packet_id=3))
        with __import__("pytest").raises(aio.TimeoutError):
            await aio.wait_for(c.acks.get(), 0.3)
        # broker recovers -> the retransmit is acked
        n.broker.publish_begin = orig
        await c.send(Publish(topic="a/b", qos=1, packet_id=3, dup=True))
        ack = await aio.wait_for(c.acks.get(), 5)
        assert ack.packet_id == 3
        c.writer.close()
    finally:
        await n.stop()


async def test_flush_error_resolves_futures():
    class Boom(Broker):
        def publish_begin(self, msgs, defer_host=False, span=None):
            raise RuntimeError("device gone")

    bat = IngressBatcher(Boom(), batch_size=2)
    f1 = bat.submit(Message(topic="t"))
    f2 = bat.submit(Message(topic="t"))
    assert f1.done() and isinstance(f1.exception(), RuntimeError)
    assert f2.done() and isinstance(f2.exception(), RuntimeError)


# -- pipelined (three-phase) flushes ---------------------------------


def _dev_broker(**kw):
    from emqx_tpu.router import MatcherConfig
    kw.setdefault("device_min_filters", 0)
    return Broker(config=MatcherConfig(**kw))


async def test_device_path_flush_is_async():
    """Above the device threshold the flush pipeline runs begin →
    (executor) fetch → finish; futures resolve with correct counts."""
    b = _dev_broker()
    s = Rec()
    b.subscribe(s, "t/+")
    bat = IngressBatcher(b, batch_size=100)
    futs = [bat.submit(Message(topic=f"t/{i}")) for i in range(5)]
    await asyncio.sleep(0)  # tick flush -> async completion
    counts = [await f for f in futs]
    assert counts == [1] * 5
    assert sorted(s.got) == sorted(f"t/{i}" for i in range(5))


async def test_ordered_delivery_across_batches():
    """Batch N+1 must not deliver before batch N even when its fetch
    finishes first (per-publisher in-order semantics)."""
    import time

    b = _dev_broker()
    s = Rec()
    b.subscribe(s, "o/+")
    orig_fetch = b.publish_fetch
    delays = {"o/first": 0.15}

    def slow_fetch(pb):
        d = max((delays.get(m.topic, 0.0) for _, m in pb.live),
                default=0.0)
        if d:
            time.sleep(d)
        orig_fetch(pb)

    b.publish_fetch = slow_fetch
    bat = IngressBatcher(b, batch_size=1, max_inflight=4)
    f1 = bat.submit(Message(topic="o/first"))
    f2 = bat.submit(Message(topic="o/second"))
    await asyncio.gather(f1, f2)
    assert s.got == ["o/first", "o/second"]


async def test_inflight_cap_accumulates_bigger_batches():
    """With all pipeline slots busy, arrivals accumulate and flush as
    one bigger batch when a slot frees (backpressure = batch growth)."""
    import time

    b = _dev_broker()
    s = Rec()
    b.subscribe(s, "p/+")
    orig_fetch = b.publish_fetch

    def slow_fetch(pb):
        time.sleep(0.05)
        orig_fetch(pb)

    b.publish_fetch = slow_fetch
    bat = IngressBatcher(b, batch_size=1, max_inflight=1)
    futs = [bat.submit(Message(topic=f"p/{i}")) for i in range(10)]
    await asyncio.gather(*futs)
    assert sorted(s.got) == sorted(f"p/{i}" for i in range(10))
    assert bat.flushes < 10  # accumulation happened
    assert bat.max_batch > 1


async def test_node_stop_drains_inflight():
    n = Node(boot_listeners=False)
    await n.start()
    s = Rec()
    n.broker.subscribe(s, "d/+")
    n.ingress.submit(Message(topic="d/1"), want_result=False)
    await n.stop()
    assert s.got == ["d/1"]


async def test_host_path_batch_ordered_behind_device_batch():
    """A flush that would take the host path (threshold crossed
    downward mid-pipeline) must still deliver AFTER the in-flight
    device batch — begin defers host routing behind the chain."""
    import time

    from emqx_tpu.router import MatcherConfig

    b = Broker(config=MatcherConfig(device_min_filters=2))
    s1, s2 = Rec("r1"), Rec("r2")
    b.subscribe(s1, "h/a")
    b.subscribe(s2, "h/b")  # 2 filters -> device path
    orig_fetch = b.publish_fetch

    def slow_fetch(pb):
        time.sleep(0.1)
        orig_fetch(pb)

    b.publish_fetch = slow_fetch
    bat = IngressBatcher(b, batch_size=1, max_inflight=4)
    f1 = bat.submit(Message(topic="h/a"))      # device, slow fetch
    await asyncio.sleep(0)
    b.unsubscribe(s2, "h/b")  # drop below threshold -> host path next
    f2 = bat.submit(Message(topic="h/a"))      # host path, instant
    await asyncio.gather(f1, f2)
    assert len(s1.got) == 2  # both delivered, in submission order
    # f2 resolved only after f1 (chained), so ordering held
    assert await f1 == 1 and await f2 == 1


async def test_drain_waits_for_inflight_before_flushing_queue():
    """drain() must complete in-flight batches BEFORE publishing the
    messages that queued behind them."""
    import time

    b = _dev_broker()
    s = Rec()
    b.subscribe(s, "z/+")
    orig_fetch = b.publish_fetch

    def slow_fetch(pb):
        time.sleep(0.1)
        orig_fetch(pb)

    b.publish_fetch = slow_fetch
    bat = IngressBatcher(b, batch_size=1, max_inflight=1)
    bat.submit(Message(topic="z/old"), want_result=False)
    await asyncio.sleep(0)      # old batch enters the pipeline
    bat.submit(Message(topic="z/new"), want_result=False)  # queued
    await bat.drain()
    assert s.got == ["z/old", "z/new"]


async def test_flush_during_completion_cannot_reorder_or_double_resolve():
    """Regression (ISSUE 3 satellite): _complete's slot-free flush
    used to run RE-ENTRANTLY inside the finishing batch's completion,
    before that batch's own futures resolved — a flush that resolves
    synchronously there (e.g. publish_begin raising) completed NEWER
    publishes' futures ahead of the older batch's, breaking ack
    order. The flush must be scheduled for after resolution."""
    import time

    b = _dev_broker()
    s = Rec()
    b.subscribe(s, "r/+")
    orig_fetch = b.publish_fetch

    def slow_fetch(pb):
        time.sleep(0.05)
        orig_fetch(pb)

    b.publish_fetch = slow_fetch
    orig_begin = b.publish_begin
    calls = [0]

    def begin(msgs, defer_host=False, span=None):
        calls[0] += 1
        if calls[0] == 2:
            raise RuntimeError("boom")  # batch B fails at begin
        return orig_begin(msgs, defer_host=defer_host, span=span)

    b.publish_begin = begin
    bat = IngressBatcher(b, batch_size=100, max_inflight=1)
    order = []
    fa = bat.submit(Message(topic="r/a"))
    fa.add_done_callback(lambda f: order.append("A"))
    await asyncio.sleep(0)        # batch A enters the pipeline
    fb = bat.submit(Message(topic="r/b"))   # queues behind A
    fb.add_done_callback(lambda f: order.append("B"))
    await asyncio.wait({fa, fb})
    await asyncio.sleep(0)        # drain done-callbacks
    assert await fa == 1
    assert isinstance(fb.exception(), RuntimeError)
    # A's future resolved before B's, and each exactly once
    assert order == ["A", "B"]


# -- a tick's flush waits for the landing (ISSUE 41) ------------------
#
# While a batch stands on the device path (between its publish_begin
# and the return of its fetch, ``_on_path``), a flush short of
# the size trigger begins nothing: what it holds leaves with the flush
# that the landed batch's completion schedules. The size trigger
# (``_trigger``) is ``batch_size`` with the pipeline empty and
# ``2 × batch_size`` (or the mark, where that is lower) while a begun
# batch has not completed; at it the next slot opens beside the
# batches in flight, up to ``max_inflight``.


async def _path_node(name, **kw):
    """(node, subscriber, PathGate) with the dispatch compiled and the
    fetch pool alive before the gate goes in."""
    node = await device_node(name, **kw)
    s = Rec()
    node.broker.subscribe(s, "w/+")
    assert await node.broker.ingress.submit(Message(topic="w/warm")) == 1
    await node.broker.ingress.drain()
    s.got.clear()
    return node, s, PathGate(node)


async def _ticks(n=3):
    for _ in range(n):
        await asyncio.sleep(0)


async def test_a_tick_beside_an_occupied_path_waits_for_the_landing():
    node, s, p = await _path_node("held@test", batch_size=16)
    try:
        ing = p.ing
        acked = []

        def submit(i):
            f = ing.submit(Message(topic=f"w/{i}"))
            f.add_done_callback(lambda _f, i=i: acked.append(i))
            return f

        futs = [submit(0)]
        await p.on_the_path()
        assert p.began == [["w/0"]]
        for tick in range(3):           # three ticks of two arrivals
            futs += [submit(1 + 2 * tick), submit(2 + 2 * tick)]
            await _ticks()
        # nothing was begun beside the occupied path, nothing acked
        assert p.began == [["w/0"]] and ing._inflight == 1
        assert len(ing._pending) == 6 and acked == []
        # one flush was armed (behind the first arrival) and held
        assert p.held == p.counted() == 1
        p.land()
        assert await asyncio.gather(*futs) == [1] * 7
        await _ticks()
        # the held arrivals left as ONE batch, in arrival order
        assert p.began == [["w/0"], [f"w/{i}" for i in range(1, 7)]]
        assert s.got == [f"w/{i}" for i in range(7)]
        assert acked == list(range(7))
        assert ing._on_path == 0 and ing._inflight == 0
        assert p.held == p.counted() == 1
    finally:
        await node.stop()


async def test_twice_batch_size_pending_begins_beside_an_occupied_path():
    node, s, p = await _path_node("size@test", batch_size=4)
    try:
        ing = p.ing
        assert ing.max_inflight == 4 and ing.queue_hiwater == 8
        futs = [ing.submit(Message(topic="w/0"))]
        await p.on_the_path()
        k = 1
        for depth in (2, 3, 4):
            # a tick's pair is held, and so is batch_size pending ...
            for n in (2, 4, 6):
                futs += [ing.submit(Message(topic=f"w/{k + n - 2 + i}"))
                         for i in range(2)]
                await _ticks()
                assert ing._on_path == depth - 1
                assert len(ing._pending) == n
            # ... and at twice batch_size the batch begins at once
            futs += [ing.submit(Message(topic=f"w/{k + 6 + i}"))
                     for i in range(2)]
            assert ing._on_path == ing._inflight == depth
            assert not ing._pending
            assert p.began[-1] == [f"w/{k + i}" for i in range(8)]
            k += 8
        # every slot busy: the accumulator stands at the mark, as ever
        futs += [ing.submit(Message(topic=f"w/{k + i}"))
                 for i in range(8)]
        await _ticks()
        assert ing._on_path == 4 and len(ing._pending) == 8
        assert ing.backlogged() and len(p.began) == 4
        # held by the rule three times; the full pipeline is not its
        assert p.held == p.counted() == 3
        p.land()
        assert await asyncio.gather(*futs) == [1] * len(futs)
        assert s.got == [f"w/{i}" for i in range(k + 8)]
        assert ing._on_path == 0 and ing._inflight == 0
        # three batches of 8 beside the path and the backlog's
        assert p.grown() == p.counted("grown") == 4
    finally:
        await node.stop()


@pytest.mark.parametrize("where", ["on_the_path", "landed"])
async def test_a_batch_grows_while_a_batch_is_in_the_pipeline(where):
    """Arrivals past ``batch_size`` and short of twice it begin
    nothing while a begun batch has not completed, be it on the device
    path or landed with its tail not done, and leave as one batch at
    that completion; with arrivals stopped nothing strands."""
    node, s, p = await _path_node(f"grown-{where}@test", batch_size=4)
    try:
        ing = p.ing
        p.hold_tails()
        futs = [ing.submit(Message(topic="w/0"))]
        await p.on_the_path()
        futs += [ing.submit(Message(topic=f"w/{i}")) for i in (1, 2)]
        await _ticks()
        if where == "landed":
            p.land()
            await p.landed()
        for i in range(3, 8):           # past batch_size = 4, one a tick
            futs.append(ing.submit(Message(topic=f"w/{i}")))
            await _ticks()
            assert p.began == [["w/0"]] and ing._inflight == 1
            assert len(ing._pending) == i
        assert not any(f.done() for f in futs[1:])
        p.land()
        p.finish()
        assert await asyncio.gather(*futs) == [1] * 8
        await _ticks()
        assert p.began == [["w/0"], [f"w/{i}" for i in range(1, 8)]]
        assert s.got == [f"w/{i}" for i in range(8)]
        assert not ing._pending
        assert ing._on_path == 0 and ing._inflight == 0
        assert p.grown() == p.counted("grown") == 1
    finally:
        await node.stop()


async def test_beside_a_landed_batch_twice_batch_size_opens_a_slot():
    """The trigger reads the pipeline (``_inflight``), not the path:
    beside a batch that landed with its tail not done, ``batch_size``
    pending begins nothing and twice it begins a batch at once."""
    node, s, p = await _path_node("landed-size@test", batch_size=4)
    try:
        ing = p.ing
        p.hold_tails()
        futs = [ing.submit(Message(topic="w/0")),
                ing.submit(Message(topic="w/1"))]
        await p.on_the_path()
        futs.append(ing.submit(Message(topic="w/2")))
        await _ticks()
        p.land()
        await p.landed()
        futs += [ing.submit(Message(topic=f"w/{i}")) for i in range(3, 8)]
        await _ticks()
        assert ing._trigger() == 8 and len(ing._pending) == 6
        assert len(p.began) == 1
        futs += [ing.submit(Message(topic=f"w/{i}")) for i in (8, 9)]
        assert not ing._pending and ing._inflight == 2
        assert p.began[1] == [f"w/{i}" for i in range(2, 10)]
        p.finish()
        assert await asyncio.gather(*futs) == [1] * 10
        assert s.got == [f"w/{i}" for i in range(10)]
        assert ing._on_path == 0 and ing._inflight == 0
        assert p.grown() == p.counted("grown") == 1
    finally:
        await node.stop()


async def test_an_empty_pipeline_triggers_at_batch_size():
    node, s, p = await _path_node("empty@test", batch_size=4)
    try:
        ing = p.ing
        assert ing._inflight == 0 and ing._trigger() == 4
        futs = [ing.submit(Message(topic=f"w/{i}")) for i in range(4)]
        # the fourth began the batch inline, no tick between
        assert p.began == [[f"w/{i}" for i in range(4)]]
        assert ing._inflight == 1 and not ing._pending
        assert ing._trigger() == 8
        p.land()
        assert await asyncio.gather(*futs) == [1] * 4
        assert ing._trigger() == 4
        assert p.grown() == p.counted("grown") == 0
    finally:
        await node.stop()


@pytest.mark.parametrize("mark", [3, 6, 8, 100])
async def test_an_explicit_mark_under_twice_batch_size_is_the_trigger(mark):
    """``queue_hiwater`` is honoured as given: beside a batch in the
    pipeline the size trigger is the mark where that lies under
    ``2 × batch_size`` (the accumulator cannot pass it), else twice
    ``batch_size``; with the pipeline empty it is ``batch_size``."""
    node, s, p = await _path_node(f"mark{mark}@test", batch_size=4)
    try:
        ing = p.ing
        ing.queue_hiwater = mark
        want = min(8, mark)
        assert ing._trigger() == 4
        futs = [ing.submit(Message(topic="w/0"))]
        await p.on_the_path()
        assert ing._trigger() == want
        for i in range(1, want):
            futs.append(ing.submit(Message(topic=f"w/{i}")))
            await _ticks()
            assert len(ing._pending) == i and len(p.began) == 1
        futs.append(ing.submit(Message(topic=f"w/{want}")))
        assert not ing._pending and ing._inflight == 2
        assert p.began[1] == [f"w/{i}" for i in range(1, want + 1)]
        p.land()
        assert await asyncio.gather(*futs) == [1] * (want + 1)
        assert s.got == [f"w/{i}" for i in range(want + 1)]
        assert p.grown() == p.counted("grown") == int(want > 4)
    finally:
        await node.stop()


def _fetch_raises(p):
    fetch = p.node.broker.publish_fetch

    def boom(pb):
        p.node.broker.publish_fetch = fetch     # the first alone
        assert p.gate.wait(30)
        raise ValueError("boom")
    p.node.broker.publish_fetch = boom
    return contextlib.nullcontext()


def _executor_dies(p):
    return faults.injected("executor.death", times=1)


def _breaker_turns_to_host(p):
    return faults.injected("device.fetch", times=1)


@pytest.mark.parametrize("how", [_fetch_raises, _executor_dies,
                                 _breaker_turns_to_host])
async def test_a_held_flush_is_released_whatever_the_landing(how):
    """The landing that releases a held flush may be a fetch that
    raised, one that met a dead executor and was run again, or one
    the breaker turned to the host path."""
    node, s, p = await _path_node(f"{how.__name__}@test", batch_size=16)
    try:
        ing = p.ing
        with how(p):
            first = ing.submit(Message(topic="w/0"))
            await p.on_the_path()
            held = [ing.submit(Message(topic=f"w/{i}"))
                    for i in range(1, 4)]
            await _ticks()
            assert p.began == [["w/0"]] and len(ing._pending) == 3
            p.land()
            assert await asyncio.gather(*held) == [1, 1, 1]
        if how is _fetch_raises:
            assert isinstance(first.exception(), ValueError)
            assert s.got == ["w/1", "w/2", "w/3"]
        else:
            assert await first == 1
            assert s.got == ["w/0", "w/1", "w/2", "w/3"]
        if how is _executor_dies:
            assert node.metrics.val("overload.heal.executor") == 1
        if how is _breaker_turns_to_host:
            assert node.metrics.val("breaker.failures") == 1
        assert p.began == [["w/0"], ["w/1", "w/2", "w/3"]]
        assert p.held == p.counted() == 1
        assert ing._on_path == 0 and ing._inflight == 0
    finally:
        await node.stop()


async def test_at_critical_overload_the_mark_is_the_size_trigger():
    """Under ``_pressure_div`` > 1 the mark lies under ``batch_size``
    and the accumulator cannot pass it, so the mark is the size
    trigger: a handful short of it is held, at the mark a batch begins
    beside an occupied path, an overloaded node keeps its depth, and
    the take a landing schedules grants the parked readers."""
    node, s, p = await _path_node("parked@test", batch_size=16)
    try:
        ing = p.ing
        ing.set_pressure(8)     # critical: 2 × batch_size = 32 → 4
        assert ing._mark() == 4 < ing.batch_size == ing._trigger()
        futs = [ing.submit(Message(topic="w/0"))]
        await p.on_the_path()
        assert ing._trigger() == 4      # the divided mark
        k = 1
        for depth in (2, 3, 4):
            futs += [ing.submit(Message(topic=f"w/{k + i}"))
                     for i in range(2)]
            await _ticks()
            assert ing._on_path == depth - 1 and len(ing._pending) == 2
            futs += [ing.submit(Message(topic=f"w/{k + 2 + i}"))
                     for i in range(2)]
            assert ing._on_path == ing._inflight == depth
            assert not ing._pending
            assert p.began[-1] == [f"w/{k + i}" for i in range(4)]
            k += 4
        # every slot busy: the accumulator stands at the mark and a
        # reader parks; neither is the rule's
        futs += [ing.submit(Message(topic=f"w/{k + i}"))
                 for i in range(4)]
        await _ticks()
        assert ing.backlogged() and len(ing._pending) == 4
        assert len(p.began) == 4

        async def reader():
            assert await ing.admit(2) is True
            return [ing.submit(Message(topic=f"w/{k + 4 + i}"))
                    for i in range(2)]

        r = asyncio.get_running_loop().create_task(reader())
        await _ticks()
        assert ing.waiting() == 1 and not r.done()
        assert p.held == p.counted() == 3
        p.land()
        futs += await asyncio.wait_for(r, 10)
        assert await asyncio.gather(*futs) == [1] * (k + 6)
        assert s.got == [f"w/{i}" for i in range(k + 6)]
        assert p.began[4] == [f"w/{k + i}" for i in range(4)]
        assert ing.waiting() == 0 and ing._granted == 0
        assert ing._on_path == 0 and ing._inflight == 0
    finally:
        await node.stop()


async def test_the_rule_needs_no_telemetry():
    """``_on_path`` moves with telemetry off, and the rule with it;
    its counter is telemetry's and stays 0."""
    from emqx_tpu.telemetry import TelemetryConfig

    node, s, p = await _path_node(
        "dark@test", batch_size=16,
        telemetry=TelemetryConfig(enabled=False))
    try:
        ing = p.ing
        futs = [ing.submit(Message(topic="w/0"))]
        await p.on_the_path()
        futs += [ing.submit(Message(topic=f"w/{i}")) for i in (1, 2)]
        await _ticks()
        assert ing._on_path == 1 and len(ing._pending) == 2
        assert p.began == [["w/0"]] and p.held == 1
        p.land()
        assert await asyncio.gather(*futs) == [1] * 3
        assert p.began == [["w/0"], ["w/1", "w/2"]]
        assert s.got == ["w/0", "w/1", "w/2"]
        assert ing._on_path == 0 and p.counted() == 0
        # a batch grows with telemetry off too, and counts nothing
        p.gate.clear()
        futs = [ing.submit(Message(topic="w/3"))]
        await p.on_the_path()
        futs += [ing.submit(Message(topic=f"w/{i}")) for i in range(4, 24)]
        await _ticks()
        assert len(ing._pending) == 20 > ing.batch_size
        p.land()
        assert await asyncio.gather(*futs) == [1] * 21
        assert p.began[-1] == [f"w/{i}" for i in range(4, 24)]
        assert p.grown() == 1 and p.counted("grown") == 0
    finally:
        await node.stop()


async def test_a_free_path_flushes_every_tick():
    """With no batch on the path the policy is the tick's, to the
    batch: nothing is held and no message waits for a timer."""
    node, s, p = await _path_node("free@test", batch_size=16)
    try:
        p.land()                        # fetches return at once
        for i in range(4):
            assert await p.ing.submit(Message(topic=f"w/{i}")) == 1
        assert p.began == [[f"w/{i}"] for i in range(4)]
        assert p.held == p.counted() == 0
    finally:
        await node.stop()
