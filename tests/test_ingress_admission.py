"""The ingress admission line (``IngressBatcher.admit``): how a read
loop is admitted to the ingress queue when the queue stands at its
mark. A bare batcher over a stub broker and stub readers, on the CPU
with no device: the queue is filled and taken by hand, so every rule
of the line is seen alone. Each rule is held in single-loop mode and
with the multi-loop front door's lock armed (``bind_multiloop``), the
last tests with a reader on a peer loop's thread and over sockets."""

import asyncio
import sys
import threading
import types

import pytest

from emqx_tpu import faults
from emqx_tpu import ingress as ingress_mod
from emqx_tpu.ingress import IngressBatcher
from emqx_tpu.metrics import Metrics
from emqx_tpu.node import Node
from tests.indie_mqtt import PUBACK, IndieClient, build_publish

MARK = 16


class _Broker:
    """What the line reads of a broker: its counters and the gate."""

    tracing = None

    def __init__(self, timed=True):
        self.metrics = Metrics()
        self.telemetry = types.SimpleNamespace(enabled=timed)


def _batcher(mode, mark=MARK, timed=True, batch_size=10 ** 6):
    ing = IngressBatcher(_Broker(timed), batch_size=batch_size,
                         queue_hiwater=mark)
    if mode == "multi":
        ing.broker.metrics.enable_threadsafe()
        ing.bind_multiloop(types.SimpleNamespace(
            home=asyncio.get_running_loop()))
    return ing


def _fill(ing, n):
    """``n`` messages land in the queue (no flush: nothing is armed)."""
    ing._pending.extend([(None, None)] * n)


def _take(ing, n):
    """A flush takes ``n`` off the queue."""
    return len(ing._take_pending(cap=n))


class _Readers:
    """Stub read loops: each asks once for its weight, notes the
    answer in arrival order of the answers, and adds ``adds`` (default
    its weight) to the queue when admitted."""

    def __init__(self, ing):
        self.ing = ing
        self.answers = []  # (name, admitted) as the readers resumed
        self.tasks = {}

    def start(self, name, weight, adds=None):
        async def read():
            ok = await self.ing.admit(weight)
            self.answers.append((name, ok))
            if ok:
                _fill(self.ing, weight if adds is None else adds)
        self.tasks[name] = asyncio.get_running_loop().create_task(read())
        return self.tasks[name]

    def names(self):
        return [name for name, _ok in self.answers]


async def _settle(n=4):
    for _ in range(n):
        await asyncio.sleep(0)


def _counts(ing):
    m = ing.broker.metrics
    return m.val("ingress.parks"), m.val("ingress.wakes")


def _invariant(ing):
    """Whenever the queue, with the room woken readers hold, is under
    the mark, the line is empty."""
    return (len(ing._pending) + ing._granted >= ing._mark()
            or ing.waiting() == 0)


MODES = pytest.mark.parametrize("mode", ["single", "multi"])


@MODES
async def test_under_the_mark_with_nobody_waiting_is_admitted_at_once(mode):
    ing = _batcher(mode)
    _fill(ing, MARK - 1)
    assert await ing.admit(500) is True  # whatever it holds
    assert ing.waiting() == 0 and ing._timer is None
    assert _counts(ing) == (0, 0)  # it never parked


@MODES
async def test_the_default_mark_is_twice_batch_size(mode):
    """With no ``queue_hiwater`` given the readers are admitted up to
    ``2 × batch_size`` pending, the size trigger beside a batch in the
    pipeline, and park there; a wake a park."""
    ing = _batcher(mode, mark=0, batch_size=8)
    assert ing.queue_hiwater == ing._mark() == 16
    ing._inflight = 1  # a batch in the pipeline: nothing here flushes
    assert ing._trigger() == 16
    r = _Readers(ing)
    for name in "abc":  # 5 + 5 + 5 = 15: under the mark, all at once
        r.start(name, 5)
    await _settle()
    assert r.names() == ["a", "b", "c"] and len(ing._pending) == 15
    assert _counts(ing) == (0, 0)
    r.start("d", 5)  # 15 < 16: the head goes whatever its weight
    await _settle()
    assert len(ing._pending) == 20 and ing.backlogged()
    r.start("e", 5)
    r.start("f", 5)
    await _settle()
    assert ing.waiting() == 2 and _counts(ing) == (2, 0)
    assert _take(ing, 20) == 20  # the grown batch leaves as one
    await _settle(8)
    assert r.names() == list("abcdef") and len(ing._pending) == 10
    assert ing.waiting() == 0 and ing._granted == 0
    assert _counts(ing) == (2, 2) and _invariant(ing)
    ing.set_pressure(4)  # critical overload divides the new mark
    assert ing._mark() == ing._trigger() == 4


@MODES
async def test_grants_go_out_in_the_order_of_arrival(mode):
    ing = _batcher(mode)
    _fill(ing, MARK)
    r = _Readers(ing)
    for name in "abcdefgh":
        r.start(name, 2, adds=0)
    await _settle()
    assert ing.waiting() == 8 and r.answers == []
    assert _take(ing, MARK) == MARK
    await _settle(12)  # each pass hands back room: the next are woken
    assert r.answers == [(name, True) for name in "abcdefgh"]
    assert ing.waiting() == 0 and ing._granted == 0
    assert _invariant(ing)


@MODES
async def test_a_take_wakes_the_readers_that_fit_and_no_other(mode):
    ing = _batcher(mode)
    _fill(ing, MARK)
    r = _Readers(ing)
    for i in range(10):
        r.start(i, 4)
    await _settle()
    assert _counts(ing) == (10, 0)
    # room for 12 of the mark's 16: three readers of 4 fit under it
    assert _take(ing, 12) == 12
    assert ing._granted == 12 and ing.waiting() == 7
    await _settle()
    assert r.names() == [0, 1, 2]
    assert len(ing._pending) == MARK and ing._granted == 0
    assert _counts(ing) == (10, 3)
    # the queue is back at the mark: nobody else was woken
    assert r.names() == [0, 1, 2] and ing.waiting() == 7
    # and the rest go as room comes, each woken exactly once
    while ing.waiting():
        _take(ing, 8)
        await _settle()
        assert _invariant(ing)
    await _settle()
    assert r.names() == list(range(10))
    assert _counts(ing) == (10, 10)


@MODES
async def test_nobody_barges_while_anyone_waits(mode):
    ing = _batcher(mode)
    _fill(ing, MARK)
    r = _Readers(ing)
    r.start("first", 4)
    await _settle()
    _take(ing, MARK)  # the queue is empty now, "first" is granted
    assert ing._granted == 4
    # room for more, but a granted reader has not resumed: a newcomer
    # of 12 still fits under the mark, one of 13 behind it does not
    r.start("second", 12)
    r.start("third", 1)
    await _settle()
    assert r.names() == ["first", "second"]
    assert ing.waiting() == 1 and len(ing._pending) == MARK
    # "fourth" finds "third" waiting: it joins behind it
    _take(ing, 1)
    r.start("fourth", 1)
    await _settle()
    assert r.names() == ["first", "second", "third"]
    _take(ing, 1)
    await _settle()
    assert r.names() == ["first", "second", "third", "fourth"]


@MODES
async def test_the_head_goes_under_the_mark_whatever_its_weight(mode):
    ing = _batcher(mode, mark=4)  # a divided mark under `critical`
    _fill(ing, 4)
    r = _Readers(ing)
    r.start("fat", 200)
    r.start("thin", 1)
    await _settle()
    _take(ing, 1)  # one under the mark is enough for the head
    await _settle()
    assert r.names() == ["fat"] and len(ing._pending) == 203
    assert ing.waiting() == 1  # and nothing is left for "thin" yet
    _take(ing, 203)
    await _settle()
    assert r.names() == ["fat", "thin"]


@MODES
async def test_a_restored_mark_admits_what_it_holds_without_traffic(mode):
    ing = _batcher(mode)
    ing.submit_wait_timeout = 30.0
    ing.set_pressure(4)  # `critical`: the mark is 4
    _fill(ing, 4)
    r = _Readers(ing)
    for i in range(5):
        r.start(i, 4, adds=0)
    await _settle()
    assert ing.waiting() == 5 and ing.backlogged()
    ing.set_pressure(1)  # back to `ok`: 4 of 16, no take, no arrival
    assert _invariant(ing)
    await _settle(12)
    assert r.names() == [0, 1, 2, 3, 4]  # long before the time-out
    assert ing.waiting() == 0 and ing._timer is None


@MODES
async def test_one_timer_for_a_line_of_a_thousand(mode, monkeypatch):
    def never(*_a, **_kw):
        raise AssertionError("asyncio.wait_for on the backpressure path")

    monkeypatch.setattr(asyncio, "wait_for", never)
    monkeypatch.setattr(asyncio, "timeout", never, raising=False)
    loop = asyncio.get_running_loop()
    ing = _batcher(mode)
    armed = []  # the timers the batcher armed (a sleep arms its own)
    inner = loop.call_at

    def call_at(when, callback, *args, **kw):
        handle = inner(when, callback, *args, **kw)
        if getattr(callback, "__self__", None) is ing:
            armed.append(handle)
        return handle

    monkeypatch.setattr(loop, "call_at", call_at)
    ing.submit_wait_timeout = 30.0
    _fill(ing, MARK)
    r = _Readers(ing)
    for i in range(1000):
        r.start(i, 4)
    await _settle()
    assert ing.waiting() == 1000 and len(armed) == 1
    assert armed[0].when() == ing._line[0].deadline
    for task in r.tasks.values():
        task.cancel()
    await _settle()
    assert ing.waiting() == 0 and ing._timer is None
    assert len(armed) == 1 and armed[0].cancelled()
    assert _counts(ing) == (1000, 1000)
    # a line whose head's deadline passes: it is shed, and only it
    del armed[:]
    ing.submit_wait_timeout = 0.3
    r = _Readers(ing)
    r.start("head", 4)
    await asyncio.sleep(0.15)
    for i in range(9):
        r.start(i, 4)
    await _settle()
    assert ing.waiting() == 10 and len(armed) == 1
    head_at, last_at = ing._line[0].deadline, ing._line[-1].deadline
    assert ing._line[1].deadline - head_at >= 0.15
    await asyncio.sleep(head_at - loop.time() + 0.05)
    assert r.answers == [("head", False)]
    assert ing.waiting() == 9
    # the one timer moved on to the next reader's deadline
    assert len(armed) == 2 and ing._timer is armed[1]
    assert armed[1].when() == ing._line[0].deadline
    # a take still serves the line from its head
    _take(ing, MARK)
    await _settle()
    assert r.answers[1:] == [(i, True) for i in range(4)]
    assert len(armed) == 2
    await asyncio.sleep(last_at - loop.time() + 0.05)
    assert [ok for _n, ok in r.answers[5:]] == [False] * 5
    assert ing.waiting() == 0 and ing._timer is None
    assert _counts(ing) == (1010, 1010)


@MODES
async def test_no_time_out_arms_no_timer(mode, monkeypatch):
    loop = asyncio.get_running_loop()
    ing = _batcher(mode)
    inner = loop.call_at

    def call_at(when, callback, *args, **kw):
        assert getattr(callback, "__self__", None) is not ing
        return inner(when, callback, *args, **kw)

    monkeypatch.setattr(loop, "call_at", call_at)
    assert ing.submit_wait_timeout == 0.0
    _fill(ing, MARK)
    r = _Readers(ing)
    r.start("a", 1)
    await _settle()
    assert ing.waiting() == 1 and ing._timer is None
    _take(ing, 1)
    await _settle()
    assert r.answers == [("a", True)]


@MODES
async def test_a_cancelled_waiter_leaves_and_its_grant_passes_on(mode):
    ing = _batcher(mode)
    ing.submit_wait_timeout = 30.0
    _fill(ing, MARK)
    r = _Readers(ing)
    for name in "abcd":
        r.start(name, MARK)
    await _settle()
    # cancelled where it stood: it leaves the line
    r.tasks["b"].cancel()
    await _settle()
    assert ing.waiting() == 3
    # cancelled with its grant in hand: the room passes to the next
    _take(ing, MARK)
    assert ing._granted == MARK and ing.waiting() == 2
    r.tasks["a"].cancel()
    await _settle()
    assert r.answers == [("c", True)]
    # cancelled in the very tick of the grant that finds it dead
    _take(ing, MARK)
    r.tasks["d"].cancel()
    await _settle()
    assert r.answers == [("c", True)]
    assert ing.waiting() == 0 and ing._granted == 0
    assert ing._timer is None  # the line is empty: no timer is left
    assert _counts(ing) == (4, 4)


@MODES
async def test_a_granted_reader_that_adds_nothing_holds_no_room(mode):
    ing = _batcher(mode)
    _fill(ing, MARK)
    r = _Readers(ing)
    r.start("idle", MARK, adds=0)  # every PUBLISH of it was refused
    r.start("next", 1)
    await _settle()
    _take(ing, MARK)
    assert ing._granted == MARK and ing.waiting() == 1
    await _settle()
    # no take, no arrival: the room "idle" handed back went to "next"
    assert r.names() == ["idle", "next"]
    assert ing._granted == 0 and len(ing._pending) == 1


@MODES
async def test_the_saturate_fault_reads_full_and_sheds_on_time(mode):
    ing = _batcher(mode)
    ing.submit_wait_timeout = 0.05
    r = _Readers(ing)
    with faults.injected("ingress.saturate", times=0):
        r.start("a", 1)  # an empty queue, yet nothing is under "full"
        await _settle()
        assert ing.waiting() == 1 and ing.backlogged()
        _take(ing, 0)
        await _settle()
        assert ing.waiting() == 1
        await asyncio.sleep(0.08)
    assert r.answers == [("a", False)]
    assert not ing.backlogged()


@MODES
async def test_with_telemetry_off_nothing_is_counted(mode):
    ing = _batcher(mode, timed=False)
    _fill(ing, MARK)
    r = _Readers(ing)
    r.start("a", 1)
    await _settle()
    _take(ing, 1)
    await _settle()
    assert r.answers == [("a", True)]
    m = ing.broker.metrics
    assert _counts(ing) == (0, 0) and m.val("ingress.park.ns") == 0


async def test_a_take_wakes_a_waiter_on_a_peer_loop():
    ing = _batcher("multi")
    ing.submit_wait_timeout = 30.0
    peer = asyncio.new_event_loop()
    thread = threading.Thread(target=peer.run_forever, daemon=True)
    thread.start()
    try:
        _fill(ing, MARK)
        where = []

        async def read(name, weight):
            ok = await ing.admit(weight)
            where.append((name, ok, threading.get_ident()))
            return ok

        far = asyncio.run_coroutine_threadsafe(read("far", 4), peer)
        while ing.waiting() < 1:
            await asyncio.sleep(0.001)
        near = asyncio.get_running_loop().create_task(read("near", 4))
        await _settle()
        # the peer's arrival asked the home loop for the one timer
        assert ing.waiting() == 2 and ing._timer is not None
        _take(ing, MARK)  # on the home loop; "far" is woken on its own
        assert await asyncio.wrap_future(far) is True
        assert await near is True
        ran_on = {name: tid for name, _ok, tid in where}
        assert ran_on["far"] == thread.ident != ran_on["near"]
        assert ing.waiting() == 0 and ing._granted == 0
        assert _counts(ing) == (2, 2)
        # a waiter whose loop has gone is passed over, not waited for
        _fill(ing, MARK)
        gone = asyncio.run_coroutine_threadsafe(read("gone", 4), peer)
        while ing.waiting() < 1:
            await asyncio.sleep(0.001)
        peer.call_soon_threadsafe(peer.stop)
        thread.join(5.0)
        last = asyncio.get_running_loop().create_task(read("last", 4))
        await _settle()
        _take(ing, 2 * MARK)
        assert await last is True
        assert ing._granted == 0 and not gone.done()
    finally:
        if thread.is_alive():
            peer.call_soon_threadsafe(peer.stop)
            thread.join(5.0)
        peer.close()


async def test_readers_on_four_peer_loops_lose_no_grant():
    """More threads than the line has room for, a shortened switch
    interval: every reader is admitted, each park is woken once, no
    room is left granted and the queue stays within the mark, a
    reader's weight and one reader a peer loop."""
    ing = _batcher("multi")
    ing.submit_wait_timeout = 30.0
    peers = [asyncio.new_event_loop() for _ in range(4)]
    threads = [threading.Thread(target=lp.run_forever, daemon=True)
               for lp in peers]
    admitted = [0]
    lock = threading.Lock()
    # a grant is spent when its reader resumes: between that and its
    # submit a reader on another thread is in neither count, so each
    # peer loop can stand one reader (of at most 4) over the bound
    bound = MARK - 1 + 4 + 4 * len(peers)

    async def reader(weight):
        for _ in range(20):
            assert await ing.admit(weight) is True
            with ing._plock:
                _fill(ing, weight)
                over = len(ing._pending) - bound
            assert over <= 0, over
            with lock:
                admitted[0] += 1
            await asyncio.sleep(0)

    was = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        for t in threads:
            t.start()
        jobs = [asyncio.run_coroutine_threadsafe(reader(1 + i % 4), lp)
                for lp in peers for i in range(25)]
        deadline = asyncio.get_running_loop().time() + 30.0
        while not all(j.done() for j in jobs):
            assert asyncio.get_running_loop().time() < deadline
            _take(ing, 8)  # the home loop's flushes
            await asyncio.sleep(0)
        for j in jobs:
            j.result(timeout=1.0)
    finally:
        sys.setswitchinterval(was)
        for lp in peers:
            lp.call_soon_threadsafe(lp.stop)
        for t in threads:
            t.join(5.0)
            assert not t.is_alive()
        for lp in peers:
            lp.close()
    assert admitted[0] == 4 * 25 * 20
    assert ing.waiting() == 0 and ing._granted == 0
    parks, wakes = _counts(ing)
    assert parks == wakes > 0


# -- served: every connection opens with a burst ready ---------------------

CONNS = 256
BURST = 4  # three QoS 0 publishes and the QoS 1 fence


async def test_256_connections_at_once_stay_within_the_mark():
    node = Node(boot_listeners=False)
    lst = node.add_listener(host="127.0.0.1", port=0)
    await node.start()
    ing = node.ingress
    mark = ing.queue_hiwater = 8  # lowered: two bursts fill the queue
    pubs = [IndieClient(f"pub-{i}") for i in range(CONNS)]
    made = [0]
    waiter = ingress_mod._Waiter

    class Counted(waiter):
        __slots__ = ()

        def __init__(self, *args):
            super().__init__(*args)
            made[0] += 1

    ingress_mod._Waiter = Counted
    try:
        for c in pubs:
            await c.connect(port=lst.port)

        async def burst(c):
            pid = c.next_pkt_id()
            c.writer.write(
                b"".join(build_publish(f"dev/{c.client_id}/state", b"x")
                         for _ in range(BURST - 1))
                + build_publish(f"dev/{c.client_id}/state", b"f",
                                qos=1, pkt_id=pid))
            await c.writer.drain()
            ack = await asyncio.wait_for(c.acks.get(), 120.0)
            assert ack.ptype == PUBACK and ack.pkt_id == pid

        await asyncio.gather(*(burst(c) for c in pubs))
        assert ing.submitted == CONNS * BURST
        # a reader goes while the queue with its grants is under the
        # mark: the mark less one and the last reader's burst at most
        assert ing.max_queue <= mark - 1 + BURST
        assert made[0] > 0, "no reader met the mark"
        assert ing.waiting() == 0 and ing._granted == 0
        assert node.metrics.val("ingress.wakes") \
            == node.metrics.val("ingress.parks") == made[0]
        assert node.metrics.val("overload.shed.ingress_timeout") == 0
    finally:
        ingress_mod._Waiter = waiter
        for c in pubs:
            await c.close()
        await node.stop()
