"""A node whose clients each hold their own connection (benchmark cell
``p2p_2k.flood`` at a size a test can hold): 64 publisher sockets each
on its own topic, 64 subscriber sockets each on its own filter, bursts
of three QoS 0 publishes and a QoS 1 fence over plain MQTT, beside a
seeded ``mixed_tree`` population in an in-process sink. Every socket's
and the sink's deliveries are held to ``benchmark/reference.py``'s
plain trie; the read loops' parks in the ingress admission line are
held to the three counters that count them (``ingress.parks``,
``ingress.wakes``, ``ingress.park.ns``); and a node that is to hold sockets takes the
descriptors its host allows at start. Runs on the CPU backend; the
chip's run is the cell."""

import asyncio
import collections
import importlib.util
import logging
import os
import resource
import sys

import pytest

from emqx_tpu import ingress as ingress_mod
from emqx_tpu import vm
from emqx_tpu.node import Node
from emqx_tpu.telemetry import TelemetryConfig
from tests.indie_mqtt import PUBACK, IndieClient, build_publish

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_BENCH = os.path.join(_ROOT, "benchmark")

PAIRS = 64          # publisher i, topic i and subscriber i are a pair
BURST = 4           # three QoS 0 publishes and the QoS 1 fence
SECTOR = 32         # pool positions a publisher: it walks them all
EVERY = 16          # every 16th position goes to the resident tree
POPULATION = {"kind": "mixed_tree", "filters": 4000, "levels": 5,
              "words_per_level": 12,
              "mix": {"literal": 0.6, "plus": 0.25, "hash": 0.15}}
LAW = {"law": "interleave", "every": EVERY, "pool": PAIRS * SECTOR,
       "main": {"law": "own_topic", "owners": PAIRS,
                "topic": "dev/{i}/state"},
       "background": {"law": "zipf_levels", "a": 1.3, "depth": [2, 5]}}
SEED = 2147483999
HIWATER = 8         # lowered: two publishers' bursts fill the queue


def _bench_module(kind, name):
    spec = importlib.util.spec_from_file_location(
        f"_p2p_node_{kind}_{name}", os.path.join(_BENCH, kind, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _deployment():
    """-> (filters, pool, reference): the benchmark's own population
    and topic laws at the test's size."""
    sys.path.insert(0, _BENCH)
    try:
        pop = _bench_module("populations", "mixed_tree")
        filters, vocab = pop.build(POPULATION, SEED)
        pool = _bench_module("topic_laws", "interleave").pool(
            LAW, vocab, SEED)
    finally:
        sys.path.remove(_BENCH)
    return filters, pool, _bench_module("", "reference")


class _Sink:
    def __init__(self):
        self.got = collections.Counter()

    def deliver(self, topic_filter, msg):
        self.got[(topic_filter, msg.topic, bytes(msg.payload))] += 1


class _Parks:
    """The admission line counted from outside the program: a waiter
    made inside a call of ``IngressBatcher.admit`` is a read loop that
    parked, that call's return its resumption."""

    def __init__(self, ingress):
        self.entered = self.left = 0
        self._parked = set()  # the tasks whose call made a waiter
        self._inner = ingress.admit
        self._waiter = ingress_mod._Waiter
        parks = self

        class Counted(self._waiter):
            __slots__ = ()

            def __init__(self, *args):
                super().__init__(*args)
                parks.entered += 1
                parks._parked.add(asyncio.current_task())

        ingress_mod._Waiter = Counted
        ingress.admit = self

    def restore(self):
        ingress_mod._Waiter = self._waiter

    async def __call__(self, weight):
        try:
            return await self._inner(weight)
        finally:
            task = asyncio.current_task()
            if task in self._parked:
                self._parked.discard(task)
                self.left += 1


async def _serve(telemetry: bool) -> dict:
    filters, pool, reference = _deployment()
    node = Node(boot_listeners=False,
                telemetry=TelemetryConfig(enabled=telemetry))
    lst = node.add_listener(host="127.0.0.1", port=0)
    sink = _Sink()
    for f in filters:
        node.broker.subscribe(sink, f)
    await node.start()
    node.ingress.queue_hiwater = HIWATER
    parks = _Parks(node.ingress)
    subs = [IndieClient(f"sub-{i}") for i in range(PAIRS)]
    pubs = [IndieClient(f"pub-{i}") for i in range(PAIRS)]
    # what each publisher sends: its sector, a message id in the payload
    sent = [[(pool[p * SECTOR + s], b"%03d:%03d" % (p, s))
             for s in range(SECTOR)] for p in range(PAIRS)]
    try:
        assert node.router.use_device_now()
        for i, c in enumerate(subs):
            await c.connect(port=lst.port)
            await c.subscribe(f"dev/{i}/#")
        for c in pubs:
            await c.connect(port=lst.port)
        m0 = node.metrics.all()

        async def publisher(p):
            c = pubs[p]
            for lo in range(0, SECTOR, BURST):
                burst = sent[p][lo:lo + BURST]
                pid = c.next_pkt_id()
                c.writer.write(b"".join(
                    build_publish(t, payload) for t, payload in burst[:-1])
                    + build_publish(*burst[-1], qos=1, pkt_id=pid))
                await c.writer.drain()
                ack = await asyncio.wait_for(c.acks.get(), 240.0)
                assert ack.ptype == PUBACK and ack.pkt_id == pid

        await asyncio.gather(*(publisher(p) for p in range(PAIRS)))
        # the plain reference: which socket's filter and which resident
        # filters match each message
        socket_trie = reference.Trie()
        for i in range(PAIRS):
            socket_trie.insert(f"dev/{i}/#")
        resident = reference.Trie()
        for f in filters:
            resident.insert(f)
        want_sockets = [collections.Counter() for _ in range(PAIRS)]
        want_sink = collections.Counter()
        for msgs in sent:
            for t, payload in msgs:
                for f in socket_trie.match(t):
                    want_sockets[int(f.split("/")[1])][(t, payload)] += 1
                for f in resident.match(t):
                    want_sink[(f, t, payload)] += 1
        # a PUBACK follows its batch's delivery tail, so everything is
        # on its way to the sockets by now
        got_sockets = []
        for c, want in zip(subs, want_sockets):
            got = collections.Counter()
            for _ in range(sum(want.values())):
                p = await c.recv(timeout=60.0)
                got[(p.topic, bytes(p.payload))] += 1
                assert p.qos == 0
            got_sockets.append(got)
        await asyncio.sleep(0.3)  # a surplus delivery would come now
        surplus = sum(c.inbox.qsize() for c in subs)
        m1 = node.metrics.all()
        return {
            "sockets": got_sockets, "want_sockets": want_sockets,
            "sink": sink.got, "want_sink": want_sink, "surplus": surplus,
            "counters": {k: m1[k] - m0.get(k, 0) for k in m1},
            "entered": parks.entered, "left": parks.left,
            "backlogged": node.ingress.backlogged(),
            "waiters": node.ingress.waiting(),
            "granted": node.ingress._granted,
            "max_queue": node.ingress.max_queue,
            "published": sum(len(m) for m in sent),
        }
    finally:
        parks.restore()
        for c in subs + pubs:
            await c.close()
        await node.stop()


@pytest.fixture(scope="module")
def served():
    return asyncio.run(_serve(telemetry=True))


@pytest.fixture(scope="module")
def served_untimed():
    return asyncio.run(_serve(telemetry=False))


def test_every_socket_gets_its_own_devices_messages_and_no_other(served):
    assert served["published"] == PAIRS * SECTOR
    for i, (got, want) in enumerate(zip(served["sockets"],
                                        served["want_sockets"])):
        assert got == want, i
        # its device's whole sector but the trickle to the resident tree
        assert sum(want.values()) == SECTOR - SECTOR // EVERY
        assert {t for t, _p in want} == {f"dev/{i}/state"}
    assert served["surplus"] == 0


def test_the_sinks_filters_are_the_plain_tries(served):
    assert served["sink"] == served["want_sink"]
    assert served["want_sink"]  # the trickle reaches resident filters
    assert not any(t.startswith("dev/") for _f, t, _p in served["sink"])


def test_the_device_served_it(served):
    c = served["counters"]
    assert c["breaker.failures"] == c["breaker.trips"] \
        == c["breaker.fallback.batches"] == 0
    assert c["dispatch.topics"] > 0
    # three of a burst's four ride the publish run, the fence does not
    assert c["channel.publish_run.msgs"] == \
        served["published"] * (BURST - 1) // BURST


def test_parks_are_counted_once_each(served):
    c = served["counters"]
    assert served["entered"] > 0, "the lowered mark provoked no park"
    assert c["ingress.parks"] == served["entered"]
    # a park has a length, and none outlasted the test
    assert 0 < c["ingress.park.ns"] < served["entered"] * 300e9
    assert c["overload.shed.ingress_timeout"] == 0


@pytest.mark.parametrize("run", ["served", "served_untimed"])
def test_no_reader_stays_parked_once_the_queue_drains(run, request):
    got = request.getfixturevalue(run)
    assert got["entered"] == got["left"] > 0
    assert got["backlogged"] is False and got["waiters"] == 0
    assert got["granted"] == 0  # and no woken reader holds room back


def test_every_park_is_woken_once(served):
    c = served["counters"]
    assert c["ingress.wakes"] == c["ingress.parks"] > 0


def test_the_queue_stays_within_the_mark_and_one_pass_of_grants(served):
    # a reader is admitted under the mark whatever it holds, and a
    # pass grants while the queue with its grants is under the mark:
    # at most the mark less one, and the last one's burst
    assert 0 < served["max_queue"] <= HIWATER - 1 + BURST


def test_with_telemetry_off_nothing_is_stamped(served_untimed):
    got = served_untimed
    c = got["counters"]
    assert got["entered"] > 0
    assert c["ingress.parks"] == c["ingress.park.ns"] == 0
    assert c["ingress.wakes"] == 0
    assert c["loop.read.calls"] == 0  # the loop counters' gate
    assert got["sockets"] == got["want_sockets"] and got["surplus"] == 0
    assert got["sink"] == got["want_sink"]


# -- the descriptor limit at start -----------------------------------------

@pytest.fixture
def soft_limit():
    """The soft RLIMIT_NOFILE at half the hard one, and back."""
    was, hard = resource.getrlimit(resource.RLIMIT_NOFILE)
    if hard == resource.RLIM_INFINITY or hard < 2048:
        pytest.skip(f"hard descriptor limit {hard}")
    resource.setrlimit(resource.RLIMIT_NOFILE, (hard // 2, hard))
    try:
        yield hard
    finally:
        resource.setrlimit(resource.RLIMIT_NOFILE, (was, hard))


def test_raise_fd_limit_takes_the_hard_limit(soft_limit):
    hard = soft_limit
    assert vm.raise_fd_limit() == {"soft": hard, "hard": hard,
                                   "was": hard // 2}
    assert resource.getrlimit(resource.RLIMIT_NOFILE) == (hard, hard)
    assert vm.raise_fd_limit() == {"soft": hard, "hard": hard, "was": hard}


def test_a_refused_limit_is_left_where_it_was(soft_limit, monkeypatch):
    def refuse(_which, _limits):
        raise ValueError("not allowed to raise maximum limit")

    monkeypatch.setattr(resource, "setrlimit", refuse)
    hard = soft_limit
    assert vm.raise_fd_limit() == {"soft": hard // 2, "hard": hard,
                                   "was": hard // 2}


@pytest.mark.parametrize("listener", [True, False])
def test_a_node_with_a_listener_raises_the_limit_at_start(
        soft_limit, caplog, listener):
    hard = soft_limit

    async def go():
        node = Node(boot_listeners=False)
        if listener:
            node.add_listener(host="127.0.0.1", port=0)
        await node.start()
        await node.stop()

    with caplog.at_level(logging.INFO, logger="emqx_tpu.node"):
        asyncio.run(go())
    said = [r for r in caplog.records
            if r.getMessage().startswith("descriptor limit")]
    soft = resource.getrlimit(resource.RLIMIT_NOFILE)[0]
    if listener:
        assert soft == hard and len(said) == 1
        assert said[0].levelno == logging.INFO
        assert said[0].getMessage() == \
            f"descriptor limit {hard} (was {hard // 2}, hard limit {hard})"
    else:
        assert soft == hard // 2 and not said  # no socket to hold


def test_a_low_hard_limit_is_said_and_the_node_serves(monkeypatch, caplog):
    monkeypatch.setattr(resource, "getrlimit", lambda _which: (1024, 1024))

    async def go():
        node = Node(boot_listeners=False)
        lst = node.add_listener(host="127.0.0.1", port=0)
        await node.start()
        try:
            c = IndieClient("c")
            await c.connect(port=lst.port)
            await c.close()
        finally:
            await node.stop()

    with caplog.at_level(logging.INFO, logger="emqx_tpu.node"):
        asyncio.run(go())
    said = [r for r in caplog.records
            if r.getMessage().startswith("descriptor limit")]
    assert len(said) == 1 and said[0].levelno == logging.WARNING
    assert "raise `ulimit -n`" in said[0].getMessage()
