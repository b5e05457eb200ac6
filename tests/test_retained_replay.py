"""Device-resident retained replay (PR 19, docs/DISPATCH.md
"Retained replay"): batched subscribe-time matching parity against
the ``T.match`` host oracle, planner-egress replay wire/metric parity (planner on/off, loops=1
vs 2), the ≤1-wakeup / onloop==0 delivery contract, device-path will
batching, and devloss riding of the retain index."""

import asyncio
import json
import random

import pytest

from emqx_tpu import topic as T
from emqx_tpu.broker import DispatchConfig
from emqx_tpu.modules.retainer import RetainerModule, RetainIndex
from emqx_tpu.mqtt import constants as C
from emqx_tpu.node import Node
from emqx_tpu.types import Message

from mqtt_client import TestClient


# -- batched kernel vs host oracle: differential fuzz ------------------------

_WORDS = ["a", "b", "c", "sensor", "west", "x", "$SYS", "$priv", ""]


def _rand_topic(rng, max_depth=20):
    return "/".join(rng.choice(_WORDS[:-1])
                    for _ in range(rng.randint(1, max_depth)))


def _rand_filter(rng):
    depth = rng.randint(1, 19)
    ws = [rng.choice(_WORDS + ["+"]) for _ in range(depth)]
    if rng.random() < 0.4:
        ws.append("#")
    return "/".join(ws)


def _oracle(live, flt):
    return sorted(t for t in live if T.match(t, flt))


def _fuzz_index(rng, n=350):
    idx = RetainIndex()
    live = set()
    for _ in range(n):
        t = _rand_topic(rng)
        idx.add(t)
        live.add(t)
    for t in rng.sample(sorted(live), n // 3):
        idx.remove(t)
        live.discard(t)
    for _ in range(n // 8):  # slot reuse
        t = _rand_topic(rng)
        idx.add(t)
        live.add(t)
    return idx, live


def _burst(rng, live):
    """A mixed burst: random filters + exact live names + edge
    shapes ($-roots, root wildcards, deeper-than-L, duplicates)."""
    flts = [_rand_filter(rng) for _ in range(rng.randint(1, 9))]
    flts += rng.sample(sorted(live), min(2, len(live)))
    flts += ["#", "+/+", "$SYS/#", "/".join(["+"] * 18) + "/#"]
    flts.append(flts[0])  # duplicate in-burst
    rng.shuffle(flts)
    return flts


def test_match_many_fuzz_parity():
    """Exact oracle parity of the BATCHED device match across mixed
    bursts."""
    rng = random.Random(77)
    for _ in range(6):
        idx, live = _fuzz_index(rng)
        flts = _burst(rng, live)
        got = idx.match_many(flts, device_threshold=0)
        assert len(got) == len(flts)
        for flt, hits in zip(flts, got):
            assert sorted(hits) == _oracle(live, flt), flt
        assert idx._last_batch == len(flts)


def test_match_many_interleaved_mutations():
    """add/remove between bursts exercises the dirty-row patch path
    under the batched kernel."""
    rng = random.Random(11)
    idx = RetainIndex()
    live = set()
    for i in range(300):
        t = f"i/{rng.randint(0, 40)}/r{i}"
        idx.add(t)
        live.add(t)
    idx.match_many(["i/#"], device_threshold=0)  # build device cache
    for step in range(12):
        for _ in range(4):
            if live and rng.random() < 0.5:
                t = rng.choice(sorted(live))
                idx.remove(t)
                live.discard(t)
            else:
                t = f"i/{rng.randint(0, 40)}/n{step}_{rng.randint(0, 99)}"
                idx.add(t)
                live.add(t)
        flts = ["i/#", "i/3/+", "#", f"i/{step}/+"]
        got = idx.match_many(flts, device_threshold=0)
        for flt, hits in zip(flts, got):
            assert sorted(hits) == _oracle(live, flt), (step, flt)


# -- devloss riding ----------------------------------------------------------

class _FakeRouter:
    def __init__(self):
        self.suspended = False

    def device_suspended(self):
        return self.suspended


def test_retain_index_devloss_suspension_and_breaker_reset():
    """Suspended device plane → host scan + cached matrix dropped;
    suspension lifting (rebuild_complete ran) → the failure breaker
    resets and the device path resumes."""
    idx = RetainIndex()
    router = _FakeRouter()
    idx.attach_router(router)
    live = {f"d/{i}" for i in range(50)}
    for t in live:
        idx.add(t)
    assert sorted(idx.match("d/+", device_threshold=0)) == sorted(live)
    assert idx._dev is not None  # device cache built
    idx._device_broken = 2  # two strikes before the devloss
    router.suspended = True
    assert sorted(idx.match("d/+", device_threshold=0)) == sorted(live)
    assert idx._dev is None  # dropped: its HBM refs may be dead
    assert idx._suspended_seen
    assert idx._device_broken == 2  # no strikes burned while down
    router.suspended = False
    assert sorted(idx.match("d/+", device_threshold=0)) == sorted(live)
    assert idx._device_broken == 0  # fresh backend, clean slate
    assert idx._dev is not None  # device path resumed
    assert idx.device_info()["suspended"] is False


async def test_retainer_module_attaches_router():
    n = Node(boot_listeners=False)
    n.modules.load(RetainerModule)
    await n.start()
    try:
        ret = n.modules._loaded["retainer"]
        assert ret._index._router is n.router
    finally:
        await n.stop()


# -- replay plan: unit-level delivery contract -------------------------------

class _PlanSession:
    """Fake with the batched protocol: records deliver_many batches."""

    def __init__(self):
        self.batches = []
        self.singles = []
        self.subscriptions = {}

    def deliver_many(self, items):
        self.batches.append(list(items))

    def deliver(self, f, m):
        self.singles.append((f, m))


async def test_replay_flush_one_deliver_many_per_session():
    """The planner path: however many (filter × topic) pairs a burst
    resolves for a session, the session takes ONE deliver_many — the
    ≤1-wakeup-per-connection contract at the session seam — and the
    legacy path (dispatch.planner=false) walks per delivery."""
    n = Node(boot_listeners=False)
    mod = n.modules.load(RetainerModule)
    await n.start()
    try:
        for t in ("p/a", "p/b", "q/c"):
            n.publish(Message(topic=t, payload=b"v",
                              flags={"retain": True}))
        s1, s2 = _PlanSession(), _PlanSession()
        items = [(s1, "p/+", {"qos": 0}), (s1, "q/c", {"qos": 0}),
                 (s2, "p/a", {"qos": 0})]
        mod._replay_flush(list(items))
        assert len(s1.batches) == 1 and not s1.singles
        assert sorted((f, m.topic) for f, m, _o, _fast in s1.batches[0]) \
            == [("p/+", "p/a"), ("p/+", "p/b"), ("q/c", "q/c")]
        assert [(f, m.topic) for f, m, _o, _fast in s2.batches[0]] \
            == [("p/a", "p/a")]
        # every replayed copy carries retain + the retained header
        for f, m, _o, _fast in s1.batches[0] + s2.batches[0]:
            assert m.flags.get("retain") and m.headers.get("retained")
        # ONE shared out-copy per stored topic per burst
        pa = [m for _f, m, _o, _x in s1.batches[0] + s2.batches[0]
              if m.topic == "p/a"]
        assert len(pa) == 2 and pa[0] is pa[1]
        assert n.metrics.val("retained.replay.batches") == 1
        assert n.metrics.val("retained.replay.messages") == 4
        assert mod.replay_info()["replay_last_batch"] == 4
        # legacy path: byte-for-byte the old per-delivery walk
        n.broker.dispatch_config.planner = False
        s3 = _PlanSession()
        mod._replay_flush([(s3, "p/+", {"qos": 0})])
        assert not s3.batches and len(s3.singles) == 2
    finally:
        await n.stop()


async def test_replay_flush_expiry_evicted_in_plan_stage():
    """An entry past Message-Expiry at replay time is filtered in the
    plan stage AND lazily evicted (store + counters)."""
    import time as _t

    n = Node(boot_listeners=False)
    mod = n.modules.load(RetainerModule)
    await n.start()
    try:
        dead = Message(topic="e/t", payload=b"x",
                       flags={"retain": True},
                       timestamp=_t.time() - 100,
                       headers={"properties":
                                {"Message-Expiry-Interval": 1}})
        n.publish(dead)
        n.publish(Message(topic="e/u", payload=b"y",
                          flags={"retain": True}))
        assert len(mod._store) == 2
        s = _PlanSession()
        mod._replay_flush([(s, "e/+", {"qos": 0})])
        assert [(f, m.topic) for f, m, _o, _x in s.batches[0]] \
            == [("e/+", "e/u")]
        assert "e/t" not in mod._store
        assert n.metrics.val("retained.expired") == 1
        assert n.metrics.val("retained.count") == 1
    finally:
        await n.stop()


# -- replay over the wire: burst coalescing, metrics, parity -----------------

async def _retained_node(**kw):
    n = Node(boot_listeners=False, **kw)
    n.modules.load(RetainerModule)
    lst = n.add_listener(port=0)
    await n.start()
    return n, lst.port


async def _seed_store(port, topics):
    pub = TestClient("seed", version=C.MQTT_V5)
    await pub.connect(port=port)
    for t, payload in topics:
        await pub.publish(t, payload, qos=1, retain=True)
    await pub.close()


_SEED = [("w/a", b"pa"), ("w/b", b"pb"), ("w/c/d", b"pcd"),
         ("v/1", b"p1"), ("v/2", b"p2")]


async def _replay_burst(node, port, client_id="burst",
                        version=C.MQTT_V5):
    """One multi-filter SUBSCRIBE → one replay burst; returns the
    delivered (filter-agnostic) packet tuples + metric deltas."""
    m = node.metrics
    before = {k: m.val(k) for k in
              ("delivery.wakeups", "delivery.serialize.onloop",
               "retained.replay.batches", "retained.replay.messages")}
    sub = TestClient(client_id, version=version)
    await sub.connect(port=port)
    await sub.subscribe(("w/+", {"qos": 1, "nl": 0, "rap": 0, "rh": 0}),
                        ("w/c/#", {"qos": 0, "nl": 0, "rap": 1, "rh": 0}),
                        ("v/1", {"qos": 1, "nl": 0, "rap": 0, "rh": 0}))
    got = []
    for _ in range(4):  # w/a, w/b, w/c/d, v/1
        p = await sub.recv(5)
        got.append((p.topic, bytes(p.payload), p.qos, p.retain))
    with pytest.raises(asyncio.TimeoutError):
        await sub.recv(0.3)
    await sub.close()
    delta = {k: m.val(k) - before[k] for k in before}
    return sorted(got), delta


_EXPECT = sorted([("w/a", b"pa", 1, True), ("w/b", b"pb", 1, True),
                  ("w/c/d", b"pcd", 0, True), ("v/1", b"p1", 1, True)])


async def test_replay_burst_planner_metrics_and_wire():
    """The full pinned contract on the default (planner+preserialize)
    path with the device index forced on: exact delivered set with
    retain kept (MQTT-3.3.1-8), ONE replay batch per SUBSCRIBE burst,
    serialization fully off-loop, and exactly one delivery wakeup for
    the subscribing connection (SUBACK is written inline by the read
    loop — it never passes through the wakeup path)."""
    n, port = await _retained_node()
    try:
        n.modules._loaded["retainer"].index_device_threshold = 0
        await _seed_store(port, _SEED)
        got, delta = await _replay_burst(n, port)
        assert got == _EXPECT
        assert delta["retained.replay.batches"] == 1
        assert delta["retained.replay.messages"] == 4
        assert delta["delivery.serialize.onloop"] == 0
        assert delta["delivery.wakeups"] == 1
    finally:
        await n.stop()


async def test_replay_wire_parity_planner_off():
    """dispatch.planner=false restores the legacy per-delivery replay
    — the delivered set must be identical (wire parity)."""
    n, port = await _retained_node(
        dispatch_config=DispatchConfig(planner=False))
    try:
        n.modules._loaded["retainer"].index_device_threshold = 0
        await _seed_store(port, _SEED)
        got, delta = await _replay_burst(n, port)
        assert got == _EXPECT
        assert delta["retained.replay.batches"] == 1
    finally:
        await n.stop()


async def test_replay_wire_parity_two_loops():
    """loops=2: the hook fires on the subscribing channel's owner
    loop and replay flushes per loop — delivered sets stay identical
    to the single-loop node for subscribers on BOTH loops."""
    n, port = await _retained_node(loops=2)
    try:
        n.modules._loaded["retainer"].index_device_threshold = 0
        await _seed_store(port, _SEED)
        # sequential connects round-robin across the ring: these two
        # land on different loops
        got1, d1 = await _replay_burst(n, port, "ring1")
        got2, d2 = await _replay_burst(n, port, "ring2")
        assert got1 == _EXPECT and got2 == _EXPECT
        assert d2["delivery.serialize.onloop"] == 0
        assert d2["retained.replay.batches"] == 1
    finally:
        await n.stop()


async def test_replay_rh_share_matrix_batched():
    """RH 2 / RH 1-on-resub / shared-group gating holds on the
    batched path: gated subscriptions contribute nothing to the
    burst (no batch fires when everything is gated)."""
    n, port = await _retained_node()
    try:
        ret = n.modules._loaded["retainer"]
        ret.index_device_threshold = 0
        await _seed_store(port, _SEED)
        m = n.metrics
        before = m.val("retained.replay.batches")
        sub = TestClient("gated", version=C.MQTT_V5)
        await sub.connect(port=port)
        await sub.subscribe(
            ("w/a", {"qos": 1, "nl": 0, "rap": 0, "rh": 2}),
            ("$share/g/w/+", {"qos": 1, "nl": 0, "rap": 0, "rh": 0}))
        with pytest.raises(asyncio.TimeoutError):
            await sub.recv(0.3)
        assert m.val("retained.replay.batches") == before  # no batch
        # rh=1 resub: gated at submit time too
        await sub.subscribe(("w/a", {"qos": 1, "nl": 0, "rap": 0,
                                     "rh": 1}))
        with pytest.raises(asyncio.TimeoutError):
            await sub.recv(0.3)
        assert m.val("retained.replay.batches") == before
        # rh=1 on a NEW subscription replays through one batch
        await sub.subscribe(("w/b", {"qos": 1, "nl": 0, "rap": 0,
                                     "rh": 1}))
        p = await sub.recv(5)
        assert (p.topic, bytes(p.payload), p.retain) == ("w/b", b"pb",
                                                         True)
        assert m.val("retained.replay.batches") == before + 1
        await sub.close()
    finally:
        await n.stop()


# -- device-path wills -------------------------------------------------------

async def test_will_storm_one_ingress_batch():
    """A mass-disconnect will storm funnels through the ingress
    accumulator: N wills submitted in one tick → ONE ingress flush,
    every will counted batched, exact fan-out to the observer."""
    n, port = await _retained_node()
    try:
        obs = TestClient("wobs", version=C.MQTT_V5)
        await obs.connect(port=port)
        await obs.subscribe("ws/#", qos=0)
        N = 12
        flushes0 = n.ingress.flushes
        for i in range(N):
            n.broker.publish_will(Message(topic=f"ws/{i}",
                                          payload=b"died"))
        got = set()
        for _ in range(N):
            p = await obs.recv(5)
            got.add(p.topic)
        assert got == {f"ws/{i}" for i in range(N)}
        assert n.metrics.val("wills.batched") == N
        assert n.metrics.val("wills.direct") == 0
        assert n.ingress.flushes == flushes0 + 1  # ONE batch
        await obs.close()
    finally:
        await n.stop()


async def test_abrupt_disconnect_will_rides_ingress():
    """End-to-end: an abnormal disconnect's will reaches subscribers
    through the batched device path (wills.batched counts it)."""
    n, port = await _retained_node()
    try:
        obs = TestClient("wobs2")
        await obs.connect(port=port)
        await obs.subscribe("wd/#", qos=1)
        w = TestClient("wful", will_flag=True, will_qos=1,
                       will_topic="wd/t", will_payload=b"gone")
        await w.connect(port=port)
        await w.close()  # abrupt: will must fire
        p = await obs.recv(5)
        assert (p.topic, bytes(p.payload)) == ("wd/t", b"gone")
        assert n.metrics.val("wills.batched") == 1
        await obs.close()
    finally:
        await n.stop()


def test_publish_will_direct_fallback_without_loop():
    """Loop-less callers (sync adapters, tests) can't ride the
    accumulator: publish_will falls back to the direct path."""
    n = Node(boot_listeners=False)
    n.modules.load(RetainerModule)
    n.broker.publish_will(Message(topic="wf/t", payload=b"x"))
    assert n.metrics.val("wills.direct") == 1
    assert n.metrics.val("wills.batched") == 0


# -- expired-retained GC on the stats tick -----------------------------------

async def test_stats_tick_gc_sweeps_expired():
    import time as _t

    n = Node(boot_listeners=False)
    mod = n.modules.load(RetainerModule)
    await n.start()
    try:
        n.publish(Message(topic="gc/t", payload=b"x",
                          flags={"retain": True},
                          timestamp=_t.time() - 100,
                          headers={"properties":
                                   {"Message-Expiry-Interval": 1}}))
        n.publish(Message(topic="gc/live", payload=b"y",
                          flags={"retain": True}))
        assert len(mod._store) == 2
        for _ in range(RetainerModule._GC_EVERY):
            n.stats.tick()
        assert "gc/t" not in mod._store and "gc/live" in mod._store
        assert n.metrics.val("retained.expired") == 1
        assert n.metrics.val("retained.count") == 1
    finally:
        await n.stop()


# -- ctl surface -------------------------------------------------------------

async def test_ctl_retained_snapshot():
    n, port = await _retained_node()
    try:
        n.modules._loaded["retainer"].index_device_threshold = 0
        await _seed_store(port, _SEED)
        got, _delta = await _replay_burst(n, port, "ctlsub")
        assert got == _EXPECT
        out = json.loads(n.ctl.run(["retained"]))
        assert out["store"] == len(_SEED)
        assert out["replay_batches"] == 1
        assert out["replay_last_batch"] == 4
        idx = out["index"]
        assert idx["rows"] == len(_SEED)
        assert idx["last_batch"] == 2  # two wildcard filters batched
        assert idx["device_broken"] == 0 and not idx["suspended"]
    finally:
        await n.stop()


async def test_ctl_retained_without_module():
    async def _bare():
        n = Node(boot_listeners=False)
        await n.start()
        return n

    n = await _bare()
    try:
        assert "not loaded" in n.ctl.run(["retained"])
    finally:
        await n.stop()
