"""Who waits for whom (ISSUE 37): the selector's time split by what
the loop was waiting for (``loop.select.poll.ns`` / ``.device.ns`` /
``.clients.ns``), the device path's occupancy from the spans' interval
record (``pipeline.device.ns`` / ``.batch_ns``), the stats flush as a
timed section (``loop.stats.*``), their telemetry gate, ``ctl telemetry
loop``, ``profiling.attribute``'s two new readings over synthetic
events, and the sixteen metric files that read the counters. CPU
only; every async case runs under its own short time limit."""

import asyncio
import functools
import importlib.util
import json
import os
import types

import pytest

from emqx_tpu import monitors, profiling, telemetry
from emqx_tpu.broker import Broker
from emqx_tpu.metrics import (ALL_METRICS, I_SELECT_NS, LOOP_METRICS,
                              PIPELINE_METRICS, Metrics)
from emqx_tpu.monitors import SysMon
from emqx_tpu.node import Node
from emqx_tpu.router import MatcherConfig, Router
from emqx_tpu.telemetry import Telemetry, TelemetryConfig, union_s
from emqx_tpu.types import Message

from helpers import Inbox as Q
from helpers import broker_node, node_port
from helpers import device_node as _device_node
from helpers import record_spans as _record_spans
from indie_mqtt import IndieClient

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SPLIT = ("loop.select.poll.ns", "loop.select.device.ns",
         "loop.select.clients.ns")
NEW = SPLIT + ("loop.stats.ns", "loop.stats.calls") \
    + tuple(PIPELINE_METRICS)
BEAT_NS = int(SysMon.BEAT_S * 1e9)


def within(seconds):
    """The case's own time limit (no pytest-timeout in this image)."""
    def deco(fn):
        @functools.wraps(fn)
        async def run(*a, **kw):
            await asyncio.wait_for(fn(*a, **kw), seconds)
        return run
    return deco


def _delta(node, base):
    return {k: v - base.get(k, 0) for k, v in node.metrics.all().items()}


async def _beats(n=3):
    """Let the heartbeat bring ``loop.wall.ns`` up to now."""
    await asyncio.sleep(n * SysMon.BEAT_S)


# -- (a) the selector's time, by what the loop was waiting for --------------


class _Clock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t


def _scripted(monkeypatch, shadow):
    """Run one script of selector calls through ``shadow(tel, ing,
    sel)`` (which installs a shadow of ``sel.select``) on a fake clock:
    each call lasts ``dt`` seconds, ``gc_s`` of which a collection
    that ran inside it. Returns the registry."""
    clock = _Clock()
    monkeypatch.setattr(telemetry, "_now", clock)
    monkeypatch.setattr(monitors, "time", types.SimpleNamespace(
        perf_counter=clock, time=lambda: 0.0))
    m = Metrics()
    tel = Telemetry(TelemetryConfig(), metrics=m)
    ing = types.SimpleNamespace(_on_path=0, _inflight=0, _pending=[])
    step = {}

    class Sel:
        def select(self, timeout=None):
            clock.t += step["dt"]
            if step["gc_s"]:
                tel.gc_done(0, step["gc_s"])
            return []

    sel = Sel()
    shadow(tel, ing, sel)
    # (timeout, on the device path, in the pipeline, accumulated, dt, gc)
    script = [
        (0, 0, 0, 0, 0.000011, 0.0),       # poll
        (0.0, 1, 2, 3, 0.000013, 0.0),     # a due timer: a poll too
        (None, 0, 0, 0, 0.25, 0.0),        # nothing anywhere: clients
        (0.02, 0, 0, 0, 0.02, 0.004),      # ... with a collection inside
        (None, 1, 1, 0, 0.0031, 0.0),      # a fetch is out: device
        (0.5, 2, 3, 5, 0.0507, 0.0007),    # ... and more queued: device
        (None, 0, 1, 0, 0.0021, 0.0),      # past its fetch, in the chain
        (0.001, 0, 0, 4, 0.001, 0.0),      # a linger timer over a queue
    ]
    for timeout, path, inflight, pend, dt, gc_s in script:
        ing._on_path, ing._inflight = path, inflight
        ing._pending = [None] * pend
        step.update(dt=dt, gc_s=gc_s)
        clock.t += 0.0003                  # the loop between two calls
        assert sel.select(timeout) == []
    return m, script


def _parents_shadow(tel, ing, sel):
    """PR 36's ``SysMon._time_selector::timed_select``, word for word."""
    select = sel.select
    now = monitors.time.perf_counter
    lc = tel

    def timed_select(timeout=None):
        t0 = now()
        n0 = lc.inner
        try:
            return select(timeout)
        finally:
            lc.loop_leave(I_SELECT_NS, t0, n0)

    sel.select = timed_select


def _this_shadow(tel, ing, sel):
    mon = SysMon(telemetry=tel, ingress=ing)
    mon._time_selector(types.SimpleNamespace(_selector=sel),
                       tel.loop_clock())
    assert mon._selector is sel


def test_the_split_by_the_state_at_entry_and_the_parents_totals(
        monkeypatch):
    m, script = _scripted(monkeypatch, _this_shadow)
    old, _ = _scripted(monkeypatch, _parents_shadow)
    # what PR 36 counted, to the nanosecond
    for k in ("loop.select.ns", "loop.select.calls"):
        assert m.val(k) == old.val(k), k
    assert m.val("loop.select.calls") == len(script)
    assert not any(old.val(k) for k in SPLIT)

    def ns(rows):  # exclusive of the collection inside, as the total is
        return sum(int((script[i][4] - script[i][5]) * 1e9) for i in rows)

    assert m.val("loop.select.poll.ns") == pytest.approx(ns([0, 1]), abs=4)
    assert m.val("loop.select.clients.ns") == \
        pytest.approx(ns([2, 3]), abs=4)
    assert m.val("loop.select.device.ns") == \
        pytest.approx(ns([4, 5]), abs=4)
    rest = m.val("loop.select.ns") - sum(m.val(k) for k in SPLIT)
    assert rest == pytest.approx(ns([6, 7]), abs=4) and rest > 0


def test_a_loop_with_no_ingress_splits_off_its_polls_only(monkeypatch):
    def shadow(tel, _ing, sel):
        _this_shadow(tel, None, sel)

    m, script = _scripted(monkeypatch, shadow)
    assert m.val("loop.select.calls") == len(script)
    assert m.val("loop.select.poll.ns") > 0
    assert m.val("loop.select.device.ns") == 0
    assert m.val("loop.select.clients.ns") == 0


@within(30)
async def test_an_idle_nodes_blocking_selects_wait_on_the_clients():
    node = Node(name="idle@test", boot_listeners=False)
    await node.start()
    try:
        await _beats(2)
        base = node.metrics.all()
        await asyncio.sleep(0.3)
        d = _delta(node, base)
        assert d["loop.select.calls"] >= 5
        assert d["loop.select.clients.ns"] > 0.2e9
        assert d["loop.select.device.ns"] == 0
        assert d["pipeline.device.ns"] == 0
        # every call was a poll or a wait on the clients: no rest
        assert d["loop.select.poll.ns"] + d["loop.select.clients.ns"] \
            == d["loop.select.ns"]
        assert d["loop.select.ns"] <= d["loop.wall.ns"] + BEAT_NS
    finally:
        await node.stop()


@within(60)
async def test_a_held_fetch_is_a_wait_on_the_device_path():
    node = await _device_node("held@test", batch_size=8)
    try:
        s = Q()
        node.broker.subscribe(s, "h/+")
        ing = node.broker.ingress
        assert await ing.submit(Message(topic="h/warm")) == 1  # compiles
        await ing.drain()
        fetch = node.broker.publish_fetch

        def held(pb):
            # on the executor thread: the loop has nothing else to do
            import time
            time.sleep(0.05)
            fetch(pb)

        node.broker.publish_fetch = held
        base = node.metrics.all()
        assert await ing.submit(Message(topic="h/1")) == 1
        assert ing._on_path == 0 and ing._inflight == 0
        d = _delta(node, base)
        assert d["loop.select.device.ns"] >= 0.04e9
        assert d["loop.select.clients.ns"] < d["loop.select.device.ns"]
        assert sum(d[k] for k in SPLIT) <= d["loop.select.ns"]
    finally:
        await node.stop()


# -- (b) the device path's occupancy ------------------------------------------


def _path_of(span):
    """[t_enq, fetch end] of a finished span, from ``record()`` alone
    and the span's own ``t0`` (absolute seconds), or None."""
    rec = span.record()
    fetch = [(a, n) for st, a, n, _w in rec["intervals"] if st == "fetch"]
    if "t_enq" not in rec or not fetch:
        return None
    a, n = fetch[-1]
    return (span.t0 + rec["t_enq"] / 1e3, span.t0 + (a + n) / 1e3)


@within(90)
async def test_occupancy_is_the_union_of_the_spans_own_stretches():
    node = await _device_node("occ@test", batch_size=16)
    try:
        subs = [Q(f"c{i}") for i in range(4)]
        for s in subs:
            node.broker.subscribe(s, "o/+")
        ing = node.broker.ingress
        await asyncio.gather(*[ing.submit(Message(topic=f"o/{i % 4}"))
                               for i in range(16)])
        await ing.drain()
        inner = node.broker._fetch_device

        def slowed(pb):
            import time
            time.sleep(0.02)   # executor threads: the fetches overlap
            inner(pb)

        node.broker._fetch_device = slowed
        spans = _record_spans(node.telemetry)
        await _beats(2)
        base = node.metrics.all()
        futs = []
        for _ in range(8):      # more batches than pipeline slots
            futs += [ing.submit(Message(topic=f"o/{i % 4}"))
                     for i in range(16)]
            await asyncio.sleep(0)
        assert await asyncio.gather(*futs) == [4] * len(futs)
        await ing.drain()
        await _beats(3)
        d = _delta(node, base)
        spans = [s for s in spans if s.topic.startswith("o/")]
        paths = [_path_of(s) for s in spans]
        assert len(spans) >= 4 and all(p is not None for p in paths)
        assert all(s.path == "device" and s.t_enq > s.t0 for s in spans)
        # enqueue instants rise with seq: the one end mark's premise
        order = [p[0] for _s, p in sorted(zip((s.seq for s in spans),
                                              paths))]
        assert order == sorted(order)
        tol = 4_000 * len(spans)   # record() rounds to the microsecond
        assert d["pipeline.device.ns"] == \
            pytest.approx(union_s(paths) * 1e9, abs=tol)
        assert d["pipeline.device.batch_ns"] == \
            pytest.approx(sum(b - a for a, b in paths) * 1e9, abs=tol)
        # each fetch was held 20 ms; up to four stand side by side (how
        # many did is the scheduler's: the overlap itself is pinned by
        # test_the_union_keeps_one_end_mark)
        assert d["pipeline.device.ns"] >= 0.02e9
        assert d["pipeline.device.ns"] <= d["pipeline.device.batch_ns"] \
            <= ing.max_inflight * d["pipeline.device.ns"]
        for k in PIPELINE_METRICS[:1]:
            assert d[k] <= d["loop.wall.ns"] + BEAT_NS
        assert ing._on_path == 0
    finally:
        await node.stop()


@within(30)
async def test_a_host_batch_adds_nothing_to_the_device_path():
    node = Node(name="host@test", boot_listeners=False)  # host regime
    await node.start()
    try:
        s = Q()
        node.broker.subscribe(s, "hp/+")
        spans = _record_spans(node.telemetry)
        ing = node.broker.ingress
        res = await asyncio.gather(*[ing.submit(Message(topic=f"hp/{i}"))
                                     for i in range(8)])
        await ing.drain()
        assert res == [1] * 8 and spans
        assert all(s.path == "host" and s.t_enq == 0.0
                   and s.device_path() is None
                   and "t_enq" not in s.record() for s in spans)
        assert [node.metrics.val(k) for k in PIPELINE_METRICS] == [0, 0]
        assert ing._on_path == 0
    finally:
        await node.stop()


def test_the_union_keeps_one_end_mark():
    """``Telemetry.finish`` over hand-made spans: overlap counts once
    in ``ns`` and once a batch in ``batch_ns``; a stretch inside an
    earlier one adds nothing to the union; a failed-over batch (an
    enqueue, no fetch) and a host batch add nothing at all."""
    m = Metrics()
    tel = Telemetry(TelemetryConfig(slow_threshold_ms=1e9), metrics=m)

    def batch(t_enq, fetch):
        sp = tel.begin(1)
        sp.t_enq = t_enq
        if fetch is not None:
            sp.ivs.append(("fetch", fetch[0], fetch[1], 7))
        tel.finish(sp)

    batch(10.000, (10.001, 10.010))   # 10 ms
    batch(10.004, (10.008, 10.016))   # overlaps: + 6 ms of union
    batch(10.005, (10.006, 10.012))   # inside: + 0
    batch(10.030, (10.031, 10.032))   # apart: + 2 ms
    batch(10.040, None)               # failed over before any fetch
    batch(0.0, (10.050, 10.060))      # never enqueued: a host batch
    assert m.val("pipeline.device.ns") == pytest.approx(18e6, abs=10)
    assert m.val("pipeline.device.batch_ns") == \
        pytest.approx((10 + 12 + 7 + 2) * 1e6, abs=10)
    assert tel.spans_total == 6


def test_the_enqueue_mark_is_the_first_device_call_only():
    tel = Telemetry(TelemetryConfig(), metrics=Metrics())
    sp = tel.begin(3)
    assert sp.t_enq == 0.0 and sp.device_path() is None
    with telemetry.enqueue_mark(sp):
        pass
    first = sp.t_enq
    assert first >= sp.t0
    with telemetry.enqueue_mark(sp):    # a later call of the batch
        pass
    assert sp.t_enq == first
    with telemetry.enqueue_mark(None):  # telemetry off, a warm-up batch
        pass


@pytest.mark.parametrize("cache", [64, 0])
def test_the_one_chip_dispatch_stamps_inside_the_match_stage(cache):
    b = Broker(router=Router(MatcherConfig(device_min_filters=0,
                                           match_cache_slots=cache),
                             node="n1"))
    tel = Telemetry(TelemetryConfig(slow_threshold_ms=0.0,
                                    slow_alarm_after=10**9),
                    metrics=b.metrics)
    b.telemetry = tel
    b.router.telemetry = tel
    b.subscribe(Q(), "e/+")
    for _ in range(2):      # the second batch: every topic hits the cache
        assert b.publish_batch([Message(topic="e/1"),
                                Message(topic="e/2")]) == [1, 1]
    recs = tel.slow_records()
    assert len(recs) == 2
    for rec in recs:
        iv = {st: (a, a + n) for st, a, n, _w in rec["intervals"]}
        lo = iv["match"][0]
        hi = iv.get("cache_gather", iv["match"])[1]
        assert lo <= rec["t_enq"] <= hi + 0.002, rec
        assert rec["t_enq"] <= iv["fetch"][0]
    assert b.metrics.val("pipeline.device.ns") > 0
    assert b.metrics.val("pipeline.device.batch_ns") \
        == b.metrics.val("pipeline.device.ns")  # one at a time


def test_the_mesh_dispatch_stamps_its_one_transfer():
    from emqx_tpu.parallel.mesh import make_mesh

    b = Broker(router=Router(
        MatcherConfig(mesh=make_mesh(1, 1), fanout_d=8,
                      match_cache_slots=128), node="local"))
    tel = Telemetry(TelemetryConfig(slow_threshold_ms=0.0,
                                    slow_alarm_after=10**9),
                    metrics=b.metrics)
    b.telemetry = tel
    b.router.telemetry = tel
    b.subscribe(Q("c1"), "a/+")
    assert b.publish_batch([Message(topic="a/b")]) == [1]
    rec = tel.slow_records()[0]
    assert rec["path"] == "mesh" and rec["t_enq"] > 0
    assert b.metrics.val("mesh.fused") == 1
    assert b.metrics.val("pipeline.device.ns") > 0


# -- (c) a two-loop node ------------------------------------------------------


@within(90)
async def test_a_two_loop_nodes_ledger_stays_in_its_bounds():
    async with broker_node(
            loops=2, matcher=MatcherConfig(device_min_filters=0)) as node:
        port = node_port(node)
        sub, pub = IndieClient("l2-sub"), IndieClient("l2-pub")
        await sub.connect(port=port)
        await pub.connect(port=port)
        await sub.subscribe("l2/#")
        for i in range(60):
            await pub.publish(f"l2/{i % 3}", b"x", qos=1 if i % 10 == 9
                              else 0)
        got = [await sub.recv(timeout=30.0) for _ in range(60)]
        assert len(got) == 60
        await node.broker.ingress.drain()
        await _beats(3)
        m = node.metrics
        vals = {k: m.val(k) for k in NEW + ("loop.select.ns",
                                            "loop.wall.ns")}
        assert all(v >= 0 for v in vals.values()), vals
        assert vals["pipeline.device.ns"] > 0
        assert vals["pipeline.device.batch_ns"] >= vals["pipeline.device.ns"]
        assert sum(vals[k] for k in SPLIT) <= vals["loop.select.ns"]
        assert node.broker.ingress._on_path == 0
        await pub.disconnect()
        await sub.disconnect()


# -- (d) the telemetry gate -----------------------------------------------------


@within(60)
async def test_with_telemetry_off_nothing_new_moves():
    node = await _device_node(
        "off@test", batch_size=8,
        telemetry=TelemetryConfig(enabled=False))
    try:
        loop = asyncio.get_running_loop()
        assert "select" not in loop._selector.__dict__  # no shadow
        s = Q()
        node.broker.subscribe(s, "off/+")
        ing = node.broker.ingress
        res = await asyncio.gather(*[ing.submit(Message(topic=f"off/{i}"))
                                     for i in range(24)])
        await ing.drain()
        assert res == [1] * 24 and len(s.inbox) == 24
        node.stats.tick()
        await _beats(2)
        assert ing._on_path == 0
        assert not any(node.metrics.val(k)
                       for k in LOOP_METRICS + PIPELINE_METRICS)
        assert node.ctl.run(["telemetry", "loop"]).startswith(
            "telemetry: disabled")
    finally:
        await node.stop()


# -- the stats flush by name, and the operator's table ---------------------------


@within(30)
async def test_the_stats_flush_is_a_timed_section():
    node = Node(name="stats@test", boot_listeners=False)
    await node.start()
    try:
        seen = []
        fold = node._fold_stats

        def slow_fold(stats):
            import time
            t0 = time.perf_counter()
            time.sleep(0.05)
            node.telemetry.gc_done(0, 0.03)   # a collection inside it
            fold(stats)
            seen.append(time.perf_counter() - t0)

        node._fold_stats = slow_fold
        base = node.metrics.all()
        node.stats.tick()
        node.stats.tick()
        d = _delta(node, base)
        assert d["loop.stats.calls"] == 2 and len(seen) == 2
        # the section's own time: the 30 ms of collection inside each
        # call are counted once, elsewhere (20 ms of room a call for a
        # loaded machine between the section's clock and this one's)
        own = (sum(seen) - 2 * 0.03) * 1e9
        assert own - 1e6 <= d["loop.stats.ns"] <= own + 2 * 0.02e9
        assert d["loop.stats.ns"] < sum(seen) * 1e9
        assert d["gc.ns.gen0"] == 2 * 30_000_000
        assert node.stats.getstat("connections.count") == 0
    finally:
        await node.stop()


@within(60)
async def test_ctl_telemetry_loop_prints_the_ledger():
    node = await _device_node("ctl@test", batch_size=8)
    try:
        node.broker.subscribe(Q(), "k/+")
        ing = node.broker.ingress
        assert await ing.submit(Message(topic="k/1")) == 1
        await ing.drain()
        node.stats.tick()
        await _beats(3)
        text = node.ctl.run(["telemetry", "loop"])
        rows = {ln.split()[0]: ln for ln in text.splitlines()}
        for name in ("wall", "read", "flush", "stats", "select", "poll",
                     "device", "clients", "other", "gc", "stalls"):
            assert name in rows, text
        assert "device path occupied" in text
        assert "device path depth" in text and "1.000" in text
        assert "loop" in node.ctl.usage()
    finally:
        await node.stop()


# -- (e) profiling.attribute: in_flight and the two latencies --------------------


def test_attribute_reads_in_flight_and_the_paths_latencies():
    us = 1e-6
    ops = [
        (1.000000, 1.000100, "%fusion.1 = s32[8] fusion(%p)"),
        # batch 1: enqueue at 1.010000, ops, fetch ends at 1.013000
        (1.010150, 1.010400, "%while.2 = (s32[8]) while(%t)"),
        (1.012000, 1.012250, "%copy-start.3"),
        # batch 2 overlaps nothing; its one op
        (1.500200, 1.500700, "%fusion.4"),
        # an op long after, ending the trace
        (2.400000, 2.400100, "%fusion.5"),
    ]
    anns = [
        (1.010000, 1.010040, "emqx/enqueue", 1),
        (1.009000, 1.010500, "emqx/match", 1),
        (1.011000, 1.013000, "emqx/fetch", 1),
        (1.500000, 1.500030, "emqx/enqueue", 2),
        (1.500100, 1.501950, "emqx/fetch", 2),
        # batch 3: on the path from 1.600000 to 1.603000, no device op
        (1.600000, 1.600020, "emqx/enqueue", 3),
        (1.601000, 1.603000, "emqx/fetch", 3),
        # batch 4: the trace stopped before its fetch ended: left out
        (2.300000, 2.300020, "emqx/enqueue", 4),
        (1.700000, 1.900000, "emqx/dispatch", 3),
        (1.050000, 1.060000, "emqx/gc", None),
    ]
    rep = profiling.attribute(ops, anns, top=4)
    gaps = {g["before"]: g for g in rep["gaps"]}
    # the gap inside batch 1's stretch (1.010400 → 1.012000)
    inside = gaps["%copy-start.3"]
    assert inside["seconds"] == pytest.approx(1600 * us)
    assert inside["in_flight"] == pytest.approx(1.0)
    # 1.012250 → 1.500200: batch 1 until 1.013000, batch 2 from 1.5
    mixed = gaps["%fusion.4"]
    assert mixed["in_flight"] == pytest.approx(
        (750 * us + 200 * us) / mixed["seconds"])
    # 1.000100 → 1.010150: the host had given the chip nothing until
    # the mark at 1.010000
    before = gaps["%while.2"]
    assert before["in_flight"] == pytest.approx(150 * us / before["seconds"])
    # 1.500700 → 2.400000: batch 2's tail, batch 3 whole, not batch 4
    last = gaps["%fusion.5"]
    assert last["in_flight"] == pytest.approx(
        (1250 * us + 3000 * us) / last["seconds"])
    assert last["host"][0][:2] == ["emqx/dispatch", 3]
    path = rep["device_path"]
    assert path["batches"] == 3 and path["no_device_op"] == 1
    first, done = (path["enqueue_to_first_op_ms"],
                   path["device_done_to_fetch_ms"])
    assert first["count"] == done["count"] == 2  # batch 3 apart, not 0
    # to the microsecond: batch 1 150 / 750 us, batch 2 200 / 1250 us
    assert profiling.path_latencies(ops, profiling.path_stretches(anns)[:1]) \
        == {"batches": 1, "no_device_op": 0,
            "enqueue_to_first_op_ms": {
                "count": 1, "median": pytest.approx(0.150, abs=1e-3),
                "p99": pytest.approx(0.150, abs=1e-3)},
            "device_done_to_fetch_ms": {
                "count": 1, "median": pytest.approx(0.750, abs=1e-3),
                "p99": pytest.approx(0.750, abs=1e-3)}}
    assert first["median"] == pytest.approx(0.200, abs=1e-3)
    assert first["p99"] == pytest.approx(0.200, abs=1e-3)
    assert done["median"] == pytest.approx(1.250, abs=1e-3)
    text = profiling.render_report(rep)
    assert "a batch on the device path 100.0% of it" in text
    assert "device path: 3 batches" in text and "1 with no device op" in text
    assert "device done -> fetch returned: 2 batches, median 1.250ms" in text
    assert "enqueue -> first device op: 2 batches" in text


def test_attribute_without_a_mark_says_so_and_reads_the_rest():
    """A trace of a program without ``emqx/enqueue`` (PR 36's): the
    gaps and their host stages as before, ``in_flight`` None."""
    ops = [(0.0, 0.1, "%a"), (0.5, 0.6, "%b")]
    anns = [(0.2, 0.4, "emqx/fetch", 9)]
    rep = profiling.attribute(ops, anns)
    assert rep["device_path"] is None
    assert rep["gaps"][0]["in_flight"] is None
    assert rep["gaps"][0]["host"][0] == ["emqx/fetch", 9,
                                         pytest.approx(0.5)]
    text = profiling.render_report(rep)
    assert "no emqx/enqueue mark" in text and "device path" in text
    # and a trace with no device plane still reports what it has
    rep = profiling.attribute([], anns)
    assert rep["device_busy_share"] is None and rep["device_path"] is None


# -- the sixteen metric files -------------------------------------------------------

BASES = {
    "select_poll_share": (["loop.select.poll.ns"], "loop.wall.ns",
                          "event loop", [".p2p"]),
    "select_wait_device_share": (["loop.select.device.ns"],
                                 "loop.wall.ns", "event loop",
                                 [".p2p", ".uniform", ".paced"]),
    "select_wait_clients_share": (["loop.select.clients.ns"],
                                  "loop.wall.ns", "event loop",
                                  [".p2p", ".paced"]),
    "device_path_share": (["pipeline.device.ns"], "loop.wall.ns",
                          "batch pipeline hand-offs",
                          [".p2p", ".uniform", ".paced"]),
    "device_path_depth": (["pipeline.device.batch_ns"],
                          "pipeline.device.ns",
                          "batch pipeline hand-offs", [".p2p", ".uniform"]),
}
CELLS = {"": ["fleet_1m.flood", "fanout_1k.flood"],
         ".p2p": ["p2p_2k.flood"], ".uniform": ["fleet_1m_uniform.flood"],
         ".paced": ["fleet_1m.paced"]}
FILES = [(b, s) for b, v in BASES.items() for s in [""] + v[3]]


def _json(*path):
    with open(os.path.join(_ROOT, *path), encoding="utf-8") as f:
        return json.load(f)


def _reduce():
    spec = importlib.util.spec_from_file_location(
        "_occ_counter_ratio", os.path.join(
            _ROOT, "benchmark", "reducers", "counter_ratio.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.reduce


def test_sixteen_files_and_no_other_entry():
    assert len(FILES) == 16
    spec = _json("BENCHMARK.json")
    names = [m["name"] for m in spec["per_layer"]]
    new = [b + s for b, s in FILES]
    assert set(new) <= set(names)
    # appended: the p2p cell's in one stretch behind its last, then
    # the rest; nothing that was there moved
    at = names.index("wakes_per_park.p2p") + 1
    assert names[at:at + 5] == [b + ".p2p" for b in BASES]
    assert set(names[at:at + 16]) == set(new)   # later PRs append behind
    # the mesh cell's list is pinned (benchmark/tests/test_mesh_cell.py)
    assert not any("fleet_10m_mesh.flood" in m["workloads"]
                   for m in spec["per_layer"][at:])


@pytest.mark.parametrize("base,suffix", FILES)
def test_metric_file_equals_its_entry_and_its_base(base, suffix):
    counters, per, layer, _twins = BASES[base]
    name = base + suffix
    data = _json("benchmark", "layer_metrics", name + ".json")
    entry = next(m for m in _json("BENCHMARK.json")["per_layer"]
                 if m["name"] == name)
    assert set(entry) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    for key in ("unit", "better", "source", "layer", "moves"):
        assert data[key] == entry[key], key
    assert entry["workloads"] == CELLS[suffix]
    assert entry["moves"] == ("deliver_p50_ms" if suffix == ".paced"
                              else "delivered_rate")
    assert entry["source"] == "program_counter" and entry["layer"] == layer
    assert data["reducer"] == "counter_ratio"
    assert data["args"]["counters"] == counters
    assert data["args"]["per"] == "counter:" + per
    assert set(counters) | {per} <= set(ALL_METRICS)
    assert data["what"] and entry["better"] in ("lower", "higher")
    twin = _json("benchmark", "layer_metrics", base + ".json")
    assert [data[k] for k in ("reducer", "args", "unit", "better",
                              "source", "layer")] == \
        [twin[k] for k in ("reducer", "args", "unit", "better",
                           "source", "layer")]
    # a program without the counter (the parent) reads nothing, never 0
    reduce = _reduce()
    parent = {"counters": {"loop.wall.ns": 20 * 10**9,
                           "loop.select.ns": 5 * 10**9}, "window_s": 20.0}
    assert reduce(parent, **data["args"]) is None
    run = {"counters": dict(parent["counters"], **{
        "loop.select.poll.ns": 10**9, "loop.select.device.ns": 2 * 10**9,
        "loop.select.clients.ns": 10**9, "pipeline.device.ns": 8 * 10**9,
        "pipeline.device.batch_ns": 12 * 10**9}), "window_s": 20.0}
    want = {"select_poll_share": 5.0, "select_wait_device_share": 10.0,
            "select_wait_clients_share": 5.0, "device_path_share": 40.0,
            "device_path_depth": 1.5}[base]
    assert reduce(run, **data["args"]) == pytest.approx(want)
    # no device batch in the window: no depth to read, never 0 or inf
    if base == "device_path_depth":
        run["counters"]["pipeline.device.ns"] = 0
        assert reduce(run, **data["args"]) is None
