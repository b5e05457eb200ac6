"""The planned tail's delivery walk (Broker._plan_prologue /
_deliver_plan_group / _plan_fold, docs/DISPATCH.md "The delivery
walk") against the legacy per-delivery tail: whatever the walk learns
once per distinct filter (of the batch, then of the group) and
whatever it counts by arithmetic, every subscriber receives the same
messages under the same filter strings, ``results``,
``messages.delivered``, the no-local counters and — where a callback is
registered — every ``message.delivered`` call are the legacy tail's;
and ``delivery.plan.resolves`` counts the (group, filter) pairs the
walk resolved."""

import asyncio
import json
import os
import types

import pytest

from emqx_tpu.broker import Broker, DispatchConfig
from emqx_tpu.loops import LoopGroup
from emqx_tpu.metrics import ALL_METRICS, Metrics
from emqx_tpu.modules.topic_metrics import TopicMetricsModule
from emqx_tpu.router import MatcherConfig, Router
from emqx_tpu.session import Session
from emqx_tpu.telemetry import Telemetry, TelemetryConfig
from emqx_tpu.types import Message, SubOpts


class Q:
    """A plain subscriber: ``deliver(filter, msg)`` and nothing else."""

    def __init__(self, client_id="c", fail_on=()):
        self.client_id = client_id
        self.inbox = []
        self.fail_on = set(fail_on)
        self.calls = 0

    def deliver(self, flt, msg):
        self.calls += 1
        if self.calls in self.fail_on:
            raise RuntimeError("subscriber down")
        self.inbox.append((flt, msg.topic, bytes(msg.payload)))


def _broker(planner: bool, **mk) -> Broker:
    mk.setdefault("device_min_filters", 0)
    return Broker(router=Router(MatcherConfig(**mk), node="node1"),
                  dispatch_config=DispatchConfig(planner=planner))


def _deltas(b):
    return {k: v for k, v in b.metrics.all().items()
            if v and k.startswith(("messages.", "delivery."))}


def _msgs(*topics, from_=None):
    return [Message(topic=t, payload=b"p%d" % i, from_=from_)
            for i, t in enumerate(topics)]


def _staged(b, msgs, between=None):
    """begin / fetch / finish by hand, with ``between`` run after the
    fetch (the plan, where there is one, is built by then)."""
    pb = b.publish_begin(msgs)
    if not pb.done:
        b.publish_fetch(pb)
        if b.dispatch_config.planner:
            assert pb.plan is not None
    if between is not None:
        between()
    return b.publish_finish(pb)


# -- scenarios: each returns what a subscriber or a caller can observe --------


def _topic_metrics_loaded(b):
    calls = []
    mod = TopicMetricsModule(types.SimpleNamespace(hooks=b.hooks))
    mod.load({"topics": ["h/1", "h/2"]})
    b.hooks.add("message.delivered",
                lambda msg, n: calls.append((msg.topic, bytes(msg.payload),
                                             n)))
    one, two, deep = Q("one"), Q("two"), Q("deep")
    for s in (one, two):
        b.subscribe(s, "h/+")
    b.subscribe(two, "h/#")
    b.subscribe(deep, "h/1")
    res = [b.publish_batch(_msgs("h/1", "h/2", "h/1", "none", "h/3"))
           for _ in range(2)]
    per_topic = {t: dict(c) for t, c in mod._topics.items()}
    # the call order inside one message follows the walk (groups by
    # subscriber id in the plan, packed slots in the legacy tail): a
    # callback sees the same calls, not the same interleaving
    return res, sorted(calls), per_topic, \
        [s.inbox for s in (one, two, deep)]


def _no_callback(b):
    ran = []
    run = b.hooks.run
    b.hooks.run = lambda name, args=(): (ran.append(name),
                                         run(name, args))[1]
    subs = [Q(f"c{i}") for i in range(3)]
    b.subscribe(subs[0], "n/+")
    b.subscribe(subs[1], "n/#")
    b.subscribe(subs[2], "n/1")
    res = b.publish_batch(_msgs("n/1", "n/2", "n/1", "gone"))
    if b.dispatch_config.planner:
        # the chain is empty: the fold never builds its arguments
        assert "message.delivered" not in ran
    else:
        assert "message.delivered" in ran
    assert "message.dropped" in ran
    return res, [s.inbox for s in subs]


def _deliver_raises_once(b):
    # the second of the subscriber's three deliveries raises: the
    # other two arrive and the counts say two
    flaky = Q("flaky", fail_on={2})
    b.subscribe(flaky, "r/+")
    res = b.publish_batch(_msgs("r/1", "r/2", "r/3"))
    assert sum(res) == 2 and len(flaky.inbox) == 2
    assert b.metrics.val("messages.delivered") == 2
    return sorted(res), sorted(flaky.inbox)


def _no_local_in_a_repeated_filter(b):
    me, other = Q("me"), Q("other")
    b.subscribe(me, "l/+", SubOpts(nl=1))
    b.subscribe(other, "l/+", SubOpts(nl=1))
    msgs = [Message(topic=f"l/{i}", payload=b"%d" % i,
                    from_="me" if i % 2 else "other") for i in range(6)]
    res = b.publish_batch(msgs)
    assert res == [1] * 6
    assert b.metrics.val("delivery.dropped.no_local") == 6
    return res, me.inbox, other.inbox


def _unsubscribed_from_one_filter_of_a_group(b):
    s, t = Q("s"), Q("t")
    b.subscribe(s, "u/+")
    b.subscribe(s, "u/#")
    b.subscribe(t, "u/#")
    res = _staged(b, _msgs("u/1", "u/2", "u/1"),
                  between=lambda: b.unsubscribe(s, "u/#"))
    assert res == [2, 2, 2]
    assert [f for f, _t, _p in s.inbox] == ["u/+"] * 3
    return res, s.inbox, t.inbox


def _shared_and_remote_destinations(b):
    forwards = []
    b.forwarder = lambda node, flt, msg: forwards.append(
        (node, flt, msg.topic))
    here, m1, m2 = Q("here"), Q("m1"), Q("m2")
    b.subscribe(here, "x/t")
    b.router.add_route("x/t", "node2")          # local AND remote
    b.router.add_route("x/+", "node3")          # remote alone
    b.subscribe(m1, "$share/g/x/t")
    b.subscribe(m2, "$share/g/x/t")
    b.subscribe(here, "y/#")                    # local alone
    res = [b.publish_batch(_msgs("x/t", "y/1", "x/t", "x/u", "z"))
           for _ in range(2)]
    assert res[0] == [2, 1, 2, 0, 0]
    return res, sorted(forwards), here.inbox, \
        len(m1.inbox) + len(m2.inbox), b.metrics.val("messages.forward")


def _a_subscriber_that_cannot_deliver(b):
    # an object with no ``deliver`` at all costs its own deliveries a
    # log line each and nobody else anything
    class Mute:
        client_id = "mute"

    good = Q("good")
    b.subscribe(Mute(), "d/+")
    b.subscribe(good, "d/#")
    res = b.publish_batch(_msgs("d/1", "d/2"))
    assert res == [1, 1]
    return res, good.inbox


@pytest.mark.parametrize("scenario", [
    _a_subscriber_that_cannot_deliver,
    _topic_metrics_loaded,
    _no_callback,
    _deliver_raises_once,
    _no_local_in_a_repeated_filter,
    _unsubscribed_from_one_filter_of_a_group,
    _shared_and_remote_destinations,
], ids=lambda f: f.__name__.strip("_"))
def test_planned_walk_equals_the_legacy_tail(scenario):
    on, off = _broker(True), _broker(False)
    got_on, got_off = scenario(on), scenario(off)
    assert got_on == got_off
    assert _deltas(on) == _deltas(off)


def test_a_callback_registered_later_is_served_from_the_next_batch():
    b = _broker(True)
    s = Q("s")
    b.subscribe(s, "k/+")
    assert b.publish_batch(_msgs("k/1", "k/1")) == [1, 1]
    calls = []
    b.hooks.add("message.delivered",
                lambda msg, n: calls.append((msg.topic, n)))
    assert b.publish_batch(_msgs("k/1", "k/2")) == [1, 1]
    assert calls == [("k/1", 1), ("k/2", 1)]
    assert b.metrics.val("messages.delivered") == 4


# -- a two-loop node: handed-off groups fold to the same results --------------


async def _two_loops(loops: int, hooked: bool):
    b = _broker(True)
    calls = []
    if hooked:
        b.hooks.add("message.delivered",
                    lambda msg, n: calls.append((msg.topic, n)))
    lg = None
    if loops > 1:
        lg = LoopGroup(loops)
        lg.start(asyncio.get_running_loop())
        b.loop_group = lg
        b.metrics.enable_threadsafe()
    try:
        sess = [Session(f"s{i}", broker=b) for i in range(4)]
        for i, s in enumerate(sess):
            if lg is not None and i % 2:
                s.owner_loop = lg.loops[1]   # a peer loop's session
            s.subscribe("m/+")
        sess[1].subscribe("m/#", SubOpts(qos=1))
        plain = Q("plain")
        b.subscribe(plain, "m/1")
        res = [b.publish_batch(
            [Message(topic="m/1", payload=b"a"),
             Message(topic="m/2", payload=b"b", qos=1),
             Message(topic="m/1", payload=b"c"),
             Message(topic="else", payload=b"d")]) for _ in range(2)]
        outs = [sorted((m.topic, bytes(m.payload), m.qos)
                       for _pid, m in s.outbox) for s in sess]
        handed = b.metrics.val("delivery.xloop.deliveries")
        return (res, outs, plain.inbox, sorted(calls),
                b.metrics.val("messages.delivered")), handed
    finally:
        if lg is not None:
            lg.stop()


@pytest.mark.parametrize("hooked", [False, True],
                         ids=["no_callback", "callback"])
async def test_two_loop_handoffs_fold_to_the_same_results(hooked):
    one, handed_one = await _two_loops(1, hooked)
    two, handed_two = await _two_loops(2, hooked)
    assert one == two
    assert one[0][0] == [6, 5, 6, 0]
    assert handed_one == 0
    # sessions 1 and 3 live on the peer loop: their 4 + 6 + 6 ... all
    # of their deliveries crossed the ring, and were counted once
    assert handed_two == 2 * (3 + 2 * 3)


# -- delivery.plan.resolves ---------------------------------------------------


@pytest.mark.parametrize("enabled", [True, False])
def test_resolves_count_the_distinct_group_filter_pairs(enabled):
    b = _broker(True)
    metrics = Metrics()
    b.metrics = metrics
    b.telemetry = b.router.telemetry = Telemetry(
        TelemetryConfig(enabled=enabled), metrics=metrics)
    a, c, d = Q("a"), Q("c"), Q("d")
    b.subscribe(a, "v/+")
    b.subscribe(a, "v/#")
    b.subscribe(c, "v/+")
    b.subscribe(d, "v/9")
    # a: v/+ and v/# recur over five messages (2 pairs); c: v/+
    # (1 pair); d: v/9 once (1 pair): 4 resolutions for 16 deliveries
    res = b.publish_batch(_msgs("v/1", "v/2", "v/1", "v/9", "v/3"))
    assert res == [3, 3, 3, 4, 3]
    assert metrics.val("messages.delivered") == 16
    assert metrics.val("delivery.plan.resolves") == (4 if enabled else 0)
    # a second batch resolves anew: the memo is the group's, per batch
    b.publish_batch(_msgs("v/1", "v/1"))
    assert metrics.val("delivery.plan.resolves") == (7 if enabled else 0)
    assert "delivery.plan.resolves" in ALL_METRICS


def test_the_legacy_tail_resolves_nothing():
    b = _broker(False)
    metrics = Metrics()
    b.metrics = metrics
    b.telemetry = b.router.telemetry = Telemetry(
        TelemetryConfig(enabled=True), metrics=metrics)
    s = Q("s")
    b.subscribe(s, "w/+")
    assert b.publish_batch(_msgs("w/1", "w/2")) == [1, 1]
    assert metrics.val("delivery.plan.resolves") == 0


# -- the benchmark's reading of the counter -----------------------------------


def _json(*path):
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, *path), encoding="utf-8") as f:
        return json.load(f)


@pytest.mark.parametrize("name,cells", [
    ("plan_resolve_share", ["fleet_1m.flood", "fanout_1k.flood"]),
    ("plan_resolve_share.uniform", ["fleet_1m_uniform.flood"]),
])
def test_plan_resolve_share_file_matches_its_benchmark_entry(name, cells):
    spec = _json("BENCHMARK.json")
    entry = _json("benchmark", "layer_metrics", name + ".json")
    listed = [m for m in spec["per_layer"] if m["name"] == name]
    assert len(listed) == 1
    for key in ("unit", "better", "source", "layer", "moves"):
        assert listed[0][key] == entry[key], key
    assert listed[0]["workloads"] == cells
    # appended at PR 34, the pair in this order (later PRs append
    # after it); the mesh cell's per-layer list is pinned elsewhere:
    # not touched
    names = [m["name"] for m in spec["per_layer"]]
    at = names.index("plan_resolve_share")
    assert names[at + 1] == "plan_resolve_share.uniform"
    assert os.path.exists(os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "benchmark", "reducers", entry["reducer"] + ".py"))
    assert entry["args"] == {"counters": ["delivery.plan.resolves"],
                             "per": "counter:messages.delivered"}
    assert set(entry["args"]["counters"]) <= set(ALL_METRICS)
    # the twin reads what the base reads, and both keep the name of
    # the tail's layer letter for letter
    base = _json("benchmark", "layer_metrics", "plan_resolve_share.json")
    assert entry == base
    tail = next(m for m in spec["per_layer"]
                if m["name"] == "tail_us_per_delivery")
    assert tail["layer"] == entry["layer"]
