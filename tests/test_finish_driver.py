"""The delivery tail has one owner (``Broker.finish_steps``): it picks
the tail of a begun batch once (deferred host routing, the planned
walk, or the per-row packed walk an overflow row forces) and both
drivers run it: the synchronous ``publish_finish`` in one step, the
async ingress a chunk at a time with the loop given back in between.
Whatever the driver and the chunk, a batch gives the same results, the
same deliveries in the same order at every subscriber, and its span is
closed once."""

import asyncio

import pytest

from emqx_tpu.broker import Broker
from emqx_tpu.ingress import IngressBatcher
from emqx_tpu.metrics import Metrics
from emqx_tpu.router import MatcherConfig, Router
from emqx_tpu.telemetry import Telemetry, TelemetryConfig
from emqx_tpu.types import Message
from helpers import record_spans

N_SUBS = 5


class Q:
    def __init__(self, client_id):
        self.client_id = client_id
        self.inbox = []

    def deliver(self, flt, msg):
        self.inbox.append((flt, msg.topic, bytes(msg.payload)))


# kind -> (matcher config, defer_host, the tail finish_steps must pick)
KINDS = {
    "deferred_host": (dict(device_min_filters=1 << 20), True,
                      "publish_host_chunk"),
    "planned": (dict(device_min_filters=0), False,
                "publish_finish_planned"),
    "overflow_row": (dict(device_min_filters=0, max_matches=1), False,
                     "publish_finish_chunk"),
}


def _broker(kind):
    b = Broker(router=Router(MatcherConfig(**KINDS[kind][0]),
                             node="node1"))
    b.metrics = Metrics()
    b.telemetry = b.router.telemetry = Telemetry(
        TelemetryConfig(enabled=True), metrics=b.metrics)
    subs = [Q(f"c{i}") for i in range(N_SUBS)]
    for i, s in enumerate(subs):
        b.subscribe(s, f"t/{i}/+")
        b.subscribe(s, "t/#")      # two filters a topic: over max_matches=1
    return b, subs


def _msgs():
    # 12 live rows, every subscriber group hit, a repeat and a miss
    topics = [f"t/{i % N_SUBS}/x" for i in range(10)] + ["none", "t/0/x"]
    return [Message(topic=t, payload=b"p%d" % i)
            for i, t in enumerate(topics)]


def _begun(b, kind):
    """A batch ready for its tail, the tail's name, and the calls to
    it from here on."""
    _, defer, tail = KINDS[kind]
    msgs = _msgs()
    pb = b.publish_begin(msgs, defer_host=defer,
                         span=b.telemetry.begin(len(msgs)))
    assert not pb.done
    if pb.host_topics is None:
        b.publish_fetch(pb)
    calls = []
    for name in ("publish_host_chunk", "publish_finish_planned",
                 "publish_finish_chunk"):
        fn = getattr(b, name)
        setattr(b, name, lambda pb, s, e, fn=fn, name=name:
                (calls.append((name, s, e)), fn(pb, s, e))[1])
    return pb, tail, calls


def _sync(b, pb):
    return b.publish_finish(pb), 0


def _steps(chunk):
    def run(b, pb):
        yields = sum(1 for _ in b.finish_steps(pb, chunk))
        b.xloop_join_sync(pb)
        return pb.results, yields
    return run


def _ingress(chunk):
    """The async driver's own completion, on a loop."""
    def run(b, pb):
        async def go():
            bat = IngressBatcher(b, finish_chunk=chunk)
            bat._inflight = 1       # what ``_flush`` counted at begin
            loop = asyncio.get_running_loop()
            futs = [loop.create_future() for _ in pb.results]
            sp = pb.span
            await bat._complete(pb, [(None, f) for f in futs], None)
            assert pb.done and bat._inflight == 0
            return ([f.result() for f in futs],
                    "tail_yield" in sp.stages)
        return asyncio.run(go())
    return run


def _units(pb, tail):
    return pb.plan.n_groups if tail == "publish_finish_planned" \
        else len(pb.live)


@pytest.mark.parametrize("kind", list(KINDS))
def test_every_driver_of_the_tail_gives_the_same_batch(kind):
    seen = {}
    drivers = {"sync": (_sync, None), "steps_1": (_steps(1), 1),
               "steps_64": (_steps(64), 64), "ingress_1": (_ingress(1), 1)}
    for name, (run, chunk) in drivers.items():
        b, subs = _broker(kind)
        spans = record_spans(b.telemetry)
        pb, tail, calls = _begun(b, kind)
        n = _units(pb, tail)
        assert n > 1
        res, yields = run(b, pb)
        # one tail, chosen once, walked in order without a gap
        step = chunk or n
        assert calls == [(tail, s, min(s + step, n))
                         for s in range(0, n, step)], (name, calls)
        if name.startswith("steps"):
            assert yields == len(calls) - 1   # between steps only
        elif name == "ingress_1":
            assert yields                     # the loop had its turns
        assert len(spans) == 1 and spans[0].closed and pb.span is None
        b.telemetry.finish(spans[0])          # closed: not counted again
        assert len(spans) == 1
        seen[name] = (list(res), [s.inbox for s in subs],
                      b.metrics.val("messages.delivered"))
    want = seen.pop("sync")
    assert want[0] == [N_SUBS + 1] * 10 + [0, N_SUBS + 1] and all(want[1])
    for name, got in seen.items():
        assert got == want, name
