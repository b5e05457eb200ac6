"""Shared live-broker fixtures for the integration-tier suites."""

import asyncio
import contextlib
import sys
import threading

import jax
from jax._src import dispatch as jax_dispatch
from jax._src.interpreters import pxla

from emqx_tpu.node import Node


@contextlib.asynccontextmanager
async def broker_node(**kw):
    n = Node(**kw)
    n.add_listener(port=0)  # ephemeral port
    await n.start()
    try:
        yield n
    finally:
        await n.stop()


def node_port(node):
    return node.listeners[0].port


class Inbox:
    """An in-process subscriber that keeps what it is delivered."""

    def __init__(self, client_id="c"):
        self.client_id = client_id
        self.inbox = []

    def deliver(self, topic, msg):
        self.inbox.append((topic, msg))


class CounterTel:
    """What ``Router._live_metrics`` asks of a telemetry: counters
    live, stage timing off."""

    enabled = False

    def __init__(self):
        from emqx_tpu.metrics import Metrics

        self.metrics = Metrics()

    def loop_clock(self):
        return self


def record_spans(tel) -> list:
    """Keep every span ``tel`` finishes from now on (the benchmark
    harness's seam: ``Telemetry.finish`` shadowed on the instance)."""
    spans = []
    finish = tel.finish

    def _record(span):
        if not span.closed:
            finish(span)
            spans.append(span)
    tel.finish = _record
    return spans


async def device_node(name: str, **kw) -> Node:
    """A started node without listeners whose every batch takes the
    device path (``device_min_filters = 0``)."""
    from emqx_tpu.router import MatcherConfig

    node = Node(name=name, boot_listeners=False,
                matcher=MatcherConfig(device_min_filters=0), **kw)
    await node.start()
    return node


async def until(cond, what="condition never held"):
    """Poll ``cond`` on the running loop, up to ~4 s."""
    for _ in range(2000):
        if cond():
            return
        await asyncio.sleep(0.002)
    raise AssertionError(what)


class PathGate:
    """Keeps batches on the device path as long as a test wants:
    every device fetch of ``node`` stands on its executor thread until
    ``land()``. Keeps the topics of every ``publish_begin`` in order
    (``$SYS`` aside), and counts from outside the flushes that began
    nothing with a slot free and messages pending (``held``)."""

    def __init__(self, node):
        self.node = node
        self.ing = ing = node.broker.ingress
        self.gate = threading.Event()
        self.began = []
        self.held = 0
        b = node.broker
        fetch, begin, flush = b._fetch_device, b.publish_begin, ing._flush

        def gated(pb):
            assert self.gate.wait(30)
            fetch(pb)

        def record(msgs, *a, **kw):
            topics = [m.topic for m in msgs
                      if not m.topic.startswith("$SYS/")]
            if topics:
                self.began.append(topics)
            return begin(msgs, *a, **kw)

        def watched():
            before = ing.flushes
            flush()
            if ing.flushes == before and ing._pending \
                    and ing._inflight < ing.max_inflight:
                self.held += 1

        b._fetch_device, b.publish_begin, ing._flush = \
            gated, record, watched

    def land(self):
        self.gate.set()

    def hold_tails(self):
        """Every batch whose fetch returns from now on delivers and
        then waits, its tail not done, where a multi-loop node's
        cross-loop join stands in ``_complete``."""
        self.tails = tails = asyncio.Event()
        self.node.broker.xloop_event = lambda pb: tails

    def finish(self):
        self.tails.set()

    async def landed(self, n=1):
        """``n`` batches off the device path, their tails not done."""
        await until(lambda: self.ing._on_path == 0
                    and self.ing._inflight == n,
                    f"never {n} landed with the tail not done")

    def counted(self, what="held"):
        return self.node.metrics.val(f"ingress.flush.{what}")

    def grown(self):
        """Takes of more than ``batch_size`` messages, from outside."""
        return sum(len(b) > self.ing.batch_size for b in self.began)

    async def on_the_path(self, n=1):
        await until(lambda: self.ing._on_path == n,
                    f"never {n} on the device path")


class Wire:
    """A ``StreamWriter`` and its transport for a ``Connection`` that
    a test drives from an ``asyncio.StreamReader`` through
    ``Connection.run``; keeps what was written."""

    def __init__(self):
        self.transport = self
        self.out = bytearray()
        self.closed = False

    def get_extra_info(self, key, default=None):
        return ("10.1.2.3", 4242) if key == "peername" else default

    def write(self, data):
        self.out += data

    def writelines(self, pieces):
        for p in pieces:
            self.out += p

    def close(self):
        self.closed = True

    abort = close

    def is_closing(self):
        return self.closed

    async def drain(self):
        pass

    async def wait_closed(self):
        pass

    def get_write_buffer_size(self):
        return 0

    def set_write_buffer_limits(self, high=None, low=None):
        pass


class Compiles:
    """Programs made ready for first use, as the benchmark's
    ``CompileClock`` counts them (a backend compile and a load from
    the persistent cache alike). ``close()`` when done."""

    def __init__(self):
        import jax.monitoring as mon

        self.compiles = 0
        mon.register_event_duration_secs_listener(self._dur)

    def _dur(self, name, secs, **_kw):
        if name.endswith("backend_compile_duration"):
            self.compiles += 1

    def close(self):
        from jax._src import monitoring

        monitoring.unregister_event_duration_listener(self._dur)


# topics that overflow the match bound alone (> M filters on a shard)
MOVF_FILTERS = [
    "mo/#", "mo/+/#", "mo/a/#", "mo/+/b/#", "mo/a/b/#", "mo/+/+/#",
    "mo/a/+/#", "mo/+/b/c", "mo/a/b/c", "mo/a/b/+", "mo/a/+/c",
    "mo/+/+/c", "mo/+/+/+", "mo/a/+/+", "mo/+/b/+", "+/a/b/c",
    "+/+/b/c", "+/a/+/c", "+/a/b/+", "+/+/+/c", "+/a/+/+", "+/+/b/+",
    "+/+/+/+", "mo/a/b/c/#", "+/a/b/c/#", "+/+/b/c/#"]


class LoopCost:
    """Counting wrappers on what the event loop can hand the device:
    eager operations (``apply_primitive`` looks its callable up through
    ``dispatch.xla_primitive_callable``), host→device transfers
    (``pxla.batched_device_put``: ``jax.device_put`` and every numpy
    argument of a jitted call go through it) and the launches of every
    jitted function the package's modules name."""

    def __init__(self, monkeypatch):
        self.eager = self.transfers = 0
        self.programs = []
        prim, put = jax_dispatch.xla_primitive_callable, \
            pxla.batched_device_put

        def counted_prim(*a, **kw):
            self.eager += 1
            return prim(*a, **kw)

        def counted_put(*a, **kw):
            self.transfers += 1
            return put(*a, **kw)

        monkeypatch.setattr(jax_dispatch, "xla_primitive_callable",
                            counted_prim)
        monkeypatch.setattr(pxla, "batched_device_put", counted_put)
        jitted = type(jax.jit(lambda: 0))
        for name, mod in list(sys.modules.items()):
            if not name.startswith("emqx_tpu") or mod is None:
                continue
            for attr, fn in list(vars(mod).items()):
                if isinstance(fn, jitted):
                    monkeypatch.setattr(mod, attr, self._counted(attr, fn))

    def _counted(self, name, fn):
        def call(*a, **kw):
            self.programs.append(name)
            return fn(*a, **kw)
        return call

    def reset(self):
        self.eager = self.transfers = 0
        self.programs = []
