"""Shared live-broker fixtures for the integration-tier suites."""

import contextlib

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
