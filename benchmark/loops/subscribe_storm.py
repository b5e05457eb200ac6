"""Loop kind ``subscribe_storm``: ``flood``'s closed loop beside a
fleet that comes back. The cell's publishers send bursts exactly as
``flood`` does (QoS 0 publishes ended by one QoS 1 fence, the next
burst on its PUBACK). Beside them the configuration's ``gateways``,
connections this loop opens itself, subscribe new wildcard filters on
an open-loop schedule: fleet-wide ``subscribe_rate`` filters a second
in SUBSCRIBE packets of ``filters_per_packet``, and every packet is
followed by the three probes that show the three states a
subscription can be in:

1. after the SUBACK one probe on the newest filter's topic: it must
   come back on this connection (the filter is pending in the delta
   automaton, or frozen behind a flatten);
2. then one UNSUBSCRIBE with the gateway's ``unsubscribe_share`` x
   ``filters_per_packet`` oldest live filters, and after the UNSUBACK
   a probe on one removed filter's topic followed by a probe on the
   gateway's now-oldest live filter's: the second must come back (that
   filter was folded into the main tables merges ago) and the first
   must not (a tombstone, or a retracted add): a returned first is a
   failure.

Arrival ``k`` of gateway ``g`` (counted over the run) takes filter
template ``k mod len(filters)``; whether a probe is due back is asked
of ``reference.matches`` over the gateway's live filters, a plain
list.

:func:`due` is the schedule, a pure function of gateway, phase and
``t0``. A gateway that is late sends late and never skips: a phase
ends when every packet that was due in it has been answered. Every
wait ends after ``churn.wait_limit`` seconds; a gateway that fails
stops the run as a device does in ``churn``: every publisher ends at
its next burst, in the measured window with a ``ConnectionError``
(``connections_failed``), in a warm round with ``churn.FleetLost``
(exit code 1 and no result line, traced or not). Probes carry phase
0xFFFF in the harness's header and are not counted among the messages
sent."""

from __future__ import annotations

import asyncio
import math
import struct
import time
from collections import deque

from loadgen import (HEADER, build_connect, build_subscribe, enc_str, frame,
                     publish_prefix, say)
from loops.churn import (PROBE_PHASE, TURN, FleetLost, _packet, _wait,
                         wait_limit)
from reference import matches

_LOST = (ConnectionError, asyncio.IncompleteReadError, OSError)


def period(traffic: dict, config: dict) -> float:
    """Seconds between two SUBSCRIBE packets of one gateway:
    ``subscribe_rate`` filters a second fleet-wide is what a fleet of
    ``subscribe_rate_fleet`` resident filters sends; a configuration
    with another population sends in proportion (a fleet that comes
    back subscribes in proportion to its size, so a cut-down copy of
    the deployment stays inside the tables it is sized for), but never
    under ``subscribe_rate_floor``: a trickle that makes the delta
    live before the harness's warmers run."""
    rate = max(traffic["subscribe_rate"] * config["population"]["filters"]
               / traffic["subscribe_rate_fleet"],
               traffic["subscribe_rate_floor"])
    return config["gateways"]["count"] * traffic["filters_per_packet"] \
        / rate


def due(g: int, n_gateways: int, phase: int, t0: float, t_end: float,
        every: float) -> list:
    """The instants in [t0, t_end) at which gateway ``g`` of
    ``n_gateways`` is due to send a SUBSCRIBE packet in a phase:
    ``t0 + (j + frac(g / n + TURN * phase)) * every``."""
    first = math.modf(g / n_gateways + TURN * phase)[0] * every
    out = []
    j = 0
    while t0 + first + j * every < t_end:
        out.append(t0 + first + j * every)
        j += 1
    return out


def build_unsubscribe(pkt_id: int, filters) -> bytes:
    return frame(10, 0x02, struct.pack(">H", pkt_id)
                 + b"".join(enc_str(f) for f in filters))


class Storm:
    """What the gateways keep from phase to phase, held on the
    ``Publishers`` object."""

    def __init__(self, pubs) -> None:
        cfg = pubs.plan.config["gateways"]
        tr = pubs.plan.traffic
        self.n = cfg["count"]
        self.cfg = cfg
        self.qos = cfg["qos"]
        self.per_packet = tr["filters_per_packet"]
        self.drop = int(tr["unsubscribe_share"] * self.per_packet)
        self.every = period(tr, pubs.plan.config)
        self.retry_s = float(tr["busy_retry_s"])
        self.conns = [None] * self.n
        #: (filter, its probe topic), oldest first
        self.live = [deque() for _ in range(self.n)]
        self.arrivals = [0] * self.n
        self.pkt_ids = [0] * self.n
        self.probes = [0] * self.n
        self.phase = None
        self.tasks = None
        self.limit = 0.0
        #: the first gateway that failed, and why: the run cannot be
        #: correct any more, and every publisher stops at its next burst
        self.first_failed = None
        self.told = False
        self.subscribed = self.unsubscribed = 0
        self.answered = self.withheld = self.refusals = 0
        self.late_s = 0.0

    def arrival(self, g: int) -> tuple:
        """The next arrival of gateway ``g``: (filter, probe topic)."""
        k = self.arrivals[g]
        self.arrivals[g] = k + 1
        i = k % len(self.cfg["filters"])
        return (self.cfg["filters"][i].format(g=g, k=k),
                self.cfg["probes"][i].format(g=g, k=k))

    def next_id(self, g: int) -> int:
        self.pkt_ids[g] = self.pkt_ids[g] % 0xFFFF + 1
        return self.pkt_ids[g]


async def _connect(pubs, st: Storm, g: int):
    host, port = pubs.conns[0][1].get_extra_info("peername")[:2]
    t_give_up = time.monotonic() + st.limit
    while True:
        r, w = await _wait(asyncio.open_connection(host, port), st.limit,
                           "TCP connection")
        w.write(build_connect(st.cfg["client_id"].format(g=g)))
        await w.drain()
        ack = await _wait(r.readexactly(4), st.limit, "CONNACK")
        # the overload guard reads critical while the node compiles:
        # tried again inside the limit, as a fleet's client does
        if ack[:3] == b"\x20\x02\x00" and ack[3] == 3 \
                and time.monotonic() + st.retry_s < t_give_up:
            st.refusals += 1
            w.close()
            await asyncio.sleep(st.retry_s)
            continue
        if ack[0] != 0x20 or ack[3] != 0:
            raise ConnectionError(f"gateway {g}: CONNECT answered "
                                  f"{ack.hex()}")
        return r, w


async def _acked(st: Storm, g: int, r, kind: int, pkt_id: int, n: int,
                 what: str) -> None:
    b0, body = await _wait(_packet(r), st.limit, what)
    if b0 >> 4 != kind or ((body[0] << 8) | body[1]) != pkt_id \
            or (kind == 9 and (len(body) != 2 + n
                               or any(rc > 2 for rc in body[2:]))):
        raise ConnectionError(f"gateway {g}: expected {what} {pkt_id}, "
                              f"got {b0:#x} {body[:24].hex()}")


def _probe(pubs, st: Storm, g: int, w, topic: str) -> tuple:
    """Write one probe; -> (topic, payload, whether it is due back: a
    live filter of the gateway matches it)."""
    n = st.probes[g]
    st.probes[g] = n + 1
    payload = HEADER.pack(PROBE_PHASE, 0, g, n, time.monotonic()) \
        + pubs.filler
    w.write(publish_prefix(topic, pubs.plan.payload_len, 0) + payload)
    return topic, payload, any(matches(topic, f) for f, _t in st.live[g])


async def _back(st: Storm, g: int, r, probe: tuple) -> None:
    topic, payload, _due = probe
    b0, body = await _wait(_packet(r), st.limit, "probe back")
    tl = (body[0] << 8) | body[1] if len(body) >= 2 else 0
    if b0 >> 4 != 3 or body[2:2 + tl].decode() != topic \
            or body[2 + tl + (2 if b0 & 0x06 else 0):] != payload:
        raise ConnectionError(
            f"gateway {g}: expected the probe on {topic} back, got "
            f"{b0:#x} {body[:40]!r}")
    st.answered += 1


async def _one_packet(pubs, st: Storm, g: int) -> None:
    """One arrival of ``filters_per_packet`` units behind gateway
    ``g``: SUBSCRIBE, the newest filter's probe, UNSUBSCRIBE of the
    oldest, the removed filter's probe and the oldest live one's."""
    r, w = st.conns[g]
    live = st.live[g]
    new = [st.arrival(g) for _ in range(st.per_packet)]
    pid = st.next_id(g)
    w.write(build_subscribe(pid, [f for f, _t in new], st.qos))
    await w.drain()
    await _acked(st, g, r, 9, pid, len(new), "SUBACK")
    live.extend(new)
    st.subscribed += len(new)
    probe = _probe(pubs, st, g, w, new[-1][1])
    await w.drain()
    if not probe[2]:
        raise ConnectionError(f"gateway {g}: the reference expects no "
                              f"probe back on {probe[0]}")
    await _back(st, g, r, probe)
    if not st.drop:
        return
    gone = [live.popleft() for _ in range(st.drop)]
    pid = st.next_id(g)
    w.write(build_unsubscribe(pid, [f for f, _t in gone]))
    await w.drain()
    await _acked(st, g, r, 11, pid, len(gone), "UNSUBACK")
    st.unsubscribed += len(gone)
    removed = _probe(pubs, st, g, w, gone[0][1])
    oldest = _probe(pubs, st, g, w, live[0][1])
    await w.drain()
    if removed[2] or not oldest[2]:
        raise ConnectionError(
            f"gateway {g}: the reference expects {removed[0]} "
            f"{'back' if removed[2] else 'withheld'} and {oldest[0]} "
            f"{'back' if oldest[2] else 'withheld'}")
    # the next PUBLISH on the connection is the second probe: the
    # first coming back instead is the failure
    await _back(st, g, r, oldest)
    st.withheld += 1


async def gateway(pubs, st: Storm, g: int, phase: int, t0: float,
                  t_end: float) -> None:
    try:
        if st.conns[g] is None:
            # before the phase's first burst: a node that is idle
            # accepts at once, one that compiles refuses for a while
            st.conns[g] = await _connect(pubs, st, g)
        for t_due in due(g, st.n, phase, t0, t_end, st.every):
            await asyncio.sleep(max(0.0, t_due - time.monotonic()))
            if st.first_failed is not None:
                return
            st.late_s = max(st.late_s, time.monotonic() - t_due)
            await _one_packet(pubs, st, g)
    except _LOST as e:
        if st.first_failed is None:
            st.first_failed = (g, repr(e))
            say(f"storm: gateway {g} failed in phase {phase}: {e!r}")


def _stopped(st: Storm, phase: int):
    """What a publisher raises once a gateway has failed: the first to
    notice it in a warm round ends the run, every other is counted."""
    g, why = st.first_failed
    if phase != 0 and not st.told:
        st.told = True
        return FleetLost(f"gateway {g} failed in warm round {phase}, "
                         f"before the window: {why}")
    return ConnectionError(f"the fleet stopped when gateway {g} "
                           f"failed: {why}")


def _begin(pubs, st: Storm, phase: int, t0: float, t_end: float) -> None:
    tr = pubs.plan.traffic
    st.phase = phase
    st.limit = wait_limit(tr, phase)
    st.late_s = 0.0
    cfg = pubs.plan.config
    held = cfg["population"]["filters"] \
        + sum(len(gr["filters"]) * gr["count"] for gr in cfg["sockets"])
    added = sum(st.arrivals)
    say(f"storm: phase {phase} starts: {added} filters subscribed so "
        f"far, {st.unsubscribed} of them unsubscribed; filter ids in "
        f"use at most {held + added} of 1048576 (headroom "
        f"{1048576 - held - added}, before any id a merge gave back), "
        f"{st.every:g} s between a gateway's packets")
    st.tasks = asyncio.gather(*(
        asyncio.ensure_future(gateway(pubs, st, g, phase, t0, t_end))
        for g in range(st.n)))


async def publisher(pubs, pub: int, phase: int, t0: float, t_end: float,
                    late) -> int:
    """Run publisher ``pub`` from ``t0`` to ``t_end`` as ``flood``
    does; return how many messages it sent (sequence numbers 0..n-1;
    probes are none). The first publisher of a phase starts the
    gateways, and every publisher waits for them at its end."""
    st = getattr(pubs, "storm", None)
    if st is None:
        st = pubs.storm = Storm(pubs)
    if st.first_failed is not None:
        raise _stopped(st, phase)
    if st.phase != phase:
        _begin(pubs, st, phase, t0, t_end)
    tasks = st.tasks
    burst = pubs.plan.traffic["burst"]
    base = pubs.plan.base(pub, pubs.start)
    _r, w = pubs.conns[pub]
    await asyncio.sleep(max(0.0, t0 - time.monotonic()))
    seq = 0
    while st.first_failed is None:
        now = time.monotonic()
        if now >= t_end:
            break
        w.write(pubs.frames(pub, phase, base, seq, burst, now, True))
        await w.drain()
        seq += burst
        await pubs.await_fence(pub, seq - 1)
    await tasks
    if st.first_failed is not None:
        raise _stopped(st, phase)
    if st.tasks is tasks:
        st.tasks = None  # said once a phase, by the first to get here
        say(f"storm: phase {phase}: {st.subscribed} filters subscribed "
            f"and {st.unsubscribed} unsubscribed so far, "
            f"{sum(len(q) for q in st.live)} live; {st.answered} probes "
            f"came back, {st.withheld} were withheld as they must; "
            f"{st.refusals} CONNECTs answered server unavailable and "
            f"tried again; a packet was sent at most "
            f"{st.late_s * 1e3:.0f} ms late")
    return seq
