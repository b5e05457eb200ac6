"""Loop kind ``churn``: ``flood``'s closed loop over links that flap.
Every publisher is a device: it holds the configuration's ``devices``
filters on its own connection, sends bursts as ``flood`` does, and
every ``session_s`` seconds it leaves and comes back with a clean
session. A session is CONNECT, CONNACK, SUBSCRIBE, SUBACK, a probe
published on the device's own command topic that must come back on
this connection, bursts, and at its end, after the last fence's PUBACK,
the probe once more. So a subscription that the broker acknowledged is
shown to be live at both ends of every session.

:func:`due` is the schedule, a pure function. A reconnect comes after
the fence in flight, never inside a burst: a slow broker gets fewer
reconnects, as it gets fewer bursts. Reconnect ``k`` of a device
(counted over the run) is a takeover when :func:`is_takeover` says so:
the new connection is opened under the same client id while the old
one is open. Every wait ends after :func:`wait_limit` seconds with a
``ConnectionError``, which ``Publishers.run_phase`` counts; a device
that failed stays failed and stops the fleet (every other device ends
at its next burst: the run cannot be correct any more, and a broker
that cannot serve the deployment ends its run soon). In the measured
window that is a run with ``connections_failed`` above 0. In a warm
round it is :class:`FleetLost`, which ``run_phase`` does not count:
the publishers' process ends, and ``run.py`` ends the run at once
with exit code 1 and no result line, traced or not (a fleet that
stopped before its window publishes nothing in it, and a traced run
in which the device ran nothing is a failure of ``run.py``'s own:
the run must not end one way untraced and another traced). Only a
CONNECT answered "server unavailable" (the overload guard) is tried
again, inside the same limit. The probe's
payload carries phase 0xFFFF in
the harness's header, so no sample of the sink can take it for a
message, and it is not counted among the messages sent."""

from __future__ import annotations

import asyncio
import math
import time

from loadgen import (HEADER, build_connect, build_subscribe,
                     publish_prefix, say)
from reference import matches

#: the golden ratio's fraction: each phase turns the fleet's schedule
#: by this share of a session, so that rounds reach different devices
TURN = 0.381966
#: the phase number a probe carries (no phase of a run has it)
PROBE_PHASE = 0xFFFF


def due(pub: int, n_pubs: int, phase: int, t0: float, t_end: float,
        session_s: float) -> list:
    """The instants in [t0, t_end) at which device ``pub`` of
    ``n_pubs`` is due to reconnect in a phase."""
    first = math.modf(pub / n_pubs + TURN * phase)[0] * session_s
    out = []
    j = 0
    while t0 + first + j * session_s < t_end:
        out.append(t0 + first + j * session_s)
        j += 1
    return out


def is_takeover(k: int, share: float) -> bool:
    """Whether a device's reconnect number ``k`` (from 0) is made over
    the open old connection."""
    return math.floor((k + 1) * share) > math.floor(k * share)


def wait_limit(traffic: dict, phase: int) -> float:
    """How long a device waits in ``phase`` before it gives up: the
    run's first ``cold_rounds`` warm rounds (phases 1, 2, ...; the
    window is phase 0) have a limit of their own, because the run's
    first batch flattens, builds and compiles with every device's
    first probe behind it."""
    if 1 <= phase <= traffic.get("cold_rounds", 0):
        return float(traffic["cold_wait_limit_s"])
    return float(traffic["wait_limit_s"])


class FleetLost(Exception):
    """A device failed in a warm round: the broker cannot serve the
    deployment, and the run ends before its window (no
    ``ConnectionError``: ``Publishers.run_phase`` lets it through)."""


class Fleet:
    """What the devices keep from phase to phase, held on the
    ``Publishers`` object."""

    def __init__(self, pubs) -> None:
        cfg = pubs.plan.config["devices"]
        n = pubs.plan.n_pubs
        self.qos = cfg["qos"]
        self.filters = [[f.format(i=p) for f in cfg["filters"]]
                        for p in range(n)]
        self.probe_topic = [cfg["probe"].format(i=p) for p in range(n)]
        self.probe_pre = [publish_prefix(t, pubs.plan.payload_len, 0)
                          for t in self.probe_topic]
        #: the probe comes back where a filter of the device matches it
        self.probe_due = [any(matches(t, f) for f in fl)
                          for t, fl in zip(self.probe_topic, self.filters)]
        self.limit = 0.0  # of the phase that runs: set_limit
        self.subscribed = [False] * n
        self.reconnects = [0] * n
        self.probes = [0] * n
        self.dead = [False] * n
        #: the first device that failed: the run cannot be correct any
        #: more, and every device stops at its next burst
        self.first_failed = None
        self.running = 0
        self.retry_s = float(pubs.plan.traffic["busy_retry_s"])
        self.opened = self.takeovers = self.answered = self.refusals = 0


async def _wait(aw, limit: float, what: str):
    try:
        async with asyncio.timeout(limit):
            return await aw
    except TimeoutError:
        raise ConnectionError(f"no {what} within {limit:g} s") from None


async def _packet(r):
    """-> (first byte, body) of the next packet on the connection."""
    head = await r.readexactly(2)
    n = head[1] & 0x7F
    shift = 7
    more = head[1] & 0x80
    while more:
        b = (await r.readexactly(1))[0]
        n |= (b & 0x7F) << shift
        shift += 7
        more = b & 0x80
    return head[0], (await r.readexactly(n) if n else b"")


async def _fence(fl: Fleet, r, pub: int, seq: int) -> None:
    want = (seq % 0xFFFF) + 1
    b0, body = await _wait(_packet(r), fl.limit, "PUBACK")
    if b0 != 0x40 or ((body[0] << 8) | body[1]) != want:
        raise ConnectionError(f"device {pub}: expected PUBACK {want}, "
                              f"got {b0:#x} {body[:8].hex()}")


async def _probe(pubs, fl: Fleet, pub: int, r, w) -> None:
    """One message on the device's own command topic; it must come
    back on this connection."""
    n = fl.probes[pub]
    fl.probes[pub] = n + 1
    payload = HEADER.pack(PROBE_PHASE, 0, pub, n, time.monotonic()) \
        + pubs.filler
    w.write(fl.probe_pre[pub] + payload)
    await w.drain()
    if not fl.probe_due[pub]:
        return
    b0, body = await _wait(_packet(r), fl.limit, "probe back")
    tl = (body[0] << 8) | body[1] if len(body) >= 2 else 0
    if b0 >> 4 != 3 or body[2:2 + tl].decode() != fl.probe_topic[pub] \
            or body[2 + tl + (2 if b0 & 0x06 else 0):] != payload:
        raise ConnectionError(f"device {pub}: probe {n} came back as "
                              f"{b0:#x} {body[:24].hex()}")
    fl.answered += 1


async def _subscribe(pubs, fl: Fleet, pub: int, r, w) -> None:
    w.write(build_subscribe(1, fl.filters[pub], fl.qos))
    await w.drain()
    b0, body = await _wait(_packet(r), fl.limit, "SUBACK")
    if b0 >> 4 != 9 or any(rc > 2 for rc in body[2:]):
        raise ConnectionError(f"device {pub}: SUBSCRIBE answered "
                              f"{b0:#x} {body.hex()}")
    fl.subscribed[pub] = True
    await _probe(pubs, fl, pub, r, w)


async def _reconnect(pubs, fl: Fleet, pub: int) -> None:
    """The session's end and the next one's start."""
    r, w = pubs.conns[pub]
    await _probe(pubs, fl, pub, r, w)
    k = fl.reconnects[pub]
    fl.reconnects[pub] = k + 1
    over = is_takeover(k, pubs.plan.traffic["takeover_share"])
    host, port = w.get_extra_info("peername")[:2]
    if not over:
        w.write(b"\xe0\x00")  # DISCONNECT
        await w.drain()
        w.close()
    fl.subscribed[pub] = False
    # a broker whose overload guard reads critical answers CONNACK 3
    # (server unavailable) and closes: the device tries again, as a
    # fleet's client does, until the wait limit has run out
    t_give_up = time.monotonic() + fl.limit
    while True:
        r2, w2 = await _wait(asyncio.open_connection(host, port), fl.limit,
                             "TCP connection")
        w2.write(build_connect(f"bench-pub-{pub}"))
        await w2.drain()
        ack = await _wait(r2.readexactly(4), fl.limit, "CONNACK")
        if ack[:3] == b"\x20\x02\x00" and ack[3] == 3 \
                and time.monotonic() + fl.retry_s < t_give_up:
            fl.refusals += 1
            w2.close()
            await asyncio.sleep(fl.retry_s)
            continue
        break
    pubs.conns[pub] = (r2, w2)
    if over:
        w.close()  # the broker has ended it, or does now
        fl.takeovers += 1
    if ack[0] != 0x20 or ack[3] != 0:
        raise ConnectionError(f"device {pub}: CONNECT answered "
                              f"{ack.hex()}")
    fl.opened += 1
    await _subscribe(pubs, fl, pub, r2, w2)


async def publisher(pubs, pub: int, phase: int, t0: float, t_end: float,
                    late) -> int:
    """Run device ``pub`` from ``t0`` to ``t_end``; return how many
    messages it sent (sequence numbers 0..n-1; probes are none)."""
    fl = getattr(pubs, "fleet", None)
    if fl is None:
        fl = pubs.fleet = Fleet(pubs)
    if fl.first_failed is not None:
        raise ConnectionError(f"the fleet stopped when device "
                              f"{fl.first_failed} failed")
    tr = pubs.plan.traffic
    fl.limit = wait_limit(tr, phase)
    burst = tr["burst"]
    base = pubs.plan.base(pub, pubs.start)
    dues = due(pub, pubs.plan.n_pubs, phase, t0, t_end, tr["session_s"])
    fl.running += 1
    try:
        await asyncio.sleep(max(0.0, t0 - time.monotonic()))
        if not fl.subscribed[pub]:
            # the harness's own connection: the device's first session
            await _subscribe(pubs, fl, pub, *pubs.conns[pub])
        seq = 0
        while True:
            if fl.first_failed is not None:
                raise ConnectionError(f"the fleet stopped when device "
                                      f"{fl.first_failed} failed")
            now = time.monotonic()
            if dues and now >= dues[0]:
                # one reconnect for all that is overdue
                while dues and now >= dues[0]:
                    dues.pop(0)
                await _reconnect(pubs, fl, pub)
                now = time.monotonic()
            if now >= t_end:
                return seq
            r, w = pubs.conns[pub]
            w.write(pubs.frames(pub, phase, base, seq, burst, now, True))
            await w.drain()
            seq += burst
            await _fence(fl, r, pub, seq - 1)
    except BaseException as e:
        fl.dead[pub] = True
        first = fl.first_failed is None
        if first:
            fl.first_failed = pub
        if first and phase != 0 and isinstance(
                e, (ConnectionError, asyncio.IncompleteReadError, OSError)):
            raise FleetLost(f"device {pub} failed in warm round {phase}, "
                            f"before the window: {e!r}") from e
        raise
    finally:
        fl.running -= 1
        if not fl.running:
            say(f"churn: phase {phase}: {fl.opened} sessions opened so "
                f"far, {fl.takeovers} of them by takeover, "
                f"{fl.answered} of {sum(fl.probes)} probes answered, "
                f"{fl.refusals} CONNECTs answered server unavailable "
                f"and tried again, "
                f"{sum(fl.dead)} devices failed")
