"""The load generator: a child process of ``run.py`` that speaks MQTT
3.1.1 over real TCP sockets to the broker in the parent.

It imports neither JAX nor ``emqx_tpu`` — the chip belongs to the
parent, and the generator must not share the broker's event loop. The
codec below is written from the OASIS specification (copied in part
from ``tests/indie_mqtt.py``, which shares no code with the broker).

One process plays one role, given on its first line of stdin:

``pub``  the cell's publishers. The loop kind (``loops/<kind>.py``)
         decides when each message is sent.
``sub``  a share of the cell's subscriber sockets. It stamps every
         PUBLISH with the clock at the ``recv`` that brought it,
         answers a QoS 1 PUBLISH with its PUBACK where the
         configuration's ``deliver_qos`` grants QoS 1, and at the end
         of a phase compares, socket by socket, the multiset of
         message ids it received with what the plain reference
         predicate (``reference.matches``) expects; the sockets of a
         shared group (``$share/<group>/<filter>``) are compared
         together, by ``reference.share_of``'s rule.

The parent drives both with JSON lines on stdin (``connect``,
``phase``, ``finish``, ``exit``); each answers with one JSON line on
stdout. Every message carries a 20-byte header in front of a seeded
filler: phase, pool position of its topic, publisher, sequence number
and the instant it was due (``time.monotonic``, one clock for all
processes of a machine).
"""

from __future__ import annotations

import array
import asyncio
import importlib
import json
import os
import random
import selectors
import socket
import struct
import sys
import time

_HERE = os.path.dirname(os.path.abspath(__file__))
if _HERE not in sys.path:
    sys.path.insert(0, _HERE)

#: phase, pool position, publisher, sequence number, due time
HEADER = struct.Struct(">HIHId")
#: the header's first six bytes: phase and pool position
HEADER_KEY = struct.Struct(">HI")


def say(msg: str) -> None:
    sys.stderr.write(f"loadgen[{os.getpid()}]: {msg}\n")
    sys.stderr.flush()


class Lines:
    """Unbuffered line reader on a file descriptor, so that a selector
    on the descriptor and the reader agree on what is still unread."""

    def __init__(self, fd: int) -> None:
        self.fd = fd
        self.buf = b""

    def fileno(self) -> int:
        return self.fd

    def available(self) -> list:
        """The complete lines one ``read`` brings ("" at end of file)."""
        chunk = os.read(self.fd, 65536)
        if not chunk:
            return [""]
        self.buf += chunk
        *lines, self.buf = self.buf.split(b"\n")
        return [ln.decode("utf-8") for ln in lines]

    def readline(self) -> str:
        while b"\n" not in self.buf:
            chunk = os.read(self.fd, 65536)
            if not chunk:
                return ""
            self.buf += chunk
        line, self.buf = self.buf.split(b"\n", 1)
        return line.decode("utf-8")


def reply(obj: dict) -> None:
    sys.stdout.write(json.dumps(obj) + "\n")
    sys.stdout.flush()


# -- codec (MQTT 3.1.1 §2, §3) ----------------------------------------------


def enc_varint(n: int) -> bytes:
    out = bytearray()
    while True:
        b = n % 128
        n //= 128
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def enc_str(s: str) -> bytes:
    b = s.encode("utf-8")
    return struct.pack(">H", len(b)) + b


def frame(ptype: int, flags: int, body: bytes) -> bytes:
    return bytes([(ptype << 4) | flags]) + enc_varint(len(body)) + body


def build_connect(client_id: str, keepalive: int = 0) -> bytes:
    # protocol level 4, clean session
    return frame(1, 0, enc_str("MQTT") + bytes([4, 0x02])
                 + struct.pack(">H", keepalive) + enc_str(client_id))


def build_subscribe(pkt_id: int, filters, qos: int) -> bytes:
    body = struct.pack(">H", pkt_id)
    for f in filters:
        body += enc_str(f) + bytes([qos])
    return frame(8, 0x02, body)


def publish_prefix(topic: str, payload_len: int, qos: int) -> bytes:
    """Everything of a PUBLISH frame before the packet id / payload."""
    t = enc_str(topic)
    rem = len(t) + payload_len + (2 if qos else 0)
    return bytes([0x30 | (qos << 1)]) + enc_varint(rem) + t


def _read_exact(sock: socket.socket, n: int) -> bytes:
    buf = b""
    while len(buf) < n:
        chunk = sock.recv(n - len(buf))
        if not chunk:
            raise ConnectionError("closed")
        buf += chunk
    return buf


def _read_packet(sock: socket.socket):
    h = _read_exact(sock, 1)[0]
    n, mult = 0, 1
    for _ in range(4):
        b = _read_exact(sock, 1)[0]
        n += (b & 0x7F) * mult
        if not b & 0x80:
            break
        mult *= 128
    return h >> 4, _read_exact(sock, n) if n else b""


# -- what both roles derive from the seed -----------------------------------


class Plan:
    """The cell as the generator sees it: topic pool, socket filters,
    publishers, payload. Made from the configuration, the traffic mix
    and the seed alone."""

    def __init__(self, init: dict) -> None:
        self.seed = init["seed"]
        self.config = cfg = init["config"]
        self.traffic = tr = init["traffic"]
        self.law = dict(cfg["publish_topics"],
                        **tr.get("publish_topics", {}))
        self.n_pool = self.law["pool"]
        self.n_pubs = tr["publishers"]
        self.payload_len = cfg["payload_bytes"]
        self.sub_qos = cfg["guarantees"]["deliver_qos"]
        if self.sub_qos not in (0, 1):
            raise ValueError("the subscribers acknowledge QoS 1 alone: "
                             f"deliver_qos {self.sub_qos!r}")
        # the least QoS a delivery may bear: what the publishers send
        # at under the grant (a burst's fence goes out at QoS 1
        # whatever publish_qos says, so between the two is right too)
        self.low_qos = min(self.sub_qos,
                           cfg["guarantees"].get("publish_qos", 0))
        # a control's switch (benchmark/tests): subscribers that keep
        # their PUBACKs back, which a run must not survive
        self.sub_acks = tr.get("subscriber_acks", True)
        # socket index -> its filters
        self.sockets: list = []
        for grp in cfg["sockets"]:
            for i in range(grp["count"]):
                self.sockets.append(
                    [f.format(i=i + grp.get("first", 0))
                     for f in grp["filters"]])
        self.groups = self.shared_groups(tr.get("subscriber_procs", 1))

    def shared_groups(self, n_procs: int) -> dict:
        """group -> (its member sockets, its filters). A group is
        compared as one subscriber, so its sockets live in one
        subscriber process, each holds every filter of the group, and
        none is in two groups: any other split is refused."""
        import reference

        members: dict = {}
        for s, filters in enumerate(self.sockets):
            mine: dict = {}
            for f in filters:
                group, real = reference.share_of(f)
                if group is not None:
                    mine.setdefault(group, set()).add(real)
            if len(mine) > 1:
                raise ValueError(f"socket {s} is in two shared groups")
            for group, reals in mine.items():
                members.setdefault(group, []).append((s, reals))
        out = {}
        for group, socks in members.items():
            if len({s % n_procs for s, _r in socks}) > 1:
                raise ValueError(
                    f"shared group {group!r} is split over subscriber "
                    f"processes: its sockets have to live in one")
            if any(r != socks[0][1] for _s, r in socks):
                raise ValueError(f"the sockets of shared group {group!r} "
                                 f"do not hold the same filters")
            out[group] = ([s for s, _r in socks], sorted(socks[0][1]))
        return out

    def pool(self) -> list:
        """The seeded topic pool: position -> topic. Publisher ``p``
        walks it from its own sector, one position a message, and goes
        on through the phases where it stopped: a stream of draws of
        the law, the same for every process that makes it."""
        pop = importlib.import_module(
            "populations." + self.config["population"]["kind"])
        vocab = pop.vocab(self.config["population"])
        pool = importlib.import_module(
            "topic_laws." + self.law["law"]).pool(self.law, vocab, self.seed)
        if len(pool) != self.n_pool:
            raise ValueError("the topic law made a pool of another size")
        return pool

    def base(self, pub: int, start: list) -> int:
        """Pool position of publisher ``pub``'s first message of a
        phase before which it had sent ``start[pub]`` messages;
        message ``seq`` of the phase takes position ``base + seq``."""
        return (pub * self.n_pool // self.n_pubs + start[pub]) % self.n_pool


# -- publishers --------------------------------------------------------------


class Publishers:
    def __init__(self, plan: Plan) -> None:
        self.plan = plan
        rng = random.Random(plan.seed ^ 0xF111E4)
        self.filler = rng.randbytes(plan.payload_len - HEADER.size)
        pool = plan.pool()
        pre = {t: (publish_prefix(t, plan.payload_len, 0),
                   publish_prefix(t, plan.payload_len, 1))
               for t in set(pool)}
        self.distinct = len(pre)
        self.pre0 = [pre[t][0] for t in pool]
        self.pre1 = [pre[t][1] for t in pool]
        self.loop_kind = importlib.import_module(
            "loops." + plan.traffic["loop"])
        self.conns: list = []
        self.errors = 0

    async def connect(self, port: int) -> dict:
        refused = 0
        for p in range(self.plan.n_pubs):
            r, w = await asyncio.open_connection("127.0.0.1", port)
            w.write(build_connect(f"bench-pub-{p}"))
            await w.drain()
            ack = await asyncio.wait_for(r.readexactly(4), 30.0)
            if ack[0] != 0x20 or ack[3] != 0:
                refused += 1
            self.conns.append((r, w))
        return {"connected": len(self.conns) - refused,
                "refused": refused}

    def frames(self, pub: int, phase: int, base: int, seq0: int, n: int,
               dues, fence: bool) -> bytes:
        """``n`` PUBLISH frames starting at sequence number ``seq0``;
        the last is the QoS 1 fence when ``fence``. ``dues`` is one
        due time or a list of ``n``."""
        n_pool = self.plan.n_pool
        pack = HEADER.pack
        filler = self.filler
        pre0 = self.pre0
        one_due = not isinstance(dues, list)
        parts = []
        for j in range(n):
            seq = seq0 + j
            i = (base + seq) % n_pool
            due = dues if one_due else dues[j]
            if fence and j == n - 1:
                parts.append(self.pre1[i])
                parts.append(struct.pack(">H", (seq % 0xFFFF) + 1))
            else:
                parts.append(pre0[i])
            parts.append(pack(phase, i, pub, seq, due))
            parts.append(filler)
        return b"".join(parts)

    async def await_fence(self, pub: int, seq: int) -> None:
        """The PUBACK of the fence with sequence number ``seq``."""
        r, _w = self.conns[pub]
        ack = await r.readexactly(4)
        want = (seq % 0xFFFF) + 1
        if ack[0] != 0x40 or ((ack[2] << 8) | ack[3]) != want:
            raise ConnectionError(f"publisher {pub}: expected PUBACK "
                                  f"{want}, got {ack.hex()}")

    async def run_phase(self, cmd: dict, out_dir: str) -> dict:
        phase, t0 = cmd["phase"], cmd["t0"]
        t_end = t0 + cmd["seconds"]
        self.start = cmd["start"]
        late = array.array("d")
        sent = [0] * self.plan.n_pubs

        async def one(pub: int) -> None:
            try:
                sent[pub] = await self.loop_kind.publisher(
                    self, pub, phase, t0, t_end, late)
            except (ConnectionError, asyncio.IncompleteReadError,
                    OSError) as e:
                self.errors += 1
                say(f"publisher {pub} failed in phase {phase}: {e!r}")
                sent[pub] = -1

        await asyncio.gather(*(one(p) for p in range(self.plan.n_pubs)))
        late_file = None
        if len(late):
            late_file = os.path.join(out_dir, f"late_{phase}.f64")
            with open(late_file, "wb") as f:
                late.tofile(f)
        return {"phase": phase, "sent": sent, "late_file": late_file,
                "errors": self.errors, "t_done": time.monotonic()}


async def pub_main(init: dict, stdin: Lines) -> None:
    plan = Plan(init)
    pubs = Publishers(plan)
    reply({"ready": True, "role": "pub", "pool": plan.n_pool,
           "distinct_topics": pubs.distinct})
    loop = asyncio.get_running_loop()
    while True:
        line = await loop.run_in_executor(None, stdin.readline)
        if not line:
            break
        cmd = json.loads(line)
        if cmd["cmd"] == "connect":
            reply(await pubs.connect(cmd["port"]))
        elif cmd["cmd"] == "phase":
            reply(await pubs.run_phase(cmd, init["dir"]))
        elif cmd["cmd"] == "exit":
            break
    for _r, w in pubs.conns:
        w.close()


# -- subscribers -------------------------------------------------------------


class Subscribers:
    """This process's share of the cell's subscriber sockets."""

    def __init__(self, plan: Plan, index: int, n_procs: int) -> None:
        import numpy as np
        import reference

        self.np = np
        self.plan = plan
        self.index = index
        self.mine = list(range(index, len(plan.sockets), n_procs))
        t0 = time.monotonic()
        pool = plan.pool()
        distinct = sorted(set(pool))
        pos_of = {t: i for i, t in enumerate(distinct)}
        pool_pos = np.fromiter((pos_of[t] for t in pool),
                               dtype=np.int64, count=plan.n_pool)
        # which sockets of this process hold which filter; the plain
        # trie says which filters match a topic, and the plain
        # predicate must agree with it on a seeded sample
        holders: dict = {}
        for k, s in enumerate(self.mine):
            for f in plan.sockets[s]:
                if reference.share_of(f)[0] is None:
                    holders.setdefault(f, []).append(k)
        trie = reference.Trie()
        for f in holders:
            trie.insert(f)
        hit = np.zeros((len(self.mine), len(distinct)), dtype=np.int8)
        for d, t in enumerate(distinct):
            for f in trie.match(t):
                for k in holders[f]:
                    hit[k, d] += 1
        rng = random.Random(plan.seed ^ 0xC4ECC)
        for t in rng.sample(distinct, min(256, len(distinct))):
            by_pred = sorted(f for f in holders if reference.matches(t, f))
            if by_pred != trie.match(t):
                raise ValueError(f"trie and predicate disagree on {t!r}")
        # match[k, i]: copies socket mine[k] must get of pool position i
        # (by its plain filters; a shared group's come on top, below)
        self.match = hit[:, pool_pos]
        # this process's shared groups: (member sockets k, copies the
        # group as a whole must get of pool position i: one for each
        # of its filters that matches)
        k_of = {s: k for k, s in enumerate(self.mine)}
        self.groups: list = []
        for socks, reals in plan.groups.values():
            if socks[0] not in k_of:
                continue
            per_topic = np.fromiter(
                (sum(reference.matches(t, f) for f in reals)
                 for t in distinct), dtype=np.int8, count=len(distinct))
            self.groups.append(([k_of[s] for s in socks],
                                per_topic[pool_pos]))
        self.pool_b = [t.encode("utf-8") for t in pool]
        self.prep_s = time.monotonic() - t0
        self.socks: list = []
        self.sel = selectors.DefaultSelector()
        self.left: list = []
        self.closed = 0
        self.refused = 0
        self.begin(-1, 0.0, 0.0, [0] * plan.n_pubs)

    def begin(self, phase: int, t0: float, t1: float, start: list) -> None:
        self.phase, self.t0, self.t1, self.start = phase, t0, t1, start
        self.ids = [array.array("Q") for _ in self.mine]
        self.lat = array.array("d")
        self.in_window = 0
        self.per_s = [0] * (int(t1 - t0) + 1)  # arrivals by second
        self.received = 0
        self.stale = 0
        self.bad_topic = 0
        self.bad_qos = 0
        self.last_arrival = time.monotonic()

    def connect(self, port: int) -> dict:
        qos = self.plan.sub_qos
        for lo in range(0, len(self.mine), 64):
            batch = []
            for k in range(lo, min(lo + 64, len(self.mine))):
                s = socket.create_connection(("127.0.0.1", port))
                s.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
                s.sendall(build_connect(f"bench-sub-{self.mine[k]}"))
                batch.append((k, s))
            ok = []
            for k, s in batch:
                ptype, body = _read_packet(s)
                if ptype != 2 or body[1] != 0:
                    self.refused += 1
                    s.close()
                    continue
                s.sendall(build_subscribe(
                    1, self.plan.sockets[self.mine[k]], qos))
                ok.append((k, s))
            for k, s in ok:
                ptype, body = _read_packet(s)
                if ptype != 9 or any(rc > 2 for rc in body[2:]):
                    self.refused += 1
                    s.close()
                    continue
                s.setblocking(False)
                self.sel.register(s, selectors.EVENT_READ, k)
                self.socks.append(s)
        self.left = [b""] * len(self.mine)
        self.unsent = [b""] * len(self.mine)  # PUBACKs a send left over
        return {"connected": len(self.socks), "refused": self.refused}

    def _on_data(self, k: int, sock: socket.socket) -> None:
        try:
            data = sock.recv(262144)
        except BlockingIOError:
            return
        except OSError:
            data = b""
        t = time.monotonic()
        if not data:
            self.closed += 1
            self.sel.unregister(sock)
            sock.close()
            return
        left = self.left[k]
        buf = left + data if left else data
        n = len(buf)
        pos = 0
        ids = self.ids[k]
        lat = self.lat
        phase = self.phase
        pool_b = self.pool_b
        unpack = HEADER.unpack_from
        sub_qos = self.plan.sub_qos
        low_qos = self.plan.low_qos
        acks = b""
        got = 0
        while n - pos >= 2:
            ln = buf[pos + 1]
            p = pos + 2
            if ln & 0x80:
                ln &= 0x7F
                shift = 7
                while p < n:
                    b = buf[p]
                    p += 1
                    ln |= (b & 0x7F) << shift
                    shift += 7
                    if not b & 0x80:
                        break
                else:
                    break  # the length itself is cut
            end = p + ln
            if end > n:
                break
            b0 = buf[pos]
            if b0 >> 4 == 3:
                tl = (buf[p] << 8) | buf[p + 1]
                q = p + 2 + tl
                if b0 & 0x06:
                    # above the granted QoS, or sent a second time
                    if (b0 >> 1) & 3 > sub_qos or b0 & 0x08:
                        self.bad_qos += 1
                    else:
                        acks += b"\x40\x02" + buf[q:q + 2]
                    q += 2
                elif low_qos:
                    self.bad_qos += 1  # under what was granted
                ph, i, pub, seq, due = unpack(buf, q)
                if ph == phase:
                    ids.append((pub << 32) | seq)
                    lat.append(t - due)
                    got += 1
                    if buf[p + 2:p + 2 + tl] != pool_b[i]:
                        self.bad_topic += 1
                else:
                    self.stale += 1
            pos = end
        self.left[k] = buf[pos:] if pos < n else b""
        if acks and self.plan.sub_acks:
            out = self.unsent[k] + acks
            try:
                sent = sock.send(out)
            except BlockingIOError:
                sent = 0
            except OSError:
                sent = len(out)  # closed: the next recv counts it
            self.unsent[k] = out[sent:]
        if got:
            self.received += got
            self.last_arrival = t
            if self.t0 <= t < self.t1:
                self.in_window += got
                self.per_s[int(t - self.t0)] += got

    def expected(self, sent: list):
        """Per socket of this process, the sorted message ids it must
        have received in the phase by its plain filters, from the
        publishers' counts; and per shared group of this process the
        ids its sockets must have received between them."""
        np = self.np
        seqs = [np.arange(max(n, 0), dtype=np.int64) for n in sent]
        pos = [(self.plan.base(p, self.start) + s) % self.plan.n_pool
               for p, s in enumerate(seqs)]

        def ids_of(copies_of):
            parts = [np.repeat((np.int64(p) << 32) | s, copies_of[pos[p]])
                     for p, s in enumerate(seqs)]
            return np.sort(np.concatenate(parts)).astype(np.uint64)

        return ([ids_of(self.match[k]) for k in range(len(self.mine))],
                [ids_of(copies) for _members, copies in self.groups])

    def _diff(self, got, want):
        """Two id arrays as multisets -> (the ids of either, how often
        each is missing from ``got``, how often surplus in it)."""
        np = self.np
        ug, cg = np.unique(got, return_counts=True)
        uw, cw = np.unique(want, return_counts=True)
        allv = np.union1d(ug, uw)
        g = np.zeros(len(allv), dtype=np.int64)
        e = np.zeros(len(allv), dtype=np.int64)
        g[np.searchsorted(allv, ug)] = cg
        e[np.searchsorted(allv, uw)] = cw
        return allv, np.clip(e - g, 0, None), np.clip(g - e, 0, None)

    def finish(self, cmd: dict, out_dir: str) -> dict:
        np = self.np
        want, group_want = self.expected(cmd["sent"])
        total = sum(len(w) for w in want) + sum(len(w) for w in group_want)
        # wait for what is still in flight; give up when nothing has
        # arrived for ``quiesce_s``
        deadline_quiet = cmd.get("quiesce_s", 5.0)
        while self.received < total and \
                time.monotonic() - self.last_arrival < deadline_quiet:
            self.pump(0.05)
        # a surplus delivery would come after the expected ones
        t_grace = time.monotonic() + 0.3
        while time.monotonic() < t_grace:
            self.pump(0.05)
        missing = surplus = 0
        member = {k: g for g, (members, _c) in enumerate(self.groups)
                  for k in members}
        # what a group's sockets got beyond their plain filters' due
        beyond = [[] for _ in self.groups]
        for k, w in enumerate(want):
            got = np.sort(np.frombuffer(self.ids[k], dtype=np.uint64))
            if k not in member and len(got) == len(w) \
                    and np.array_equal(got, w):
                continue
            allv, short, over = self._diff(got, w)
            missing += int(short.sum())
            if k in member:
                beyond[member[k]].append(np.repeat(allv, over))
            else:
                surplus += int(over.sum())
        for parts, w in zip(beyond, group_want):
            # each message once on one socket of the group: never on
            # two (surplus), never on none (missing)
            _v, short, over = self._diff(np.concatenate(parts), w)
            missing += int(short.sum())
            surplus += int(over.sum())
        lat_file = os.path.join(
            out_dir, f"lat_{self.index}_{self.phase}.f64")
        with open(lat_file, "wb") as f:
            self.lat.tofile(f)
        return {"phase": self.phase, "attempted": total,
                "received": self.received, "missing": missing,
                "surplus": surplus, "bad_topic": self.bad_topic,
                "bad_qos": self.bad_qos, "stale": self.stale,
                "in_window": self.in_window, "per_s": self.per_s,
                "closed": self.closed,
                "refused": self.refused, "lat_file": lat_file}

    def pump(self, timeout: float) -> list:
        """Serve the sockets once; return the command lines read."""
        lines = []
        for key, _ev in self.sel.select(timeout):
            if key.data == "stdin":
                lines.extend(self.stdin.available())
            else:
                self._on_data(key.data, key.fileobj)
        return lines


def sub_main(init: dict, stdin: Lines) -> None:
    plan = Plan(init)
    subs = Subscribers(plan, init["index"], init["n_procs"])
    subs.stdin = stdin
    subs.sel.register(stdin, selectors.EVENT_READ, "stdin")
    reply({"ready": True, "role": "sub", "sockets": len(subs.mine),
           "prep_s": subs.prep_s})
    while True:
        for line in subs.pump(0.2):
            if not line:
                return
            cmd = json.loads(line)
            if cmd["cmd"] == "connect":
                reply(subs.connect(cmd["port"]))
            elif cmd["cmd"] == "phase":
                subs.begin(cmd["phase"], cmd["t0"],
                            cmd["t0"] + cmd["seconds"], cmd["start"])
                reply({"phase": cmd["phase"], "armed": True})
            elif cmd["cmd"] == "finish":
                reply(subs.finish(cmd, init["dir"]))
            elif cmd["cmd"] == "exit":
                for s in subs.socks:
                    s.close()
                return


def main() -> int:
    stdin = Lines(0)
    init = json.loads(stdin.readline())
    if init["role"] == "pub":
        asyncio.run(pub_main(init, stdin))
    else:
        sub_main(init, stdin)
    return 0


if __name__ == "__main__":
    sys.exit(main())
