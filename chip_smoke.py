#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that emqx_tpu still starts, and
does its work, on the chip.

One process that holds the chip drives the served path once, through
the entry points a user calls, at deployment size:

    sockets → Node → Broker.publish_begin/fetch/finish →
    Router.match_dispatch → device walk → fan-out/pack → delivery

Default phase (one TPU chip): build the native library from source,
boot a ``Node`` with a real TCP listener and the retainer, seed the
BASELINE headline population (1,000,000 mixed filters, 60/25/15
literal/``+``/``#``) through ``Broker.subscribe``, a >1024-subscriber
topic (bitmap path), a ``$share`` group and ≥131,072 retained names;
connect independent MQTT clients (``tests/indie_mqtt.py``) over the
socket, publish a few thousand messages in several ingress batches,
take a retained replay, and compare every subscriber's delivered set
with ``oracle.TrieOracle`` + ``topic.match``. Then prove the DEVICE
did it: the product's breaker hides a broken device path behind an
exact host fallback, so every fallback/failure counter must be zero
and every publish span must have taken ``path == "device"``.

``--chips 4`` runs only the multi-chip phase: ``Router(MatcherConfig(
mesh=...))`` on the four real devices through ``Broker.publish_batch``
with per-shard fan tables, for ``data=4`` and ``data=2 × trie=2``.

Timings printed here are information, never a result. The last line
of stdout is the contract's JSON object; without a TPU the script
exits non-zero before running any phase and prints no result.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import logging
import os
import random
import subprocess
import sys
import time
from collections import Counter

_HERE = os.path.dirname(os.path.abspath(__file__))
if _HERE not in sys.path:
    sys.path.insert(0, _HERE)

#: device kinds this script knows, with the published peaks of one
#: chip (Google Cloud documentation, "TPU v5e"). A kind that is not
#: here is an error, not a default.
KNOWN_KINDS = {
    # what a v5e reports as jax.devices()[0].device_kind
    "TPU v5 lite": {"hbm_gb": 16, "hbm_gbps": 819, "bf16_tflops": 197},
}

#: log lines that mean the device path failed and the host covered
_BAD_LOG = ("Traceback", "host-oracle fallback", "host fallback",
            "host scan from now on", "breaker OPEN", "REBUILDING")


class SmokeFailure(Exception):
    """A phase failed or the device did not do the work."""


def say(msg: str) -> None:
    print(msg, flush=True)


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


class _LogCapture(logging.Handler):
    def __init__(self) -> None:
        super().__init__(level=logging.WARNING)
        self.lines: list = []

    def emit(self, record: logging.LogRecord) -> None:
        self.lines.append(self.format(record))


class _CompileClock:
    """Sums JAX's own backend-compile durations and counts
    persistent-cache hits (jax.monitoring events)."""

    def __init__(self) -> None:
        import jax.monitoring as mon

        self.secs = 0.0
        self.compiles = 0
        self.cache_hits = 0
        mon.register_event_duration_secs_listener(self._dur)
        mon.register_event_listener(self._evt)

    def _dur(self, name: str, secs: float, **_kw) -> None:
        if name.endswith("backend_compile_duration"):
            self.secs += secs
            self.compiles += 1

    def _evt(self, name: str, **_kw) -> None:
        if name.endswith("compilation_cache/cache_hits"):
            self.cache_hits += 1

    def snap(self):
        return (round(self.secs, 2), self.compiles, self.cache_hits)


def device_gate(want_count: int, allow_platform=None) -> dict:
    """Refuse to run unless JAX's first device is a TPU of a known
    kind. ``allow_platform`` is the tier-1 rehearsal's seam (passed by
    tests/test_chip_smoke.py through ``main``), never an option of the
    program."""
    import jax

    devs = jax.devices()
    d0 = devs[0]
    dev = {"platform": d0.platform, "kind": d0.device_kind,
           "count": len(devs)}
    say(f"device: platform={dev['platform']} kind={dev['kind']!r} "
        f"count={dev['count']} jax={jax.__version__}")
    if d0.platform != "tpu" and d0.platform != allow_platform:
        raise SystemExit(
            f"chip_smoke: no TPU (platform={d0.platform!r}); refusing "
            f"to run — nothing was measured")
    if d0.platform == "tpu" and d0.device_kind not in KNOWN_KINDS:
        raise SystemExit(
            f"chip_smoke: unknown device_kind {d0.device_kind!r}; add "
            f"it to KNOWN_KINDS with its published peaks")
    if len(devs) < want_count:
        raise SystemExit(
            f"chip_smoke: need {want_count} devices, have {len(devs)}")
    return dev


def build_native() -> None:
    """Rebuild native/libemqx_native.so from the committed source
    (the .so is git-ignored and a copied tree's mtimes cannot be
    trusted) and refuse the pure-Python builders."""
    t0 = time.perf_counter()
    subprocess.run(["make", "-B", "-C", os.path.join(_HERE, "native")],
                   check=True, capture_output=True, timeout=300)
    from emqx_tpu.ops import native

    check(native.available(), "native library did not load: ops/native"
          ".py would fall back to the pure-Python builders")
    check(native.has_frame_parser(), "native frame parser missing")
    say(f"native: built libemqx_native.so in "
        f"{time.perf_counter() - t0:.1f}s")


# -- workload ---------------------------------------------------------------


class Sink:
    """In-process subscriber (the broker's subscriber protocol is one
    method). Records, per smoke message, which filters delivered."""

    __slots__ = ("label", "got", "other")

    def __init__(self, label: str) -> None:
        self.label = label
        self.got: dict = {}
        self.other = 0

    def deliver(self, topic_filter: str, msg) -> None:
        p = msg.payload
        if p[:2] == b"m:":
            self.got.setdefault(p, []).append(topic_filter)
        else:
            self.other += 1


def build_filters(rng, n_subs, words_per_level, levels=5):
    """The BASELINE headline population: ``n_subs`` distinct filters
    over a ``levels``-deep tree, 60/25/15 literal/``+``/``#``
    (``benchmark/populations/mixed_tree.py`` is the cells' own copy)."""
    filters = set()
    vocab = [[f"w{lvl}_{i}" for i in range(words_per_level)]
             for lvl in range(levels)]
    while len(filters) < n_subs:
        depth = rng.randint(2, levels)
        ws = [rng.choice(vocab[i]) for i in range(depth)]
        r = rng.random()
        if r < 0.25:  # single-level '+'
            ws[rng.randrange(depth)] = "+"
        elif r < 0.40:  # multi-level '#'
            ws = ws[: rng.randint(1, depth)] + ["#"]
        filters.add("/".join(ws))
    return list(filters), vocab


def zipf_choice(rng, items, a=1.3):
    """Zipf-ish publish mix (BASELINE config 2)."""
    n = len(items)
    while True:
        k = int(rng.paretovariate(a)) - 1
        if k < n:
            return items[k]


class Workload:
    """Everything made from ``--seed``: the filter population, the
    client subscriptions and the publish rounds."""

    def __init__(self, seed: int, n_filters: int, n_retained: int,
                 n_messages: int, n_fan: int) -> None:
        rng = random.Random(seed)
        self.rng = rng
        self.filters, self.vocab = build_filters(
            rng, n_filters, words_per_level=60)
        self.n_fan = n_fan
        v = self.vocab
        # socket clients: label -> filters (non-overlapping per client
        # so "one copy per matching subscription" is unambiguous)
        self.client_filters = {
            "c_plus": [f"{v[0][0]}/+/{v[2][0]}", f"{v[0][1]}/+"],
            "c_hash": [f"{v[0][0]}/{v[1][0]}/#", f"{v[0][2]}/#"],
            "c_sys": ["$SYS/smoke/#"],
            "c_deep": ["deep/#"],
            "c_hot": ["hot/topic", "fan/big"],
        }
        self.share_filter = "shared/+/x"
        self.share_members = ["s0", "s1", "s2"]
        self.late_filter = "ret/+/7/#"
        self.retained = [f"ret/{i % 64}/{(i >> 6) % 16}/n{i}"
                         for i in range(n_retained)]
        self.deep_topic = "deep/" + "/".join(
            f"l{i}" for i in range(18))
        per_round = max(8, n_messages // 2)
        self.rounds = [self._round(r, per_round) for r in range(2)]

    def _round(self, r: int, n: int):
        rng, v = self.rng, self.vocab
        topics = []
        for i in range(n):
            x = i % 20
            if x == 0:
                t = "hot/topic"
            elif x == 1 and i % 40 == 1:
                t = "fan/big"
            elif x == 2:
                t = f"shared/k{i % 7}/x"
            elif x == 3 and i % 100 == 3:
                t = f"$SYS/smoke/r{r}"
            elif x == 4 and i % 200 == 4:
                t = self.deep_topic
            else:
                depth = rng.randint(2, 5)
                t = "/".join(zipf_choice(rng, v[l])
                             for l in range(depth))
            topics.append(t)
        return [(t, b"m:%d:%d" % (r, i)) for i, t in enumerate(topics)]


def expected_deliveries(wl: Workload, subs_of: dict, msgs) -> dict:
    """label -> Counter{payload: copies}, from the host oracle alone:
    ``TrieOracle.match`` over every filter in the node, cross-checked
    against the per-filter ``topic.match`` predicate for the client
    filters. Never touches the router."""
    from emqx_tpu import topic as T
    from emqx_tpu.oracle import TrieOracle

    t0 = time.perf_counter()
    oracle = TrieOracle()
    for f in subs_of:
        oracle.insert(f)
    say(f"oracle: {len(subs_of)} filters inserted in "
        f"{time.perf_counter() - t0:.1f}s")
    small = {f for f, labels in subs_of.items()
             if any(lb != "sink" for lb in labels)}
    exp: dict = {}
    sink_exp: dict = {}
    share_exp = Counter()
    memo: dict = {}
    for topic, payload in msgs:
        matched = memo.get(topic)
        if matched is None:
            matched = memo[topic] = sorted(oracle.match(topic))
            by_pred = sorted(f for f in small if T.match(topic, f))
            check(by_pred == [f for f in matched if f in small],
                  f"oracle disagrees with topic.match on {topic!r}")
        for f in matched:
            for lb in subs_of[f]:
                if lb == "sink":
                    sink_exp.setdefault(payload, []).append(f)
                elif lb == "share":
                    share_exp[payload] += 1
                else:
                    exp.setdefault(lb, Counter())[payload] += 1
    return {"clients": exp, "sink": sink_exp, "share": share_exp}


def sink_equal(sink: Sink, want: dict) -> bool:
    """Per message, the filters that delivered == the oracle's."""
    return (set(sink.got) == set(want) and all(
        sorted(sink.got[p]) == sorted(want[p]) for p in want))


async def _drain(client, want: int, timeout: float) -> Counter:
    """Collect PUBLISH payloads from one socket client until ``want``
    arrived (plus a grace read for surplus) or ``timeout``."""
    got: Counter = Counter()
    deadline = time.monotonic() + timeout
    n = 0
    while n < want:
        left = deadline - time.monotonic()
        if left <= 0:
            break
        try:
            p = await asyncio.wait_for(client.inbox.get(), left)
        except asyncio.TimeoutError:
            break
        if p is None:
            break
        got[p.payload] += 1
        n += 1
    # surplus = a wrong extra delivery; give it a moment to show up
    await asyncio.sleep(0.2)
    while not client.inbox.empty():
        p = client.inbox.get_nowait()
        if p is not None:
            got[p.payload] += 1
    return got


async def publish_round(pubs, msgs, burst: int = 512) -> None:
    """Several ingress batches: bursts round-robin over publishers,
    QoS0 with a QoS1 fence ending each burst."""
    build_publish = _mqtt().build_publish
    for b0 in range(0, len(msgs), burst):
        chunk = msgs[b0:b0 + burst]
        pub = pubs[(b0 // burst) % len(pubs)]
        for topic, payload in chunk[:-1]:
            pub.writer.write(build_publish(topic, payload))
        await pub.writer.drain()
        topic, payload = chunk[-1]
        await pub.publish(topic, payload, qos=1, timeout=600.0)


def _mqtt():
    from tests import indie_mqtt
    return indie_mqtt


# -- the one-chip phase -----------------------------------------------------


async def settle(node, after: str) -> None:
    """Wait for the node's overload monitor to read ``ok`` again. A
    cold compile runs inside ``publish_begin`` on the event loop, the
    monitor reads the stall as loop lag, and at ``critical`` it
    refuses CONNECTs — product behaviour the smoke reports, not
    hides."""
    from emqx_tpu.overload import OK

    mon = node.overload
    t0 = time.monotonic()
    # the monitor reads a stall as lag on its NEXT tick
    await asyncio.sleep(mon.cfg.interval_s * 1.5)
    peak = mon.level
    while mon.level != OK:
        peak = max(peak, mon.level)
        check(time.monotonic() - t0 < 120.0,
              f"overload monitor stuck at level {mon.level} {after}")
        await asyncio.sleep(0.25)
    if peak != OK:
        say(f"overload: monitor reached level {peak} {after} (loop "
            f"stalled by set-up/cold compiles); ok again after "
            f"{time.monotonic() - t0:.1f}s")


async def serve_phase(args, clock: _CompileClock,
                      logcap: _LogCapture, sabotage=None) -> None:
    from emqx_tpu.modules.retainer import RetainerModule
    from emqx_tpu.node import Node
    from emqx_tpu.types import Message

    mq = _mqtt()
    wl = Workload(args.seed, args.filters, args.retained, args.messages,
                  n_fan=args.fan)
    say(f"workload: seed={args.seed} filters={len(wl.filters)} "
        f"retained={len(wl.retained)} messages="
        f"{sum(len(r) for r in wl.rounds)} fan={wl.n_fan}")

    # README "Running a node": a Node with one TCP listener + retainer
    node = Node(boot_listeners=False)
    ret = node.modules.load(RetainerModule)
    lst = node.add_listener(host="127.0.0.1", port=0)
    await node.start()
    br = node.broker.breaker
    check(br is not None, "Node built without its DeviceBreaker")
    from emqx_tpu import faults
    faults0 = faults.info()["injected_total"]
    spans: list = []
    tel = node.telemetry
    _finish = tel.finish

    def _record(span):  # every PublishSpan of the run
        if not span.closed:
            spans.append((span.path, span.bucket, span.batch,
                          span.n_uniq, span.fallbacks))
        _finish(span)

    tel.finish = _record
    try:
        # -- seed: 1M filters through Broker.subscribe ---------------------
        subs_of: dict = {}
        sink = Sink("sink")
        t0 = time.perf_counter()

        def _seed():
            for f in wl.filters:
                node.broker.subscribe(sink, f)

        # off the loop (Broker.subscribe is any-thread): the listener
        # and the monitors keep running, as they would in service
        await asyncio.get_running_loop().run_in_executor(None, _seed)
        subs_of.update((f, ["sink"]) for f in wl.filters)
        say(f"seed: {len(wl.filters)} filters subscribed in "
            f"{time.perf_counter() - t0:.1f}s "
            f"(native trie: {node.router._native is not None})")
        check(node.router._native is not None,
              "router fell back to the pure-Python trie builder")
        fans = [Sink(f"fan{i}") for i in range(wl.n_fan)]
        for s in fans:
            node.broker.subscribe(s, "fan/big")
        subs_of.setdefault("fan/big", []).extend(s.label for s in fans)
        check(node.router.use_device_now(), "router chose the host "
              "regime: too few filters for the device path")

        # -- retained names through the broker's publish path --------------
        t0 = time.perf_counter()
        c0 = clock.snap()
        for i in range(0, len(wl.retained), 8192):
            node.broker.publish_batch([
                Message(topic=t, payload=b"r", flags={"retain": True})
                for t in wl.retained[i:i + 8192]])
            await asyncio.sleep(0)
        say(f"seed: {len(wl.retained)} retained names stored in "
            f"{time.perf_counter() - t0:.1f}s (first device batch "
            f"flattens the 1M-filter automaton and compiles; "
            f"compile {clock.snap()[0] - c0[0]:.1f}s)")
        check(len(ret._index) == len(wl.retained),
              f"retainer holds {len(ret._index)} names")

        await settle(node, "after seeding")

        # -- socket clients ------------------------------------------------
        clients = {}
        for label, flts in wl.client_filters.items():
            c = mq.IndieClient(label)
            await c.connect(port=lst.port)
            await c.subscribe(*flts, timeout=600.0)
            clients[label] = c
            for f in flts:
                subs_of.setdefault(f, []).append(label)
        for label in wl.share_members:
            c = mq.IndieClient(label)
            await c.connect(port=lst.port)
            await c.subscribe("$share/g1/" + wl.share_filter,
                              timeout=600.0)
            clients[label] = c
        subs_of.setdefault(wl.share_filter, []).append("share")
        pubs = []
        for i in range(2):
            p = mq.IndieClient(f"pub{i}")
            await p.connect(port=lst.port)
            pubs.append(p)
        say(f"clients: {len(clients)} subscribers + {len(pubs)} "
            f"publishers connected on 127.0.0.1:{lst.port}")

        all_msgs = [m for r in wl.rounds for m in r]
        exp = expected_deliveries(wl, subs_of, all_msgs)

        if sabotage is not None:
            sabotage(node)

        # -- publish rounds ------------------------------------------------
        for r, msgs in enumerate(wl.rounds):
            c0 = clock.snap()
            t0 = time.perf_counter()
            await publish_round(pubs, msgs)
            dt = time.perf_counter() - t0
            c1 = clock.snap()
            say(f"round {r}: {len(msgs)} messages published+acked in "
                f"{dt:.2f}s wall ({'cold' if r == 0 else 'warm'}; "
                f"compile {c1[0] - c0[0]:.1f}s in {c1[1] - c0[1]} "
                f"programs, {c1[2] - c0[2]} cache hits)")

        # -- compare delivered sets with the oracle ------------------------
        bad = 0
        for label, c in clients.items():
            if label in wl.share_members:
                continue
            want = exp["clients"].get(label, Counter())
            got = await _drain(c, sum(want.values()), 120.0)
            ok = got == want
            bad += not ok
            say(f"deliver: client {label}: {sum(got.values())} "
                f"received, {sum(want.values())} expected — "
                f"{'equal' if ok else 'MISMATCH'}")
        share_got: Counter = Counter()
        per_member = []
        for label in wl.share_members:
            g = await _drain(clients[label], 0, 1.0)
            per_member.append(sum(g.values()))
            share_got.update(g)
        ok = share_got == exp["share"]
        bad += not ok
        say(f"deliver: $share group g1: members got {per_member}, "
            f"union {sum(share_got.values())} of "
            f"{sum(exp['share'].values())} — "
            f"{'equal' if ok else 'MISMATCH'}")
        check(sum(exp["share"].values()) > 0, "$share path not driven")
        sink_ok = sink_equal(sink, exp["sink"])
        bad += not sink_ok
        say(f"deliver: 1M-filter sink: "
            f"{sum(len(v) for v in sink.got.values())} deliveries over "
            f"{len(sink.got)} messages, "
            f"{sum(len(v) for v in exp['sink'].values())} expected — "
            f"{'equal' if sink_ok else 'MISMATCH'}")
        n_big = sum(1 for t, _ in all_msgs if t == "fan/big")
        fan_ok = all(
            len(s.got) == n_big and all(v == ["fan/big"]
                                        for v in s.got.values())
            for s in fans)
        bad += not fan_ok
        say(f"deliver: bitmap fan-out: {len(fans)} subscribers × "
            f"{n_big} messages — {'equal' if fan_ok else 'MISMATCH'}")
        check(n_big > 0 and len(fans) > node.router.config
              .fanout_threshold, "bitmap path not driven")
        check(bad == 0, f"{bad} delivered sets differ from the oracle")

        # -- late wildcard subscribe: retained replay ----------------------
        from emqx_tpu import topic as T

        want_ret = Counter(t for t in wl.retained
                           if T.match(t, wl.late_filter))
        await settle(node, "after the publish rounds")
        late = mq.IndieClient("late")
        await late.connect(port=lst.port)
        t0 = time.perf_counter()
        await late.subscribe(wl.late_filter, timeout=600.0)
        got_ret: Counter = Counter()
        deadline = time.monotonic() + 120.0
        while sum(got_ret.values()) < sum(want_ret.values()) \
                and time.monotonic() < deadline:
            try:
                p = await asyncio.wait_for(late.inbox.get(), 5.0)
            except asyncio.TimeoutError:
                continue
            if p is None:
                break
            check(p.retain, f"replayed {p.topic} without RETAIN")
            got_ret[p.topic] += 1
        ok = got_ret == want_ret
        say(f"retained: late subscribe {wl.late_filter!r}: "
            f"{sum(got_ret.values())} replayed of "
            f"{sum(want_ret.values())} matching (index "
            f"{len(ret._index)} names, device threshold "
            f"{ret.index_device_threshold}) in "
            f"{time.perf_counter() - t0:.2f}s — "
            f"{'equal' if ok else 'MISMATCH'}")
        check(ok and want_ret, "retained replay differs from the oracle")
        for c in list(clients.values()) + pubs + [late]:
            await c.close()

        prove_device(node, spans, logcap, ret, faults0)
    finally:
        tel.finish = _finish
        await node.stop()


def prove_device(node, spans, logcap, ret, faults0: int) -> None:
    """The product's breaker turns a broken device path into a correct
    host-served one; only these counters tell the two apart."""
    m = node.metrics
    br = node.broker.breaker
    from emqx_tpu import faults

    counters = {
        "breaker.failures": m.val("breaker.failures"),
        "breaker.trips": m.val("breaker.trips"),
        "breaker.fallback.batches": m.val("breaker.fallback.batches"),
        "faults.injected": faults.info()["injected_total"] - faults0,
        "retain_index.strikes":
            ret._index.device_info()["device_broken"],
    }
    state = br.STATE_NAMES[br.state]
    paths = Counter(p for p, *_ in spans)
    zero_bucket = sum(1 for _, b, *_ in spans if not b)
    rows = sum(u for *_, u, _ in spans)
    fallbacks = sum(f for *_, f in spans)
    node.metrics.fold_cache_stats(node.router.drain_cache_stats())
    walked = m.val("cache.match.miss")
    info = node.router.walk_info()
    say(f"proof: counters {json.dumps(counters)} breaker={state}")
    say(f"proof: {len(spans)} publish spans, paths {dict(paths)}, "
        f"{zero_bucket} with bucket 0; {rows} unique topic rows on the "
        f"device, {fallbacks} overflow rows host-resolved; "
        f"{walked} rows walked (cache misses)")
    say(f"proof: walk mode={info['mode']} "
        f"k={node.router.effective_k()} "
        f"delta={node.router.delta_info()['active']}")
    bad_log = [ln for ln in logcap.lines
               if any(b in ln for b in _BAD_LOG)]
    for i, ln in enumerate(bad_log[:10]):
        # the first two in full (the traceback is the finding)
        say(f"proof: LOG {ln if i < 2 else ln[:200]}")
    check(all(v == 0 for v in counters.values()),
          f"device path failed over to the host: {counters}")
    check(state == "closed", f"breaker is {state}")
    check(spans and set(paths) == {"device"},
          f"publish spans off the device path: {dict(paths)}")
    check(zero_bucket == 0, f"{zero_bucket} spans with bucket 0")
    check(walked > 0, "no topic row was walked on the device")
    check(fallbacks * 20 <= max(rows, 1),
          f"{fallbacks} of {rows} rows were host-resolved")
    check(not bad_log, f"{len(bad_log)} fallback/traceback log lines")


# -- the four-chip phase ----------------------------------------------------


def mesh_phase(args, clock: _CompileClock) -> None:
    """BASELINE config 5's shape on the four real devices: the mesh
    router behind ``Broker.publish_batch`` with real per-shard fan
    tables, compared with the oracle's delivered sets."""
    import jax

    from emqx_tpu.broker import Broker
    from emqx_tpu.metrics import Metrics
    from emqx_tpu.parallel import sharded
    from emqx_tpu.parallel.mesh import make_mesh
    from emqx_tpu.router import MatcherConfig, Router
    from emqx_tpu.types import Message

    wl = Workload(args.seed, args.filters, 0, args.messages,
                  n_fan=args.fan)
    subs_of = {f: ["sink"] for f in wl.filters}
    subs_of.setdefault("fan/big", []).extend(
        f"fan{i}" for i in range(wl.n_fan))
    msgs = wl.rounds[0]
    exp = expected_deliveries(wl, subs_of, msgs)
    devices = jax.devices()[:args.chips]
    for n_data, n_trie in ((args.chips, 1),
                           (args.chips // 2, 2)):
        mesh = make_mesh(n_data, n_trie, devices)
        say(f"mesh data={n_data} trie={n_trie}: devices "
            f"{[d.id for d in mesh.devices.flat]}")
        metrics = Metrics()
        broker = Broker(router=Router(MatcherConfig(mesh=mesh)),
                        metrics=metrics)
        sink = Sink("sink")
        fans = [Sink(f"fan{i}") for i in range(wl.n_fan)]
        t0 = time.perf_counter()
        for f in wl.filters:
            broker.subscribe(sink, f)
        for s in fans:
            broker.subscribe(s, "fan/big")
        say(f"mesh seed: {len(wl.filters)} filters in "
            f"{time.perf_counter() - t0:.1f}s")
        # record what the step program was lowered to (the Pallas
        # bitmap kernel, the collectives) the first time it runs
        hlo: dict = {}
        _step = sharded.publish_step

        def _spy(*a, **kw):
            if kw.get("with_fanout") and a[6] is not None \
                    and "text" not in hlo:
                hlo["text"] = _step.lower(*a, **kw).compile().as_text()
            return _step(*a, **kw)

        sharded.publish_step = _spy
        try:
            c0 = clock.snap()
            t0 = time.perf_counter()
            for i in range(0, len(msgs), 512):
                broker.publish_batch([
                    Message(topic=t, payload=p)
                    for t, p in msgs[i:i + 512]])
            dt = time.perf_counter() - t0
        finally:
            sharded.publish_step = _step
        c1 = clock.snap()
        say(f"mesh publish: {len(msgs)} messages in {dt:.2f}s wall "
            f"(compile {c1[0] - c0[0]:.1f}s, {c1[1] - c0[1]} programs)")
        # tables really spread: every stacked array of the published
        # automaton lives on all four devices, one shard each
        auto = broker.router.automaton()[0]
        for name, arr in zip(auto._fields, auto):
            ids = sorted(s.device.id for s in arr.addressable_shards)
            check(len(set(ids)) == len(devices),
                  f"{name} lives on devices {ids}, not all four")
            if n_trie > 1:
                shard0 = arr.addressable_shards[0].data.shape[0]
                check(shard0 * n_trie == arr.shape[0],
                      f"{name} is not split over the trie axis")
        say(f"mesh tables: wt {tuple(auto.wt.shape)} sharding "
            f"{auto.wt.sharding.spec} on devices "
            f"{sorted({s.device.id for s in auto.wt.addressable_shards})}")
        check("text" in hlo, "the fan-out step never ran")
        check("tpu_custom_call" in hlo["text"]
              or mesh.devices.flat[0].platform != "tpu",
              "use_dma did not take the Pallas branch")
        colls = [c for c in ("all-gather", "all-reduce")
                 if c in hlo["text"]]
        say(f"mesh step program: pallas="
            f"{'tpu_custom_call' in hlo['text']} collectives={colls}")
        metrics.fold_device_stats(broker.router.drain_device_stats())
        n_match = metrics.val("device.matches")
        sink_ok = sink_equal(sink, exp["sink"])
        n_big = sum(1 for t, _ in msgs if t == "fan/big")
        fan_ok = all(len(s.got) == n_big for s in fans)
        say(f"mesh deliver: sink "
            f"{sum(len(v) for v in sink.got.values())} deliveries of "
            f"{sum(len(v) for v in exp['sink'].values())} expected — "
            f"{'equal' if sink_ok else 'MISMATCH'}; bitmap fan "
            f"{len(fans)}×{n_big} — "
            f"{'equal' if fan_ok else 'MISMATCH'}; "
            f"device.matches={n_match}")
        check(sink_ok and fan_ok,
              "mesh deliveries differ from the oracle")
        check(n_match > 0, "device.matches stayed 0 on the mesh")
        del broker, auto


# -- entry ------------------------------------------------------------------


def main(argv=None, *, allow_platform=None, sabotage=None) -> int:
    """``allow_platform`` / ``sabotage`` are the tier-1 rehearsal's
    seams (tests/test_chip_smoke.py): the first lets the toy-size
    rehearsal run on the CPU backend, the second breaks the device
    path on purpose so the proof is shown to fail. Neither is
    reachable from the command line."""
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="1 = the served path on one chip (default); "
                         "4 = only the mesh phase on four chips")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--filters", type=int, default=1_000_000)
    ap.add_argument("--retained", type=int, default=131_072)
    ap.add_argument("--messages", type=int, default=4000)
    ap.add_argument("--fan", type=int, default=1500,
                    help="subscribers of the bitmap-path topic "
                         "(> fanout_threshold = 1024)")
    args = ap.parse_args(argv)

    t_start = time.perf_counter()
    dev = device_gate(args.chips, allow_platform)
    cuts = [f"{k}={getattr(args, k)}"
            for k, full in (("filters", 1_000_000),
                            ("retained", 131_072), ("messages", 4000))
            if getattr(args, k) < full]
    if cuts:
        say(f"reduced: {' '.join(cuts)} (below the deployment size)")
    logcap = _LogCapture()
    logcap.setFormatter(logging.Formatter(
        "%(levelname)s %(name)s: %(message)s"))
    root = logging.getLogger()
    root.addHandler(logcap)
    clock = _CompileClock()
    try:
        build_native()
        from emqx_tpu.profiling import compile_cache_dir
        if args.chips == 1:
            asyncio.run(serve_phase(args, clock, logcap,
                                    sabotage=sabotage))
        else:
            from emqx_tpu.profiling import enable_compile_cache
            enable_compile_cache()
            mesh_phase(args, clock)
        secs, n, hits = clock.snap()
        say(f"compile: {secs}s in {n} backend compiles, {hits} "
            f"persistent-cache hits (cache at {compile_cache_dir()})")
        say(f"total: {time.perf_counter() - t_start:.1f}s")
    except SmokeFailure as e:
        say(f"FAILED: {e}")
        return 1
    finally:
        root.removeHandler(logcap)
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
