#!/usr/bin/env python3
"""benchmark/run.py — one run of one cell of ``BENCHMARK.json``.

    python3 benchmark/run.py --workload <cell> --seed <n> \
        --seconds <run_seconds> --trace <0|1>

One new process that holds the chip: it refuses to start without a
TPU, builds ``native/libemqx_native.so``, boots a ``Node`` with a real
TCP listener, seeds the deployment through ``Broker.subscribe``, and
drives it from the client's side of the sockets with load-generator
children (``loadgen.py``) that import neither JAX nor ``emqx_tpu``. It
warms with the cell's own traffic and the configuration's warmers until
rounds pass in which no program is first used, measures for
``--seconds``, checks every delivery against the
plain reference (``reference.py``), and prints one JSON object as the
last line of stdout. Everything else it prints goes on earlier lines.

This file holds no cell's name, no configuration's numbers and no
metric's name. A cell is ``workloads/<cell>.json`` (configuration +
traffic mix + overrides); a metric is ``end_to_end/<name>.json`` or
``layer_metrics/<name>.json`` (a reducer from ``reducers/`` and its
arguments); population kinds, topic laws, loop kinds and warmers are
modules found by the name the data gives. ``BENCHMARK.json`` says which metric
is reported in which cell.
"""

from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import asyncio  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import logging  # noqa: E402
import os  # noqa: E402
import random  # noqa: E402
import shutil  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

_HERE = os.path.dirname(os.path.abspath(__file__))
if _HERE not in sys.path:
    sys.path.insert(0, _HERE)

from loadgen import HEADER_KEY, Plan  # noqa: E402  (imports no JAX)

#: the measured window's phase number; warm rounds count up from 1
MEASURE = 0
#: warm-up: rounds of the cell's own traffic of WARM_ROUND_S seconds;
#: the configuration's warmers run after WARM_ROUNDS_FIRST of them, and
#: the warm-up ends when WARM_QUIET_ROUNDS in a row first used no
#: program (it fails after WARM_ROUNDS_MAX)
WARM_ROUND_S = 2.0
WARM_ROUNDS_FIRST = 2
WARM_QUIET_ROUNDS = 2
WARM_ROUNDS_MAX = 40
#: log lines that mean the device path failed and the host covered
_BAD_LOG = ("Traceback", "host-oracle fallback", "host fallback",
            "host scan from now on", "breaker OPEN", "REBUILDING")
#: counters that must not move in the window: each marks a batch that
#: the product served from the host behind its breaker
_BREAKER = ("breaker.failures", "breaker.trips", "breaker.fallback.batches")


class BenchFailure(Exception):
    """The run cannot give a result (not: the result is not correct)."""


def say(msg: str) -> None:
    print(msg, flush=True)


def _load_json(path: str) -> dict:
    with open(path, encoding="utf-8") as f:
        return json.load(f)


class _LogCapture(logging.Handler):
    def __init__(self) -> None:
        super().__init__(level=logging.WARNING)
        self.lines: list = []

    def emit(self, record: logging.LogRecord) -> None:
        self.lines.append(self.format(record))


class _Names(logging.Handler):
    """Keeps the head of JAX's "Compiling <program> with global shapes
    ..." lines, so that a program first used in the window has a name."""

    def __init__(self, names: list) -> None:
        super().__init__(level=logging.DEBUG)
        self.names = names

    def emit(self, record: logging.LogRecord) -> None:
        msg = record.getMessage()
        if msg.startswith("Compiling "):
            self.names.append(msg[10:].split(". Argument mapping")[0][:400])


class CompileClock:
    """Counts the programs JAX makes ready for first use and sums the
    time that takes (``jax.monitoring`` events). The event fires for a
    backend compile and for a load from the persistent cache alike;
    ``cache_hits`` says how many were loads."""

    def __init__(self) -> None:
        import jax.monitoring as mon

        self.secs = 0.0
        self.compiles = 0
        self.cache_hits = 0
        self.names: list = []
        mon.register_event_duration_secs_listener(self._dur)
        mon.register_event_listener(self._evt)
        # JAX names each program it makes ready in a debug line
        lg = logging.getLogger("jax._src.interpreters.pxla")
        lg.setLevel(logging.DEBUG)
        lg.addHandler(_Names(self.names))

    def _dur(self, name: str, secs: float, **_kw) -> None:
        if name.endswith("backend_compile_duration"):
            self.secs += secs
            self.compiles += 1

    def _evt(self, name: str, **_kw) -> None:
        if name.endswith("compilation_cache/cache_hits"):
            self.cache_hits += 1


class GcWatch:
    """Times the interpreter's garbage collections (``gc.callbacks``):
    a broker that holds a million filters holds millions of Python
    objects, and a full collection stalls its one event loop."""

    def __init__(self) -> None:
        import gc

        self.pauses: list = []  # (generation, seconds)
        self._t0 = 0.0
        gc.callbacks.append(self._cb)

    def _cb(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._t0 = time.monotonic()
        else:
            self.pauses.append((info["generation"],
                                time.monotonic() - self._t0))

    def close(self) -> None:
        import gc

        gc.callbacks.remove(self._cb)


class Bench:
    """The benchmark's files: where they are and what they say."""

    def __init__(self, bench_dir: str) -> None:
        self.dir = bench_dir
        self.root = os.path.dirname(bench_dir)
        self.spec = _load_json(os.path.join(self.root, "BENCHMARK.json"))
        self._mods: dict = {}

    def data(self, kind: str, name: str) -> dict:
        return _load_json(os.path.join(self.dir, kind, name + ".json"))

    def module(self, kind: str, name: str):
        """``<kind>/<name>.py`` of this benchmark directory, by path (a
        test may run a copy beside the original)."""
        key = (kind, name)
        if key not in self._mods:
            path = os.path.join(self.dir, kind, name + ".py")
            spec = importlib.util.spec_from_file_location(
                f"_bench_{kind}_{name}_{abs(hash(self.dir))}", path)
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            self._mods[key] = mod
        return self._mods[key]

    def cell(self, name: str):
        """-> (workload, configuration, traffic with the cell's
        overrides applied)."""
        if not any(w["name"] == name for w in self.spec["workloads"]):
            raise SystemExit(f"run.py: BENCHMARK.json has no cell {name!r}")
        wl = self.data("workloads", name)
        cfg = self.data("configs", wl["config"])
        traffic = dict(self.data("traffic", wl["traffic"]),
                       **wl.get("overrides", {}))
        return wl, cfg, traffic

    def metrics_for(self, group: str, kind: str, cell: str) -> list:
        """The metrics of ``group`` that BENCHMARK.json reports in
        ``cell``, each with its reducer's file."""
        out = []
        for m in self.spec[group]:
            if cell in m.get("workloads", [cell]):
                out.append((m, self.data(kind, m["name"])))
        return out

    def reduce(self, entry: dict, run: dict):
        mod = self.module("reducers", entry["reducer"])
        return mod.reduce(run, **entry.get("args", {}))


def device_gate(want: int, peaks: dict, allow_platform=None) -> dict:
    """Refuse to run unless JAX's first device is a TPU of a known kind
    and there are as many as the cell asks for. ``allow_platform`` is
    the rehearsal's seam (``benchmark/tests``), never an option of the
    command."""
    import jax

    devs = jax.devices()
    d0 = devs[0]
    dev = {"platform": d0.platform, "kind": d0.device_kind,
           "count": len(devs)}
    try:
        nodes = sum(1 for n in os.listdir("/sys/devices/system/node")
                    if n.startswith("node") and n[4:].isdigit())
    except OSError:
        nodes = "?"
    say(f"device: platform={dev['platform']} kind={dev['kind']!r} "
        f"count={dev['count']} jax={jax.__version__} "
        f"cpu_count={os.cpu_count()} cpus_allowed="
        f"{len(os.sched_getaffinity(0))} numa_nodes={nodes} "
        f"loadavg={os.getloadavg()[0]:.2f}")
    if d0.platform != "tpu" and d0.platform != allow_platform:
        raise SystemExit(f"run.py: no TPU (platform={d0.platform!r}); "
                         f"refusing to run, nothing was measured")
    if d0.platform == "tpu" and d0.device_kind not in peaks:
        raise SystemExit(f"run.py: unknown device_kind {d0.device_kind!r}; "
                         f"add it to peaks.json with its published peaks")
    if len(devs) < want:
        raise SystemExit(f"run.py: the cell needs {want} chips, JAX "
                         f"finds {len(devs)}")
    return dev


def build_native(root: str) -> None:
    """Rebuild ``native/libemqx_native.so`` from the committed source
    (the .so is git-ignored) and refuse the pure-Python builders."""
    t0 = time.monotonic()
    subprocess.run(["make", "-B", "-C", os.path.join(root, "native")],
                   check=True, capture_output=True, timeout=300)
    from emqx_tpu.ops import native

    if not (native.available() and native.has_frame_parser()):
        raise BenchFailure("native library did not load")
    say(f"native: built libemqx_native.so in {time.monotonic() - t0:.1f}s")


class Sink:
    """The in-process subscriber that holds the population's filters
    (the broker's subscriber protocol is one method). For the sampled
    pool positions it keeps which filters delivered each message of the
    measured window; everything else it only counts."""

    __slots__ = ("sample", "count")

    def __init__(self) -> None:
        self.sample: dict = {}
        self.count = 0

    def deliver(self, topic_filter: str, msg) -> None:
        self.count += 1
        p = msg.payload
        got = self.sample.get(p[:6])  # phase + pool position
        if got is not None:
            got.append((p[6:12], topic_filter))  # publisher + sequence


class Child:
    """One helper process on JSON lines."""

    def __init__(self, proc, what: str) -> None:
        self.proc = proc
        self.what = what

    @classmethod
    async def start(cls, script: str, what: str, init: dict) -> "Child":
        proc = await asyncio.create_subprocess_exec(
            sys.executable, script, stdin=asyncio.subprocess.PIPE,
            stdout=asyncio.subprocess.PIPE, limit=1 << 26)
        self = cls(proc, what)
        self.send(init)
        return self

    def send(self, obj: dict) -> None:
        self.proc.stdin.write((json.dumps(obj) + "\n").encode())

    async def recv(self, timeout: float = 900.0) -> dict:
        line = await asyncio.wait_for(self.proc.stdout.readline(), timeout)
        if not line:
            raise BenchFailure(f"{self.what} ended without an answer "
                               f"(rc={self.proc.returncode})")
        return json.loads(line)

    async def ask(self, obj: dict, timeout: float = 900.0) -> dict:
        self.send(obj)
        return await self.recv(timeout)

    async def stop(self) -> None:
        if self.proc.returncode is None:
            try:
                self.send({"cmd": "exit"})
                self.proc.stdin.close()
                await asyncio.wait_for(self.proc.wait(), 10.0)
            except (asyncio.TimeoutError, OSError):
                self.proc.kill()
                await self.proc.wait()


async def settle(node, after: str) -> None:
    """Wait for the node's overload monitor to read ``ok``. A cold
    compile stalls the event loop, the monitor reads that as lag, and
    at ``critical`` it refuses CONNECTs and tightens ingress: the
    window must not start in that state."""
    from emqx_tpu.overload import OK

    mon = node.overload
    t0 = time.monotonic()
    await asyncio.sleep(mon.cfg.interval_s * 1.5)
    peak = mon.level
    while mon.level != OK:
        peak = max(peak, mon.level)
        if time.monotonic() - t0 > 120.0:
            raise BenchFailure(
                f"overload monitor stuck at level {mon.level} {after}")
        await asyncio.sleep(0.25)
    if peak != OK:
        say(f"overload: monitor reached level {peak} {after}; ok again "
            f"after {time.monotonic() - t0:.1f}s")


class Run:
    """One run of one cell."""

    def __init__(self, bench: Bench, args, clock: CompileClock,
                 logcap: _LogCapture, peaks: dict, dev: dict,
                 sabotage=None) -> None:
        self.bench = bench
        self.args = args
        self.clock = clock
        self.logcap = logcap
        self.peaks = peaks
        self.dev = dev
        self.sabotage = sabotage
        self.wl, self.cfg, self.traffic = bench.cell(args.workload)
        self.spans: list = []
        self.subs: list = []
        self.pub = None
        self.ref = None
        self.node = None
        self.tmp = None

    # -- children ----------------------------------------------------------

    async def start_children(self, init: dict, sample: list):
        gen = os.path.join(self.bench.dir, "loadgen.py")
        n_sub = self.traffic["subscriber_procs"]
        ref = await Child.start(
            os.path.join(self.bench.dir, "reference.py"), "trie child",
            {"population": self.cfg["population"], "seed": self.args.seed,
             "publish_topics": self.plan.law, "positions": sample})
        self.pub = await Child.start(
            gen, "publisher child", dict(init, role="pub"))
        self.subs = [await Child.start(
            gen, f"subscriber child {i}",
            dict(init, role="sub", index=i, n_procs=n_sub))
            for i in range(n_sub)]
        say(f"generator: 1 publisher process ({self.traffic['publishers']}"
            f" publishers, loop {self.traffic['loop']}), {n_sub} "
            f"subscriber processes, 1 trie child; "
            f"cpu_count={os.cpu_count()}")
        return ref

    async def phase(self, phase: int, seconds: float, during=None) -> dict:
        """One phase of traffic, drained to the end: publishers run for
        ``seconds``, subscribers wait for everything that is due and
        compare. ``during(t0)`` runs beside it."""
        t0 = time.monotonic() + 0.3
        cmd = {"cmd": "phase", "phase": phase, "t0": t0, "seconds": seconds,
               "start": list(self.start)}
        await asyncio.gather(*(s.ask(cmd) for s in self.subs))
        self.pub.send(cmd)
        side = asyncio.ensure_future(during(t0)) if during else None
        done = await self.pub.recv()
        fin = {"cmd": "finish", "phase": phase, "sent": done["sent"]}
        got = await asyncio.gather(*(s.ask(fin) for s in self.subs))
        if side is not None:
            await side
        out = {k: sum(g[k] for g in got)
               for k in ("attempted", "received", "missing", "surplus",
                         "bad_topic", "bad_qos", "stale", "in_window",
                         "closed", "refused")}
        self.start = [a + max(n, 0) for a, n in zip(self.start, done["sent"])]
        out.update(t0=t0, sent=done["sent"], start=cmd["start"],
                   pub_errors=done["errors"],
                   late_file=done["late_file"],
                   lat_files=[g["lat_file"] for g in got],
                   per_s=[sum(c) for c in zip(*(g["per_s"] for g in got))])
        return out

    # -- the run -----------------------------------------------------------

    async def serve(self) -> dict:
        from emqx_tpu.node import Node

        args, cfg, traffic = self.args, self.cfg, self.traffic
        loop = asyncio.get_running_loop()
        self.tmp = tempfile.mkdtemp(prefix="bench-run-")
        init = {"seed": args.seed, "config": cfg, "traffic": traffic,
                "dir": self.tmp}
        self.plan = plan = Plan(init)
        pop = self.bench.module("populations", cfg["population"]["kind"])
        # a seeded sample of pool positions: the messages whose sink
        # deliveries are held against the trie
        sample = sorted(random.Random(args.seed ^ 0x5A3B1E).sample(
            range(plan.n_pool), min(cfg["sink_sample"], plan.n_pool)))
        self.start = [0] * plan.n_pubs
        ref = self.ref = await self.start_children(init, sample)

        node = self.node = Node(boot_listeners=False)
        lst = node.add_listener(host="127.0.0.1", port=0)
        if node.broker.breaker is None:
            raise BenchFailure("Node built without its DeviceBreaker")

        # -- the deployment restores its subscriptions at boot, through
        # Broker.subscribe, before the node serves. (Seeded into a node
        # that serves, the first $SYS alarm publish flattens a partial
        # automaton and the rest of the filters goes through the delta
        # and its background compactions: seeding then takes 40-210 s
        # and the run matches on two automatons. That is another cell,
        # PERF.md section 7.)
        sink = Sink()
        t0 = time.monotonic()

        def _seed() -> int:
            filters, _vocab = pop.build(cfg["population"], args.seed)
            t1 = time.monotonic()
            for f in filters:
                node.broker.subscribe(sink, f)
            say(f"seed: {len(filters)} filters made in {t1 - t0:.1f}s, "
                f"subscribed in {time.monotonic() - t1:.1f}s")
            return len(filters)

        n_filters = await loop.run_in_executor(None, _seed)
        await node.start()
        import jax

        # programs that compile in under half a second are most of a
        # warm process's compile time: keep them in the cache too
        # (Node.start sets 0.5 s, so this comes after it)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
        if node.router._native is None:
            raise BenchFailure("router fell back to the pure-Python trie")
        if not node.router.use_device_now():
            raise BenchFailure("router chose the host regime")
        await settle(node, "after start")

        # -- sockets -------------------------------------------------------
        ready = [await c.recv() for c in [self.pub] + self.subs]
        say(f"generator: ready {json.dumps(ready)}")
        t0 = time.monotonic()
        conn = [await c.ask({"cmd": "connect", "port": lst.port})
                for c in self.subs + [self.pub]]
        refused = sum(c["refused"] for c in conn)
        say(f"sockets: {sum(c['connected'] for c in conn)} connected, "
            f"{refused} refused, in {time.monotonic() - t0:.1f}s on "
            f"127.0.0.1:{lst.port}")

        # -- warm-up: the cell's own traffic (it sets the shapes the
        # program learns from what it sees: budgets, depths, the largest
        # batch), then every bucket combination, then the traffic again
        # until rounds pass in which no program was first used ---------------
        rounds = quiet = 0
        self.warmers_s = 0.0
        while True:
            if rounds == WARM_ROUNDS_FIRST:
                for name in cfg.get("warmers", []):
                    c0 = self.clock.compiles
                    t0 = time.monotonic()
                    n = await self.bench.module("warmers", name).warm(
                        node, self.clock, say)
                    took = time.monotonic() - t0
                    self.warmers_s += took
                    say(f"warmer {name}: {n} batches, "
                        f"{self.clock.compiles - c0} programs first used, "
                        f"{took:.1f}s of set-up; largest ingress batch so "
                        f"far {node.ingress.max_batch}")
            rounds += 1
            c0 = (self.clock.secs, self.clock.compiles,
                  self.clock.cache_hits)
            t0 = time.monotonic()
            n_names = len(self.clock.names)
            w = await self.phase(rounds, WARM_ROUND_S)
            dc = self.clock.compiles - c0[1]
            if rounds > WARM_ROUNDS_FIRST:
                for nm in self.clock.names[n_names:]:
                    say(f"warm {rounds}: first used {nm}")
            say(f"warm {rounds}: {sum(max(n, 0) for n in w['sent'])} "
                f"messages, {w['received']} of {w['attempted']} socket "
                f"deliveries, {time.monotonic() - t0:.1f}s wall; {dc} programs "
                f"first used ({self.clock.cache_hits - c0[2]} from the "
                f"persistent cache) in {self.clock.secs - c0[0]:.1f}s")
            quiet = quiet + 1 if dc == 0 else 0
            if quiet >= WARM_QUIET_ROUNDS:
                break
            if rounds >= WARM_ROUNDS_MAX:
                raise BenchFailure(f"still compiling after {rounds} rounds")
        await settle(node, "after warm-up")
        say(f"compile: set-up loaded {self.clock.compiles} programs in "
            f"{self.clock.secs:.1f}s, {self.clock.cache_hits} of them from "
            f"the persistent cache")

        answer = await ref.recv()
        await ref.stop()
        if answer["filters"] != n_filters:
            raise BenchFailure("the trie child built another population")
        say(f"reference: plain trie over {answer['filters']} filters in "
            f"{answer['build_s']:.1f}s, {len(sample)} pool topics matched")

        self.sample, self.ref_matches = sample, answer["matches"]
        di = node.router.delta_info()
        say(f"router: {node.router.stats()['rebuilds']} flattens, delta "
            f"pending {di['pending']}, added {di['filters']}, merges "
            f"{di['merges']} (a pending delta means every batch matches "
            f"on two automatons)")

        if self.sabotage is not None:
            self.sabotage(self)

        # -- the measured window -------------------------------------------
        key = HEADER_KEY.pack
        sink.sample = {key(MEASURE, i): [] for i in sample}
        tel = node.telemetry
        finish = tel.finish
        if args.trace:
            def _record(span):
                if not span.closed:
                    finish(span)
                    self.spans.append({
                        "t0": span.t0, "path": span.path,
                        "bucket": span.bucket, "batch": span.batch,
                        "n_uniq": span.n_uniq, "fallbacks": span.fallbacks,
                        "stages": dict(span.stages)})
            tel.finish = _record
        n_log = len(self.logcap.lines)
        gcw = GcWatch()
        m0 = node.metrics.all()
        c0 = self.clock.compiles
        n_names = len(self.clock.names)
        trace = Trace(self) if args.trace else None
        try:
            m = await self.phase(MEASURE, args.seconds,
                                 during=trace.slice if trace else None)
        finally:
            tel.finish = finish
            gcw.close()
        full = sorted((s for g, s in gcw.pauses if g == 2), reverse=True)
        say(f"window: {len(gcw.pauses)} garbage collections, "
            f"{sum(s for _g, s in gcw.pauses):.3f}s in all; {len(full)} "
            f"full ones, the longest {[round(s, 3) for s in full[:4]]}s")
        m1 = node.metrics.all()
        counters = {k: v - m0.get(k, 0) for k, v in m1.items()}
        window_compiles = self.clock.compiles - c0
        for nm in self.clock.names[n_names:]:
            say(f"window: first used {nm}")

        # -- reduce ----------------------------------------------------------
        import numpy as np

        run = {
            "window_s": float(args.seconds),
            "setup_s": m["t0"] - T_START,
            "socket_deliveries_in_window": m["in_window"],
            "latency_s": np.concatenate(
                [np.fromfile(f, dtype=np.float64) for f in m["lat_files"]]),
            "gen_late_s": (np.fromfile(m["late_file"], dtype=np.float64)
                           if m["late_file"] else None),
            "counters": counters,
            "spans": self.spans if args.trace else None,
            "warmers_s": self.warmers_s,
        }
        if trace is not None:
            run.update(trace.read())
        lat = run["latency_s"]
        say(f"window: socket deliveries in each second {m['per_s']}")
        if len(lat):
            say(f"window: largest ingress batch {node.ingress.max_batch}")
            say(f"window: {sum(m['sent'])} messages published, "
                f"{m['received']} socket deliveries ({m['in_window']} "
                f"inside the window), sink deliveries {sink.count}; "
                f"delivery delay median, p99, largest "
                f"{float(np.median(lat)) * 1e3:.3f}, "
                f"{float(np.percentile(lat, 99)) * 1e3:.3f}, "
                f"{float(lat.max()) * 1e3:.3f} ms over {len(lat)} samples")

        correct = self.check(m, refused, sink, sample, answer["matches"],
                             counters, window_compiles, n_log)
        return {"run": run, "correct": correct, "phase": m}

    # -- correctness -------------------------------------------------------

    def check(self, m: dict, refused: int, sink: Sink, sample: list,
              ref_matches: list, counters: dict, window_compiles: int,
              n_log: int) -> dict:
        """Each number compared, beside its limit (all exact: 0)."""
        node = self.node
        # (a) sockets: multiset of message ids per socket == reference
        socket_failed = (m["missing"] + m["surplus"] + m["bad_topic"]
                         + m["bad_qos"] + m["stale"])
        conn_failed = (refused + m["closed"] + m["pub_errors"]
                       + sum(1 for n in m["sent"] if n < 0))
        # (b) the in-process subscriber's filters on the sampled topics
        # == the plain trie's, for every message of the window
        n_pool = self.plan.n_pool
        sink_bad = sink_msgs = sink_nonempty = 0
        for pos, want in zip(sample, ref_matches):
            got = sink.sample[HEADER_KEY.pack(MEASURE, pos)]
            by_msg: dict = {}
            for mid, flt in got:
                by_msg.setdefault(mid, []).append(flt)
            # how many of the window's messages took this pool position
            due = 0
            for p, n in enumerate(m["sent"]):
                first = (pos - self.plan.base(p, m["start"])) % n_pool
                if n > first:
                    due += (n - 1 - first) // n_pool + 1
            sink_msgs += due
            if want:
                sink_nonempty += due
                sink_bad += abs(due - len(by_msg))
                sink_bad += sum(1 for fl in by_msg.values()
                                if sorted(fl) != want)
            else:
                sink_bad += len(by_msg)
        # (c) the device did the work
        br = node.broker.breaker
        breaker = {k: counters.get(k, 0) for k in _BREAKER}
        state = br.STATE_NAMES[br.state]
        bad_log = [ln for ln in self.logcap.lines[n_log:]
                   if any(b in ln for b in _BAD_LOG)]
        off_device = sum(1 for s in self.spans
                         if s["path"] != "device" or not s["bucket"])
        numbers = [
            ("socket deliveries missing, surplus, misrouted or stale",
             socket_failed),
            ("connects refused, sockets closed, publishers failed",
             conn_failed),
            ("sampled sink messages whose filters differ from the trie's",
             sink_bad),
            ("breaker counters moved " + json.dumps(breaker),
             sum(breaker.values())),
            (f"breaker state {state!r} not closed", int(state != "closed")),
            ("fallback or traceback log lines in the window", len(bad_log)),
            ("programs first compiled or loaded inside the window",
             window_compiles),
            ("sampled sink messages for which the trie expects a filter "
             f"(limit: at least 1): {sink_nonempty}", int(not sink_nonempty)),
        ]
        if self.args.trace:
            numbers.append(("publish spans off the device path or with "
                            "bucket 0", off_device))
            numbers.append(("publish spans recorded (limit: at least 1)",
                            int(not self.spans)))
        for ln in bad_log[:3]:
            say(f"check: LOG {ln[:600]}")
        ok = True
        for what, value in numbers:
            ok = ok and value == 0
            say(f"check: {what}: {value} (limit 0)")
        say(f"check: {m['attempted']} socket deliveries expected, "
            f"{sink_msgs} sampled sink messages over {len(sample)} topics")
        if m["attempted"] == 0:
            ok = False
            say("check: nothing was attempted")
        return {"correct": ok, "attempted": m["attempted"] + sink_msgs,
                "failed": socket_failed + conn_failed + sink_bad}


class Trace:
    """A profiler trace of a slice in mid-window, and its reduction to
    the device's operations."""

    SLICE_S = 3.0

    def __init__(self, run: Run) -> None:
        self.run = run
        self.dir = os.path.join(run.tmp, "trace")
        self.window_s = None

    async def slice(self, t0: float) -> None:
        import jax

        seconds = self.run.args.seconds
        length = min(self.SLICE_S, seconds / 2.0)
        loop = asyncio.get_running_loop()
        await asyncio.sleep(
            max(0.0, t0 + (seconds - length) / 2.0 - time.monotonic()))
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        # start and stop off the loop: the broker keeps serving
        await loop.run_in_executor(
            None, lambda: jax.profiler.start_trace(
                self.dir, profiler_options=opts))
        t_on = time.monotonic()
        await asyncio.sleep(length)
        self.window_s = time.monotonic() - t_on
        await loop.run_in_executor(None, jax.profiler.stop_trace)

    def read(self) -> dict:
        import tracefile

        data = tracefile.load(self.dir)
        for ln in tracefile.describe(data):
            say(f"trace: {ln}")
        where = self.run.peaks.get(self.run.dev["kind"], {}).get("trace")
        if where is None:
            return {}
        per_plane = tracefile.device_ops(
            data, where["plane_prefix"], where["op_lines"])
        if not per_plane:
            raise BenchFailure("the trace holds no device plane")
        if len(per_plane) != 1:
            raise BenchFailure(f"the trace holds {len(per_plane)} device "
                               f"planes; every cell runs on one chip")
        (ops,) = per_plane.values()
        return {"device_ops": ops, "trace_window_s": self.window_s}


async def _amain(run: Run) -> dict:
    try:
        return await run.serve()
    finally:
        for c in [run.ref, run.pub] + run.subs:
            if c is not None:
                await c.stop()
        if run.node is not None:
            await run.node.stop()
        if run.tmp is not None:
            shutil.rmtree(run.tmp, ignore_errors=True)


def main(argv=None, *, allow_platform=None, sabotage=None,
         bench_dir=None) -> int:
    """``allow_platform``, ``sabotage`` and ``bench_dir`` are the seams
    of ``benchmark/tests``: the first lets a toy-size rehearsal run on
    the CPU backend, the second breaks the timed path on purpose so
    that ``correct`` is shown to fail, the third runs a copy of the
    benchmark's files. None is reachable from the command line."""
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    bench = Bench(bench_dir or _HERE)
    for p in (bench.dir, bench.root):
        if p not in sys.path:
            sys.path.insert(0, p)
    wl, _cfg, _traffic = bench.cell(args.workload)
    peaks = _load_json(os.path.join(bench.dir, "peaks.json"))
    try:
        import emqx_tpu  # noqa: F401
    except ImportError:
        raise SystemExit("run.py: the program (emqx_tpu) is not in this "
                         "checkout; nothing to measure")
    dev = device_gate(wl["chips"], peaks, allow_platform)
    logcap = _LogCapture()
    logcap.setFormatter(logging.Formatter(
        "%(levelname)s %(name)s: %(message)s"))
    root_log = logging.getLogger()
    root_log.addHandler(logcap)
    clock = CompileClock()
    try:
        build_native(os.path.dirname(os.path.dirname(emqx_tpu.__file__)))
        run = Run(bench, args, clock, logcap, peaks, dev, sabotage)
        res = asyncio.run(_amain(run))
    except BenchFailure as e:
        say(f"FAILED: {e}")
        return 1
    finally:
        root_log.removeHandler(logcap)

    # -- the result line -----------------------------------------------------
    import jax

    group, kind = (("per_layer", "layer_metrics") if args.trace
                   else ("end_to_end", "end_to_end"))
    metrics = {}
    for m, entry in bench.metrics_for(group, kind, args.workload):
        value = bench.reduce(entry, res["run"])
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = dict(dev, memory_peak_bytes=(
        jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use", 0))
    out = {**res["correct"], "metrics": metrics, "device": device}
    if args.trace and "device_ops" in res["run"]:
        device["busy_s"] = bench.module(
            "reducers", "trace_idle_share").busy_seconds(res["run"])
        device["window_s"] = res["run"]["trace_window_s"]
        out["breakdown"] = {
            k: bench.reduce(e, res["run"])
            for k, e in _load_json(
                os.path.join(bench.dir, "breakdown.json")).items()}
    say(f"total: {time.monotonic() - T_START:.1f}s")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
