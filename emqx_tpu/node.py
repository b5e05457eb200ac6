"""Broker node assembly + lifecycle — the ``emqx_app``/``emqx_sup``
analogue (src/emqx_app.erl:31-44, src/emqx_sup.erl:64-80).

Order mirrors the reference boot: kernel services (hooks, metrics,
stats, alarms) → router/broker → connection manager → modules/plugins
→ listeners. asyncio supervision replaces OTP supervisors: crashed
connection tasks die alone; the listener and node survive.
"""

from __future__ import annotations

import asyncio
import logging
import time
from typing import List, Optional

from emqx_tpu import faults as _faults
from emqx_tpu.alarm import AlarmManager
from emqx_tpu.banned import Banned
from emqx_tpu.broker import Broker, DispatchConfig
from emqx_tpu.cm import ConnectionManager
from emqx_tpu.connection import Listener
from emqx_tpu.ctl import Ctl
from emqx_tpu.flapping import Flapping
from emqx_tpu.gc import GlobalGc, freeze_resident
from emqx_tpu.hooks import Hooks
from emqx_tpu.ingress import IngressBatcher
from emqx_tpu.monitors import OsMon, SysMon, VmMon
from emqx_tpu.metrics import I_STATS_NS, Metrics
from emqx_tpu.modules import ModuleRegistry
from emqx_tpu.overload import (DeviceBreaker, OverloadConfig,
                               OverloadMonitor)
from emqx_tpu.modules.acl_file import AclFileModule
from emqx_tpu.modules.delayed import DelayedModule
from emqx_tpu.plugins import Plugins
from emqx_tpu.router import MatcherConfig, Router
from emqx_tpu.stats import Stats
from emqx_tpu.sys_topics import SysTopics
from emqx_tpu.telemetry import Telemetry, TelemetryConfig
from emqx_tpu.tracer import Tracer
from emqx_tpu.tracing import Tracing
from emqx_tpu.zone import Zone, get_zone

log = logging.getLogger("emqx_tpu.node")


class Node:
    def __init__(self, name: str = "emqx_tpu@127.0.0.1",
                 zone: Optional[Zone] = None,
                 matcher: Optional[MatcherConfig] = None,
                 telemetry: Optional[TelemetryConfig] = None,
                 dispatch_config: Optional[DispatchConfig] = None,
                 boot_listeners: bool = True,
                 sys_interval: float = 60.0,
                 load_default_modules: bool = False,
                 batch_ingress: bool = True,
                 batch_size: int = 256,
                 batch_linger_ms: float = 0.0,
                 loops: int = 1,
                 frame: str = "py",
                 overload: Optional[OverloadConfig] = None,
                 faults_config=None,
                 durability=None,
                 drain=None,
                 tracing=None,
                 plugin_config_dir: Optional[str] = None) -> None:
        self.name = name
        self.zone = zone or get_zone()
        # multi-loop front door ([node] loops, docs/DISPATCH.md
        # "Multi-loop front door"): shard accepted connections over N
        # event loops with loop-affine sessions and a cross-loop
        # delivery ring. loops = 1 builds NO LoopGroup — every code
        # path is the single-loop build byte-for-byte
        if not isinstance(loops, int) or isinstance(loops, bool) \
                or loops < 1:
            raise ValueError(f"loops must be an integer >= 1, "
                             f"got {loops!r}")
        if loops > 1:
            from emqx_tpu.loops import LoopGroup
            self.loop_group = LoopGroup(loops)
        else:
            self.loop_group = None
        # [node] frame: wire-framing parser variant for every
        # listener this node boots ("py" | "native": the Python
        # Parser or the per-connection C++ handle behind
        # NativeParser, mqtt/frame.py make_parser). Stored as
        # CONFIGURED (reload diffs file vs config); the EMQX_TPU_FRAME
        # env override resolves at listener construction.
        if frame not in ("py", "native"):
            raise ValueError(f'frame must be "py" or "native", '
                             f"got {frame!r}")
        self.frame = frame
        # kernel services (emqx_kernel_sup)
        self.hooks = Hooks()
        self.metrics = Metrics()
        self.stats = Stats()
        self.tracer = Tracer()
        # routing + pubsub core
        self.router = Router(config=matcher, node=name)
        self.broker = Broker(router=self.router, hooks=self.hooks,
                             metrics=self.metrics, node=name,
                             dispatch_config=dispatch_config)
        self.broker.tracer = self.tracer
        # ingress batcher: PUBLISHes from all connections aggregate
        # into one device publish_batch per tick (ingress.py)
        self.ingress = (IngressBatcher(self.broker,
                                       batch_size=batch_size,
                                       linger_ms=batch_linger_ms)
                        if batch_ingress else None)
        self.broker.ingress = self.ingress
        # connection/session management (emqx_cm_sup)
        self.cm = ConnectionManager(broker=self.broker)
        self.broker.banned = Banned()
        self.broker.flapping = Flapping(
            banned=self.broker.banned, metrics=self.metrics)
        # ops (emqx_sys_sup)
        self.alarms = AlarmManager(broker=self.broker, node=name)
        self.broker.alarms = self.alarms
        # overload protection + device-path circuit breaker
        # (overload.py, docs/ROBUSTNESS.md). [overload] enabled =
        # false builds NEITHER: the broker/channel/session guards
        # read None and the hot paths are byte-for-byte the
        # pre-overload build (pinned by tests/test_chaos.py)
        ocfg = overload or OverloadConfig()
        self.overload_config = ocfg
        if ocfg.enabled:
            self.overload = OverloadMonitor(self, ocfg)
            self.broker.overload = self.overload
            if ocfg.breaker:
                self.broker.breaker = DeviceBreaker(
                    self.metrics, alarms=self.alarms,
                    failures=ocfg.breaker_failures,
                    cooldown_s=ocfg.breaker_cooldown_s,
                    slow_ms=ocfg.breaker_slow_ms)
                if ocfg.breaker_rebuild:
                    # device-loss recovery (devloss.py): classify
                    # trips, rebuild HBM state on a lost backend,
                    # re-warm kernels, re-arm the half-open probe
                    from emqx_tpu.devloss import DeviceRecovery
                    self.broker.breaker.recovery = DeviceRecovery(
                        self.broker, self.metrics, self.alarms,
                        backoff_s=ocfg.rebuild_backoff_s,
                        sentinel_timeout_s=ocfg.sentinel_timeout_s)
            if self.ingress is not None:
                self.ingress.submit_wait_timeout = \
                    ocfg.ingress_wait_timeout_s
        else:
            self.overload = None
        # fault injection ([faults], faults.py): arm specs applied at
        # build; no section = the module-level registry is untouched
        # (kept for the live-reload diff, emqx_tpu/reload.py)
        self.faults_config = faults_config
        if faults_config is not None:
            _faults.configure(faults_config)
        # graceful drain ([drain], drain.py, docs/OPERATIONS.md):
        # always built, passive until `ctl drain start` / SIGTERM —
        # the channel's CONNECT gate reads broker.draining (None
        # until a drain is active, the usual zero-cost guard)
        from emqx_tpu.drain import NODE_RUNNING, DrainManager
        self.node_state = NODE_RUNNING
        self.drain = DrainManager(self, drain)
        self.broker.draining = None
        # the parsed boot NodeConfig when built from a file
        # (config.build_node) — the live-reload diff's baseline for
        # listener topology; None on programmatic nodes
        self.boot_config = None
        # durability layer ([durability], durability.py,
        # docs/DURABILITY.md): write-ahead journal + atomic
        # checkpoints + crash recovery. enabled = false (the default)
        # builds NO manager: broker/cm/channel/session/retainer
        # guards read None and the hot paths are byte-for-byte the
        # pre-durability build
        self.durability = None
        if durability is not None and durability.enabled:
            from emqx_tpu.durability import DurabilityManager
            self.durability = DurabilityManager(self, durability)
            self.broker.durability = self.durability
            self.cm.durability = self.durability
        # crashed background compaction: the router's thread records
        # the error here (plain attribute store — thread-safe); the
        # monitor/stats tick turns it into the alarm + backoff-retry
        self._flatten_err: Optional[str] = None
        self._flatten_alarmed = False
        self.router.on_bg_error = self._note_flatten_error
        # publish-path telemetry (telemetry.py): stage histograms +
        # slow-publish log. Wired onto broker AND router — the broker
        # stamps the spans, the router's cache-split dispatch leaves
        # its probe/merge share for the span to pick up
        self.telemetry = Telemetry(telemetry, tracer=self.tracer,
                                   alarms=self.alarms, node=name,
                                   metrics=self.metrics)
        self.broker.telemetry = self.telemetry
        self.router.telemetry = self.telemetry
        self.broker.helper.telemetry = self.telemetry
        # per-message span tracing ([tracing], tracing.py): always
        # constructed (like Telemetry) so reload/ctl can read the
        # config; with sample_rate = 0 no seam ever stamps a context
        # and the hot paths are byte-for-byte the untraced build
        self.tracing = Tracing(tracing, metrics=self.metrics,
                               alarms=self.alarms, node=name)
        self.broker.tracing = self.tracing
        self.sys = SysTopics(self.broker, node=name, stats=self.stats,
                             interval=sys_interval,
                             telemetry=self.telemetry,
                             tracing=self.tracing)
        # host monitors (emqx_os_mon / emqx_vm_mon / emqx_sys_mon)
        self.os_mon = OsMon(self.alarms)
        self.vm_mon = VmMon(self.alarms, self.cm.connection_count,
                            max_count=1024000)
        self.sys_mon = SysMon(metrics=self.metrics, hooks=self.hooks,
                              telemetry=self.telemetry,
                              ingress=self.ingress)
        self.global_gc = GlobalGc()
        # extension system
        self.modules = ModuleRegistry(self)
        self.plugins = Plugins(self, config_dir=plugin_config_dir)
        self.ctl = Ctl(self)
        self.listeners: List[Listener] = []
        self.boot_listeners = boot_listeners
        self._load_default_modules = load_default_modules
        self._started = False
        self._bg_tasks: list = []
        # cluster agent (set by enable_cluster + start, or by an
        # externally constructed Cluster attaching itself)
        self.cluster = None
        # replicated-durability agent (replication.py): set by
        # Cluster.__init__ on clustered nodes — journal shipper when
        # [durability] standby names a peer, warm standby replicas
        # for peers that ship here
        self.replication = None
        self._cluster_cfg: Optional[tuple] = None
        # fid-quarantine growth watch (stats tick): depth at the last
        # tick + consecutive-growth streak behind the
        # router_ids_quarantined alarm (_update_stats)
        self._quar_prev = 0
        self._quar_streak = 0
        # cluster-plane observability state (stats tick): cumulative
        # forward-drop count at the last tick (alarm edge detection)
        # + the per-member gauge rows published last tick (departed
        # peers' rows are deleted, not left stale)
        self._fwd_dropped_prev = 0
        self._cluster_stat_keys: set = set()
        self.stats.register_update(self._update_stats)

    # convenience accessors
    @property
    def banned(self) -> Banned:
        return self.broker.banned

    @property
    def flapping(self) -> Flapping:
        return self.broker.flapping

    def add_listener(self, host: str = "127.0.0.1", port: int = 1883,
                     zone: Optional[Zone] = None,
                     name: str = "tcp:default",
                     max_connections: int = 1024000,
                     reuse_port: bool = False,
                     proxy_protocol: bool = False,
                     proxy_protocol_timeout: float = 3.0,
                     access_rules=None,
                     max_conn_rate: float = 0.0) -> Listener:
        lst = Listener(self.broker, self.cm, host=host, port=port,
                       zone=zone or self.zone, name=name,
                       max_connections=max_connections,
                       reuse_port=reuse_port,
                       proxy_protocol=proxy_protocol,
                       proxy_protocol_timeout=proxy_protocol_timeout,
                       access_rules=access_rules,
                       max_conn_rate=max_conn_rate,
                       frame=self.frame)
        self.listeners.append(lst)
        return lst

    def add_ws_listener(self, host: str = "127.0.0.1", port: int = 8083,
                        path: str = "/mqtt", zone: Optional[Zone] = None,
                        name: str = "ws:default", ssl_context=None,
                        max_connections: int = 1024000):
        from emqx_tpu.ws_connection import WsListener
        lst = WsListener(self.broker, self.cm, host=host, port=port,
                         path=path, zone=zone or self.zone, name=name,
                         ssl_context=ssl_context,
                         max_connections=max_connections,
                         frame=self.frame)
        self.listeners.append(lst)
        return lst

    def add_tls_listener(self, host: str = "127.0.0.1", port: int = 8883,
                         tls_options=None, zone: Optional[Zone] = None,
                         name: str = "ssl:default",
                         max_connections: int = 1024000,
                         access_rules=None,
                         max_conn_rate: float = 0.0,
                         peer_cert_as_username=None) -> Listener:
        """TLS-terminating MQTT listener (reference mqtt:ssl via
        esockd, src/emqx_listeners.erl:43-76). A PSK-only option set
        on an interpreter whose ``ssl`` lacks server-side PSK falls
        through to the native OpenSSL engine (psk_tls.py)."""
        import ssl as _ssl

        from emqx_tpu.tls import TlsOptions, make_server_context
        opts = tls_options or TlsOptions()
        if (opts.psk is not None and not opts.certfile
                and not hasattr(_ssl.SSLContext,
                                "set_psk_server_callback")):
            from emqx_tpu.psk_tls import PskTlsListener
            lst = PskTlsListener(
                self.broker, self.cm, host=host, port=port,
                zone=zone or self.zone, name=name,
                max_connections=max_connections, psk=opts.psk,
                psk_identity_hint=opts.psk_identity_hint,
                psk_ciphers=opts.ciphers or "PSK",
                access_rules=access_rules,
                max_conn_rate=max_conn_rate,
                frame=self.frame)
            self.listeners.append(lst)
            return lst
        ctx = make_server_context(opts)
        lst = Listener(self.broker, self.cm, host=host, port=port,
                       zone=zone or self.zone, name=name,
                       ssl_context=ctx,
                       max_connections=max_connections,
                       access_rules=access_rules,
                       max_conn_rate=max_conn_rate,
                       peer_cert_as_username=peer_cert_as_username,
                       frame=self.frame)
        self.listeners.append(lst)
        return lst

    def add_wss_listener(self, host: str = "127.0.0.1", port: int = 8084,
                         path: str = "/mqtt", tls_options=None,
                         zone: Optional[Zone] = None,
                         name: str = "wss:default",
                         max_connections: int = 1024000):
        """TLS WebSocket listener (reference https:wss via cowboy)."""
        from emqx_tpu.tls import TlsOptions, make_server_context
        ctx = make_server_context(tls_options or TlsOptions())
        return self.add_ws_listener(host=host, port=port, path=path,
                                    zone=zone, name=name,
                                    ssl_context=ctx,
                                    max_connections=max_connections)

    def enable_cluster(self, port: int = 0, host: str = "127.0.0.1",
                       cookie: str = "emqxtpu", config=None) -> None:
        """Arrange for a socket cluster transport + Cluster agent to
        come up during :meth:`start` (the transport captures the
        serving loop). ``node.cluster.join_remote(host, port)`` joins
        a peer once started. ``config`` is the ``[cluster]``
        :class:`~emqx_tpu.cluster.ClusterConfig` (failure detector +
        auto-heal, docs/CLUSTER.md); None = legacy EOF-only failure
        detection."""
        self._cluster_cfg = (host, port, cookie, config)

    async def start(self) -> None:
        if self._started:
            return
        # persistent compile cache for the served path: a restarted
        # broker must not recompile every batch bucket (README
        # "Benchmarks"; placed by JAX_COMPILATION_CACHE_DIR or at
        # <repo>/.jax_cache — profiling.enable_compile_cache)
        from emqx_tpu.profiling import enable_compile_cache
        enable_compile_cache()
        if self._load_default_modules:
            self.load_default_modules()
        if self.durability is not None:
            # crash recovery BEFORE any listener accepts: newest
            # intact checkpoint into HBM, journal tail replayed,
            # retained topics re-armed, persistent sessions
            # resurrected (docs/DURABILITY.md). Runs with modules
            # loaded so the retainer can take its store back
            self.durability.recover()
        # what was restored at boot lives as long as the node does:
        # out of the collector's reach before the node serves
        freeze_resident(self.router.stats()["topics.count"])
        if self.boot_listeners and not self.listeners:
            self.add_listener()
        if self.listeners:
            # a connection is a descriptor: take what the host allows
            # before the first accept (vm.raise_fd_limit)
            from emqx_tpu.vm import raise_fd_limit
            fd = raise_fd_limit()
            low = 0 <= fd["soft"] < 4096
            log.log(logging.WARNING if low else logging.INFO,
                    "descriptor limit %d (was %d, hard limit %d)%s",
                    fd["soft"], fd["was"], fd["hard"],
                    ": this node can hold fewer connections than "
                    "that; raise `ulimit -n`" if low else "")
        if self.loop_group is not None:
            # multi-loop front door: peer loops come up BEFORE the
            # listeners (a dispatched socket needs a running owner),
            # and the shared-state paths arm their cross-thread modes
            self.loop_group.start(asyncio.get_running_loop())
            self.broker.loop_group = self.loop_group
            self.metrics.enable_threadsafe()
            if self.ingress is not None:
                self.ingress.bind_multiloop(self.loop_group)
            # per-loop lag probes (monitors.SysMon.run): every peer
            # loop gets a scheduling-lag gauge, not just the main loop
            self.sys_mon.bind_loops(self.loop_group)
        for lst in self.listeners:
            lst.loop_group = self.loop_group
            await lst.start()
        if self._cluster_cfg is not None and self.cluster is None:
            from emqx_tpu.cluster import Cluster
            from emqx_tpu.cluster_net import SocketTransport
            host, port, cookie, ccfg = self._cluster_cfg
            tr = SocketTransport(self.name, host=host, port=port,
                                 cookie=cookie, config=ccfg)
            tr.serve()
            self.cluster = Cluster(self, transport=tr, config=ccfg)
            log.info("cluster transport on %s:%s", tr.host, tr.port)
        # vm_mon watches the node-wide connection count, so the
        # watermark denominator is the summed listener capacity
        total_cap = sum(lst.max_connections for lst in self.listeners)
        if total_cap > 0:
            self.vm_mon.max_count = total_cap
        # config-file modules loaded before any loop existed start
        # their background tasks now (delayed timers, scrape sockets)
        self.modules.on_loop_start()
        loop = asyncio.get_event_loop()
        self._bg_tasks.append(loop.create_task(self._housekeeping()))
        self._bg_tasks.append(loop.create_task(self._sys_loop()))
        for mon in (self.os_mon, self.vm_mon, self.sys_mon,
                    self.global_gc):
            self._bg_tasks.append(loop.create_task(mon.run()))
        if self.overload is not None:
            self._bg_tasks.append(
                loop.create_task(self.overload.run()))
        if self.durability is not None:
            self._bg_tasks.append(
                loop.create_task(self.durability.run()))
        self._started = True
        log.info("node %s started", self.name)

    def load_default_modules(self) -> None:
        """The reference's default loaded modules
        (data/loaded_modules): delayed + internal ACL — plus the
        retainer (the reference ships it as a separate plugin app;
        users expect retained messages in the box)."""
        from emqx_tpu.modules.retainer import RetainerModule

        self.modules.load(DelayedModule)
        self.modules.load(AclFileModule)
        self.modules.load(RetainerModule)

    async def stop(self) -> None:
        from emqx_tpu.drain import NODE_STOPPING
        self.node_state = NODE_STOPPING
        # a still-active drain's wave task dies with the node; its
        # CONNECT gate is moot once the listeners close
        if self.drain.active:
            self.drain.stop()
            self.node_state = NODE_STOPPING
        for t in self._bg_tasks:
            t.cancel()
        self._bg_tasks.clear()
        br = self.broker.breaker
        if br is not None and br.recovery is not None:
            # an in-flight device-state rebuild must not retry into
            # a dying process (its thread is daemon — this just
            # breaks the backoff loop early)
            br.recovery.stop()
        # quiesce module background tasks (scrape sockets, timers)
        # without unloading — start() re-kicks them
        self.modules.on_loop_stop()
        drain_ref = self.drain.server_ref()
        if drain_ref is not None:
            # a drain target is configured: the stop is a REDIRECT
            # (docs/OPERATIONS.md) — v5 clients get 0x9C
            # Use-Another-Server + the Server-Reference instead of
            # 0x8B, and wills are suppressed like the cm takeover
            # path (custody moves; the sessions are not dying)
            from emqx_tpu.mqtt import reason_codes as RC
            for lst in self.listeners:
                lst.shutdown_rc = RC.USE_ANOTHER_SERVER
                lst.shutdown_ref = drain_ref
                lst.shutdown_drain = True
        elif self.durability is not None:
            # graceful shutdown (docs/DURABILITY.md): v5 clients get
            # DISCONNECT Server-Shutting-Down (0x8B) before their
            # sockets close, so fleets reconnect-and-resume instead
            # of diagnosing a dead peer
            from emqx_tpu.mqtt import reason_codes as RC
            for lst in self.listeners:
                lst.shutdown_rc = RC.SERVER_SHUTTING_DOWN
        # listeners first: drain() loops until quiescent, which never
        # happens while live connections keep submitting publishes
        for lst in self.listeners:
            await lst.stop()
        if self.ingress is not None:
            await self.ingress.drain()
        if self.durability is not None:
            # after listeners closed (sessions detached, final state
            # records written) and the ingress drained: flush the
            # journal and commit a clean-shutdown checkpoint — the
            # next boot recovers from the checkpoint, not a replay
            loop = asyncio.get_event_loop()
            await loop.run_in_executor(None,
                                       self.durability.shutdown)
        if self.cluster is not None:
            # heal/anti-entropy worker first (it calls through the
            # transport), then the transport itself
            self.cluster.close()
            if self._cluster_cfg is not None:
                close = getattr(self.cluster.transport, "close", None)
                if close is not None:
                    close()
        if self.loop_group is not None:
            # after listeners + ingress drain: in-flight cross-loop
            # handoffs have reported back, peer loops are idle
            self.loop_group.stop()
        # the loop profiler's sampler thread must not outlive the
        # loops it samples (no-op unless `ctl profile loops start`)
        self.tracing.profiler.stop()
        self._started = False

    async def _housekeeping(self) -> None:
        while True:
            await asyncio.sleep(5.0)
            self.cm.expire_sessions()
            self.broker.banned.expire()
            self.broker.flapping.gc()

    async def _sys_loop(self) -> None:
        while True:
            await asyncio.sleep(self.sys.interval)
            try:
                self.sys.heartbeat()
            except Exception:
                log.exception("sys heartbeat failed")

    def _update_stats(self, stats: Stats) -> None:
        """The stats flush, once a stats interval on the loop: a timed
        section of the loop's ledger (``loop.stats.ns`` / ``.calls``,
        exclusive like a read chunk or a flush wake-up) around
        :meth:`_fold_stats`."""
        lc = self.telemetry.loop_clock()
        if lc is None:
            self._fold_stats(stats)
            return
        t0 = time.perf_counter()
        n0 = lc.inner
        try:
            self._fold_stats(stats)
        finally:
            lc.loop_leave(I_STATS_NS, t0, n0)

    def _fold_stats(self, stats: Stats) -> None:
        # node lifecycle gauge (docs/OPERATIONS.md): 0 running /
        # 1 draining / 2 stopping — the fleet dashboard's one-glance
        # "is anything mid-maintenance" signal
        stats.setstat("node.state", self.node_state)
        stats.setstat("connections.count", self.cm.connection_count(),
                      "connections.max")
        stats.setstat("sessions.count", self.cm.session_count(),
                      "sessions.max")
        rstats = self.router.stats()
        stats.setstat("topics.count", rstats["topics.count"], "topics.max")
        stats.setstat("routes.count", rstats["routes.count"], "routes.max")
        nsubs = sum(len(s) for s in self.broker._subscriptions.values())
        stats.setstat("subscriptions.count", nsubs, "subscriptions.max")
        nshared = sum(len(m) for m in self.broker.shared._subs.values())
        stats.setstat("subscriptions.shared.count", nshared,
                      "subscriptions.shared.max")
        stats.setstat("subscribers.count",
                      sum(len(v) for v in self.broker._subscribers.values()),
                      "subscribers.max")
        dev = self.router.drain_device_stats()
        if any(dev.values()):
            self.metrics.fold_device_stats(dev)
        cache = self.router.drain_cache_stats()
        if any(cache.values()):
            self.metrics.fold_cache_stats(cache)
        auto = self.router.drain_automaton_stats()
        if any(auto.values()):
            self.metrics.fold_automaton_stats(auto)
        stats.setstat("automaton.compaction.ratio",
                      self.router.walk_info()["ratio"])
        stats.setstat("match.cache.entries.count",
                      self.router.cache_entries(),
                      "match.cache.entries.max")
        stats.setstat("match.cache.partition.live",
                      self.router.cache_partitions_live())
        if self.loop_group is not None:
            # per-loop connection gauges (docs/OBSERVABILITY.md): the
            # dispatcher's round-robin keeps these balanced — a skewed
            # row means a loop is wedged or leaking handlers
            per = [0] * self.loop_group.n
            for lst in self.listeners:
                for i, c in enumerate(getattr(lst, "_loop_conns", ())):
                    per[i] += c
            for i, c in enumerate(per):
                stats.setstat(f"loop.{i}.connections", c,
                              f"loop.{i}.connections.max")
        self._watch_quarantine(stats)
        if self.overload is not None:
            stats.setstat("overload.level", self.overload.level)
        if self.broker.breaker is not None:
            stats.setstat("breaker.state", self.broker.breaker.state)
        inj = _faults.drain_injected()
        if inj:
            self.metrics.inc("faults.injected", inj)
        if self.durability is not None:
            # journal/checkpoint counters are written off-loop —
            # fold their deltas here, apply thread-recorded alarm
            # transitions, and publish the operator gauges
            # (docs/OBSERVABILITY.md)
            self.durability.fold_metrics(self.metrics)
            self.durability.drain_events(self.alarms)
            dinfo = self.durability.info()
            j = dinfo["journal"]
            stats.setstat("journal.bytes", int(j.get("bytes", 0)))
            stats.setstat("journal.records", int(j.get("records", 0)))
            stats.setstat("durability.generation",
                          dinfo["generation"])
            age = dinfo.get("checkpoint_age_s")
            if age is not None:
                stats.setstat("checkpoint.age_s", int(age))
        if self.cluster is not None:
            self._fold_cluster_stats(stats)
        if self.replication is not None:
            # replication counters/lag gauges + the
            # replication_lagging alarm hysteresis
            self.replication.fold(self.metrics, self.alarms, stats)
        self.drain_robustness_events()
        stats.setstat("publish.spans.count", self.telemetry.spans_total,
                      "publish.spans.max")
        stats.setstat("publish.slow.count", self.telemetry.slow_total,
                      "publish.slow.max")
        # trace-span drain: swap the per-thread rings, fold flush
        # spans into slow_subs, bump tracing.* counters + gauges —
        # the ONE off-hot-path collection point (docs/OBSERVABILITY.md
        # "Tracing"). Cheap no-op while nothing is sampled
        self.tracing.drain_tick(stats)
        # per-loop scheduling lag (monitors.SysMon probes; index 0 is
        # the main loop, peers land as dynamic loop.<i>.lag_ms rows)
        for i, lag in enumerate(self.sys_mon.loop_lags):
            stats.setstat(f"loop.{i}.lag_ms", round(lag, 3))

    #: failure-detector state → gauge value (docs/OBSERVABILITY.md)
    _MEMBER_STATE_RANK = {"ok": 0, "suspect": 1, "down": 2}

    def _fold_cluster_stats(self, stats: Stats) -> None:
        """Cluster-plane observability, off the hot path: fold the
        drained event counters into Metrics as ``cluster.<key>``,
        publish the membership/health gauges, and edge-detect the
        ``cluster_forward_dropped`` alarm (docs/CLUSTER.md)."""
        cl = self.cluster
        self.metrics.fold_cluster_stats(cl.drain_counters())
        dropped = self.metrics.val("cluster.forward.dropped")
        if dropped > self._fwd_dropped_prev:
            self.alarms.activate(
                "cluster_forward_dropped",
                details={"dropped_total": dropped},
                message="cluster data-plane forwards dropped "
                        "(at-most-once loss; anti-entropy repairs "
                        "replicated state, QoS0 deliveries are gone)")
        elif dropped == self._fwd_dropped_prev:
            self.alarms.deactivate("cluster_forward_dropped")
        self._fwd_dropped_prev = dropped
        stats.setstat("cluster.members.count", len(cl.members))
        health = cl.transport.health_info()
        worst = 0
        slowest = 0.0
        keys = set()
        for name, info in health.items():
            rank = self._MEMBER_STATE_RANK.get(info["state"], 0)
            worst = max(worst, rank)
            rtt = info.get("rtt_ms")
            if rtt:
                slowest = max(slowest, float(rtt))
            for key, val in ((f"cluster.member.{name}.state", rank),
                             (f"cluster.member.{name}.rtt_ms",
                              round(float(rtt), 3) if rtt else 0)):
                keys.add(key)
                stats.setstat(key, val)
        # the named aggregate gauges: worst member state + slowest
        # heartbeat RTT (a single scrapeable signal per cluster)
        stats.setstat("cluster.member.state", worst)
        stats.setstat("cluster.hb.rtt_ms", round(slowest, 3))
        for stale in self._cluster_stat_keys - keys:
            stats.delstat(stale)
        self._cluster_stat_keys = keys

    def _note_flatten_error(self, exc) -> None:
        """Router background-compaction outcome callback — may run ON
        the compaction thread, so it only stores (alarm/metric work
        happens on-loop in :meth:`drain_robustness_events`)."""
        self._flatten_err = repr(exc) if exc is not None else None

    def drain_robustness_events(self) -> None:
        """Turn thread-recorded robustness events into alarms/metrics
        — called from the overload monitor tick and the stats flush
        (whichever runs first; both run on the main loop)."""
        err = self._flatten_err
        if err is not None and not self._flatten_alarmed:
            self._flatten_alarmed = True
            self.metrics.inc("overload.heal.flatten")
            self.alarms.activate(
                "router_compaction_failed",
                details={"error": err},
                message="background compaction crashed; "
                        "backoff retry armed")
        elif err is None and self._flatten_alarmed:
            self._flatten_alarmed = False
            self.alarms.deactivate("router_compaction_failed")

    #: consecutive growing stats ticks before the fid-quarantine
    #: alarm fires (with the default 60s sys_interval: ~3 minutes of
    #: monotonic growth — the round-4 soak leak crossed 200K ids in
    #: one)
    QUARANTINE_ALARM_TICKS = 3

    def _watch_quarantine(self, stats: Stats) -> None:
        """Publish the fid-quarantine depth gauge and raise the
        ``router_ids_quarantined`` alarm on sustained growth past the
        router's own reclaim bound — the device-regime analogue of
        the host-regime reclaim (router.py ``_retire_id``): between
        flattens nothing drains ``_pending_free``, so depth growing
        every tick means subscribe churn is outpacing
        compaction/rebuild and host memory grows linearly. Clears on
        the first non-growing tick (a flatten drained it)."""
        q = self.router.quarantined_ids()
        stats.setstat("router.ids.quarantined.count", q,
                      "router.ids.quarantined.max")
        bound = self.router.config.host_reclaim_pending
        if q > self._quar_prev and q > bound:
            self._quar_streak += 1
        else:
            self._quar_streak = 0
            self.alarms.deactivate("router_ids_quarantined")
        self._quar_prev = q
        if self._quar_streak >= self.QUARANTINE_ALARM_TICKS:
            self.alarms.activate(
                "router_ids_quarantined",
                details={"quarantined": q,
                         "streak_ticks": self._quar_streak,
                         "bound": bound},
                message=(f"router fid quarantine growing for "
                         f"{self._quar_streak} stats ticks "
                         f"(depth {q})"))

    # -- facade (src/emqx.erl:26-64) --------------------------------------

    def subscribe(self, sub, topic_filter: str, **kw):
        return self.broker.subscribe(sub, topic_filter, **kw)

    def unsubscribe(self, sub, topic_filter: str):
        return self.broker.unsubscribe(sub, topic_filter)

    def publish(self, msg):
        return self.broker.publish(msg)
