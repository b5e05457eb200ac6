"""The PubSub core: subscription tables, publish entry, dispatch.

Mirrors ``src/emqx_broker.erl``: ``subscribe/3`` (127-136),
``publish/1`` (200-210, incl. the 'message.publish' hook veto at
204-205), ``dispatch/2`` (283-309) and ``subscriber_down/1``
(331-348). The route step (aggre/forward, 233-281) goes through the
:class:`~emqx_tpu.router.Router`, whose match side is the compiled
TPU automaton; remote destinations are handed to a pluggable
``forwarder`` (the emqx_rpc seam — kept behind one interface so tests
and single-node runs exercise the full match/dispatch logic, SURVEY
§4 "multi-node without a real cluster").

Subscribers are any objects with ``deliver(topic, msg)``; sessions
(:mod:`emqx_tpu.session`) implement this protocol. For bulk/batched
publishing, :meth:`Broker.publish_batch` matches a whole batch on
device in one compiled call — this is the TPU-native throughput path
(the reference's per-connection processes ingest one message at a
time; here ingress batches per tick, SURVEY §2.2).
"""

from __future__ import annotations

import collections
import dataclasses
import logging
import threading
import time
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from emqx_tpu import faults
from emqx_tpu import topic as T
from emqx_tpu.concurrency import (any_thread, bg_thread,
                                  executor_thread, owner_loop,
                                  shared_state)
from emqx_tpu.broker_helper import FanoutManager, unpack_sids
from emqx_tpu.hooks import Hooks
from emqx_tpu.metrics import Metrics
from emqx_tpu.ops.bitmap import or_bitmaps_auto, rows_for_matches
from emqx_tpu.ops.dispatch_plan import (big_rows_for, build_plan,
                                        preserialize_plan)
from emqx_tpu.ops.fanout import expand_packed
from emqx_tpu.ops.pack import (budget_for, bundle_i32, mask_pad_flags,
                               pack_chip, pack_fanout, pack_matches,
                               pack_mesh, pack_union_rows)
from emqx_tpu.router import MatcherConfig, Router
from emqx_tpu.shared_sub import SharedSub
from emqx_tpu.types import Message, SubOpts
from emqx_tpu.utils.batch import dedup_topics

log = logging.getLogger("emqx_tpu.broker")


@dataclasses.dataclass
class DispatchConfig:
    """``[dispatch]`` TOML section: the publish delivery tail
    (docs/DISPATCH.md). Closed schema, like ``[matcher]``."""

    #: batch dispatch planner (ops/dispatch_plan.py): group the
    #: fetched packed deliveries BY SUBSCRIBER, resolve each session
    #: once per batch, enqueue its whole group in one deliver_many and
    #: fire one notify wakeup per connection per batch. False restores
    #: the legacy per-(filter, subscriber) walk byte-for-byte.
    planner: bool = True

    #: egress pre-serialization (docs/DISPATCH.md "Egress
    #: pre-serialization"): after the plan is built — on the same
    #: (possibly executor) fetch thread — QoS0 shared wire images and
    #: QoS1/2 packet-id-placeholder templates are pre-built per
    #: (message, proto_ver, flags variant), so the event loop's
    #: delivery tail patches 2 pid bytes into a buffer copy instead
    #: of running a full serialize() per frame. False restores the
    #: on-loop per-delivery serialization byte-for-byte. No effect
    #: when the planner is off (there is no plan to walk).
    preserialize: bool = True

    #: live-reloadable knobs (emqx_tpu/reload.py): both flags are
    #: read per publish batch (not a dataclass field: unannotated)
    RELOADABLE = frozenset({"planner", "preserialize"})


class _PlanState:
    """Per-batch host routing state the planned delivery tail shares
    between its prologue (per-row routing) and group chunks — plus,
    on a multi-loop node, the cross-loop delivery ring's join state
    (docs/DISPATCH.md "Multi-loop front door"): the set of handed-off
    groups, the per-handoff accepted deliveries, and the events
    the fold joins on. Everything after the prologue is read-only to
    the handoff loops except the ``xloop_*`` fields, which mutate
    under ``xloop_lock``.

    ``accepted`` is what the group walks delivered, one entry a
    delivery: the live row, or ``(row, filter)`` when ``hooked``
    (a ``message.delivered`` callback is registered, so the fold
    owes it every (message, filter) count); ``resolves`` counts the
    walks' (group, filter) resolutions (``delivery.plan.resolves``)."""

    __slots__ = ("row_local", "row_fast", "ftabs", "accepted",
                 "hooked", "resolves",
                 "xg_set", "xloop_results", "xloop_resolves",
                 "xloop_left", "xloop_lock", "xloop_tev", "xloop_aev",
                 "xloop_t0", "xloop_tdone", "folded")


#: what the planned tail's prologue knows of a matched filter id whose
#: one destination is this node, and of one that routes nowhere (freed
#: in the snapshot's id map): shared constants, so a distinct filter of
#: a batch costs two dict gets and builds no object. Any other filter
#: (a shared group, a remote node, a route deleted since the snapshot)
#: is a ``(filter, local, shared_items, remote_nodes)`` tuple
_DEST_LOCAL = ("local",)
_DEST_NONE = ("none",)


class PendingBatch:
    """An in-flight batched publish (see :meth:`Broker.publish_begin`).

    Carries the host bookkeeping (live messages, snapshot id map,
    fan-out state) plus the dispatched device values; after
    :meth:`Broker.publish_fetch` the packed host copies. ``done``
    short-circuits: the host path (below the device threshold, empty
    route table, vetoed-out batch) computes ``results`` inside
    ``publish_begin`` and never touches the device. A sharded mesh
    always takes the device path (its match syncs over ICI inside
    the step, but fan-out/pack fetch still runs in
    ``publish_fetch`` — possibly on an executor thread)."""

    __slots__ = (
        "done", "results", "live", "host_topics", "inv", "n_uniq",
        "host_matched", "host_inv", "host_only", "span", "tbatch",
        "plan", "plan_state", "xgroups", "dev_counts",
        "id_map",
        "epoch", "st", "ids_dev", "ovf_dev", "pm", "pq",
        "m_ptr_d", "ids_packed_d",
        "f_ptr_d", "subs_packed_d", "src_packed_d",
        "bovf_d", "sel_d", "rows_packed_d", "bm_total_d",
        "subs_dense_d", "src_dense_d", "union_dense_d", "has_big_d",
        "sh_big", "movf_d", "movf", "bundle_d",
        "m_ptr", "ids_packed", "ovf",
        "f_ptr", "subs_packed", "src_packed",
        "bovf", "sel", "rows_packed",
    )

    def __init__(self) -> None:
        self.done = False
        # telemetry span (telemetry.PublishSpan | None) — None is the
        # disabled fast path: every instrumented section below guards
        # on it with one branch and touches no clock
        self.span = None
        # trace batch (tracing._TraceBatch | None) — set only when
        # the batch carries sampled messages; same one-branch rule
        self.tbatch = None
        self.results: List[int] = []
        self.live: List[Tuple[int, Message]] = []
        self.host_topics: Optional[List[str]] = None
        self.host_matched = None  # host-path lazy match cache
        self.host_inv = None
        # breaker fallback: match on the host trie ONLY — an open or
        # rebuilding breaker means the device plane is suspect, and
        # the oracle fallback must never re-execute against it (a
        # LOST backend would raise out of the fallback itself)
        self.host_only = False
        # batch dispatch plan (ops/dispatch_plan.DispatchPlan), built
        # by publish_fetch when the planner is on and the batch has no
        # capacity-overflow row; None = legacy per-delivery walk
        self.plan = None
        self.plan_state = None
        # cross-loop delivery partition (multi-loop front door):
        # owning-loop index -> plan group indices, computed in
        # publish_fetch; None = every group delivers from this loop
        self.xgroups = None
        # (matches, deliveries, overflows) of this batch as the one
        # fetch read them (single-chip served path); folded into the
        # device.* counters by the first finish chunk, on the loop
        self.dev_counts = None
        self.inv: Optional[List[int]] = None
        self.n_uniq = 0
        self.st = None
        self.ids_dev = self.ovf_dev = None
        self.m_ptr_d = self.ids_packed_d = None
        self.f_ptr_d = None
        self.subs_packed_d = self.src_packed_d = None
        self.bovf_d = self.sel_d = self.rows_packed_d = None
        self.bm_total_d = None
        # mesh path: dense gathered (subs, src) and bitmap unions
        # kept for re-pack, the big-filter ids the device CSR gather
        # excluded (bitmap rows), and the match-only overflow (the
        # boost_k signal — fan overflow must not grow k)
        self.subs_dense_d = self.src_dense_d = None
        self.union_dense_d = self.has_big_d = None
        self.sh_big: frozenset = frozenset()
        self.movf_d = self.movf = None
        # the fetch's one buffer where the packer already laid it
        # (pack_chip / pack_mesh; a re-pack bundles anew)
        self.bundle_d = None
        self.f_ptr = self.subs_packed = None
        self.src_packed = None
        self.bovf = self.sel = self.rows_packed = None


@shared_state(lock="_route_lock", attrs=("_subscribers",
                                          "_subscriptions"))
class Broker:
    def __init__(
        self,
        router: Optional[Router] = None,
        hooks: Optional[Hooks] = None,
        metrics: Optional[Metrics] = None,
        shared: Optional[SharedSub] = None,
        node: str = "local",
        config: Optional[MatcherConfig] = None,
        dispatch_config: Optional[DispatchConfig] = None,
    ) -> None:
        self.node = node
        self.dispatch_config = dispatch_config or DispatchConfig()
        self.router = router or Router(config=config, node=node)
        self.hooks = hooks or Hooks()
        self.metrics = metrics or Metrics()
        self.shared = shared or SharedSub()
        # subscriber-id registry + device fan-out tables
        # (emqx_broker_helper analogue; see broker_helper.py)
        rcfg = self.router.config
        self.helper = FanoutManager(threshold=rcfg.fanout_threshold,
                                    use_device=rcfg.use_device)
        # a compaction's swap keeps every filter's id: the fan-out
        # tables go over to its epoch as they are (docs/DELTA.md)
        self.router.on_swap = self.helper.carry
        # filter -> {subscriber: SubOpts}   (emqx_subscriber / emqx_suboption)
        self._subscribers: Dict[str, Dict[object, SubOpts]] = {}
        # subscriber -> {filter: SubOpts}   (emqx_subscription)
        self._subscriptions: Dict[object, Dict[str, SubOpts]] = {}
        # pluggable cross-node forwarder (emqx_rpc seam); set by cluster
        self.forwarder = None
        # ingress batcher (ingress.py); Node attaches one so channels
        # batch their PUBLISH broker calls per tick
        self.ingress = None
        # cluster-wide shared-group router: (group, flt, nodes, msg)
        # -> local delivery count; None = single-node (local pick)
        self.shared_router = None
        # optional subsystems wired by Node (channel consults them)
        self.banned = None
        self.flapping = None
        self.delayed = None
        self.tracer = None
        # publish-path telemetry (telemetry.Telemetry), wired by Node
        # next to router.telemetry; None = uninstrumented
        self.telemetry = None
        # per-message span tracing (tracing.Tracing), wired by Node;
        # None (or sample_rate = 0) = untraced, byte-identical wire
        self.tracing = None
        # overload protection (overload.py), wired by Node when
        # [overload] enabled: the monitor (channel consults it at
        # CONNECT, sessions at QoS0 enqueue), the device-path circuit
        # breaker (publish begin/fetch), and the alarm manager.
        # All None = byte-for-byte the pre-overload build
        self.overload = None
        self.breaker = None
        self.alarms = None
        # durability layer (durability.py, docs/DURABILITY.md), wired
        # by Node when [durability] enabled: route mutations journal
        # an absolute refcount record, durable-session subscriptions
        # journal alongside, and publish_fetch flushes the batched
        # journal from the executor thread. None = byte-for-byte the
        # pre-durability build (one attribute test per site)
        self.durability = None
        # multi-loop front door (loops.LoopGroup), set by Node.start
        # when [node] loops > 1; None = single-loop, every multi-loop
        # branch below is skipped entirely
        self.loop_group = None
        # serializes route/table mutations (subscribe/unsubscribe/
        # subscriber_down) across front-door loops: a subscribe is a
        # multi-step update over _subscribers + helper + router, and
        # two loops interleaving them would corrupt the automaton.
        # The publish match path stays lock-free — it reads published
        # snapshots behind the router's epoch guards
        self._route_lock = threading.RLock()
        # learned packed-transfer budgets per batch bucket: a workload
        # whose steady-state fan-out exceeds the configured budget
        # would otherwise pay a re-pack + second transfer EVERY batch
        self._pack_budgets: Dict[int, List[int]] = {}

    # -- subscribe / unsubscribe (emqx_broker.erl:127-196) ----------------

    @any_thread
    def subscribe(self, sub: object, topic_filter: str,
                  opts: Optional[SubOpts] = None) -> SubOpts:
        """Subscribe ``sub`` to ``topic_filter`` (may carry a
        ``$share/<group>/`` prefix). Subscriptions are keyed by the
        full filter string, so a shared and a plain subscription on
        the same bare filter coexist independently."""
        T.validate(topic_filter, "filter")
        flt, popts = T.parse(topic_filter)
        opts = opts or SubOpts()
        if "share" in popts:
            opts.share = popts["share"]
        with self._route_lock:
            subs = self._subscriptions.setdefault(sub, {})
            resub = topic_filter in subs
            subs[topic_filter] = opts
            if opts.share is not None:
                dest = (opts.share, self.node)
                if not resub:
                    self.shared.subscribe(opts.share, flt, sub)
                    self.router.add_route(flt, dest=dest)
            else:
                dest = self.node
                self._subscribers.setdefault(flt, {})[sub] = opts
                if not resub:
                    self.helper.subscribe(flt, sub)
                    self.router.add_route(flt, dest=self.node)
            d = self.durability
            if d is not None:
                d.journal_subscribe(sub, topic_filter, flt, dest,
                                    opts, resub)
        return opts

    @any_thread
    def unsubscribe(self, sub: object, topic_filter: str) -> bool:
        flt, popts = T.parse(topic_filter)
        with self._route_lock:
            subs = self._subscriptions.get(sub)
            if subs is None or topic_filter not in subs:
                return False
            opts = subs.pop(topic_filter)
            if not subs:
                del self._subscriptions[sub]
            share = popts.get("share", opts.share)
            if share is not None:
                dest = (share, self.node)
                self.shared.unsubscribe(share, flt, sub)
                self.router.delete_route(flt, dest=dest)
            else:
                dest = self.node
                ftab = self._subscribers.get(flt)
                if ftab is not None:
                    ftab.pop(sub, None)
                    if not ftab:
                        del self._subscribers[flt]
                self.helper.unsubscribe(flt, sub)
                self.router.delete_route(flt, dest=self.node)
            if sub not in self._subscriptions:
                self.helper.release(sub)
            d = self.durability
            if d is not None:
                d.journal_unsubscribe(sub, topic_filter, flt, dest)
        return True

    @any_thread
    def subscriber_down(self, sub: object) -> None:
        """Drop all of a dead subscriber's subscriptions
        (emqx_broker.erl:331-348); unacked shared-group messages are
        redispatched to the surviving members (the reference's
        shared-sub nack/redispatch, emqx_shared_sub.erl:131-227)."""
        with self._route_lock:
            for key in list(self._subscriptions.get(sub, {})):
                self.unsubscribe(sub, key)
            self.shared.subscriber_down(sub)
        pending = getattr(sub, "take_shared_pending", None)
        if pending is not None:
            for group, flt, orig, was_sent in pending():
                # never mutate the shared original (other sessions'
                # copies reference its state); DUP is decided per
                # delivery in Session._enrich AFTER the survivor's QoS
                # downgrade, so a QoS0 member never sees DUP=1
                msg = orig.copy()
                if was_sent:
                    msg.set_header("redispatch", True)
                nodes = [r.dest[1] for r in self.router.lookup_routes(flt)
                         if isinstance(r.dest, tuple) and r.dest[0] == group]
                if self.shared_router is not None and nodes:
                    # surviving members may live on other nodes
                    n = self.shared_router(group, flt, nodes, msg)
                else:
                    n = self.shared.dispatch(group, flt, msg)
                if n:
                    self.metrics.inc("messages.redispatched")

    @any_thread
    def detach_subscriber(self, sub: object) -> None:
        """Remove a subscriber's table entries WITHOUT the death-path
        side effects (no shared redispatch): the session is being
        handed to another node's broker, which resubscribes it."""
        with self._route_lock:
            for key in list(self._subscriptions.get(sub, {})):
                self.unsubscribe(sub, key)
            self.shared.subscriber_down(sub)

    @any_thread
    def restore_subscription(self, sub: object, topic_filter: str,
                             opts: Optional[SubOpts] = None) -> None:
        """Crash-recovery resubscribe (durability.py): rebuild the
        subscriber/fanout/shared tables for a resurrected persistent
        session WITHOUT bumping the router — its route refs were
        already restored from the checkpoint + journal, and a second
        ``add_route`` here would leave a stale route behind on the
        session's eventual unsubscribe. Adds the route only if the
        restored table somehow lacks it (self-healing a journal
        gap)."""
        T.validate(topic_filter, "filter")
        flt, popts = T.parse(topic_filter)
        opts = opts or SubOpts()
        if "share" in popts:
            opts.share = popts["share"]
        with self._route_lock:
            subs = self._subscriptions.setdefault(sub, {})
            resub = topic_filter in subs
            subs[topic_filter] = opts
            if opts.share is not None:
                dest = (opts.share, self.node)
                if not resub:
                    self.shared.subscribe(opts.share, flt, sub)
            else:
                dest = self.node
                self._subscribers.setdefault(flt, {})[sub] = opts
                if not resub:
                    self.helper.subscribe(flt, sub)
            if not self.router.has_dest(flt, dest):
                self.router.add_route(flt, dest=dest)

    def subscribers(self, topic_filter: str) -> List[object]:
        return list(self._subscribers.get(topic_filter, ()))

    def subscriptions(self, sub: object) -> Dict[str, SubOpts]:
        return dict(self._subscriptions.get(sub, {}))

    def suboption(self, sub: object, topic_filter: str) -> Optional[SubOpts]:
        return self._subscriptions.get(sub, {}).get(topic_filter)

    # -- publish (emqx_broker.erl:200-309) --------------------------------

    def publish(self, msg: Message) -> int:
        """Publish one message; returns delivery count."""
        lg = self.loop_group
        if lg is not None and not lg.on_home_thread():
            # multi-loop front door: a publish originating on a peer
            # loop (a will firing in a peer-loop disconnect, a shared
            # redispatch during one) must not drive the device plane
            # from that thread — funnel it through the ingress
            # accumulator (ordering preserved with in-flight batches)
            # or, without one, post it to the main loop. The delivery
            # count is unknown here; these paths ignore it.
            ing = self.ingress
            if ing is not None and ing.accepts_threadsafe():
                ing.submit(msg, want_result=False)
            else:
                try:
                    lg.post(0, lambda: self.publish_batch([msg]))
                except RuntimeError:
                    # home loop gone (shutdown race / dead loop):
                    # this publish is LOST — count it instead of
                    # vanishing silently (docs/ROBUSTNESS.md)
                    self.metrics.inc("delivery.xloop.orphaned")
                    log.warning("publish of %r dropped: home loop "
                                "gone", msg.topic)
                    return 0
            return 0
        return self.publish_batch([msg])[0]

    def publish_will(self, msg: Message) -> None:
        """Will dispatch (channel teardown, delayed-will expiry,
        clean-start fires): funnel through the ingress accumulator
        whenever one is taking submissions — INCLUDING on the home
        loop, unlike :meth:`publish`, which only funnels peer-loop
        callers. Nobody awaits a will's delivery count, so the
        fire-and-forget submit is free, and a mass-disconnect wave
        (loop death, drain, fleet churn) coalesces its wills into the
        accumulator's normal device batches instead of N one-message
        ``publish_batch`` calls — each a full match/fan-out/fetch
        round-trip. Falls back to :meth:`publish` when no accumulator
        loop is running (sync drivers, shutdown tail)."""
        ing = self.ingress
        if ing is not None:
            if ing.submit(msg, want_result=False) is not None:
                self.metrics.inc("wills.batched")
                return
        self.metrics.inc("wills.direct")
        self.publish(msg)

    def publish_batch(self, msgs: Sequence[Message]) -> List[int]:
        """Batch publish — the TPU hot path, synchronously.

        One compiled device *match* for the whole batch, one compiled
        device *fan-out* (CSR subscriber gather for small filters +
        Pallas bitmap OR for >threshold filters), one compiled *pack*
        (sparse compaction, ops/pack.py), ONE coalesced device→host
        transfer; the host loop is only the delivery tail (sub-id →
        session ``deliver``) plus remote/shared routing. Mirrors the
        reference's two hot loops (trie walk src/emqx_trie.erl:161-186;
        subscriber fold src/emqx_broker.erl:283-309).

        The async ingress path calls the three phases separately so
        the blocking transfer runs off the event loop and batches
        pipeline (:mod:`emqx_tpu.ingress`).
        """
        pb = self.publish_begin(msgs)
        if pb.done:
            return pb.results
        self.publish_fetch(pb)
        return self.publish_finish(pb)

    @owner_loop
    def publish_begin(self, msgs: Sequence[Message],
                      defer_host: bool = False,
                      span=None) -> PendingBatch:
        """Phase 1 — host pre-work + device dispatch, no sync.

        Runs hooks/veto/metrics, picks host vs device matching
        (:meth:`Router.use_device_now`), and for the device path
        enqueues match → fan-out → pack without any device→host
        transfer. Returns a :class:`PendingBatch`; if ``pb.done`` the
        results are already computed (host path).

        ``defer_host`` postpones host-path ROUTING to
        :meth:`publish_finish` (``pb.done`` stays False): the pipelined
        ingress uses it while earlier batches are still in flight so a
        host-path batch cannot deliver ahead of them. ``span`` is the
        telemetry span the ingress batcher already opened at the
        batch's first arrival (its ``ingress_wait`` is on it)."""
        pb = PendingBatch()
        if span is not None:
            pb.span = span
        else:
            tel = self.telemetry
            if tel is not None and tel.enabled:
                pb.span = tel.begin(len(msgs))
        sp = pb.span
        if sp is not None:
            # metrics, message.publish hooks, veto, dedup: closed by
            # whichever stage starts next
            sp.start("prepare")
        trc = self.tracing
        tracing_on = trc is not None and trc.active
        tctxs = None
        pb.results = [0] * len(msgs)
        for i, msg in enumerate(msgs):
            self.metrics.inc_msg(msg)
            if self.tracer is not None:
                self.tracer.trace_publish(msg)
            out = self.hooks.run_fold("message.publish", (), msg)
            if out is None or (
                    out.get_header("allow_publish") is False):
                self.metrics.inc("messages.dropped")
                self.hooks.run("message.dropped",
                               (out if out is not None else msg, "vetoed"))
                continue
            self.metrics.inc("messages.publish")
            if out.flags.get("retain"):
                self.metrics.inc("messages.retained")
            pb.live.append((i, out))
            if tracing_on:
                # idempotent: a context stamped at ingress submit (or
                # carried over a cluster forward) is kept as-is
                ctx = trc.stamp(out)
                if ctx is not None:
                    if tctxs is None:
                        tctxs = []
                    tctxs.append(ctx)
        if not pb.live:
            pb.done = True
            self._span_finish(pb)
            return pb
        if tctxs is not None:
            pb.tbatch = trc.batch_begin(tctxs)
        if sp is not None:
            sp.topic = pb.live[0][1].topic
        topics = [m.topic for _, m in pb.live]
        cfg = self.router.config
        if not self.router.use_device_now():
            # host regime: let the router shed a stale automaton's id
            # quarantine once it has grown past its bound (bounded
            # hysteresis — an oscillating filter count must not pay a
            # re-flatten per threshold crossing)
            self.router.reclaim_host_regime()
            return self._begin_host(pb, topics, defer_host)
        br = self.breaker
        if br is not None and not br.allow_device():
            # device-path circuit breaker OPEN: exact host-oracle
            # matching until a half-open probe closes it
            # (docs/ROBUSTNESS.md). The automaton is NOT reclaimed —
            # the probe rides it straight back
            self.metrics.inc("breaker.fallback.batches")
            return self._begin_host(pb, topics, defer_host,
                                    host_only=True)
        try:
            return self._begin_device(pb, topics, cfg)
        except Exception:
            if br is None:
                raise
            # device dispatch died (kernel failure, injected fault):
            # record for the breaker and serve THIS batch exactly
            # from the host oracle — no wrong or lost deliveries
            br.record_failure()
            log.exception("device publish dispatch failed — "
                          "host-oracle fallback for this batch")
            return self._begin_host(pb, topics, defer_host,
                                    host_only=True)

    def _begin_host(self, pb: PendingBatch, topics: List[str],
                    defer_host: bool,
                    host_only: bool = False) -> PendingBatch:
        """The host-path tail of ``publish_begin`` (true host regime,
        breaker-forced fallback, or a device dispatch failure).
        ``host_only`` pins the batch's matching to the host trie —
        the breaker paths use it so a suspect (or LOST) device plane
        is never re-entered through ``match_filters``."""
        pb.host_only = host_only
        sp = pb.span
        if sp is not None:
            sp.path = "host"
            # prepare — or the device stage a failed dispatch left
            sp.stop()
        if defer_host:
            pb.host_topics = topics
        else:
            self._publish_host(pb, topics)
            pb.done = True
            self._span_finish(pb)
        return pb

    def _begin_device(self, pb: PendingBatch, topics: List[str],
                      cfg) -> PendingBatch:
        # device match (HOT LOOP 1) → device fan-out (HOT LOOP 2)
        # → pack (transfer compaction); all async-dispatched.
        # Duplicate topics in the batch (hot topics arrive many times
        # per tick) collapse to one device row; the delivery tail
        # expands per message via the inverse index. INTER-batch
        # repeats additionally hit the router's epoch-guarded match
        # cache (ops/match_cache.py): the dispatch below splits the
        # unique topics into cache hits (one HBM gather, no NFA walk)
        # and misses (walked, then inserted) — transparent here, the
        # merged [B_pad, M] id array feeds the same fan-out/pack
        # kernels either way. With the cache on the batch costs the
        # loop one transfer and two programs: the router's match
        # (walk + insert + merge; the merge alone where every topic
        # hit) and one packer here.
        sp = pb.span
        if faults.enabled:
            faults.fire("device.walk")
            faults.fire("device.lost")
        uniq, pb.inv = dedup_topics(topics)
        pb.n_uniq = len(uniq)
        if sp is not None:
            sp.n_uniq = pb.n_uniq
        if cfg.mesh is not None:
            return self._publish_begin_mesh(pb, uniq, cfg)
        if sp is not None:
            sp.start("match")
        # ids come back with their pad rows blanked (phantom pad-row
        # matches must not reach the packers or the learned budgets)
        pb.ids_dev, pb.ovf_dev, pb.id_map, pb.epoch = \
            self.router.match_dispatch(uniq, span=sp)
        if sp is not None:
            # closes the match stage; the router's cache-split path
            # (telemetry-gated) left the cache_gather share to split
            sp.stop_match(self.router)
            # the fan-out tables brought up to the memberships that
            # changed since the last batch: a compare where none did
            sp.start("fan_sync")
        pb.st = self.helper.state(pb.epoch, pb.id_map)
        if sp is not None:
            sp.start("pack")
        bucket = pb.ids_dev.shape[0]
        budgets = self._pack_budgets.setdefault(
            bucket, [budget_for(bucket, cfg.pack_m),
                     budget_for(bucket, cfg.pack_q),
                     max(1, cfg.pack_rows)])
        pb.pm = budgets[0]
        st = pb.st
        fan = st.fan if st is not None else None
        if fan is not None:
            pb.pq = budgets[1]
        if self.router.cache_slots() and (st is None or st.bm is None):
            # the packers (matches, then the fused sparse expansion:
            # packed matches → packed deliveries, gather work
            # proportional to actual traffic) and what the fetch would
            # bundle, one program; the fetch launches nothing
            (pb.m_ptr_d, pb.ids_packed_d, pb.f_ptr_d, pb.subs_packed_d,
             pb.src_packed_d, pb.bundle_d) = pack_chip(
                fan, pb.ids_dev, pb.ovf_dev, pm=pb.pm,
                pq=pb.pq if fan is not None else 0)
            self.router.count_fused()
        else:
            # big-filter bitmaps live (their kernels need the dense
            # ids) or the match cache off: the calls apart, and the
            # fetch lays the bundle (docs/MATCH_CACHE.md)
            pb.m_ptr_d, pb.ids_packed_d = pack_matches(pb.ids_dev,
                                                       pm=pb.pm)
            if fan is not None:
                pb.f_ptr_d, pb.subs_packed_d, pb.src_packed_d, _tot = \
                    expand_packed(fan, pb.m_ptr_d, pb.ids_packed_d,
                                  q=pb.pq)
            if st is not None and st.bm is not None:
                rows_d, pb.bovf_d = rows_for_matches(
                    st.bm, pb.ids_dev, mb=cfg.fanout_mb)
                union_d = or_bitmaps_auto(st.bm.bitmaps, rows_d)
                has_big = (rows_d >= 0).any(axis=1)
                pb.sel_d, pb.rows_packed_d, pb.bm_total_d = \
                    pack_union_rows(union_d, has_big, pr=budgets[2])
        if sp is not None:
            sp.bucket = bucket
            sp.stop()
        return pb

    def _publish_begin_mesh(self, pb: PendingBatch, uniq: List[str],
                            cfg) -> PendingBatch:
        """Mesh publish dispatch: ONE collective step does match +
        per-shard subscriber gather + ICI all-gather
        (``publish_step(with_fanout=True)`` with the FanoutManager's
        per-shard tables); the dense gathered (subs, src) then pack
        on device for the coalesced fetch, ids and fan-out in one
        program (``pack_mesh``). Filters too big for the ``d`` bound
        deliver host-side from ``pb.sh_big``. Repeat topics hit the
        router's sharded match cache (cached ids/subs/src rows gather
        from HBM; only misses run the collective step — see
        Router._dispatch_fused: with this packer a batch costs the
        loop one transfer and two or three programs)."""
        def fan_provider(epoch, id_map):
            return self.helper.sharded_state(
                epoch, id_map, cfg.mesh, self.router.effective_d())

        sp = pb.span
        if sp is not None:
            sp.path = "mesh"
            sp.start("match")
        # ids / subs / src come back with their pad rows blanked
        # (phantom pad-row matches must not reach the packers or the
        # learned budgets)
        (pb.ids_dev, pb.subs_dense_d, pb.src_dense_d, bm, pb.ovf_dev,
         pb.movf_d, pb.id_map, pb.epoch, pb.sh_big) = \
            self.router.publish_dispatch_sharded(uniq, fan_provider,
                                                 span=sp)
        if sp is not None:
            # the cache probe, the collective step's enqueue for the
            # misses (match + gather + ICI all-gather + the cache
            # insert as one program) and the merge's; the cache-split
            # path leaves its gather share like the single-chip one
            sp.stop_match(self.router)
            sp.start("pack")
        bucket = pb.ids_dev.shape[0]
        budgets = self._pack_budgets.setdefault(
            bucket, [budget_for(bucket, self.router.config.pack_m),
                     budget_for(bucket, self.router.config.pack_q),
                     max(1, self.router.config.pack_rows)])
        pb.pm = budgets[0]
        if pb.subs_dense_d is not None:
            pb.pq = budgets[1]
            (pb.m_ptr_d, pb.ids_packed_d, pb.f_ptr_d, pb.subs_packed_d,
             pb.src_packed_d, bundle) = pack_mesh(
                pb.ids_dev, pb.subs_dense_d, pb.src_dense_d,
                pb.ovf_dev, pb.movf_d, pm=pb.pm, pq=pb.pq)
            if bm is None:
                # what the fetch would bundle, already laid
                pb.bundle_d = bundle
        else:
            pb.m_ptr_d, pb.ids_packed_d = pack_matches(pb.ids_dev,
                                                       pm=pb.pm)
        if bm is not None:
            # big-filter bitmap unions (per-shard OR + ICI combine):
            # pack only the rows that actually matched a big filter
            union_d, has_big_d, pb.bovf_d = bm
            pb.union_dense_d = union_d
            pb.has_big_d = mask_pad_flags(has_big_d,
                                          np.int32(pb.n_uniq))
            pb.sel_d, pb.rows_packed_d, pb.bm_total_d = pack_union_rows(
                union_d, pb.has_big_d, pr=budgets[2])
        if sp is not None:
            sp.bucket = bucket
            sp.stop()
        return pb

    def _publish_host(self, pb: PendingBatch, topics: List[str]) -> None:
        """Host-path matching + routing for a begun batch (below the
        device threshold, device off, or empty route table). Hot
        topics dedup here too — one trie walk per unique topic."""
        sp = pb.span
        tb = pb.tbatch
        if sp is not None:
            sp.start("match")  # host regime: the actual trie walk
        if tb is not None:
            t_m = time.perf_counter()
        uniq, inv = dedup_topics(topics)
        pb.n_uniq = len(uniq)
        matched = (self.router.match_filters_host(uniq)
                   if pb.host_only else self.router.match_filters(uniq))
        if sp is not None:
            sp.n_uniq = pb.n_uniq
            sp.start("dispatch")  # closes match
        if tb is not None:
            self.tracing.mark_match(tb, t_m)
        for row, (i, msg) in enumerate(pb.live):
            filters = matched[inv[row]]
            if not filters:
                self._drop_no_subs(msg)
                continue
            pb.results[i] = self._route(filters, msg)
        if sp is not None:
            sp.stop()

    def _span_finish(self, pb: PendingBatch) -> None:
        """Close a batch's telemetry span and trace batch (idempotent;
        no-op when both are off)."""
        sp = pb.span
        if sp is not None:
            sp.stop()  # a stage an early exit left open
            self.telemetry.finish(sp)
            pb.span = None
        if pb.tbatch is not None:
            self.tracing.close_batch(pb.tbatch)
            pb.tbatch = None

    @executor_thread
    def publish_fetch(self, pb: PendingBatch) -> None:
        """Phase 2 — the blocking device→host transfer, coalesced.

        Touches no broker state (except monotonically raising the
        learned pack budgets): safe to run on an executor thread
        while the event loop keeps serving sockets. With a breaker
        attached a failed (or, past ``breaker_slow_ms``, stalled)
        transfer is recorded and the batch converts to the exact
        host-oracle path — results stay correct, the breaker decides
        whether the NEXT batch rides the device."""
        sp = pb.span
        if sp is not None:
            # run_in_executor call → this thread picked the batch up
            sp.wait_mark("executor_wait")
        try:
            if pb.done or pb.host_topics is not None:
                return
            br = self.breaker
            if br is None:
                self._fetch_device(pb)
                return
            t0 = time.perf_counter()
            try:
                self._fetch_device(pb)
            except Exception:
                br.record_failure()
                log.exception("device fetch failed — host-oracle "
                              "fallback for this batch")
                # convert the batch to the deferred-host shape:
                # finish re-matches every live topic on the host trie
                # (exact), so nothing is delivered wrong or lost.
                # host_only: the device just failed mid-batch — the
                # re-match must not ride it again (a LOST backend
                # would raise out of the fallback itself)
                pb.plan = None
                pb.xgroups = None
                pb.bundle_d = None  # a laid bundle dies with the fetch
                pb.host_topics = [m.topic for _, m in pb.live]
                pb.host_matched = None
                pb.host_only = True
                return
            br.record_success(time.perf_counter() - t0)
        finally:
            d = self.durability
            if d is not None:
                # batched journal flush OFF the event loop: the
                # previous batch's dirty session states + any buffered
                # route/retain records hit disk with ONE fsync here,
                # on the executor thread the fetch already occupies
                # (docs/DURABILITY.md "one append per batch")
                d.on_batch()
            if sp is not None:
                # "fetch returned": chain_wait / loop_wait start here
                sp.t_mark = sp.clock()

    @executor_thread
    def _fetch_device(self, pb: PendingBatch) -> None:
        """The device fetch body — on packed-budget overflow re-packs
        with the next power-of-two bucket (the dispatched dense
        arrays are still live on device) and remembers the grown
        budget for the bucket, so a steady-state workload re-packs
        once, not per batch."""
        if faults.enabled:
            faults.fire("device.fetch")
            faults.fire("device.lost")
        import jax

        sp = pb.span
        if sp is not None:
            # the ONE synchronizing stage: device execution queued by
            # publish_begin surfaces as transfer wait here (no
            # block_until_ready added — device_get already syncs)
            sp.start("fetch")
        cfg = self.router.config
        Bp = pb.ids_dev.shape[0]
        budgets = self._pack_budgets.get(Bp)
        while True:
            # ONE device buffer → ONE transfer (the host link charges
            # per-buffer round-trip latency; see ops/pack.bundle_i32)
            fetch = [pb.m_ptr_d, pb.ids_packed_d, pb.ovf_dev]
            if pb.movf_d is not None:
                fetch += [pb.movf_d]
            if pb.f_ptr_d is not None:
                fetch += [pb.f_ptr_d, pb.subs_packed_d,
                          pb.src_packed_d]
            if pb.sel_d is not None:
                fetch += [pb.sel_d, pb.rows_packed_d, pb.bm_total_d,
                          pb.bovf_d]
            bundle, pb.bundle_d = pb.bundle_d, None
            buf = jax.device_get(bundle_i32(*fetch)
                                 if bundle is None else bundle)
            off = 0

            def take(n):
                nonlocal off
                out = buf[off:off + n]
                off += n
                return out

            m_ptr = take(Bp + 1)
            ids_packed = take(pb.pm)
            ovf = take(Bp).astype(bool)
            movf = take(Bp).astype(bool) if pb.movf_d is not None \
                else None
            if pb.f_ptr_d is not None:
                f_ptr = take(Bp + 1)
                subs_p = take(pb.pq)
                src_p = take(pb.pq)
            else:
                f_ptr = subs_p = src_p = None
            if pb.sel_d is not None:
                pr, W = pb.rows_packed_d.shape
                sel = take(Bp)
                rows_p = take(pr * W).view(np.uint32).reshape(pr, W)
                bm_total = int(take(1)[0])
                bovf = take(Bp).astype(bool)
            else:
                sel = rows_p = bm_total = bovf = None
            # budget overflow → re-pack with the next bucket; rare
            # (budgets start at cfg.pack_* × batch) and self-corrects
            retry = False
            m_repacked = False
            if int(m_ptr[-1]) > pb.pm:
                while pb.pm < int(m_ptr[-1]):
                    pb.pm *= 2
                if budgets is not None:
                    budgets[0] = max(budgets[0], pb.pm)
                pb.m_ptr_d, pb.ids_packed_d = pack_matches(
                    pb.ids_dev, pm=pb.pm)
                m_repacked = True
                retry = True
            mesh_fan = pb.subs_dense_d is not None
            if f_ptr is not None and (
                    (m_repacked and not mesh_fan)
                    or int(f_ptr[-1]) > pb.pq):
                # a truncated match pack also truncates the expansion
                # (single-chip only: the mesh fan packs from the dense
                # gathered arrays, independent of the match pack)
                while pb.pq < int(f_ptr[-1]):
                    pb.pq *= 2
                if budgets is not None:
                    budgets[1] = max(budgets[1], pb.pq)
                if mesh_fan:
                    pb.f_ptr_d, pb.subs_packed_d, pb.src_packed_d = \
                        pack_fanout(pb.subs_dense_d, pb.src_dense_d,
                                    pq=pb.pq)
                else:
                    pb.f_ptr_d, pb.subs_packed_d, pb.src_packed_d, _t = \
                        expand_packed(pb.st.fan, pb.m_ptr_d,
                                      pb.ids_packed_d, q=pb.pq)
                retry = True
            if bm_total is not None and int(bm_total) > pb.rows_packed_d.shape[0]:
                pr = pb.rows_packed_d.shape[0]
                while pr < int(bm_total):
                    pr *= 2
                if budgets is not None:
                    budgets[2] = max(budgets[2], pr)
                if pb.union_dense_d is not None:
                    # mesh: the collective union is still live on
                    # device — re-pack it with the grown budget
                    pb.sel_d, pb.rows_packed_d, pb.bm_total_d = \
                        pack_union_rows(pb.union_dense_d,
                                        pb.has_big_d, pr=pr)
                else:
                    rows_d, pb.bovf_d = rows_for_matches(
                        pb.st.bm, pb.ids_dev, mb=cfg.fanout_mb)
                    union_d = or_bitmaps_auto(pb.st.bm.bitmaps, rows_d)
                    has_big = (rows_d >= 0).any(axis=1)
                    pb.sel_d, pb.rows_packed_d, pb.bm_total_d = \
                        pack_union_rows(union_d, has_big, pr=pr)
                retry = True
            if retry:
                continue
            # adaptive capacity: a batch where >1/8 of the unique
            # topics overflowed the MATCH bound means K undersizes
            # the live workload — grow for the NEXT batch (this one
            # already has its exact host fallback). On the mesh the
            # combined ovf includes fan-out d overflow, which k
            # cannot fix — only the match-only flag may boost
            n_u = max(1, pb.n_uniq)
            k_ovf = movf if movf is not None else ovf
            n_fb = int(ovf[:n_u].sum())
            if n_fb:
                # host-oracle fallbacks feed the patcher's stale-hop
                # compaction trigger (ADVICE r5): a patch-deepened
                # automaton rebuilds instead of pinning hot deep
                # topics to the host (and out of the match cache)
                self.router.note_match_fallbacks(n_fb)
            if int(k_ovf[:n_u].sum()) * 8 > n_u:
                self.router.boost_k()
            if movf is not None:
                # fan-ONLY overflow (mesh): the d bound undersizes
                # the live fan-out — grow d, not k
                f_ovf = ovf[:n_u] & ~movf[:n_u]
                if int(f_ovf.sum()) * 8 > n_u:
                    self.router.boost_d()
            pb.movf = movf
            pb.m_ptr = m_ptr
            # slice to true occupancy before the per-element list
            # conversion — the budget tail is dead -1 padding
            pb.ids_packed = ids_packed[:int(m_ptr[-1])].tolist()
            pb.ovf = ovf
            pb.f_ptr = f_ptr
            if subs_p is not None:
                occ = int(f_ptr[-1])
                subs_occ = subs_p[:occ]
                src_occ = src_p[:occ]
            else:
                subs_occ = src_occ = None
            pb.sel = sel
            pb.rows_packed = rows_p
            pb.bovf = bovf
            if cfg.mesh is None:
                # the totals the one transfer already brought to the
                # host (the mesh step psums its own: router._dev_stats)
                pb.dev_counts = (
                    int(m_ptr[-1]),
                    int(f_ptr[-1]) if f_ptr is not None else 0, n_fb)
            if sp is not None:
                sp.fallbacks = n_fb
                sp.stop()
            tb = pb.tbatch
            if tb is not None:
                # device regime: walk + fan-out + coalesced transfer,
                # timed from batch begin (the dispatch was async)
                self.tracing.mark_match(tb, tb.t0p)
            if self.dispatch_config.planner:
                if sp is not None:
                    sp.start("dispatch_plan")
                pb.plan = self._build_plan(pb, subs_occ, src_occ)
                if sp is not None:
                    sp.stop()
                if pb.plan is not None \
                        and self.dispatch_config.preserialize:
                    # egress pre-serialization: prime the messages'
                    # shared wire images / pid templates here — off
                    # the event loop when fetch runs on the ingress
                    # executor — so the delivery tail patches bytes
                    # instead of serializing (docs/DISPATCH.md)
                    if sp is not None:
                        sp.start("serialize")
                    t_s = time.perf_counter() \
                        if tb is not None else 0.0
                    preserialize_plan(pb.plan, pb.live, pb.id_map,
                                      self._subscribers,
                                      self.helper.registry.lookup)
                    if sp is not None:
                        sp.stop()
                    if tb is not None:
                        self.tracing.span_mark(tb, "serialize", t_s)
                if pb.plan is not None and self.loop_group is not None:
                    # cross-loop delivery ring: partition the plan's
                    # subscriber groups by owning loop here — still
                    # off the event loop when fetch runs on the
                    # ingress executor — so the finish prologue only
                    # has to post one handoff per loop
                    pb.xgroups = self._xloop_partition(pb.plan)
            if pb.plan is not None:
                # planned batches keep the numpy views (the plan
                # already indexed them; the legacy walk's per-element
                # list conversion is skipped entirely)
                pb.subs_packed = subs_occ
                pb.src_packed = src_occ
            elif subs_occ is not None:
                pb.subs_packed = subs_occ.tolist()
                pb.src_packed = src_occ.tolist()
            else:
                pb.subs_packed = pb.src_packed = None
            return

    @executor_thread
    def _build_plan(self, pb: PendingBatch, subs_packed, src_packed):
        """Build the batch's subscriber-grouped dispatch plan
        (ops/dispatch_plan.py) from the fetched packed arrays. Runs
        wherever :meth:`publish_fetch` runs — possibly an executor
        thread — so it touches no broker state beyond a lock-held
        member snapshot for bitmap attribution. ``None`` = batch not
        plannable (an overflow row needs the legacy mid-walk host
        fallback); the legacy per-delivery path then runs unchanged."""
        n_u = pb.n_uniq
        if n_u and bool(pb.ovf[:n_u].any()):
            return None
        if pb.bovf is not None and n_u and bool(pb.bovf[:n_u].any()):
            return None
        big_set = pb.st.big_fids if pb.st is not None else pb.sh_big
        big_map: Dict[int, list] = {}
        if pb.sel is not None and big_set:
            id_map = pb.id_map
            big_map = big_rows_for(
                pb.ids_packed, pb.m_ptr, pb.sel, pb.rows_packed,
                sorted(set(pb.inv)), big_set,
                lambda fid: self.helper.members_sorted(id_map[fid]))
        return build_plan(pb.inv, n_u, pb.ovf, pb.bovf, pb.f_ptr,
                          subs_packed, src_packed, big_map)

    @bg_thread
    def warm_device_path(self) -> int:
        """Device-loss recovery, step 3 (devloss.py): execute the
        real dispatch → fetch kernel chain once for every program the
        match dispatch can be asked for by a batch no larger than
        live traffic has formed (the learned pack-budget keys ARE the
        observed batch buckets), on the recovery thread, so the first
        post-recovery publish batch pays zero compile
        (docs/ROBUSTNESS.md "Device-loss recovery"). The fan-out
        manager's device tables re-derive at the rebuilt epoch as a
        side effect. Returns the number of warmed batches."""
        return sum(1 for _ in self.warm_dispatch(
            max(self._pack_budgets, default=1)))

    def warm_dispatch(self, max_topics: int):
        """Drive every batch of :meth:`Router.dispatch_shapes` — on
        one chip one for each (batch bucket × depth) variant of the
        match's program and one fully hit batch a bucket, on the mesh
        one for each miss bucket of the step and each (batch, hit,
        miss) merge triple, that a batch of up to ``max_topics``
        unique topics can ask for (the ingress forms up to its
        ``batch_cap``) — through
        :meth:`_begin_device` / :meth:`_fetch_device`, over synthetic
        NUL-rooted topics (ops/warmup.py) that no real filter can
        match: nothing delivers, no hooks or message metrics fire.
        After it, traffic of those sizes first-uses no program of the
        dispatch; what the broker learns from traffic (pack budgets,
        ``boost_k``) it still learns from traffic.

        A generator, one batch a step, yielding ``(seconds, shape)``
        as each is fetched: a caller on the event loop yields to the
        loop between batches, the device-loss rewarm runs it through
        on its own thread."""
        from emqx_tpu.ops.warmup import warm_batches
        from emqx_tpu.router import DispatchShape

        router = self.router
        cfg = router.config
        router.warm_delta()
        for shape, topics in warm_batches(
                router.dispatch_shapes(max_topics), router.cache_slots()):
            t0 = time.monotonic()
            pb = PendingBatch()
            pb.results = [0] * len(topics)
            pb.live = [(i, Message(topic=t, payload=b""))
                       for i, t in enumerate(topics)]
            self._begin_device(pb, topics, cfg)
            self._fetch_device(pb)
            yield time.monotonic() - t0, DispatchShape(*shape)

    @owner_loop
    def publish_finish(self, pb: PendingBatch) -> List[int]:
        """Phase 3 — the host delivery tail over the packed results
        (must run where broker state is owned, i.e. the event loop)."""
        if pb.done:
            return pb.results
        for _ in self.finish_steps(pb):
            pass
        # multi-loop: block until the cross-loop handoffs report
        # back, then fold (no-op on a single-loop node)
        self.xloop_join_sync(pb)
        pb.done = True
        return pb.results

    @owner_loop
    def finish_steps(self, pb: PendingBatch,
                     chunk: Optional[int] = None):
        """The delivery tail of a begun (and, on the device path,
        fetched) batch as a generator: picks the tail once, runs
        ``chunk`` units a step (``None``: all in one) and yields
        BETWEEN steps, so the async ingress can give the loop back
        there and finished work's deliveries flush to subscriber
        sockets while the rest still routes. The unit depends on the
        tail: deferred host routing and the legacy packed walk step
        over LIVE ROWS; a planned batch steps over SUBSCRIBER GROUPS
        (each session still gets its whole batch in one deliver_many
        and one wakeup). The caller joins the cross-loop handoffs
        (:meth:`xloop_join_sync` / :meth:`xloop_event`) and sets
        ``pb.done``."""
        if pb.host_topics is not None:
            step, n = self.publish_host_chunk, len(pb.live)
        elif pb.plan is not None:
            step, n = self.publish_finish_planned, pb.plan.n_groups
        else:
            step, n = self.publish_finish_chunk, len(pb.live)
        chunk = chunk or max(1, n)
        for s in range(0, max(1, n), chunk):
            step(pb, s, min(s + chunk, n))
            if s + chunk < n:
                yield

    @owner_loop
    def _plan_prologue(self, pb: PendingBatch) -> None:
        """Per-batch routing pass before grouped delivery: classify
        every matched filter id ONCE (local / shared / remote — per
        distinct fid of the batch, not per message) and every unique
        topic once (its filters are the same for each message that
        bears it), then walk the live rows in order doing only the
        per-message host work the plan cannot carry: no-subscriber
        drops, shared-group picks, remote forwards. Local delivery is
        the plan's.

        A filter whose one destination is this node resolves to a
        shared constant from the route table's own destinations (no
        ``Route``, dict or tuple is built); a topic all of whose
        filters are such costs its later messages one byte read."""
        ps = _PlanState()
        live = pb.live
        n_live = len(live)
        row_local = ps.row_local = bytearray(n_live)
        row_fast = ps.row_fast = bytearray(n_live)
        ftabs = ps.ftabs = {}
        ps.accepted = []
        ps.resolves = 0
        # the one thing the fold owes a ``message.delivered`` callback
        # is the per-(message, filter) count: kept only while one is
        # registered (modules/topic_metrics.py), decided per batch
        ps.hooked = self.hooks.has("message.delivered")
        id_map = pb.id_map
        m_ptr = pb.m_ptr.tolist()
        ids_packed = pb.ids_packed
        inv = pb.inv
        results = pb.results
        node = self.node
        routes_get = self.router._routes.get
        subs_get = self._subscribers.get
        route_of: Dict[int, tuple] = {}
        # per unique topic: 0 not looked at yet, 1 no live filter (a
        # drop), 2 every live filter local-only, 3 anything else (a
        # shared group, a remote node, a vanished route: walked per
        # message, below)
        topic_class = bytearray(len(m_ptr) - 1)
        for r in range(n_live):
            urow = inv[r]
            c = topic_class[urow]
            if c == 0:
                c = 1
                for j in ids_packed[m_ptr[urow]:m_ptr[urow + 1]]:
                    if j < 0:
                        continue  # pad slot: id_map[-1] would alias
                    info = route_of.get(j)
                    if info is None:
                        flt = id_map[j]
                        if flt is None:
                            info = _DEST_NONE
                        else:
                            ftabs[j] = subs_get(flt)
                            dests = routes_get(flt)
                            if dests is not None and len(dests) == 1 \
                                    and node in dests:
                                info = _DEST_LOCAL
                            else:
                                info = self._classify_dests(flt, dests)
                        route_of[j] = info
                    if info is _DEST_LOCAL:
                        if c == 1:
                            c = 2
                    elif info is not _DEST_NONE:
                        c = 3
                topic_class[urow] = c
            i, msg = live[r]
            if c == 1:
                self._drop_no_subs(msg)
                continue
            if c == 2:
                row_local[r] = 1
            else:
                n = 0
                local = False
                for j in ids_packed[m_ptr[urow]:m_ptr[urow + 1]]:
                    if j < 0:
                        continue
                    info = route_of[j]
                    if info is _DEST_LOCAL:
                        local = True
                        continue
                    if info is _DEST_NONE:
                        continue
                    flt, loc, sh_items, rem_nodes = info
                    local = local or loc
                    for group, nodes in sh_items:
                        if self.shared_router is not None:
                            # cluster: ONE delivery per group, all nodes
                            n += self.shared_router(group, flt, nodes,
                                                    msg)
                        elif node in nodes:
                            n += self.shared.dispatch(group, flt, msg)
                    for nd in rem_nodes:
                        if self.forwarder is not None:
                            self.forwarder(nd, flt, msg)
                            self.metrics.inc("messages.forward")
                results[i] = n
                if local:
                    row_local[r] = 1
            if msg.qos == 0 and not msg.flags.get("retain"):
                # the message half of the QoS0 broadcast fast-path
                # predicate, hoisted to once per row; the subopts half
                # joins it per (group, filter) below
                row_fast[r] = 1
        ps.xg_set = None
        ps.folded = False
        pb.plan_state = ps
        if pb.xgroups:
            # cross-loop delivery ring: hand each owning loop its
            # share of the plan NOW, so peer loops enqueue their
            # sessions' batches while this loop walks its own groups
            self._post_xloop_handoffs(pb, ps)

    def _classify_dests(self, flt: str, dests) -> tuple:
        """The prologue's slow class: a filter with other
        destinations than this node alone (``dests`` is the route
        table's ``dest -> refs`` for it, None where the route went
        since the snapshot) as ``(filter, local, ((group, nodes),
        ...), remote nodes)``."""
        loc = False
        sh: Dict[str, List[str]] = {}
        rem: List[object] = []
        for dest in dests or ():
            if isinstance(dest, tuple):
                sh.setdefault(dest[0], []).append(dest[1])
            elif dest == self.node:
                loc = True
            else:
                rem.append(dest)
        return (flt, loc, tuple(sh.items()), tuple(rem))

    @owner_loop
    def publish_finish_planned(self, pb: PendingBatch, gstart: int,
                               gstop: int) -> None:
        """Deliver subscriber groups ``[gstart, gstop)`` of a planned
        batch — the planner's analogue of
        :meth:`publish_finish_chunk`, chunked over plan GROUPS so the
        async ingress can yield between sessions while every session
        still receives its whole batch in one ``deliver_many`` call
        and one notify wakeup. The first chunk runs the routing
        prologue (which also posts the cross-loop handoffs on a
        multi-loop node — handed-off groups are skipped here); the
        chunk that crosses the last group folds the accepted
        deliveries into metrics/hooks/results (the legacy walk's
        accounting, batched) — unless handoffs are
        still in flight, in which case the fold belongs to the join
        (:meth:`xloop_fold` / :meth:`xloop_join_sync`)."""
        plan = pb.plan
        sp = pb.span
        if sp is not None:
            sp.start("dispatch")
        if gstart == 0:
            self._fold_device_counts(pb)
            self._plan_prologue(pb)
        ps = pb.plan_state
        accepted = ps.accepted
        xg_set = ps.xg_set
        n_groups = plan.n_groups
        resolves = 0
        for g in range(gstart, min(gstop, n_groups)):
            if xg_set is not None and g in xg_set:
                continue  # handed to its owning loop
            resolves += self._deliver_plan_group(pb, ps, g, accepted)
        ps.resolves += resolves
        folded = False
        if gstop >= n_groups and (xg_set is None
                                  or ps.xloop_left == 0):
            self._plan_fold(pb)
            folded = True
        if sp is not None:
            sp.stop()
        if folded:
            self._span_finish(pb)

    @owner_loop
    def _deliver_plan_group(self, pb: PendingBatch, ps: _PlanState,
                            g: int, out: list) -> int:
        """Deliver one plan group — one subscriber's whole batch:
        resolve the session once and each of the group's filters once
        (``fid -> (filter, SubOpts, the subopts half of the fast
        predicate, no-local)``, or False where the subscriber no longer
        holds it: a filter that recurs in the slice costs one dict
        get), enqueue everything in one ``deliver_many``, fire one
        notify; a plain subscriber (``deliver(filter, msg)`` alone:
        tests, sinks, in-process consumers) is called straight from
        the walk. Appends one entry per accepted delivery to ``out``
        (the live row; ``(row, filter)`` when ``ps.hooked``) and
        returns the number of resolutions made. Runs on
        whichever loop owns the group's session: the main loop for
        local groups, an owning peer loop inside a cross-loop handoff
        (everything read here — plan arrays, prologue tables, live
        messages with their pre-built wire images — is immutable
        after the prologue)."""
        plan = pb.plan
        sub = self.helper.registry.lookup(plan.g_sids[g])
        if sub is None:
            return 0  # unsubscribed since the tables were built
        id_map = pb.id_map
        live = pb.live
        row_local = ps.row_local
        row_fast = ps.row_fast
        ftabs = ps.ftabs
        hooked = ps.hooked
        a = plan.g_ptr[g]
        b = plan.g_ptr[g + 1]
        dm = getattr(sub, "deliver_many", None)
        items: List[tuple] = []
        if dm is None:
            # the per-delivery protocol: nothing is staged (an object
            # that cannot deliver fails inside the per-delivery try)
            deliver = getattr(sub, "deliver", None)
            acc = out
        else:
            acc = []
        # fid -> (filter, SubOpts, subopts half of `fast`, no-local),
        # or False where the subscriber does not hold the filter
        resolved: Dict[int, object] = {}
        for r, fid in zip(plan.rows[a:b], plan.fids[a:b]):
            if not row_local[r]:
                continue
            ent = resolved.get(fid)
            if ent is None:
                ftab = ftabs.get(fid)
                opts = ftab.get(sub) if ftab is not None else None
                if opts is None:
                    resolved[fid] = False
                    continue
                flt = id_map[fid]
                nl = opts.nl
                ofast = opts.share is None and not nl \
                    and opts.subid is None \
                    and (opts.qos == 0
                         or not getattr(sub, "upgrade_qos", False))
                resolved[fid] = (flt, opts, ofast, nl)
            elif ent is False:
                continue
            else:
                flt, opts, ofast, nl = ent
            msg = live[r][1]
            if nl and getattr(sub, "client_id", None) == msg.from_:
                self.metrics.inc("delivery.dropped")
                self.metrics.inc("delivery.dropped.no_local")
                continue
            if "_wire" not in msg.headers:
                # shared wire-image cache, as _deliver_one primes
                msg.headers["_wire"] = {}
            if dm is None:
                try:
                    deliver(flt, msg)
                except Exception:
                    log.exception("deliver to %r failed", sub)
                    continue
            else:
                items.append((flt, msg, opts,
                              ofast and row_fast[r] == 1))
            acc.append((r, flt) if hooked else r)
        if not items:
            return len(resolved)
        runs = None
        if plan.g_runs is not None and len(items) == b - a:
            # every planned delivery accepted, so item k is the
            # slice's delivery k: a run whose every item is fast
            # holds those items' messages, in order — the session
            # may take each as one outbox entry (docs/DISPATCH.md
            # "Wire runs")
            runs = [seg for seg in plan.g_runs[g] or ()
                    if all([it[3] for it in items[seg[0]:seg[1]]])]
        try:
            if runs:
                dm(items, runs)
            else:
                dm(items)
        except Exception:
            log.exception("deliver_many to %r failed", sub)
        else:
            out.extend(acc)
        return len(resolved)

    @owner_loop
    def _plan_fold(self, pb: PendingBatch) -> None:
        """Fold the batch's accepted deliveries into
        metrics/hooks/results — the legacy walk's accounting, batched:
        each row's count into ``results``, the batch's total into
        ``messages.delivered`` in one add and, only where a
        ``message.delivered`` callback is registered, one call per
        (message, filter count) in the legacy order.
        Runs exactly once, on the main loop, after every cross-loop
        handoff reported back (idempotent via ``ps.folded``)."""
        ps = pb.plan_state
        if ps.folded:
            return
        ps.folded = True
        accepted = ps.accepted
        resolves = ps.resolves
        if ps.xg_set and ps.xloop_left:
            # folding with handoffs still outstanding (join timed
            # out, handoff dropped, owning loop died): their groups'
            # delivery counts are lost — surface the loss instead of
            # under-reporting silently
            self.metrics.inc("delivery.xloop.orphaned", ps.xloop_left)
            log.warning("cross-loop delivery: %d handoff(s) never "
                        "reported back — folding partial counts",
                        ps.xloop_left)
        if ps.xg_set:
            # merge the handoff loops' accepted deliveries (no more
            # writers once xloop_left hit zero)
            n_x = 0
            for rc in ps.xloop_results:
                n_x += len(rc)
                accepted.extend(rc)
            resolves += ps.xloop_resolves
            if n_x:
                self.metrics.inc("delivery.xloop.deliveries", n_x)
            sp = pb.span
            if sp is not None:
                sp.add_ms("xloop",
                          (ps.xloop_tdone - ps.xloop_t0) * 1000.0)
            tb = pb.tbatch
            if tb is not None:
                self.tracing.span_abs(
                    tb, "xloop", ps.xloop_t0,
                    (ps.xloop_tdone - ps.xloop_t0) * 1000.0)
        if resolves:
            tel = self.telemetry
            if tel is not None and tel.loop_clock() is not None:
                tel.metrics.inc("delivery.plan.resolves", resolves)
        if not accepted:
            return
        self.metrics.inc("messages.delivered", len(accepted))
        live = pb.live
        results = pb.results
        if not ps.hooked:
            for r, cnt in collections.Counter(accepted).items():
                results[live[r][0]] += cnt
            return
        counts: Dict[int, Dict[str, int]] = {}
        for r, flt in accepted:
            d = counts.get(r)
            if d is None:
                d = counts[r] = {}
            d[flt] = d.get(flt, 0) + 1
        run_hook = self.hooks.run
        for r in sorted(counts):
            i, msg = live[r]
            n = 0
            for cnt in counts[r].values():
                n += cnt
                run_hook("message.delivered", (msg, cnt))
            results[i] += n

    # -- cross-loop delivery ring (docs/DISPATCH.md) ----------------------

    def _xloop_partition(self, plan) -> Optional[Dict[int, List[int]]]:
        """Owning-loop index → plan group indices, for every group
        whose session lives on a non-home loop (``Session.owner_loop``
        stamped at CONNECT). Runs wherever ``publish_fetch`` runs —
        registry lookups and attribute reads only. ``None`` = every
        group is home-owned (the single-loop fast path)."""
        lg = self.loop_group
        lookup = self.helper.registry.lookup
        g_sids = plan.g_sids
        xg: Optional[Dict[int, List[int]]] = None
        for g in range(plan.n_groups):
            sub = lookup(g_sids[g])
            if sub is None:
                continue
            idx = lg.index_of(getattr(sub, "owner_loop", None))
            if idx == 0:
                continue
            if xg is None:
                xg = {}
            xg.setdefault(idx, []).append(g)
        return xg

    @owner_loop
    def _post_xloop_handoffs(self, pb: PendingBatch,
                             ps: _PlanState) -> None:
        """Post each owning loop its share of the plan — ONE
        ``call_soon_threadsafe`` per loop per batch, carrying the
        whole group list (the pre-built wire images/templates ride
        along in the live messages' headers). The fold joins on the
        results via :meth:`xloop_fold` / :meth:`xloop_join_sync`."""
        import asyncio

        lg = self.loop_group
        xg_set: set = set()
        for gids in pb.xgroups.values():
            xg_set.update(gids)
        ps.xg_set = xg_set
        ps.xloop_results = []
        ps.xloop_resolves = 0
        ps.xloop_lock = threading.Lock()
        ps.xloop_left = len(pb.xgroups)
        ps.xloop_t0 = ps.xloop_tdone = time.perf_counter()
        ps.xloop_tev = threading.Event()
        ps.xloop_aev = asyncio.Event()
        self.metrics.inc("delivery.xloop.handoffs", len(pb.xgroups))
        for idx, gids in pb.xgroups.items():
            if faults.enabled and faults.fire("xloop.handoff"):
                # injected handoff loss: the join bound + orphan
                # accounting (xloop_fold) take over, exactly as for
                # a loop that died with the handoff in flight
                continue
            try:
                lg.post(idx, self._run_xloop_groups, pb, gids)
            except RuntimeError:
                # owning loop gone (shutdown race): deliver from here
                # — a cross-thread enqueue beats dropped messages
                self._run_xloop_groups(pb, gids)

    @owner_loop
    def _run_xloop_groups(self, pb: PendingBatch, gids) -> None:
        """One cross-loop handoff, running ON the owning loop: deliver
        this loop's subscriber groups (each session still gets its
        whole batch in one ``deliver_many`` + one notify — the
        single-loop invariants, preserved across the ring), then
        report the accepted deliveries and the resolutions made back
        for the main-loop fold."""
        ps = pb.plan_state
        accepted: list = []
        resolves = 0
        try:
            for g in gids:
                resolves += self._deliver_plan_group(pb, ps, g,
                                                     accepted)
        except Exception:
            log.exception("cross-loop delivery handoff failed")
        finally:
            with ps.xloop_lock:
                ps.xloop_results.append(accepted)
                ps.xloop_resolves += resolves
                ps.xloop_left -= 1
                done = ps.xloop_left == 0
                if done:
                    ps.xloop_tdone = time.perf_counter()
            if done:
                ps.xloop_tev.set()
                lg = self.loop_group
                aev = ps.xloop_aev
                if lg is not None and aev is not None:
                    try:
                        lg.home.call_soon_threadsafe(aev.set)
                    except RuntimeError:
                        # home loop gone (shutdown race): deliveries
                        # happened, but the async fold wakeup is
                        # orphaned (sync joins still see the
                        # threading event) — count it, don't vanish
                        self.metrics.inc("delivery.xloop.orphaned")
                        log.warning("cross-loop handoff result "
                                    "orphaned: home loop gone")

    def xloop_event(self, pb: PendingBatch):
        """The home-loop asyncio event the async ingress awaits before
        folding a batch with cross-loop handoffs; ``None`` = no
        handoffs (single loop, or every group was home-owned)."""
        ps = pb.plan_state
        if ps is None or not getattr(ps, "xg_set", None):
            return None
        return ps.xloop_aev

    @owner_loop
    def xloop_fold(self, pb: PendingBatch) -> None:
        """Join point once the handoffs completed: merge + fold +
        close the span. No-op when the batch had no handoffs, or the
        final local chunk already folded (the handoffs beat it)."""
        ps = pb.plan_state
        if ps is None or not getattr(ps, "xg_set", None):
            return
        self._plan_fold(pb)
        self._span_finish(pb)

    #: bound on the synchronous cross-loop join (shutdown flush, sync
    #: publish_batch): peer loops run on their own threads, so the
    #: wait cannot deadlock on them — the bound only breaks a wedged
    #: loop out of the fold, with partial counts and a loud log
    XLOOP_JOIN_TIMEOUT = 30.0

    def xloop_join_sync(self, pb: PendingBatch) -> None:
        """Blocking join for the synchronous publish path."""
        ps = pb.plan_state
        if ps is None or not getattr(ps, "xg_set", None):
            return
        if not ps.folded and ps.xloop_left:
            if not ps.xloop_tev.wait(self.XLOOP_JOIN_TIMEOUT):
                log.error("cross-loop delivery handoff incomplete "
                          "after %.0fs — folding partial counts",
                          self.XLOOP_JOIN_TIMEOUT)
        self.xloop_fold(pb)

    @owner_loop
    def publish_host_chunk(self, pb: PendingBatch, start: int,
                           stop: int) -> None:
        """Deliver rows ``[start, stop)`` of a deferred HOST-path
        batch (the streaming form of the host branch — same contract
        as :meth:`publish_finish_chunk`). The one trie walk over the
        batch's unique topics happens on the first chunk and is
        cached on the batch."""
        sp = pb.span
        tb = pb.tbatch
        if pb.host_matched is None:
            if sp is not None:
                sp.start("match")
            if tb is not None:
                t_m = time.perf_counter()
            uniq, pb.host_inv = dedup_topics(pb.host_topics)
            pb.host_matched = (
                self.router.match_filters_host(uniq) if pb.host_only
                else self.router.match_filters(uniq))
            if sp is not None:
                sp.n_uniq = len(uniq)
                sp.stop()
            if tb is not None:
                self.tracing.mark_match(tb, t_m)
        if sp is not None:
            sp.start("dispatch")
        for row in range(start, stop):
            i, msg = pb.live[row]
            filters = pb.host_matched[pb.host_inv[row]]
            if not filters:
                self._drop_no_subs(msg)
                continue
            pb.results[i] = self._route(filters, msg)
        if sp is not None:
            sp.stop()
        if stop >= len(pb.live):
            self._span_finish(pb)

    @owner_loop
    def publish_finish_chunk(self, pb: PendingBatch, start: int,
                             stop: int) -> None:
        """Deliver rows ``[start, stop)`` of a fetched batch — the
        streaming form of :meth:`publish_finish`: the async ingress
        yields to the event loop between chunks so early rows'
        deliveries flush to subscriber sockets while later rows are
        still routing, instead of the whole batch's tail waiting on
        the full host loop (round-4 live p99 finding)."""
        m_ptr = pb.m_ptr
        sp = pb.span
        if sp is not None:
            sp.start("dispatch")
        if start == 0:
            self._fold_device_counts(pb)
        for row in range(start, stop):
            i, msg = pb.live[row]
            urow = pb.inv[row]  # packed results are per UNIQUE topic
            if pb.ovf[urow]:
                # match overflow: this topic's result is unknown —
                # full host path for it (exact parity, no truncation)
                t_fb = sp.clock() if sp is not None else 0.0
                filters = self.router.host_match(msg.topic)
                if not filters:
                    self._drop_no_subs(msg)
                else:
                    pb.results[i] = self._route(filters, msg)
                if sp is not None:
                    # a subset of dispatch time, split out so the
                    # oracle-fallback cost is attributable on its own
                    sp.add("host_fallback", t_fb)
                continue
            # pad slots (-1) must never resolve through the id map —
            # python's negative indexing would silently alias the
            # LAST filter and deliver phantoms
            row_ids = [j for j in
                       pb.ids_packed[m_ptr[urow]:m_ptr[urow + 1]]
                       if j >= 0]
            filters = [pb.id_map[j] for j in row_ids]
            filters = [f for f in filters if f is not None]
            if not filters:
                self._drop_no_subs(msg)
                continue
            pb.results[i] = self._route_packed(urow, row_ids, filters,
                                               msg, pb)
        if sp is not None:
            sp.stop()
        if stop >= len(pb.live):
            self._span_finish(pb)

    @owner_loop
    def _fold_device_counts(self, pb: PendingBatch) -> None:
        """``device.matches`` / ``device.deliveries`` /
        ``device.overflows`` on the single-chip served path: what
        :meth:`_fetch_device` read off the one transfer, folded where
        the counters are owned."""
        dc = pb.dev_counts
        if dc is not None:
            pb.dev_counts = None
            m = self.metrics
            m.inc("device.matches", dc[0])
            m.inc("device.deliveries", dc[1])
            m.inc("device.overflows", dc[2])

    def _drop_no_subs(self, msg: Message) -> None:
        self.metrics.inc("messages.dropped")
        self.metrics.inc("messages.dropped.no_subscribers")
        self.hooks.run("message.dropped", (msg, "no_subscribers"))

    def _route(self, filters: List[str], msg: Message,
               local_deliver=None) -> int:
        """Fan a matched message out to local subscribers, shared
        groups, and remote nodes (route/2 + aggre/1 + forward/4).

        ``local_deliver(local_filters) -> int`` overrides the local
        delivery step (the device fan-out tail plugs in here); the
        default is the host dispatch loop. Shared/remote destinations
        always resolve host-side — they are per-group/per-node picks,
        not per-subscriber."""
        n = 0
        remote: set = set()  # (node, filter) — aggre/1 dedup
        shared: Dict[Tuple[str, str], List[str]] = {}  # (group,flt)->nodes
        local: List[str] = []
        for flt in filters:
            for route in self.router.lookup_routes(flt):
                dest = route.dest
                if isinstance(dest, tuple):  # (group, node) shared route
                    group, node = dest
                    shared.setdefault((group, flt), []).append(node)
                elif dest == self.node:
                    local.append(flt)
                else:
                    remote.add((dest, flt))
        if local:
            if local_deliver is not None:
                n += local_deliver(local)
            else:
                for flt in local:
                    n += self.dispatch(flt, msg)
        for (group, flt), nodes in shared.items():
            if self.shared_router is not None:
                # cluster: ONE delivery per group across all nodes
                n += self.shared_router(group, flt, nodes, msg)
            elif self.node in nodes:
                n += self.shared.dispatch(group, flt, msg)
        for node, flt in remote:
            if self.forwarder is not None:
                # remote node dispatches by the matched filter — no
                # re-match there (emqx_broker:forward/4 :266-281)
                self.forwarder(node, flt, msg)
                self.metrics.inc("messages.forward")
        return n

    def _route_packed(self, row: int, row_ids: List[int],
                      filters: List[str], msg: Message,
                      pb: PendingBatch) -> int:
        """Route one matched message with local delivery from the
        packed device fan-out results (gathered sub-id slots + bitmap
        union rows) instead of the ``_subscribers`` dicts."""
        def local_deliver(local_filters: List[str]) -> int:
            overflowed = (pb.bovf is not None and pb.bovf[row]) \
                or (pb.st is None and pb.f_ptr is None)
            if overflowed:
                # per-message capacity exceeded: host dispatch loop
                return sum(self.dispatch(flt, msg)
                           for flt in local_filters)
            n = 0
            per_filter: Dict[str, int] = {}
            id_map = pb.id_map
            lookup = self.helper.registry.lookup
            if pb.f_ptr is not None:
                for k in range(pb.f_ptr[row], pb.f_ptr[row + 1]):
                    if pb.src_packed[k] < 0:
                        continue  # pad slot: never index with -1
                    flt = id_map[pb.src_packed[k]]
                    sub = lookup(pb.subs_packed[k])
                    if sub is not None and flt is not None:
                        d = self._deliver_one(flt, sub, msg)
                        if d:
                            per_filter[flt] = per_filter.get(flt, 0) + d
            big_set = pb.st.big_fids if pb.st is not None else pb.sh_big
            if pb.sel is not None and pb.sel[row] >= 0 and big_set:
                self._deliver_big(row, row_ids, msg, pb, per_filter,
                                  big_set)
            for flt, cnt in per_filter.items():
                n += cnt
                self.metrics.inc("messages.delivered", cnt)
                self.hooks.run("message.delivered", (msg, cnt))
            return n

        return self._route(filters, msg, local_deliver=local_deliver)

    def _deliver_big(self, row: int, row_ids: List[int], msg: Message,
                     pb: PendingBatch, per_filter: Dict[str, int],
                     big_set: frozenset) -> None:
        """Deliver a message's bitmap-path (>threshold) fan-out: the
        device OR'd the matched big rows into one subscriber bitmap
        (transferred only for rows that had one, ops/pack.py); the
        tail walks its set bits, accumulating counts into
        ``per_filter``. With multiple matched big filters each
        (filter, member) pair delivers separately — per-subscription
        semantics, as the reference's shard walk. On the mesh the
        union rows come from the per-shard OR + ICI combine and the
        big set is ``pb.sh_big``."""
        matched_big = [j for j in row_ids if j in big_set]
        if not matched_big:
            return
        id_map = pb.id_map
        sids = unpack_sids(pb.rows_packed[pb.sel[row]])
        if len(matched_big) == 1:
            flt = id_map[matched_big[0]]
            ftab = self._subscribers.get(flt)
            for sid in sids:
                sub = self.helper.registry.lookup(int(sid))
                if sub is not None:
                    d = self._deliver_one(flt, sub, msg, ftab)
                    if d:
                        per_filter[flt] = per_filter.get(flt, 0) + d
        else:
            rows_by_fid = [(fid, id_map[fid],
                            self.helper.members(id_map[fid]),
                            self._subscribers.get(id_map[fid]))
                           for fid in matched_big]
            for sid in sids:
                isid = int(sid)
                sub = self.helper.registry.lookup(isid)
                if sub is None:
                    continue
                for fid, flt, members, ftab in rows_by_fid:
                    if isid in members:
                        d = self._deliver_one(flt, sub, msg, ftab)
                        if d:
                            per_filter[flt] = per_filter.get(flt, 0) + d

    def _deliver_one(self, topic_filter: str, sub: object,
                     msg: Message, ftab: Optional[dict] = None) -> int:
        """One (filter, subscriber) delivery with the no-local check;
        the deliver carries the *subscribed filter* so the session can
        resolve its subopts (emqx_broker.erl:298). Callers iterating
        one filter's subscribers pass ``ftab`` (the filter's subopts
        table) so the loop pays one dict fetch per FILTER, not per
        subscriber."""
        if ftab is None:
            ftab = self._subscribers.get(topic_filter)
        opts = ftab.get(sub) if ftab else None
        if opts is None:
            return 0  # unsubscribed since the tables were built
        if opts.nl and getattr(sub, "client_id", None) == msg.from_:
            self.metrics.inc("delivery.dropped")
            self.metrics.inc("delivery.dropped.no_local")
            return 0
        if "_wire" not in msg.headers:
            # shared wire-image cache: Session._enrich either returns
            # this very object (fast path) or copies headers SHALLOWLY
            # (dict(msg.headers)), so delivering sessions share this
            # inner dict and reuse one serialized QoS0 frame
            # (channel.handle_deliver broadcast fast path) instead of
            # serializing per subscriber. Message.copy() deep-copies
            # nested dicts — a copy() product gets a private cache,
            # primed but unshared.
            msg.headers["_wire"] = {}
        try:
            sub.deliver(topic_filter, msg)
            return 1
        except Exception:
            log.exception("deliver to %r failed", sub)
            return 0

    def dispatch(self, topic_filter: str, msg: Message) -> int:
        """Deliver to every local subscriber of ``topic_filter``
        (emqx_broker.erl:283-309) — the host dispatch loop, used by
        the no-device configuration and as the per-message overflow
        fallback of the device fan-out path."""
        ftab = self._subscribers.get(topic_filter)
        if not ftab:
            return 0
        n = 0
        for sub in list(ftab):
            n += self._deliver_one(topic_filter, sub, msg, ftab)
        if n:
            self.metrics.inc("messages.delivered", n)
            self.hooks.run("message.delivered", (msg, n))
        return n
