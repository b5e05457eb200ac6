"""Broker metrics: fixed-index counter array + named registry.

Mirrors ``src/emqx_metrics.erl``: a lock-free counters array indexed
by a name registry (emqx_metrics.erl:230-271) with the standard
BYTES/PACKETS/MESSAGES/DELIVERY metric names pre-registered
(emqx_metrics.erl:82-183). Host counters are a flat int list
(single-writer per-process); the device publish step additionally
accumulates per-batch counts on-TPU and folds them in with one
transfer per flush (the reference's pdict-batched counter idea,
src/emqx_pd.erl).
"""

from __future__ import annotations

from typing import Dict, List

from emqx_tpu.concurrency import any_thread, owner_loop, shared_state

MAX_METRICS = 1024

# Pre-registered names (counter kind), reference emqx_metrics.erl:82-183
BYTES_METRICS = ["bytes.received", "bytes.sent"]
PACKET_METRICS = [
    "packets.received", "packets.sent",
    "packets.connect.received", "packets.connack.sent",
    "packets.connack.error", "packets.connack.auth_error",
    "packets.publish.received", "packets.publish.sent",
    "packets.publish.error", "packets.publish.auth_error",
    "packets.publish.dropped",
    "packets.puback.received", "packets.puback.sent",
    "packets.puback.inuse", "packets.puback.missed",
    "packets.pubrec.received", "packets.pubrec.sent",
    "packets.pubrec.inuse", "packets.pubrec.missed",
    "packets.pubrel.received", "packets.pubrel.sent",
    "packets.pubrel.missed",
    "packets.pubcomp.received", "packets.pubcomp.sent",
    "packets.pubcomp.inuse", "packets.pubcomp.missed",
    "packets.subscribe.received", "packets.suback.sent",
    "packets.subscribe.error", "packets.subscribe.auth_error",
    "packets.unsubscribe.received", "packets.unsuback.sent",
    "packets.unsubscribe.error",
    "packets.pingreq.received", "packets.pingresp.sent",
    "packets.disconnect.received", "packets.disconnect.sent",
    "packets.auth.received", "packets.auth.sent",
]
MESSAGE_METRICS = [
    "messages.received", "messages.sent",
    "messages.qos0.received", "messages.qos0.sent",
    "messages.qos1.received", "messages.qos1.sent",
    "messages.qos2.received", "messages.qos2.sent",
    "messages.publish", "messages.dropped",
    "messages.dropped.expired", "messages.dropped.no_subscribers",
    "messages.forward", "messages.retained", "messages.redispatched",
    "messages.delayed", "messages.delivered", "messages.acked",
]
# will dispatch (Broker.publish_will, docs/DISPATCH.md "Will
# batching"): wills funneled through the ingress accumulator — a
# mass-disconnect wave coalesces into device batches — vs published
# directly (no accumulator running: sync drivers, shutdown tail)
WILL_METRICS = [
    "wills.batched", "wills.direct",
]
DELIVERY_METRICS = [
    "delivery.dropped", "delivery.dropped.no_local",
    "delivery.dropped.too_large", "delivery.dropped.qos0_msg",
    "delivery.dropped.queue_full", "delivery.dropped.expired",
    # connection flush wakeups actually scheduled (after
    # Connection._schedule_flush coalescing): with the dispatch
    # planner this is ≤1 per connection per batch
    "delivery.wakeups",
    # wire runs (docs/DISPATCH.md "Wire runs"): a planned batch's
    # QoS0 broadcast to one session written as ONE pre-joined piece
    # — the runs written, and the PUBLISH frames that left inside
    # them (Channel._emit stamps both, beside messages.sent: frames
    # over messages.sent is the share of egress the runs carry)
    "delivery.wire_runs",
    "delivery.wire_run.frames",
    # the planned tail's group walk (Broker._deliver_plan_group,
    # docs/DISPATCH.md "The delivery walk"): (group, filter)
    # resolutions made — a subscriber's SubOpts for one filter of its
    # slice of the plan, looked up once however often the filter
    # recurs there. Folded once a batch (Broker._plan_fold), gated on
    # [telemetry] enabled like ``dispatch.*``; 1 − resolves ÷
    # ``messages.delivered`` is the share of deliveries served from an
    # entry already resolved
    "delivery.plan.resolves",
    # PUBLISH frames serialized ON the event loop (the per-delivery
    # slow path, plus template/image cache misses that build there).
    # With egress pre-serialization on (docs/DISPATCH.md) eligible
    # traffic patches pre-built frames instead, so this stays ~0
    "delivery.serialize.onloop",
    # cross-loop delivery ring (docs/DISPATCH.md "Multi-loop front
    # door"): handoffs posted to a session's owning event loop — at
    # most one per loop per batch — and the deliveries they carried.
    # Both stay 0 with [node] loops = 1
    "delivery.xloop.handoffs",
    "delivery.xloop.deliveries",
    # cross-loop deliveries/results LOST to a gone or wedged loop
    # (shutdown race, dead loop thread, join timeout): every
    # formerly-silent `home loop gone` path counts here, with one
    # warning log per batch (docs/ROBUSTNESS.md)
    "delivery.xloop.orphaned",
]
CLIENT_METRICS = [
    "client.connect", "client.connack", "client.connected",
    "client.authenticate", "client.check_acl", "client.subscribe",
    "client.unsubscribe", "client.disconnected",
]
SESSION_METRICS = [
    "session.created", "session.resumed", "session.takeovered",
    "session.discarded", "session.terminated",
]
AUTH_ACL_METRICS = [
    "client.auth.anonymous", "client.acl.cache_hit", "client.acl.deny",
]
# on-device accumulators (psum'd in the sharded publish step), folded
# into the host array by Metrics.fold_device_stats — the pdict-batched
# counter idea (src/emqx_pd.erl) applied across the PCIe boundary
DEVICE_METRICS = [
    "device.matches", "device.deliveries", "device.overflows",
]

# publish match cache (ops/match_cache.py): per-unique-topic hit/miss
# split counters, drained from the router by the stats flush (and
# thence into $SYS heartbeats + the Prometheus exposition). `stale`
# counts entries found but epoch-invalidated (route churn / rebuild).
# The `bump.*` pair splits epoch-bump traffic by invalidation scope
# (docs/MATCH_CACHE.md "Partitioned epochs"): `bump.partition` =
# literal-rooted filter mutations that invalidated one partition,
# `bump.global` = root-wildcard mutations / rebuilds / reclaims that
# invalidated everything — a churn-driven hit-rate collapse is
# diagnosable from this split alone (global racing ⇒ root-wildcard
# churn; partition racing with `stale` ⇒ literal churn colliding
# into hot partitions)
CACHE_METRICS = [
    "cache.match.hit", "cache.match.miss",
    "cache.match.insert", "cache.match.stale",
    "cache.match.bump.global", "cache.match.bump.partition",
]

TRANSPORT_METRICS = [
    # slow-consumer guard closes (zone send_timeout)
    "connections.closed.slow_consumer",
]

# online delta automaton + off-lock compaction (ops/delta.py,
# docs/DELTA.md), drained from the router by the stats flush:
# `delta.probes` = match batches that ran the two-probe walk,
# `delta.filters` = route adds absorbed by the side-automaton,
# `delta.merges` = compactions that folded a delta into the main
# tables, `rebuild.stall_ms` = cumulative milliseconds the router
# lock was held across compaction freeze/swap sections (the number
# the off-lock design keeps near zero — a multi-second value here
# means rebuilds are stalling route ops again)
AUTOMATON_METRICS = [
    "automaton.delta.probes", "automaton.delta.filters",
    "automaton.delta.merges", "automaton.rebuild.stall_ms",
    # route deletes the delta took: `delta.retracts` = of a filter
    # still pending in the side-automaton (the add is withdrawn,
    # nothing is masked), `delta.tombstones` = of a filter in the main
    # tables (its id joins the mask until the next compaction)
    "automaton.delta.tombstones", "automaton.delta.retracts",
    # level-compressed walk tables (ops/csr.py compress_automaton):
    # `compaction.chains` = compressed edges carrying a fused
    # single-child run, `compaction.fused_edges` = interior states
    # those runs absorbed — table-state snapshots carried as drain
    # deltas (GAUGE_METRICS: a rebuild may shrink them); 0/0 means
    # the live tables walk narrow (no deep chains worth fusing)
    "automaton.compaction.fused_edges", "automaton.compaction.chains",
    # a background compaction (router.Router._compact_offlock):
    # `compaction.ns` = from its freeze to the end of its swap, on
    # the compaction thread beside the loop (÷ `delta.merges`: what
    # one merge takes, during which the next delta generation fills);
    # `freeze.deferred` = route operations that arrived while a
    # flatten held the trie frozen and went to the freeze log;
    # `delta.grows` = flattens of the delta's side tables at a larger
    # capacity than the one they are sized for (a new shape of the
    # walk's program, first used on the loop: 0 is the design)
    "automaton.compaction.ns", "automaton.freeze.deferred",
    "automaton.delta.grows",
]

# overload protection + self-healing (overload.py,
# docs/ROBUSTNESS.md): `shed.*` counts work refused under pressure
# (QoS0 at mqueue pressure, ServerBusy CONNACKs at critical, ingress
# publishers shed after the bounded submit wait), `force_shutdown`
# the per-connection OOM-policy kills, `transitions` the ok/warn/
# critical level changes, `heal.*` the supervision actions (fetch
# executor respawned, crashed flatten put on backoff-retry, dead
# front-door loop routed around), `takeover.timeout` the bounded
# cross-loop takeover waits that expired (the client got a fresh
# session instead of a hung CONNECT)
OVERLOAD_METRICS = [
    "overload.shed.qos0", "overload.shed.connect",
    "overload.shed.ingress_timeout", "overload.force_shutdown",
    "overload.transitions", "overload.heal.executor",
    "overload.heal.flatten", "overload.heal.loop",
    "overload.takeover.timeout",
]

# device-path circuit breaker (overload.DeviceBreaker): `failures` =
# device steps that failed (or exceeded breaker_slow_ms), `trips` =
# closed/half-open → open transitions, `probes` = half-open probe
# batches admitted, `fallback.batches` = publish batches matched on
# the exact host oracle because the breaker was open or rebuilding.
# Device-loss recovery (devloss.py): `rebuilds` = successful
# device-state reconstructions after a lost-backend classification
# (trie re-flattened straight to HBM, caches cold-started, kernels
# re-warmed), `rebuild.failures` = rebuild attempts that failed
# (backend still gone — retried with backoff)
BREAKER_METRICS = [
    "breaker.failures", "breaker.trips", "breaker.probes",
    "breaker.fallback.batches",
    "breaker.rebuilds", "breaker.rebuild.failures",
]

# fault injection (faults.py): total armed injection points that
# actually fired — 0 in any production configuration
FAULT_METRICS = [
    "faults.injected",
]

# zero-downtime operations (drain.py + reload.py,
# docs/OPERATIONS.md): `drain.rejected.connects` = CONNECTs refused
# with 0x9C Use-Another-Server while DRAINING, `drain.redirects` =
# live clients redirected by the paced waves, `drain.waves` /
# `drain.waves.deferred` = waves executed / held because the target
# reported critical overload, `drain.handoff.sessions` = persistent
# sessions whose custody moved to the drain target,
# `drain.handoff.errors` = hand-offs that failed or whose digest
# never settled inside the bound, `config.reload.applied` /
# `config.reload.rejected` = knobs applied by / boot-only knobs that
# rejected a `ctl reload`
OPS_METRICS = [
    "drain.rejected.connects", "drain.redirects", "drain.waves",
    "drain.waves.deferred", "drain.handoff.sessions",
    "drain.handoff.errors",
    "config.reload.applied", "config.reload.rejected",
]

# durability layer (wal.py + durability.py + replication.py,
# docs/DURABILITY.md): `wal.appends` = journal records framed,
# `wal.fsyncs` = batched write+sync cycles (one per shard per group
# commit with dirty state, NOT one per record — the fsync-batching
# contract), `wal.fsync_errors` = flushes that failed and degraded a
# shard to memory-only, `wal.degraded.dropped` = records shed by the
# memory-only degrade path's bounded drop-oldest buffers (per-shard
# AND the pre-recovery pending buffer — they used to vanish
# silently), `wal.group.commits`/`wal.group.coalesced` = leader
# group-commit passes / follower flushes that rode one,
# `checkpoint.saves`/`checkpoint.errors` = atomic generation commits
# and failed attempts, `checkpoint.delta.saves` = the subset that
# were incremental (differential) generations, `recovery.replayed` =
# journal records applied at boot, `recovery.torn` = journals
# truncated at a torn tail (a crash mid-append — expected, alarmed,
# never fatal), `recovery.sessions` = persistent sessions
# resurrected, `recovery.routes.pruned` = crash-dead clean-session
# route refs removed after restore. Replication (journal-shipped
# warm standby): `durability.repl.shipped`/`.acked` = records
# shipped to / acknowledged by the standby, `.ship_errors` = ship
# calls that failed (shipper drops to local-only), `.resyncs` = full
# snapshot re-syncs (first contact, gap repair, queue overflow),
# `.dropped` = queued-but-unshipped records discarded by the bounded
# ship queue (triggers a resync), `.promotions` = standby
# promotions executed after a primary death. Replication groups
# (multi-standby fan-out + quorum): `.quorum.waits` = group commits
# that blocked (bounded) for the ack quorum, `.quorum.timeouts` =
# waits that hit quorum_timeout_ms and degraded, `.failbacks` =
# completed FAILBACK hand-offs (either side), `.failback_errors` =
# hand-off attempts aborted by a transfer failure (the standby stays
# promoted and retries)
DURABILITY_METRICS = [
    "wal.appends", "wal.fsyncs", "wal.fsync_errors",
    "wal.degraded.dropped", "wal.group.commits",
    "wal.group.coalesced",
    "checkpoint.saves", "checkpoint.errors", "checkpoint.delta.saves",
    "recovery.replayed", "recovery.torn", "recovery.sessions",
    "recovery.routes.pruned",
    "durability.repl.shipped", "durability.repl.acked",
    "durability.repl.ship_errors", "durability.repl.resyncs",
    "durability.repl.dropped", "durability.repl.promotions",
    "durability.repl.quorum.waits", "durability.repl.quorum.timeouts",
    "durability.repl.failbacks", "durability.repl.failback_errors",
]

# cluster plane (cluster.py + cluster_net.py, docs/CLUSTER.md),
# folded from the per-node Cluster/transport event counters on the
# stats tick: `cluster.hb.*` = failure-detector transitions
# (ok→suspect, suspect→down, down→reappeared), `cluster.rpc.fastfail`
# = calls refused WITHOUT touching the wire because the detector held
# the peer suspect/down, `cluster.forward.dropped` = at-most-once
# data-plane casts shed (cast buffer full, or net.drop chaos) — the
# loss anti-entropy exists to repair, `cluster.heal.rejoins` =
# auto-heal handshakes completed, `cluster.ae.sweeps`/
# `cluster.ae.repairs` = anti-entropy rounds run / entries re-pushed,
# `cluster.locker.degraded` = lock quorums that proceeded without a
# suspect member's vote
CLUSTER_METRICS = [
    "cluster.hb.suspects", "cluster.hb.downs",
    "cluster.hb.reappears", "cluster.rpc.fastfail",
    "cluster.rpc.errors",
    "cluster.forward.dropped", "cluster.heal.rejoins",
    "cluster.ae.sweeps", "cluster.ae.repairs",
    "cluster.locker.degraded",
]

# sampled end-to-end tracing + slow-subscriber attribution
# (emqx_tpu/tracing.py, docs/OBSERVABILITY.md "Tracing"), folded on
# the stats tick: `tracing.spans` = span records drained from the
# per-loop rings, `tracing.dropped` = spans shed because a ring was
# full when its owner loop tried to record (the ring never blocks the
# hot path), `slow_subs.flushes` = flush spans folded into the
# slow-subscriber ranking, `slow_subs.breaches` = flushes whose
# delivery latency crossed slow_subs_threshold_ms
TRACING_METRICS = [
    "tracing.spans", "tracing.dropped",
    "slow_subs.flushes", "slow_subs.breaches",
]

# MQTT frame-parser engine (emqx_tpu/mqtt/frame.py NativeParser,
# docs/OBSERVABILITY.md "Frame parser"): `frame.native.frames` = MQTT
# frames decoded through the C++ incremental parser, `frame.fallback` =
# connections that asked for frame="native" but got the Python parser
# (shared library missing or built without the parser symbols),
# `frame.oversize` = frames rejected at header-decode time for
# exceeding the zone's max_packet_size (both engines; counted before
# the body is ever buffered)
FRAME_METRICS = [
    "frame.native.frames", "frame.fallback", "frame.oversize",
]
# the loop's time outside publish batches and its stalls
# (telemetry.py "The loop outside publish batches"; gated on
# [telemetry] enabled). ``*.ns`` are nanosecond sums EXCLUSIVE of
# what nested inside the section, ``*.calls`` the sections counted;
# each ``.ns`` is followed by its count (and ``loop.flush.ns`` by
# ``wait_ns`` after that): Telemetry.loop_leave / gc_done index them
# as base, base + 1, base + 2
LOOP_METRICS = [
    # socket read → parse → channel → ingress submit, per read chunk
    # (connection.Connection.run)
    "loop.read.ns", "loop.read.calls",
    # Connection._flush_deliver per wake-up; wait_ns = outbox first
    # filled (_schedule_flush) → the flush ran
    "loop.flush.ns", "loop.flush.calls", "loop.flush.wait_ns",
    # the loop inside its selector: waiting for a socket or a timer,
    # or polling with work queued (monitors.SysMon wraps the
    # selector's select). Polls are kernel work inside ``select``, so
    # 1 − select ÷ wall understates the loop's busy share by
    # ``loop.select.poll.ns`` (below)
    "loop.select.ns", "loop.select.calls",
    # every garbage collection by generation (monitors.SysMon's
    # gc.callbacks hook), not only those over long_gc_ms
    "gc.ns.gen0", "gc.collections.gen0",
    "gc.ns.gen1", "gc.collections.gen1",
    "gc.ns.gen2", "gc.collections.gen2",
    # the loop's heartbeat overdue by more than 50 ms
    # (monitors.SysMon._beat): stalls and their summed length
    "loop.stalls", "loop.stall.ns",
    # wall clock on the same terms as the sums above: the heartbeat
    # adds the time since its last beat, so a window's delta of any
    # ``*.ns`` over this one's is that section's share of the window
    # (to one beat, 20 ms), whoever cut the window and however late
    "loop.wall.ns",
    # ``loop.select.ns`` by what the loop was waiting for, decided
    # from the state at each call's entry (monitors.SysMon's shadow;
    # any change of that state wakes the loop): ``poll`` = called
    # with timeout 0, the loop had ready handles (kernel work, not a
    # wait); ``device`` = a blocking call with a batch on the device
    # path (enqueued by publish_begin, its publish_fetch not yet back
    # on the loop: ingress.IngressBatcher._on_path); ``clients`` = a
    # blocking call with nothing on the device path, no batch in the
    # pipeline and nothing accumulated: nothing to do until a socket
    # speaks. What is left of ``loop.select.ns`` (a linger timer, a
    # batch past its fetch waiting on its predecessor) has no counter
    "loop.select.poll.ns", "loop.select.device.ns",
    "loop.select.clients.ns",
    # the stats flush (Node._update_stats, once a stats interval):
    # exclusive like read and flush
    "loop.stats.ns", "loop.stats.calls",
    # the front door's two sections, exclusive like the rest
    # (channel.Channel): ``session.open`` = a decoded CONNECT to its
    # CONNACK handed to the connection (authentication, the connection
    # manager's registration or the kick of the channel it takes over,
    # the session made), counted where the CONNACK says success, so
    # Σ calls = Σ ``client.connected``; ``session.close`` = a connected
    # channel's teardown (the will, the session's unsubscribes and the
    # route deletes and fan-out rows they cause, the connection
    # manager's unregistration), Σ calls = Σ ``client.disconnected``.
    # A takeover's kicked channel closes inside the new one's open and
    # is taken out of it; both are taken out of the read chunk that
    # brought them
    "loop.session.open.ns", "loop.session.open.calls",
    "loop.session.close.ns", "loop.session.close.calls",
    # a connected channel's SUBSCRIBE and UNSUBSCRIBE packets,
    # exclusive like the rest (channel.Channel): from the decoded
    # packet to its SUBACK / UNSUBACK handed to the connection (hooks,
    # caps, ACL, the session, Broker.subscribe / unsubscribe with the
    # route operation and the fan-out row it marks); `filters` = the
    # topic filters the packets carried, so ns ÷ filters is what one
    # subscription costs the loop
    "loop.subscribe.ns", "loop.subscribe.calls",
    "loop.subscribe.filters",
    "loop.unsubscribe.ns", "loop.unsubscribe.calls",
    "loop.unsubscribe.filters",
]

# the device path's occupancy, from the publish spans' interval record
# (telemetry.Telemetry.finish; gated on [telemetry] enabled): ``ns`` =
# the union over device batches of [``t_enq``: the clock just before
# the batch's first device call, the end of its ``fetch`` stage] — the
# time the host held the device path occupied, whose complement in
# ``loop.wall.ns`` is time in which the chip had been given nothing;
# ``batch_ns`` = the same summed per batch, so batch_ns ÷ ns is the
# mean number of batches overlapping on the path. Host batches add
# nothing
PIPELINE_METRICS = [
    "pipeline.device.ns", "pipeline.device.batch_ns",
]

# what a [matcher] mesh adds to the match dispatch (router.py's mesh
# branch; gated on [telemetry] enabled like the loop's counters):
# ``batches`` = publish batches dispatched on the mesh path and
# ``topics`` the unique topics in them; ``steps`` = collective
# publish_step programs enqueued (a batch whose topics all hit the
# sharded match cache takes none) and ``step.topics`` the unique
# topics that walked in them, before padding. steps ÷ batches and
# step.topics ÷ topics say how much of the traffic the collectives
# carry and how much the cache's gather; ``fused`` = batches that
# left as one transfer and two or three programs
# (Router._dispatch_fused); batches − fused fell to the legacy whole
# dispatch (cache off, a big-filter bitmap live, a snapshot that moved
# under the split)
MESH_METRICS = [
    "mesh.batches", "mesh.topics", "mesh.steps", "mesh.step.topics",
    "mesh.fused",
]

# the one-chip match dispatch, per batch (router.py's
# ``_count_dispatch``; gated on [telemetry] enabled like the mesh's
# above, whose twins they are): ``topics`` = unique topics dispatched
# (after the batch's own dedup, before padding), ``walk.topics`` =
# those of them that walked the automaton; the rest were gathered
# from the match cache. Current at any instant, where
# ``cache.match.hit`` / ``.miss`` wait for the stats flush.
# ``batches`` = device batches dispatched on one chip, ``fused`` =
# those of them that left the loop as one host→device transfer and
# two programs (``Broker._begin_device``; the rest: the match cache
# off, or big-filter bitmaps live), the twins of ``mesh.batches`` /
# ``mesh.fused``. ``programs`` = the compiled programs the loop
# launched for fused batches, counted where each is launched (the
# router's match or merge, the broker's packer; the calls-apart paths
# count none): programs ÷ batches reads 2.0 where every batch is
# fused (3 a batch with a miss before the match became one program)
DISPATCH_METRICS = [
    "dispatch.topics", "dispatch.walk.topics",
    "dispatch.batches", "dispatch.fused", "dispatch.programs",
]

# the publish run (connection.Connection.run →
# channel.Channel.handle_publish_run, docs/OBSERVABILITY.md "The
# publish run"): PUBLISH packets that went from a read chunk to the
# ingress queue inside a run (over ``messages.received``: the share
# of ingress that skipped the per-packet path)
CHANNEL_METRICS = [
    "channel.publish_run.msgs",
]

# ingest backpressure as the connections feel it
# (ingress.IngressBatcher.admit, the admission line; gated on
# [telemetry] enabled like ``dispatch.*``): ``parks`` = read loops
# that found the accumulator at its high-water mark (or others
# waiting for it) before handing over what they had read, and joined
# the line; ``wakes`` = parked readers back on their loop, by a grant,
# a time-out or a cancellation (Σ wakes = Σ parks at rest: one wake a
# park); ``park.ns`` = from each park to that resumption (a shed
# publisher's park counts up to its time-out). ns ÷ parks is what one
# park costs a publisher; parks ÷ ``messages.received`` how often
# traffic meets it. ``flush.held`` (IngressBatcher._flush, gated
# alike) = flushes short of the size trigger that found a batch on the
# device path and began nothing: what they held leaves with the flush
# that batch's completion schedules. held ÷ ``dispatch.batches`` is
# how often a tick met an occupied path. ``flush.grown`` (gated alike)
# = takes of more than ``batch_size`` messages: batches that grew
# beside a batch in the pipeline (the size trigger is 2 × batch_size
# there, IngressBatcher._trigger) or behind a full one
INGRESS_METRICS = [
    "ingress.parks", "ingress.park.ns", "ingress.wakes",
    "ingress.flush.held", "ingress.flush.grown",
]

# the fan-out tables' syncs that changed them
# (broker_helper.FanoutManager.state, once a batch on the event loop;
# gated on [telemetry] enabled like ``dispatch.*``): ``patches`` =
# syncs that wrote the rows a membership change touched and no other
# (work proportional to the change), ``rebuilds`` = syncs that built
# the tables from every filter (a new automaton epoch, a bitmap row
# changed, a table out of room; the first of a node is one),
# ``sync.ns`` = the time of both. A batch that found the tables
# current counts nowhere. rebuilds ÷ (rebuilds + patches) under
# subscribe churn is what the patch path keeps at 0
FANOUT_METRICS = [
    "fanout.patches", "fanout.rebuilds", "fanout.sync.ns",
]

ALL_METRICS = (BYTES_METRICS + PACKET_METRICS + MESSAGE_METRICS
               + WILL_METRICS
               + DELIVERY_METRICS + CLIENT_METRICS + SESSION_METRICS
               + AUTH_ACL_METRICS + DEVICE_METRICS + CACHE_METRICS
               + AUTOMATON_METRICS + TRANSPORT_METRICS
               + OVERLOAD_METRICS + BREAKER_METRICS + FAULT_METRICS
               + OPS_METRICS + DURABILITY_METRICS + CLUSTER_METRICS
               + TRACING_METRICS + FRAME_METRICS + LOOP_METRICS
               + MESH_METRICS + DISPATCH_METRICS + CHANNEL_METRICS
               + INGRESS_METRICS + PIPELINE_METRICS + FANOUT_METRICS)

#: registry names that are NOT monotonic — ``Metrics.dec`` runs on
#: them in steady state (today: the retainer's live-entry count,
#: modules/retainer.py). Prometheus semantics split on this: a
#: ``counter`` may only go up (scrapers compute rate() over it and
#: treat any decrease as a process restart), so the exposition
#: (modules/prometheus.render) must emit these as ``gauge``. Add any
#: new dec'd name here or its scraped rates turn to garbage.
GAUGE_METRICS = frozenset({
    "retained.count",
    "automaton.compaction.fused_edges",
    "automaton.compaction.chains",
})


@shared_state(lock="_lock", attrs=("_counters",))
class Metrics:
    def __init__(self) -> None:
        # a plain list, not numpy: scalar element updates are the
        # hottest metric op and a list add is ~3x cheaper than
        # numpy item assignment (single-writer per process, like
        # the reference's counters array)
        self._counters: List[int] = [0] * MAX_METRICS
        self._index: Dict[str, int] = {}
        # multi-loop front door ([node] loops > 1): counters are then
        # incremented from several event-loop threads, and the bare
        # read-modify-write below would lose updates under the GIL's
        # opcode-level interleaving. Node.start() arms the lock; the
        # single-loop build keeps the lock-free single-writer path
        self._lock = None
        for name in ALL_METRICS:
            self.new(name)

    def enable_threadsafe(self) -> None:
        """Arm the increment lock (multi-loop nodes). One-way: a
        started multi-loop node never goes back to single-writer."""
        if self._lock is None:
            import threading
            self._lock = threading.Lock()

    def new(self, name: str) -> int:
        idx = self._index.get(name)
        if idx is None:
            idx = len(self._index)
            if idx >= MAX_METRICS:
                raise RuntimeError("metric index overflow")
            self._index[name] = idx
        return idx

    @any_thread
    def inc(self, name: str, n: int = 1) -> None:
        lock = self._lock
        if lock is None:
            # lint: ok-CD102 single-writer fast path: the lock stays
            # None until Node.start arms multi-loop mode, and until
            # then every increment runs on the one event loop
            self._counters[self._index[name]] += n
        else:
            with lock:
                self._counters[self._index[name]] += n

    @any_thread
    def dec(self, name: str, n: int = 1) -> None:
        lock = self._lock
        if lock is None:
            # lint: ok-CD102 single-writer fast path, as in inc()
            self._counters[self._index[name]] -= n
        else:
            with lock:
                self._counters[self._index[name]] -= n

    @any_thread
    def add_at(self, idx: int, n: int) -> None:
        """``inc`` by registry index (the ``I_*`` constants below):
        the timed sections' path, which cannot afford the name
        lookup."""
        lock = self._lock
        if lock is None:
            # lint: ok-CD102 single-writer fast path, as in inc()
            self._counters[idx] += n
        else:
            with lock:
                self._counters[idx] += n

    def val(self, name: str) -> int:
        return int(self._counters[self._index[name]])

    def all(self) -> Dict[str, int]:
        return {n: int(self._counters[i]) for n, i in self._index.items()}

    def names(self) -> List[str]:
        return list(self._index)

    def inc_msg(self, msg) -> None:
        """Count an inbound message by QoS (emqx_metrics.erl qos_received)."""
        self.inc("messages.received")
        self.inc(_QOS_RECV[min(msg.qos, 2)])

    def inc_sent(self, msg) -> None:
        self.inc("messages.sent")
        self.inc(_QOS_SENT[min(msg.qos, 2)])

    @owner_loop
    def fold_device_stats(self, stats: Dict[str, int]) -> None:
        """Fold a drained device accumulator (matches/deliveries/
        overflows) into the host counters — one transfer per flush."""
        for key, val in stats.items():
            self.inc(f"device.{key}", int(val))

    def fold_cache_stats(self, stats: Dict[str, int]) -> None:
        """Fold drained match-cache counter deltas (hit/miss/insert/
        stale) into the host counters (Router.drain_cache_stats)."""
        for key, val in stats.items():
            self.inc(f"cache.match.{key}", int(val))

    def fold_automaton_stats(self, stats: Dict[str, int]) -> None:
        """Fold drained delta-automaton / rebuild counter deltas
        (Router.drain_automaton_stats)."""
        for key, val in stats.items():
            self.inc(f"automaton.{key}", int(val))

    @owner_loop
    def fold_cluster_stats(self, stats: Dict[str, int]) -> None:
        """Fold drained cluster-plane event counters
        (Cluster.drain_counters). Keys outside CLUSTER_METRICS are
        registered on first sight — the cluster/transport layers may
        grow event names without a registry edit here."""
        for key, val in stats.items():
            name = f"cluster.{key}"
            if name not in self._index:
                self.new(name)
            self.inc(name, int(val))


_QOS_RECV = ("messages.qos0.received", "messages.qos1.received",
             "messages.qos2.received")
_QOS_SENT = ("messages.qos0.sent", "messages.qos1.sent",
             "messages.qos2.sent")

_global = Metrics()

# every Metrics registers ALL_METRICS first and in order, so these
# indexes hold for any instance
I_READ_NS = _global._index["loop.read.ns"]
I_FLUSH_NS = _global._index["loop.flush.ns"]
I_SELECT_NS = _global._index["loop.select.ns"]
I_WALL_NS = _global._index["loop.wall.ns"]
I_GC_NS = _global._index["gc.ns.gen0"]
I_SELECT_POLL_NS = _global._index["loop.select.poll.ns"]
I_SELECT_DEVICE_NS = _global._index["loop.select.device.ns"]
I_SELECT_CLIENTS_NS = _global._index["loop.select.clients.ns"]
I_STATS_NS = _global._index["loop.stats.ns"]
I_SESSION_OPEN_NS = _global._index["loop.session.open.ns"]
I_SESSION_CLOSE_NS = _global._index["loop.session.close.ns"]
I_SUBSCRIBE_NS = _global._index["loop.subscribe.ns"]
I_UNSUBSCRIBE_NS = _global._index["loop.unsubscribe.ns"]
I_PIPELINE_NS = _global._index["pipeline.device.ns"]


def global_metrics() -> Metrics:
    return _global
