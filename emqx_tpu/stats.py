"""Gauge statistics with max-watermarks and registered update
functions (reference: src/emqx_stats.erl — subsystems register
update funs that run on the stats tick, e.g.
src/emqx_broker_helper.erl:118)."""

from __future__ import annotations

from typing import Callable, Dict, List

STATS_KEYS = [
    "connections.count", "connections.max",
    "sessions.count", "sessions.max",
    "topics.count", "topics.max",
    "suboptions.count", "suboptions.max",
    "subscribers.count", "subscribers.max",
    "subscriptions.count", "subscriptions.max",
    "subscriptions.shared.count", "subscriptions.shared.max",
    "routes.count", "routes.max",
    "retained.count", "retained.max",
    "channels.count", "channels.max",
    # live publish match-cache entries (emqx_tpu/ops/match_cache.py)
    "match.cache.entries.count", "match.cache.entries.max",
    # partition epoch keys in effect for the match cache (0 = cache
    # off, 1 = legacy whole-epoch, else MatcherConfig.cache_partitions
    # — docs/MATCH_CACHE.md "Partitioned epochs")
    "match.cache.partition.live",
    # freed filter ids quarantined until the next flatten
    # (Router._pending_free — the round-4 soak leak's device-regime
    # visibility; sustained growth raises the router_ids_quarantined
    # alarm from the stats tick)
    "router.ids.quarantined.count", "router.ids.quarantined.max",
    # publish-path telemetry (emqx_tpu/telemetry.py): recorded batch
    # spans and slow-publish breaches (the .max watermarks make a
    # between-heartbeats burst visible even after a reset)
    "publish.spans.count", "publish.spans.max",
    "publish.slow.count", "publish.slow.max",
    # durability layer (docs/DURABILITY.md): current journal segment
    # size, committed checkpoint generation, and seconds since the
    # last committed checkpoint (an ever-growing age with a non-empty
    # journal means checkpoints are failing — see checkpoint_failed)
    "journal.bytes", "journal.records",
    "durability.generation", "checkpoint.age_s",
    # cluster plane (docs/CLUSTER.md): membership size, worst
    # failure-detector state across peers (0 ok / 1 suspect / 2
    # down — any non-zero means a peer is unhealthy right now), and
    # the slowest peer heartbeat RTT. Per-peer rows land as
    # ``cluster.member.<name>.state`` / ``.rtt_ms`` dynamically.
    "cluster.members.count",
    "cluster.member.state", "cluster.hb.rtt_ms",
    # node lifecycle (docs/OPERATIONS.md): 0 running / 1 draining /
    # 2 stopping — set by the drain subsystem (drain.py); a fleet
    # dashboard's one-glance "is anything mid-maintenance" gauge
    "node.state",
    # overload protection (docs/ROBUSTNESS.md): monitor level (0 ok /
    # 1 warn / 2 critical) and device-path breaker state (0 closed /
    # 1 half-open / 2 open / 3 rebuilding — device-loss recovery) —
    # surfaced by lint rule RD204: they were set dynamically and
    # invisible to registry-built dashboards
    "overload.level", "breaker.state",
    # replicated durability (docs/DURABILITY.md): journal-ship lag
    # and ack age on a replicating primary
    "durability.repl.lag_records", "durability.repl.lag_bytes",
    "durability.repl.last_ack_age_s",
    # walk-table level compression (ops/csr.py compress_automaton):
    # permille of deepest-level walk steps the compressed tables
    # save over one-hop-per-level (0 = narrow mode / nothing saved)
    "automaton.compaction.ratio",
    # sampled tracing + slow-subscriber attribution (emqx_tpu/
    # tracing.py, docs/OBSERVABILITY.md "Tracing"): span records
    # still buffered in the per-loop rings, clientids currently in
    # the slow_subs ranking, and the worst average delivery latency
    # across them
    "tracing.spans.pending",
    "slow_subs.tracked", "slow_subs.worst_ms",
    # per-loop event-loop scheduling lag (monitors.SysMon over the
    # LoopGroup, docs/OBSERVABILITY.md): ``loop.0.lag_ms`` is the
    # main loop; peer rows land as ``loop.<i>.lag_ms`` dynamically,
    # one per front-door loop
    "loop.0.lag_ms",
]


class Stats:
    def __init__(self) -> None:
        self._vals: Dict[str, int] = {k: 0 for k in STATS_KEYS}
        self._update_funs: List[Callable[["Stats"], None]] = []

    def setstat(self, key: str, value: int, max_key: str = "") -> None:
        self._vals[key] = value
        if max_key:
            if value > self._vals.get(max_key, 0):
                self._vals[max_key] = value

    def getstat(self, key: str) -> int:
        return self._vals.get(key, 0)

    def delstat(self, key: str) -> None:
        """Drop a dynamically-created row (a departed cluster peer's
        per-member gauges must not linger at their last value)."""
        self._vals.pop(key, None)

    def all(self) -> Dict[str, int]:
        return dict(self._vals)

    def register_update(self, fn: Callable[["Stats"], None]) -> None:
        self._update_funs.append(fn)

    def tick(self) -> None:
        for fn in list(self._update_funs):
            try:
                fn(self)
            except Exception:
                pass
