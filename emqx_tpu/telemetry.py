"""Publish-path pipeline telemetry: per-stage latency histograms,
per-batch span records, and the slow-publish log.

The reference broker attributes production latency with BEAM VM
introspection and system monitors (SURVEY §5 "Tracing/profiling",
``emqx_vm.erl``, long_gc/long_schedule); the TPU reproduction's
publish path is a *pipeline* — host pre-work → device walk /
match-cache gather → fan-out/pack dispatch → ONE coalesced transfer →
host delivery tail — so the equivalent question is "which STAGE did
this batch spend its time in". This module answers it with:

  - :class:`Histogram` — fixed log-spaced latency buckets (Prometheus
    ``_bucket``/``_sum``/``_count`` exposition) plus a ring buffer of
    raw samples for exact p50/p95/p99 over the recent window
    (single-writer, like :class:`~emqx_tpu.metrics.Metrics`);
  - :class:`PublishSpan` — one per :class:`~emqx_tpu.broker
    .PendingBatch`, stamped through ``publish_begin`` →
    ``publish_fetch`` → ``publish_finish`` (and the host / mesh /
    chunked-ingress variants), tagged with batch size, unique-topic
    count, cache hit/miss split, host-fallback count and the padding
    bucket;
  - :class:`Telemetry` — the per-node registry: folds finished spans
    into the stage histograms, keeps the last-N slow batches, emits
    the slow-publish log line (plus a tee through the
    :class:`~emqx_tpu.tracer.Tracer`) and drives the sustained-breach
    :class:`~emqx_tpu.alarm.AlarmManager` alarm.

Stage semantics (all host wall-clock, milliseconds; each stage is
kept as intervals ``(stage, start, end, thread)`` on the span and
summed into ``stages``). Busy stages first, then the waits:

  ``prepare``        ``publish_begin`` entry → start of ``match``:
                     message metrics, ``message.publish`` hooks, the
                     veto filter, topic dedup. On the loop.
  ``match``          async dispatch of the NFA walk (device regime:
                     encode + enqueue, NOT device execution — that
                     surfaces in ``fetch``); host regime: the actual
                     trie walk.
  ``cache_gather``   the match-cache probe (cache-split batches
                     only; carved out of the ``match`` interval). On
                     one chip the probe alone since the match became
                     one program: the merge's launch is ``match``'s.
                     On the mesh the probe + the HBM-row merge
                     dispatch. Rows of one stage from before and
                     after that change do not compare; their sum
                     does.
  ``fan_sync``       the fan-out tables brought up to the membership
                     changes since the last batch
                     (``FanoutManager.state``; one chip): one compare
                     where none changed, a patch of the changed rows,
                     or a build over every filter. On the loop.
  ``pack``           fan-out + sparse-compaction kernel dispatch.
  ``fetch``          the ONE coalesced device→host transfer — the
                     only synchronizing stage, so queued device
                     execution time surfaces here. No NEW
                     ``block_until_ready`` is introduced anywhere:
                     spans only read the clock at boundaries the
                     pipeline already crosses. Executor thread.
  ``dispatch_plan``  the batch dispatch planner's numpy grouping pass
                     (ops/dispatch_plan.py): CSR/bitmap expansion +
                     subscriber argsort over the fetched packed
                     arrays. Runs right after the transfer, on the
                     same (possibly executor) thread — recorded
                     separately so planner cost is attributable
                     against the dispatch time it saves. Zero when
                     the planner is off or the batch fell back.
  ``serialize``      the egress pre-serialization pass
                     (ops/dispatch_plan.preserialize_plan): QoS0
                     shared wire images + QoS1/2 pid-placeholder
                     templates built per (message, variant) right
                     after the plan, on the same (possibly executor)
                     thread — the serialize work the delivery tail no
                     longer pays on-loop. Zero when ``[dispatch]
                     preserialize = false`` or the batch didn't plan.
  ``host_fallback``  overflow topics re-matched on the host oracle
                     during the delivery tail (a subset of
                     ``dispatch`` time, recorded separately so
                     fallback cost is attributable).
  ``dispatch``       the host delivery tail (packed-row expansion +
                     session ``deliver`` calls), one interval per
                     chunk.
  ``xloop``          the cross-loop delivery ring (docs/DISPATCH.md
                     "Multi-loop front door"): handoff post → last
                     owning loop's group enqueue complete. Overlaps
                     ``dispatch`` (the main loop delivers its own
                     groups while peer loops run theirs); zero with
                     ``[node] loops = 1``.
  ``gc_inside``      garbage collection (any generation) that ran
                     inside this batch's on-loop busy stages. Those
                     stages stay inclusive of it; the loop's time
                     accounting subtracts it so a collection is
                     counted once (``gc.ns.gen*``).
  ``ingress_wait``   first arrival in the empty accumulator
                     (``IngressBatcher.submit``) → ``_take_pending``:
                     the linger, the tick, or every pipeline slot
                     busy (tag ``inflight`` = slots busy at the take).
  ``executor_wait``  ``run_in_executor`` call → ``publish_fetch``
                     entry on the executor thread.
  ``chain_wait``     fetch returned → the previous batch's completion
                     (ordered delivery across batches).
  ``loop_wait``      the batch was ready and the loop was busy with
                     something else: ``publish_begin`` returned → its
                     completion task first ran, and fetch returned (or
                     the chain freed) → first delivery chunk.
  ``tail_yield``     time the delivery tail gave back to the loop
                     between finish chunks.
  ``unattributed``   ``end_to_end`` minus the union of all intervals:
                     the span's own completeness check.
  ``end_to_end``     first arrival in the accumulator (or
                     ``publish_begin`` entry for callers that bypass
                     it) → last delivery chunk.

Every busy stage is also a ``jax.profiler.TraceAnnotation`` named
``emqx/<stage>`` with the batch's sequence number as its ``seq``
argument: one native check when no profiler trace runs, an event on
the thread's line of the ``/host:CPU`` plane when one does — the same
``xplane.pb`` as the device's ``XLA Ops`` line, so host stages and
device ops share a clock (``ctl profile report``). Waits are the gaps
between one batch's annotations.

One instant is kept beside the intervals: ``t_enq``, the clock just
before the batch's first device call (the put of its buffer or its
first program; router.py's dispatch, through :func:`enqueue_mark`),
held around that call as the annotation ``emqx/enqueue`` — where the
batch enters the DEVICE PATH. It leaves it at the end of its
``fetch`` interval. :meth:`Telemetry.finish` keeps the union of those
stretches over batches in ``pipeline.device.ns`` (the time the host
held the device path occupied; its complement in ``loop.wall.ns`` is
time in which the chip had been given nothing) and their per-batch sum
in ``pipeline.device.batch_ns`` (over the union: how many batches
overlap on the path). A host batch has no ``t_enq`` and adds nothing.

The loop outside publish batches is counted, not spanned:
``loop.read.*`` (socket read → parse → channel → submit, per read
chunk), ``loop.flush.*`` (``Connection._flush_deliver`` per wake-up),
``loop.stats.*`` (the stats flush, ``Node._update_stats``),
``loop.select.*`` (the loop inside its selector: waiting, or polling
with work queued) and ``gc.ns.gen*`` / ``gc.collections.gen*`` are
``Metrics`` counters holding nanoseconds EXCLUSIVE of whatever nested
inside them (:attr:`Telemetry.inner`), so on-loop stages −
``gc_inside`` + read + flush + stats + select + gc sum to the loop's
attributed time without counting a second twice; what is left of the
wall clock is loop work that has no name yet. Each selector call's
time goes a second time to what the loop was waiting for, read from
the ingress's state at the call's entry
(:meth:`Telemetry.select_leave`): ``loop.select.poll.ns`` (timeout 0:
kernel work, no wait), ``.device.ns`` (a batch on the device path),
``.clients.ns`` (nothing anywhere: nothing to do until a socket
speaks); the rest of ``loop.select.ns`` has no counter. Exact on a
single-loop node; with ``[node] loops > 1``
the peer loops share the one accumulator and the split is
approximate. Loop stalls (the heartbeat of ``monitors.SysMon``
overdue by more than 50 ms) land in a bounded ring beside the
slow-publish ring (:meth:`Telemetry.note_stall`).

Cost model: disabled (``[telemetry] enabled = false``) the broker
takes one predicate branch per batch and records nothing — the
dispatch byte-stream is identical to the un-instrumented path (pinned
by tests/test_telemetry.py), the selector is not shadowed and none of
the counters above moves. Enabled, the cost is ~21 ``perf_counter``
reads and ~9 annotation objects per batch (not per message; ``t_enq``
and its mark are one of each), a scan of the span's first few
intervals and two counter adds at its finish, two clock reads per
socket read chunk, three per delivery-flush wake-up, and per selector
call two clock reads, a compare and — on a blocking call only — one
to three attribute loads of the ingress.
"""

from __future__ import annotations

import contextlib
import dataclasses
import json
import logging
import threading
import time
from collections import deque
from typing import Dict, List, Optional

from jax.profiler import TraceAnnotation

from emqx_tpu.metrics import I_GC_NS, I_PIPELINE_NS, I_SELECT_NS

log = logging.getLogger("emqx_tpu.telemetry")

#: guards direct (cross-thread) stage observes — see
#: :meth:`Telemetry.observe_stage`; span folds stay lock-free
#: (single-writer on the event loop)
_observe_lock = threading.Lock()

#: the publish pipeline's stage names, in pipeline order (ctl and the
#: $SYS heartbeat render in this order; Prometheus sorts its own).
#: ``rebuild`` is the one non-span stage: automaton compaction /
#: re-flatten durations (inline and background), observed directly
#: via :meth:`Telemetry.observe_stage` — it shares the histogram
#: surfaces so a churn-driven rebuild storm shows up next to the
#: publish latencies it would otherwise silently explain
STAGES = ("ingress_wait", "prepare", "match", "cache_gather",
          "fan_sync", "pack", "executor_wait", "fetch",
          "dispatch_plan", "serialize",
          "chain_wait", "loop_wait", "host_fallback", "dispatch",
          "tail_yield", "xloop", "gc_inside", "rebuild",
          "unattributed", "end_to_end")

#: what a background compaction's ``rebuild`` is made of
#: (router.Router._compact_offlock), each observed as
#: ``rebuild.<stage>`` beside it: the short lock that freezes the trie,
#: the flatten off the lock, the new tables put on the device, the
#: fan-out tables handed over the epoch, the swap under the second
#: short lock. Not publish stages: ``ctl telemetry`` and ``ctl
#: profile`` print them under the table (:meth:`Telemetry.rebuild_stats`)
REBUILD_STAGES = ("freeze", "flatten", "put", "handover", "swap")

#: profiler annotation names, built once (``emqx/<stage>``)
_ANN = {s: "emqx/" + s for s in STAGES}
#: the mark around a batch's first device call
#: (:meth:`PublishSpan.enqueue`): no stage, the start of the device
#: path on the trace's clock
ENQUEUE_ANN = "emqx/enqueue"
_UNMARKED = contextlib.nullcontext()

#: the heartbeat's lateness past which the loop counts as stalled
#: (fixed, not a configuration key: monitors.SysMon)
STALL_S = 0.05

#: fixed log-spaced bucket upper bounds, milliseconds (1-2.5-5 per
#: decade, 10µs..5s). Fixed — not adaptive — so scrapes from
#: different nodes/epochs aggregate; the raw-sample ring carries the
#: exact percentiles the coarse buckets can't.
BUCKETS_MS = (0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0,
              10.0, 25.0, 50.0, 100.0, 250.0, 500.0, 1000.0,
              2500.0, 5000.0)

_now = time.perf_counter


def union_s(intervals) -> float:
    """Length covered by ``(start, end)`` pairs; where they overlap
    the time counts once."""
    total = 0.0
    end = None
    for t0, t1 in sorted(intervals):
        if end is None or t0 > end:
            total += t1 - t0
            end = t1
        elif t1 > end:
            total += t1 - end
            end = t1
    return total


def enqueue_mark(span):
    """What the dispatch holds around a device call that may be the
    batch's first: :meth:`PublishSpan.enqueue`, or nothing without a
    span (telemetry off, a warm-up batch)."""
    return span.enqueue() if span is not None else _UNMARKED


@dataclasses.dataclass
class TelemetryConfig:
    """``[telemetry]`` TOML section (emqx_tpu/config.py). Unknown
    keys are startup errors — same closed-schema rule as zones."""

    enabled: bool = True
    #: end-to-end batch latency past this emits one slow-publish log
    #: line (and counts toward the sustained-breach alarm)
    slow_threshold_ms: float = 100.0
    #: per-stage raw-sample ring size (exact p50/p99 window)
    ring_size: int = 2048
    #: how many slow-batch records ``ctl telemetry slow`` keeps
    slow_log_size: int = 64
    #: consecutive slow batches before the AlarmManager alarm fires
    #: (one slow batch is a blip; a streak is a regime)
    slow_alarm_after: int = 10

    #: live-reloadable knobs (emqx_tpu/reload.py): read per span;
    #: ``enabled``/``ring_size``/``slow_log_size`` shape the
    #: histograms and the slow-record ring at build (not a dataclass
    #: field: unannotated)
    RELOADABLE = frozenset({"slow_threshold_ms", "slow_alarm_after"})


class Histogram:
    """One latency family: fixed log-bucket counts + sum/count for
    the Prometheus exposition, and a bounded ring of raw samples for
    exact recent percentiles. Single-writer (the event loop folds
    finished spans); plain ints/floats, no locks — same discipline as
    the Metrics counter array."""

    __slots__ = ("bounds", "counts", "sum", "count", "ring")

    def __init__(self, ring_size: int = 2048,
                 bounds=BUCKETS_MS) -> None:
        self.bounds = bounds
        self.counts = [0] * len(bounds)  # per-bucket (non-cumulative)
        self.sum = 0.0
        self.count = 0
        self.ring: deque = deque(maxlen=max(1, ring_size))

    def observe(self, ms: float) -> None:
        # linear scan beats bisect at 18 buckets, and the common case
        # (sub-ms stages) exits in the first few probes
        for i, b in enumerate(self.bounds):
            if ms <= b:
                self.counts[i] += 1
                break
        self.sum += ms
        self.count += 1
        self.ring.append(ms)

    def percentile(self, q: float) -> float:
        """Exact percentile over the raw-sample ring (0 when empty)."""
        if not self.ring:
            return 0.0
        xs = sorted(self.ring)
        # nearest-rank on the sorted window — matches numpy's
        # 'lower' interpolation within one sample
        idx = min(len(xs) - 1, int(q / 100.0 * len(xs)))
        return xs[idx]

    def snapshot(self) -> dict:
        """Prometheus-shaped view: CUMULATIVE ``(le, count)`` pairs
        (``+Inf`` is implicit — it equals ``count``), plus sum/count."""
        cum = []
        acc = 0
        for b, c in zip(self.bounds, self.counts):
            acc += c
            cum.append((b, acc))
        return {"buckets": cum, "sum": self.sum, "count": self.count}

    def stats(self) -> dict:
        return {
            "count": self.count,
            "p50_ms": self.percentile(50),
            "p95_ms": self.percentile(95),
            "p99_ms": self.percentile(99),
            "sum_ms": self.sum,
        }

    def reset(self) -> None:
        self.counts = [0] * len(self.bounds)
        self.sum = 0.0
        self.count = 0
        self.ring.clear()


class PublishSpan:
    """Per-batch stage stopwatch + tags. Created by
    :meth:`Telemetry.begin`, carried on ``PendingBatch.span``, closed
    by :meth:`Telemetry.finish` when the last delivery chunk lands.

    Each stage is kept as an interval ``(stage, start, end, thread)``
    in ``ivs`` (``thread`` 0 = the span's home thread, i.e. the loop;
    -1 = a wait, which no thread spends; else the executor thread's
    ident) and summed into ``stages`` as it closes.

    Writers hand off in pipeline order (begin on the event loop,
    fetch possibly on an executor thread, finish back on the loop) —
    the ingress pipeline sequences those with happens-before edges,
    so no stage field is ever written concurrently."""

    __slots__ = ("seq", "t0", "t_mark", "home", "tel", "ivs", "stages",
                 "batch", "n_uniq", "bucket", "path", "cache_hit",
                 "cache_miss", "fallbacks", "inflight", "topic",
                 "closed", "open", "t_enq")

    def __init__(self, batch: int, seq: int = 0, tel=None,
                 t_first: Optional[float] = None,
                 inflight: int = 0) -> None:
        now = _now()
        #: where ``end_to_end`` starts: the first arrival in the
        #: accumulator when the ingress batcher made the span
        self.t0 = now if t_first is None else t_first
        #: end of the last interval on the batch's critical path —
        #: the next wait starts here (:meth:`wait_mark`)
        self.t_mark = now
        #: the clock just before the batch's first device call (the
        #: put of its buffer or its first program): where the batch
        #: enters the device path. 0.0 = it never did (a host batch)
        self.t_enq = 0.0
        self.seq = seq
        self.home = threading.get_ident()
        self.tel = tel
        self.ivs: List[tuple] = []
        self.stages: Dict[str, float] = {}
        self.batch = batch
        self.n_uniq = 0
        self.bucket = 0          # device padding bucket (0 = host)
        self.path = "device"     # device | host | mesh
        self.cache_hit = -1      # -1 = batch wasn't cache-split
        self.cache_miss = -1
        self.fallbacks = 0
        self.inflight = inflight  # pipeline slots busy at the take
        self.topic: Optional[str] = None  # sample (tracer tee)
        self.closed = False
        self.open = None         # the open busy stage (start/stop)
        if t_first is not None:
            self.wait("ingress_wait", t_first, now)

    # -- busy stages ------------------------------------------------------

    def start(self, stage: str) -> None:
        """Open a busy stage on the calling thread: a profiler
        annotation plus the clock. One stage is open at a time: a
        stage still open (``prepare``, or one an exception skipped
        past) closes here, where the next begins."""
        if self.open is not None:
            self.stop()
        ann = TraceAnnotation(_ANN[stage], seq=self.seq)
        ann.__enter__()
        tel = self.tel
        self.open = (stage, ann, tel.inner if tel is not None else 0.0,
                     _now())

    def stop(self) -> None:
        """Close the open stage, on the thread that opened it (no-op
        when none is open)."""
        tok = self.open
        if tok is None:
            return
        t1 = _now()
        self.open = None
        stage, ann, n0, t0 = tok
        ann.__exit__(None, None, None)
        tid = threading.get_ident()
        if tid == self.home:
            tid = 0
            tel = self.tel
            if tel is not None:
                # the loop's exclusive-time ledger: what nested in
                # this stage (a collection) is already counted
                # elsewhere; hand the whole stage up to an enclosing
                # section (a read chunk that flushed a full batch)
                nested = tel.inner - n0
                if nested > 0.0:
                    self.add_ms("gc_inside", nested * 1000.0)
                tel.inner = n0 + (t1 - t0)
        self.ivs.append((stage, t0, t1, tid))
        self.add_ms(stage, (t1 - t0) * 1000.0)
        self.t_mark = t1

    def stop_match(self, router) -> None:
        """Close the match-dispatch stage, splitting out the
        cache-gather share when the router's cache-split path left
        its per-dispatch info (set only while telemetry is enabled —
        see Router._match_dispatch_cached). The share is carved out
        of the interval's tail; the ``emqx/match`` annotation covers
        both."""
        self.stop()
        info = router._last_dispatch
        if info is None:
            return
        router._last_dispatch = None
        self.cache_hit = info["hit"]
        self.cache_miss = info["miss"]
        _stage, t0, t1, tid = self.ivs[-1]
        gather = min((t1 - t0) * 1000.0, info["cache_gather_ms"])
        cut = t1 - gather / 1000.0
        self.ivs[-1] = ("match", t0, cut, tid)
        self.ivs.append(("cache_gather", cut, t1, tid))
        self.add_ms("match", -gather)
        self.add_ms("cache_gather", gather)

    def enqueue(self):
        """The batch's first device call is next (router.py's
        dispatch, inside the ``match`` stage): stamp ``t_enq`` and
        hand back the ``emqx/enqueue`` annotation to hold around the
        call. Any later call of the batch is not its first: no
        stamp, no mark."""
        if self.t_enq:
            return _UNMARKED
        self.t_enq = _now()
        return TraceAnnotation(ENQUEUE_ANN, seq=self.seq)

    # -- waits ------------------------------------------------------------

    def wait(self, stage: str, t_start: float, t_end: float) -> None:
        """Record a wait: an interval no thread spent on the batch."""
        if t_end > t_start:
            self.ivs.append((stage, t_start, t_end, -1))
            self.add_ms(stage, (t_end - t_start) * 1000.0)

    def wait_mark(self, stage: str) -> None:
        """The wait from the end of the batch's last interval to
        now; moves the mark."""
        now = _now()
        self.wait(stage, self.t_mark, now)
        self.t_mark = now

    # -- sums only --------------------------------------------------------

    @staticmethod
    def clock() -> float:
        return _now()

    def add(self, stage: str, t_start: float) -> None:
        """Accumulate ``now - t_start`` into a stage that lies inside
        another stage's interval (``host_fallback`` inside
        ``dispatch``): summed, not an interval of its own."""
        self.add_ms(stage, (_now() - t_start) * 1000.0)

    def add_ms(self, stage: str, ms: float) -> None:
        self.stages[stage] = self.stages.get(stage, 0.0) + ms

    def covered_s(self) -> float:
        """Seconds covered by the union of the span's intervals."""
        return union_s((iv[1], iv[2]) for iv in self.ivs)

    def device_path(self) -> Optional[tuple]:
        """``(t_enq, end of the fetch stage)``: the stretch this
        batch held the device path, or None for a batch that never
        entered it or never came back through a fetch (a host batch;
        a dispatch that failed over to the host oracle)."""
        t_enq = self.t_enq
        if t_enq:
            for iv in self.ivs:
                if iv[0] == "fetch":
                    return (t_enq, iv[2]) if iv[2] > t_enq else None
        return None

    def record(self) -> dict:
        """The structured form (slow log / ctl telemetry slow)."""
        t0 = self.t0
        rec = {
            "seq": self.seq,
            "batch": self.batch,
            "n_uniq": self.n_uniq,
            "path": self.path,
            "bucket": self.bucket,
            "fallbacks": self.fallbacks,
            "inflight": self.inflight,
            "stages_ms": {k: round(v, 3)
                          for k, v in self.stages.items()},
            # [stage, start (ms after the span's t0), length (ms),
            # where]
            "intervals": [
                [st, round((a - t0) * 1000.0, 3),
                 round((b - a) * 1000.0, 3),
                 "loop" if tid == 0 else
                 "wait" if tid == -1 else "executor"]
                for st, a, b, tid in self.ivs],
        }
        if self.t_enq:
            # the first device call, ms after the span's t0
            rec["t_enq"] = round((self.t_enq - t0) * 1000.0, 3)
        if self.cache_hit >= 0:
            rec["cache_hit"] = self.cache_hit
            rec["cache_miss"] = self.cache_miss
        if self.topic is not None:
            rec["topic"] = self.topic
        return rec


class Telemetry:
    """Per-node telemetry registry (wired by Node onto broker +
    router + sys/ctl). Histogram folds and the slow ring are
    single-writer — finished spans land on the event loop, the same
    place the Metrics counters mutate."""

    def __init__(self, config: Optional[TelemetryConfig] = None,
                 tracer=None, alarms=None,
                 node: str = "local", metrics=None) -> None:
        self.config = config or TelemetryConfig()
        self.tracer = tracer
        self.alarms = alarms
        self.node = node
        #: where the loop counters live (``loop.*``, ``gc.*``); None
        #: (a bare Telemetry in a unit test) = spans only
        self.metrics = metrics
        self.hists: Dict[str, Histogram] = {
            s: Histogram(self.config.ring_size) for s in STAGES}
        for s in REBUILD_STAGES:
            self.hists["rebuild." + s] = Histogram(self.config.ring_size)
        self.spans_total = 0
        self.slow_total = 0
        self._seq = 0
        self._slow_streak = 0
        self._slow_ring: deque = deque(
            maxlen=max(1, self.config.slow_log_size))
        self._stall_ring: deque = deque(
            maxlen=max(1, self.config.slow_log_size))
        #: the loop's exclusive-time ledger: running seconds already
        #: attributed to an INNER section. Every timed section on the
        #: loop (span stage, read chunk, flush wake-up, collection)
        #: reads it on entry (n0) and on exit takes ``dt − (inner −
        #: n0)`` as its own, then sets ``inner = n0 + dt``
        self.inner = 0.0
        #: running seconds of garbage collection (all generations)
        self.gc_s = 0.0
        #: where the union of the device path's occupied stretches
        #: ends so far (``pipeline.device.ns``, :meth:`finish`)
        self._path_end = 0.0
        #: automaton rebuilds running now / when the last one ended
        self.rebuilding = 0
        self.rebuild_end = 0.0

    @property
    def enabled(self) -> bool:
        return self.config.enabled

    # -- span lifecycle ---------------------------------------------------

    def begin(self, batch: int, t_first: Optional[float] = None,
              inflight: int = 0) -> Optional[PublishSpan]:
        """A new span, or None when disabled (the broker stores the
        None and every instrumented section reduces to one ``is not
        None`` branch — the near-zero disabled cost). ``t_first`` is
        the batch's first arrival in the ingress accumulator."""
        if not self.config.enabled:
            return None
        self._seq += 1
        return PublishSpan(batch, self._seq, self, t_first, inflight)

    def finish(self, span: PublishSpan) -> None:
        """Fold a finished span into the stage histograms; slow-log /
        alarm on threshold breach. Idempotent (the chunked delivery
        tail and the one-shot finish can both reach the end)."""
        if span.closed:
            return
        span.closed = True
        e2e = (_now() - span.t0) * 1000.0
        span.stages["unattributed"] = max(
            0.0, e2e - span.covered_s() * 1000.0)
        span.stages["end_to_end"] = e2e
        for stage, ms in span.stages.items():
            h = self.hists.get(stage)
            if h is not None:
                h.observe(ms)
        self.spans_total += 1
        path = span.device_path()
        if path is not None and self.metrics is not None:
            # the device path's occupancy: batches finish in the order
            # they began, so enqueue instants only rise and one end
            # mark keeps the union of [t_enq, fetch returned]
            t_enq, t_end = path
            m = self.metrics
            m.add_at(I_PIPELINE_NS + 1, int((t_end - t_enq) * 1e9))
            end = self._path_end
            if t_end > end:
                m.add_at(I_PIPELINE_NS,
                         int((t_end - max(t_enq, end)) * 1e9))
                self._path_end = t_end
        if e2e >= self.config.slow_threshold_ms:
            self._slow(span, e2e)
        else:
            self._slow_streak = 0
            if self.alarms is not None:
                self.alarms.deactivate("slow_publish")

    def _slow(self, span: PublishSpan, e2e: float) -> None:
        self.slow_total += 1
        self._slow_streak += 1
        rec = span.record()
        rec["end_to_end_ms"] = round(e2e, 3)
        rec["ts"] = time.time()
        self._slow_ring.append(rec)
        # ONE structured line per slow batch — a saturated broker must
        # not drown its own logs, and the ring keeps the rest
        log.warning("slow publish batch: %s", json.dumps(rec))
        if self.tracer is not None:
            self.tracer.trace_slow_publish(rec)
        if (self.alarms is not None
                and self._slow_streak >= self.config.slow_alarm_after):
            self.alarms.activate(
                "slow_publish",
                details={"streak": self._slow_streak,
                         "threshold_ms": self.config.slow_threshold_ms,
                         "last": rec},
                message=(f"publish end-to-end latency over "
                         f"{self.config.slow_threshold_ms}ms for "
                         f"{self._slow_streak} consecutive batches"))

    def observe_stage(self, stage: str, ms: float) -> None:
        """Record one direct (non-span) stage sample — the rebuild
        histogram's entry point. Unlike span folds this may be called
        from the background compaction thread, so it takes a small
        lock (rebuilds are rare and ms-scale; the cost is noise)."""
        if not self.config.enabled:
            return
        h = self.hists.get(stage)
        if h is None:
            return
        with _observe_lock:
            h.observe(ms)

    # -- the loop outside publish batches ---------------------------------

    def loop_leave(self, idx: int, t0: float, n0: float,
                   wait_s: float = -1.0) -> int:
        """Close a timed section of the loop (a read chunk, a flush
        wake-up) opened with ``t0 = clock(); n0 = tel.inner``: its
        exclusive nanoseconds go to the counter at ``idx`` (and back
        to the caller), one call to ``idx + 1``, and ``wait_s`` (when
        given) to ``idx + 2``."""
        dt = _now() - t0
        own = int((dt - (self.inner - n0)) * 1e9)
        self.inner = n0 + dt
        m = self.metrics
        lock = m._lock
        if lock is None:
            c = m._counters
            c[idx] += own
            c[idx + 1] += 1
            if wait_s >= 0.0:
                c[idx + 2] += int(wait_s * 1e9)
        else:
            with lock:
                c = m._counters
                c[idx] += own
                c[idx + 1] += 1
                if wait_s >= 0.0:
                    c[idx + 2] += int(wait_s * 1e9)
        return own

    def select_leave(self, kind: int, t0: float, n0: float) -> None:
        """Close one call of the loop's selector
        (monitors.SysMon's shadow): :meth:`loop_leave` into
        ``loop.select.ns`` / ``.calls``, and the same exclusive
        nanoseconds into the counter at ``kind`` (what the loop was
        waiting for: ``loop.select.poll.ns`` / ``.device.ns`` /
        ``.clients.ns``; -1 = none of them)."""
        ns = self.loop_leave(I_SELECT_NS, t0, n0)
        if kind >= 0:
            self.metrics.add_at(kind, ns)

    def loop_clock(self) -> Optional["Telemetry"]:
        """``self`` when the loop counters are live (enabled, and a
        Metrics to count into), else None: callers cache the answer
        and branch on it once per section."""
        if self.config.enabled and self.metrics is not None:
            return self
        return None

    def gc_done(self, gen: int, seconds: float) -> None:
        """One finished collection (monitors.SysMon's gc hook, any
        thread — the collector holds the GIL, so the loop stood still
        for it whichever thread ran it)."""
        self.inner += seconds
        self.gc_s += seconds
        m = self.metrics
        if m is not None:
            i = I_GC_NS + 2 * min(gen, 2)
            m.add_at(i, int(seconds * 1e9))
            m.add_at(i + 1, 1)

    def rebuild_begin(self) -> TraceAnnotation:
        """An automaton rebuild starts (router, any thread): the
        annotation to close with :meth:`rebuild_done`."""
        self.rebuilding += 1
        ann = TraceAnnotation("emqx/rebuild")
        ann.__enter__()
        return ann

    def rebuild_done(self, ann: TraceAnnotation) -> None:
        ann.__exit__(None, None, None)
        self.rebuilding -= 1
        self.rebuild_end = _now()

    def note_stall(self, rec: dict) -> None:
        """One loop stall (monitors.SysMon's heartbeat, on the loop
        once it is back): ring + counters."""
        self._stall_ring.append(rec)
        m = self.metrics
        if m is not None:
            m.inc("loop.stalls")
            m.inc("loop.stall.ns", int(rec["ms"] * 1e6))

    # -- read surfaces ----------------------------------------------------

    def stage_stats(self) -> Dict[str, dict]:
        """Per-stage count/p50/p95/p99 from the sample rings — the
        ctl table and the $SYS heartbeat both read this."""
        return {s: self.hists[s].stats() for s in STAGES}

    def rebuild_stats(self) -> Dict[str, dict]:
        """A background compaction by stage (REBUILD_STAGES)."""
        return {s: self.hists["rebuild." + s].stats()
                for s in REBUILD_STAGES}

    def histograms(self) -> Dict[str, dict]:
        """Prometheus families: ``emqx_tpu_publish_stage_<stage>_ms``
        → cumulative-bucket snapshots (modules/prometheus.render)."""
        return {f"emqx_tpu_publish_stage_{s}_ms": self.hists[s].snapshot()
                for s in STAGES}

    def slow_records(self) -> List[dict]:
        """The last-N slow batches, oldest first."""
        return list(self._slow_ring)

    def stall_records(self) -> List[dict]:
        """The last-N loop stalls, oldest first."""
        return list(self._stall_ring)

    def reset(self) -> None:
        for h in self.hists.values():
            h.reset()
        self.spans_total = 0
        self.slow_total = 0
        self._slow_streak = 0
        self._slow_ring.clear()
        self._stall_ring.clear()
