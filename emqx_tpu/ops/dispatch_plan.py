"""Batch dispatch planner: subscriber-grouped delivery tail.

The packed device results (CSR subscriber slots + bitmap union rows,
ops/pack.py) used to be walked one ``(filter, subscriber)`` pair at a
time through ``Broker._route_packed`` → ``_deliver_one`` →
``Session.deliver`` — one registry lookup, one subopts dict fetch and
one notify wakeup **per delivery**. At live fan-outs that Python walk
is the whole publish tail; the
reference's own hot loop 2 is the same walk (``emqx_broker:dispatch/2``,
src/emqx_broker.erl:283-309), and its ``emqx_batch.erl``
accumulate-then-flush idea applies to the tail as much as to ingress.

This module builds the whole batch's delivery plan with numpy on the
**already-fetched** packed arrays — no broker state, no device work —
so :meth:`~emqx_tpu.broker.Broker.publish_fetch` can run it on the
ingress executor thread:

  1. expand the CSR slices ``(f_ptr, subs_packed, src_packed)`` per
     live message (vectorized repeat/arange arithmetic, one scatter);
  2. append the bitmap-path deliveries (union-row set bits, attributed
     to their matched big filters);
  3. stable-argsort the ``(sub_id, fid, row)`` triples **by
     subscriber** and cut group boundaries.

Stability is the correctness keystone: triples are laid out in the
legacy walk order (row-major; CSR slots then bitmap bits within a
row), so after the stable sort every subscriber's deliveries are in
exactly the order the per-delivery walk would have produced — the
grouped enqueue is a permutation **across** subscribers only, which no
connection can observe. The broker then resolves each subscriber's
session once per batch, hands it its whole group in one
``deliver_many`` call, and fires one notify wakeup per connection per
batch.

A batch with any match/bitmap capacity overflow row plans as ``None``
and takes the legacy per-delivery path unchanged (overflow rows host-
re-match mid-walk; interleaving that with grouped delivery would
reorder a subscriber's stream). Overflow self-corrects via boost_k /
pack-budget growth, so steady state always plans.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from emqx_tpu.broker_helper import unpack_sids
from emqx_tpu.mqtt.constants import MQTT_V5
from emqx_tpu.mqtt.frame import WireBlob, publish_template
from emqx_tpu.mqtt.frame import serialize as wire_serialize
from emqx_tpu.mqtt.packet import Publish, from_message


class DispatchPlan:
    """One batch's subscriber-grouped delivery order.

    Per-delivery sequences (all length ``n_deliveries``, sorted so
    each subscriber's deliveries are contiguous and in legacy walk
    order). The grouping math is numpy; the stored fields are plain
    Python lists because the delivery loop consumes them one element
    at a time, and list indexing + int dict hashing beat numpy
    scalar access several-fold there:

      - ``fids``  matched filter id (automaton snapshot id)
      - ``rows``  live-row index into ``PendingBatch.live``

    Groups: ``g_ptr[g]:g_ptr[g+1]`` slices group ``g``; ``g_sids[g]``
    is its subscriber id. ``n_groups`` is the chunking unit the
    ingress yields between (one group = one session's whole batch).

    ``g_runs`` (set by :func:`preserialize_plan`, else ``None``):
    per group its wire runs as ``(a, b, run)`` segments — deliveries
    ``a:b`` of the group's slice are :class:`WireRun` ``run``'s frames,
    one pre-joined write — or ``None`` where the group forms none.
    """

    __slots__ = ("fids", "rows", "g_ptr", "g_sids", "n_deliveries",
                 "g_runs")

    def __init__(self, sids: np.ndarray, fids: np.ndarray,
                 rows: np.ndarray) -> None:
        self.g_runs: Optional[List[Optional[tuple]]] = None
        self.n_deliveries = int(sids.shape[0])
        if self.n_deliveries:
            order = np.argsort(sids, kind="stable")
            sids = sids[order]
            self.fids = fids[order].tolist()
            self.rows = rows[order].tolist()
            cuts = np.flatnonzero(sids[1:] != sids[:-1]) + 1
            self.g_ptr = np.concatenate(
                ([0], cuts, [self.n_deliveries])).tolist()
            self.g_sids = sids[np.concatenate(([0], cuts))].tolist()
        else:
            self.fids = self.rows = []
            self.g_ptr = [0]
            self.g_sids = []

    @property
    def n_groups(self) -> int:
        return len(self.g_sids)


#: ftab memo sentinel — a filter whose subscriber table resolved to
#: None must not be re-resolved per delivery
_NO_FTAB = object()

#: a group forms a wire run from this many frames up (a single frame
#: is one write already)
MIN_RUN_FRAMES = 2


def _broadcast_image(msg, wire: dict, key: tuple) -> bytes:
    """Build and cache one shared QoS0 wire image of ``msg`` under
    ``key`` = ``(proto_ver, 0, retain, dup)`` in its ``_wire`` dict —
    the bytes ``Channel._wire_cached`` would build lazily on the
    loop."""
    ver = key[0]
    pub = from_message(None, msg)
    pub.qos = 0
    pub.retain = key[2]
    if ver != MQTT_V5:
        pub.properties = {}
    data = wire[key] = wire_serialize(pub, ver)
    return data


def run_eligible(msg) -> bool:
    """The message half of the wire-run predicate: a QoS0,
    non-retained message whose shared image every plain subscriber
    can take as it is — no Message-Expiry countdown, no
    Subscription-Identifier, and not traced (the egress-flush span
    is stamped per frame)."""
    if msg.qos != 0 or msg.flags.get("retain"):
        return False
    headers = msg.headers
    if "_trace" in headers:
        return False
    props = headers.get("properties")
    return not (props and ("Message-Expiry-Interval" in props
                           or "Subscription-Identifier" in props))


class WireRun:
    """A stretch of one subscriber group's batch on the QoS0
    broadcast fast path — the whole batch, where nothing interrupts
    it — as ONE outbox entry and ONE transport write (docs/
    DISPATCH.md "Wire runs"): the live messages in delivery order,
    and per protocol version their shared wire images joined into one
    :class:`~emqx_tpu.mqtt.frame.WireBlob`. Groups whose slices of the
    plan's ``rows`` hold an equal stretch receive the same frames in
    the same order, so they share one run object and one join per
    version — 1,000 of them in a broadcast.

    Immutable once :func:`preserialize_plan` returns, except the
    blob cache: a version nobody hinted joins lazily where it is
    first flushed (possibly on two loops at once — both build the
    same bytes, the last store wins)."""

    __slots__ = ("msgs", "n", "_blobs")

    def __init__(self, msgs: tuple) -> None:
        self.msgs = msgs
        self.n = len(msgs)
        self._blobs: Dict[int, WireBlob] = {}

    def joined(self, ver: int) -> Optional[WireBlob]:
        return self._blobs.get(ver)

    def join(self, ver: int) -> Tuple[WireBlob, int]:
        """Join (and cache) the run's image for ``ver``. Returns
        ``(blob, built)`` — ``built`` counts the frames that had no
        shared image yet and were serialized here."""
        frames = []
        built = 0
        for msg in self.msgs:
            headers = msg.headers
            wire = headers.get("_wire")
            if wire is None:
                wire = headers["_wire"] = {}
            key = (ver, 0, False, msg.flags.get("dup", False))
            data = wire.get(key)
            if data is None:
                data = _broadcast_image(msg, wire, key)
                built += 1
            frames.append(data)
        blob = self._blobs[ver] = WireBlob(frames)
        return blob, built


def _run_segments(rkey: tuple, live, ok: bytearray,
                  runs: Dict[tuple, WireRun]) -> tuple:
    """Cut one group's slice of the plan's rows into its wire runs:
    the maximal stretches of :func:`run_eligible` messages,
    ``MIN_RUN_FRAMES`` long or more, as ``(a, b, run)`` offsets into
    the slice. ``ok`` memoizes the predicate per live row (0 unknown,
    1 eligible, 2 not); ``runs`` shares a run between slices that hold
    the same stretch."""
    segs = []
    a = -1
    n = len(rkey)
    for i in range(n + 1):
        e = 2
        if i < n:
            r = rkey[i]
            e = ok[r]
            if not e:
                e = ok[r] = 1 if run_eligible(live[r][1]) else 2
        if e == 1:
            if a < 0:
                a = i
        elif a >= 0:
            if i - a >= MIN_RUN_FRAMES:
                key = rkey[a:i]
                run = runs.get(key)
                if run is None:
                    run = runs[key] = WireRun(
                        tuple([live[r][1] for r in key]))
                segs.append((a, i, run))
            a = -1
    return tuple(segs)


def preserialize_plan(plan: "DispatchPlan",
                      live: Sequence[Tuple[int, object]],
                      id_map: Sequence[Optional[str]],
                      subscribers: Dict[str, dict],
                      lookup) -> int:
    """Egress pre-serialization: collect the plan's distinct
    subscriber-filter classes, then prime each live message's wire
    caches BEFORE the finish tail runs
    (docs/DISPATCH.md "Egress pre-serialization"):

      - QoS0 broadcast deliveries share one serialized frame per
        (proto_ver, flags variant) through the message's ``_wire``
        dict — built here instead of lazily on-loop by
        ``Channel._wire_cached``;
      - QoS1/2 deliveries get a packet-id-placeholder template per
        (proto_ver, effective qos, retain, dup) in ``_wiretpl``
        (:func:`~emqx_tpu.mqtt.frame.publish_template`): the pid is
        always 2 bytes at a fixed offset, so the loop-side tail is a
        ``bytearray`` copy + 2-byte patch per subscriber.

    Per-session rewrites the template cannot carry — shared-group
    redispatch state, Subscription-Identifier, the Message-Expiry
    countdown — are detected here and skipped; those deliveries take
    the existing per-delivery serialize path unchanged.

    The same walk builds the plan's wire runs (``plan.g_runs``): a
    hinted session's group is cut into its maximal stretches of
    :func:`run_eligible` messages (:func:`_run_segments`), each a
    :class:`WireRun` shared with every group that holds the same
    stretch and joined once per hinted protocol version. Whether a
    group may USE a run is the delivery walk's call (every delivery
    of the group accepted, every one of the stretch ``fast``:
    ``Broker._deliver_plan_group``).

    Runs wherever :meth:`~emqx_tpu.broker.Broker.publish_fetch` runs
    (possibly an ingress executor thread): every broker read is a
    plain dict get (GIL-atomic, same discipline as the plan build's
    member snapshot), the session hints (``proto_ver`` /
    ``wire_fast_hint``) are stamped once at CONNECT, and the primed
    caches are best-effort — a variant the finish tail needs but
    doesn't find simply builds on-loop (counted by
    ``delivery.serialize.onloop``). Returns the number of frames
    built."""
    # Pass 1 — subscriber-filter CLASSES. The wire variant a delivery
    # needs is fully determined by (proto_ver, upgrade_qos, granted
    # qos, rap) plus the message's own flags, so instead of walking
    # every (subscriber, delivery) pair — O(deliveries) Python work
    # per batch — collect the distinct classes over the plan's
    # (group, fid) pairs and build per (class, message) in pass 2.
    # Variants dedupe by cache key, so a class that happens not to
    # touch a message over-builds a frame at worst (harmless); every
    # ACTUAL delivery's variant is covered. The delivery walk itself
    # shrinks to a fid-change probe per slot.
    classes: Dict[tuple, None] = {}
    g_ptr = plan.g_ptr
    fids = plan.fids
    rows = plan.rows
    ftab_of: Dict[int, object] = {}
    # the run table: a group's slice of ``rows`` -> [its segments,
    # the protocol versions the slice's sessions hint]; equal slices
    # (1,000 of them in a broadcast) share one entry
    slices: Dict[tuple, list] = {}
    runs: Dict[tuple, WireRun] = {}
    row_ok = bytearray(len(live))
    g_runs: List[Optional[tuple]] = [None] * plan.n_groups
    for g in range(plan.n_groups):
        sub = lookup(plan.g_sids[g])
        if sub is None:
            continue
        ver = getattr(sub, "proto_ver", None)
        if ver is None or not getattr(sub, "wire_fast_hint", False):
            continue
        if g_ptr[g + 1] - g_ptr[g] >= MIN_RUN_FRAMES:
            rkey = tuple(rows[g_ptr[g]:g_ptr[g + 1]])
            ent = slices.get(rkey)
            if ent is None:
                ent = slices[rkey] = [
                    _run_segments(rkey, live, row_ok, runs), {}]
            if ent[0]:
                g_runs[g] = ent[0]
                ent[1][ver] = None
        upgrade = getattr(sub, "upgrade_qos", False)
        last_fid = -1          # within a group the same fid repeats
        seen: Optional[set] = None   # row-major — catch runs cheaply
        for k in range(g_ptr[g], g_ptr[g + 1]):
            fid = fids[k]
            if fid == last_fid:
                continue
            last_fid = fid
            if seen is None:
                seen = set()
            elif fid in seen:
                continue
            seen.add(fid)
            ftab = ftab_of.get(fid)
            if ftab is None:
                flt = id_map[fid]
                ftab = (subscribers.get(flt) or _NO_FTAB) \
                    if flt is not None else _NO_FTAB
                ftab_of[fid] = ftab
            opts = ftab.get(sub) if ftab is not _NO_FTAB else None
            if opts is None or opts.share is not None \
                    or opts.subid is not None:
                continue  # per-session rewrites: slow path
            classes[(ver, upgrade, opts.qos, opts.rap)] = None
    if not classes:
        return 0
    # Pass 2 — build per (class, live message): O(classes × batch)
    # serializes, each shared by every subscriber of that variant.
    built = 0
    class_list = list(classes)
    for _i, msg in live:
        headers = msg.headers
        props = headers.get("properties")
        if props and ("Message-Expiry-Interval" in props
                      or "Subscription-Identifier" in props):
            continue  # per-delivery countdown / per-session subid
        flags = msg.flags
        mqos = msg.qos
        retain = flags.get("retain", False)
        dup = flags.get("dup", False)
        retained = bool(headers.get("retained"))
        wire = tpl = None
        for ver, upgrade, oqos, rap in class_list:
            qos = max(oqos, mqos) if upgrade else min(oqos, mqos)
            if qos == 0:
                if mqos == 0 and not retain:
                    # broadcast fast path: the ORIGINAL message is
                    # shared, its own flags key the image
                    key = (ver, 0, retain, dup)
                else:
                    # downgraded-to-QoS0 enriched copy: _enrich
                    # clears retain unless rap/retained; the qos-in-
                    # key rule keeps it apart from any QoS>0 frame
                    key = (ver, 0,
                           retain and bool(rap or retained), dup)
                if wire is None:
                    wire = headers.get("_wire")
                    if wire is None:
                        wire = headers["_wire"] = {}
                if key not in wire:
                    _broadcast_image(msg, wire, key)
                    built += 1
                continue
            key = (ver, qos,
                   retain and bool(rap or retained), dup)
            if tpl is None:
                tpl = headers.get("_wiretpl")
                if tpl is None:
                    tpl = headers["_wiretpl"] = {}
            if key not in tpl:
                pub = Publish(
                    dup=dup, qos=qos, retain=key[2], topic=msg.topic,
                    packet_id=0,
                    properties=dict(props)
                    if (ver == MQTT_V5 and props) else {},
                    payload=msg.payload)
                tpl[key] = publish_template(pub, ver)
                built += 1
    # the runs' joins, once per (run, hinted version), over the
    # images pass 2 just built
    for segs, vers in slices.values():
        for _a, _b, run in segs:
            for ver in vers:
                if run.joined(ver) is None:
                    built += run.join(ver)[1]
    if runs:
        plan.g_runs = g_runs
    return built


def big_rows_for(ids_packed: Sequence[int], m_ptr: np.ndarray,
                 sel: np.ndarray, rows_packed: np.ndarray,
                 urows: Sequence[int], big_set: frozenset,
                 members_of) -> Dict[int, List[Tuple[int, np.ndarray]]]:
    """Per-unique-row bitmap deliveries: ``urow -> [(fid, sids)]``.

    ``members_of(fid) -> sorted int64 array`` attributes a union
    row's set bits when several big filters matched the same topic
    (the union OR'd their rows together); with a single matched big
    filter every set bit is its delivery, no membership test — the
    exact split ``Broker._deliver_big`` makes per message, hoisted to
    once per unique topic."""
    out: Dict[int, List[Tuple[int, np.ndarray]]] = {}
    if sel is None or not big_set:
        return out
    for urow in urows:
        if sel[urow] < 0:
            continue
        row_ids = ids_packed[m_ptr[urow]:m_ptr[urow + 1]]
        matched = [j for j in row_ids if j in big_set]
        if not matched:
            continue
        sids = unpack_sids(rows_packed[sel[urow]]).astype(np.int64)
        if len(matched) == 1:
            out[urow] = [(matched[0], sids)]
            continue
        parts: List[Tuple[int, np.ndarray]] = []
        for fid in matched:
            members = members_of(fid)
            parts.append((fid, sids[np.isin(sids, members,
                                            assume_unique=True)]))
        out[urow] = parts
    return out


def build_plan(inv: Sequence[int], n_uniq: int,
               ovf: np.ndarray, bovf: Optional[np.ndarray],
               f_ptr: Optional[np.ndarray],
               subs_packed: Optional[np.ndarray],
               src_packed: Optional[np.ndarray],
               big_by_urow: Dict[int, List[Tuple[int, np.ndarray]]],
               ) -> Optional[DispatchPlan]:
    """The numpy grouping pass. ``None`` = batch not plannable (a
    capacity-overflow row needs the legacy mid-walk host fallback).

    ``inv`` maps live rows to unique-topic rows; ``ovf``/``bovf`` are
    the fetched per-unique-row overflow flags; the CSR triple comes
    straight from the fetched pack (numpy, NOT the legacy ``tolist``
    copies); ``big_by_urow`` from :func:`big_rows_for`.
    """
    n_live = len(inv)
    if n_uniq and bool(ovf[:n_uniq].any()):
        return None
    if bovf is not None and n_uniq and bool(bovf[:n_uniq].any()):
        return None
    u = np.asarray(inv, dtype=np.int64)
    if f_ptr is not None:
        fp = np.asarray(f_ptr, dtype=np.int64)
        start = fp[u]
        cnt = fp[u + 1] - start
    else:
        start = cnt = np.zeros(n_live, np.int64)
    bm_cnt = np.zeros(n_live, np.int64)
    if big_by_urow:
        totals = {urow: sum(len(s) for _, s in parts)
                  for urow, parts in big_by_urow.items()}
        for r, urow in enumerate(inv):
            t = totals.get(urow)
            if t:
                bm_cnt[r] = t
    row_tot = cnt + bm_cnt
    out_ptr = np.concatenate(([0], np.cumsum(row_tot)))
    total = int(out_ptr[-1])
    sids = np.empty(total, np.int64)
    fids = np.empty(total, np.int64)
    rows = np.empty(total, np.int64)
    n_csr = int(cnt.sum())
    if n_csr:
        cum = np.concatenate(([0], np.cumsum(cnt)))
        ar = np.arange(n_csr)
        intra = ar - np.repeat(cum[:-1], cnt)
        src_idx = intra + np.repeat(start, cnt)
        dst = intra + np.repeat(out_ptr[:-1], cnt)
        sids[dst] = np.asarray(subs_packed, np.int64)[src_idx]
        fids[dst] = np.asarray(src_packed, np.int64)[src_idx]
        rows[dst] = np.repeat(np.arange(n_live), cnt)
    if big_by_urow:
        for r, urow in enumerate(inv):
            parts = big_by_urow.get(urow)
            if not parts:
                continue
            off = int(out_ptr[r] + cnt[r])
            for fid, part in parts:
                n = len(part)
                sids[off:off + n] = part
                fids[off:off + n] = fid
                rows[off:off + n] = r
                off += n
    return DispatchPlan(sids, fids, rows)
