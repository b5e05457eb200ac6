"""Sharded automaton + the collective publish step.

Multi-chip design (replaces the reference's replicated-Mnesia reads +
gen_rpc forwarding, SURVEY §2.3):

  - the filter set is partitioned round-robin into T *trie shards*;
    each shard is flattened into its own CSR automaton whose tables
    carry GLOBAL filter ids, padded to common capacities and stacked
    along a leading shard axis sharded over the mesh's ``trie`` axis;
  - the publish batch is sharded over the ``data`` axis and
    *replicated* over ``trie`` (every trie shard sees every topic in
    its data slice);
  - inside ``shard_map`` each chip matches its batch slice against its
    automaton shard, then match ids are all-gathered over ``trie``
    (ICI collective — the analogue of aggre/forward,
    src/emqx_broker.erl:243-281) giving every data shard its full
    route set;
  - per-batch counters are ``psum``-reduced over the whole mesh (the
    metrics fold, src/emqx_metrics.erl:230-271).

The walk is identical to the single-chip kernel — sharding composes
around :func:`emqx_tpu.ops.match.match_batch`.
"""

from __future__ import annotations

import functools
import zlib
from typing import Dict, List, NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from emqx_tpu.oracle import TrieOracle
from emqx_tpu.ops.csr import Automaton, build_automaton, capacity_for
from emqx_tpu.ops.match import match_batch
from emqx_tpu.ops.fanout import (FanoutTable, build_fanout,
                                 gather_subscribers_src)
from emqx_tpu.ops.tokenize import WordTable


def _shard_map(fn, mesh, in_specs, out_specs):
    """``jax.shard_map`` with replication checking off — the walk's
    scan carries start replicated and become varying."""
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


class ShardedAutomaton(NamedTuple):
    """T stacked walk tables; leading axis is the trie-shard axis.

    Only the fields the compiled walk reads are stacked (the CSR
    flatten artifacts stay host-side with the per-shard patchers).
    All shards share the bucket count, state capacity, slot layout
    and step bound — the shard_map program is one compiled walk."""

    wt: jax.Array        # int32[T, NB, slots*SW]
    wt_seed: jax.Array   # uint32[T, 1]
    node2: jax.Array     # int32[T, S2_cap, 4]


class ShardedFanout(NamedTuple):
    row_ptr: jax.Array  # [T, F_cap+1] — filter-id -> local sub rows
    sub_ids: jax.Array  # [T, N_cap]
    row_pairs: jax.Array | None = None  # [T, F_cap, 2] packed pairs


class ShardedBitmaps(NamedTuple):
    """Per-trie-shard subscriber bitmaps for big (> d) filters: a
    filter's bitmap row lives in ITS shard (same stable assignment as
    the automaton), so HBM for huge subscriber sets scales with the
    mesh instead of replicating (BASELINE config 5 at multi-chip)."""

    bitmaps: jax.Array  # uint32[T, R_cap, W]
    big_row: jax.Array  # int32[T, F_cap] — global fid -> local row | -1


def build_sharded_bitmaps(
    rows_per_shard: Sequence[Dict[int, Sequence[int]]],
    num_filters: int,
    n_subs: int,
    row_capacity: int | None = None,
) -> ShardedBitmaps:
    from emqx_tpu.ops.bitmap import build_bitmaps

    r_cap = max(1, max(len(r) for r in rows_per_shard))
    if row_capacity is not None:
        r_cap = max(r_cap, row_capacity)
    tables = [build_bitmaps(rows, num_filters, n_subs,
                            row_capacity=r_cap)
              for rows in rows_per_shard]
    return ShardedBitmaps(
        bitmaps=np.stack([t.bitmaps for t in tables]),
        big_row=np.stack([t.big_row for t in tables]))


def shard_of(filter_: str, n_shards: int) -> int:
    """STABLE filter→shard assignment (crc32 + avalanche finalizer,
    not Python's salted hash): a filter keeps its shard across route
    churn and across processes, so a mutation touches exactly one
    shard's automaton — the precondition for per-shard O(delta)
    patching (round-robin over the sorted set would reshuffle every
    assignment on insert). The murmur-style finalizer matters: CRC32
    is LINEAR, so near-identical filter names (``a/x`` vs ``a/+``)
    keep correlated low bits and ``crc % 2^k`` collapses structured
    name families into one shard."""
    h = zlib.crc32(filter_.encode("utf-8"))
    h ^= h >> 16
    h = (h * 0x7FEB352D) & 0xFFFFFFFF
    h ^= h >> 15
    h = (h * 0x846CA68B) & 0xFFFFFFFF
    h ^= h >> 16
    return h % n_shards


def shard_filters(filters: Sequence[str], n_shards: int) -> List[List[str]]:
    """Partition by :func:`shard_of` (uniform in expectation; stable
    under mutation)."""
    shards: List[List[str]] = [[] for _ in range(n_shards)]
    for f in filters:
        shards[shard_of(f, n_shards)].append(f)
    return shards


def finalize_parts(
    autos: Sequence[Automaton],
    state_capacity: int | None = None,
    n_buckets: int | None = None,
) -> List[Automaton]:
    """Compress + pack a list of per-shard flattened automatons with
    SHARED shapes (state capacity, bucket count, slot layout, step
    bound): the stacked shard_map program is one compiled walk, so
    every shard must agree on every static. Mode is voted — if any
    shard's trie is deep enough to want wide rows, all shards use
    them (wide is correct for shallow tries, just wider gathers)."""
    from emqx_tpu.ops.csr import (attach_walk_tables,
                                  buckets_for_capacity, capacity_for,
                                  compress_automaton)

    comp = [compress_automaton(a) for a in autos]
    if len({c[0].wt_slots for c in comp}) > 1:
        comp = [compress_automaton(a, force_mode="wide") for a in autos]
        if len({c[0].wt_slots for c in comp}) > 1:
            # a shard hit compress_automaton's wide-mode fallback
            # guard (packed-lane capacity: states ≥ 2^26 or depth >
            # 31) and stayed narrow despite the force — mixed row
            # widths would crash the np.stack below, so demote EVERY
            # shard to narrow (correct for any trie, just unskipped)
            comp = [compress_automaton(a, force_mode="narrow")
                    for a in autos]
    assert len({c[0].wt_slots for c in comp}) == 1, \
        "per-shard walk tables must agree on slot layout"
    s2_cap = max(c[0].node2.shape[0] for c in comp)
    if state_capacity is not None:
        s2_cap = max(s2_cap, state_capacity)
    e2_cap = capacity_for(max(len(c[1].src) for c in comp) + 1)
    slots = comp[0][0].wt_slots
    nb = buckets_for_capacity(e2_cap, slots)
    if n_buckets is not None:
        nb = max(nb, n_buckets)
    # one merged step bound: the stacked walk runs every shard for the
    # max hop depth (per-shard patchers keep accounting on the merged
    # array so a deep patch on one shard grows the shared bound)
    hlen = max(len(c[0].hops_for_level) for c in comp)
    merged = np.zeros(hlen, np.int32)
    for a, _ in comp:
        hl = a.hops_for_level
        ext = np.concatenate(
            [hl, np.minimum(int(hl[-1]) + np.arange(1, hlen - len(hl) + 1),
                            np.arange(len(hl), hlen) + 1)]) \
            if len(hl) < hlen else hl
        merged = np.maximum(merged, ext.astype(np.int32))
    parts = []
    for a, edges in comp:
        a = _pad_v2(a, s2_cap)
        a = a._replace(hops_for_level=merged.copy())
        parts.append(attach_walk_tables(a, edges, n_buckets=nb))
    return parts


def _pad_v2(a: Automaton, s2_cap: int) -> Automaton:
    """Grow the v2 state-indexed arrays to a shared capacity."""
    def pad2(arr, fill):
        if arr.shape[0] == s2_cap:
            return arr
        out = np.full((s2_cap,) + arr.shape[1:], fill, dtype=arr.dtype)
        out[: arr.shape[0]] = arr
        return out

    return a._replace(node2=pad2(a.node2, -1),
                      v2_hop=pad2(a.v2_hop, -1),
                      v2_depth=pad2(a.v2_depth, -1))


def build_sharded(
    filter_shards: Sequence[Sequence[str]],
    filter_ids: Dict[str, int],
    table: WordTable,
    state_capacity: int | None = None,
    n_buckets: int | None = None,
    return_parts: bool = False,
) -> ShardedAutomaton:
    """Build one automaton per shard (global filter ids), compress
    with shared shapes, and stack.

    ``state_capacity``/``n_buckets`` are retention floors (the router
    passes its previous caps so rebuilds keep device shapes — and jit
    specializations — stable). ``return_parts=True`` also returns the
    per-shard HOST automatons: they seed the per-shard
    :class:`~emqx_tpu.ops.patch.AutoPatcher` mirrors."""
    autos = []
    for shard in filter_shards:
        trie = TrieOracle()
        for f in shard:
            trie.insert(f)
        autos.append(build_automaton(trie, filter_ids, table,
                                     skip_hash=True))
    parts = finalize_parts(autos, state_capacity=state_capacity,
                           n_buckets=n_buckets)
    stacked = _stack_sharded(parts)
    if return_parts:
        return stacked, parts
    return stacked


def _stack_sharded(parts: Sequence[Automaton]) -> ShardedAutomaton:
    return ShardedAutomaton(
        wt=np.stack([a.wt for a in parts]),
        wt_seed=np.stack([a.wt_seed for a in parts]),
        node2=np.stack([a.node2 for a in parts]),
    )


def build_sharded_fanout(
    rows_per_shard: Sequence[Dict[int, Sequence[int]]],
    num_filters: int,
    filter_capacity: int | None = None,
    entry_capacity: int | None = None,
) -> ShardedFanout:
    # the shards' common capacities, as build_fanout would choose them
    # (10M filters: the tables are built once, not once to be measured)
    f_cap = capacity_for(num_filters)
    e_cap = max(capacity_for(sum(len(v) for v in rows.values()) + 1)
                for rows in rows_per_shard)
    if filter_capacity is not None:
        f_cap = max(f_cap, filter_capacity)
    if entry_capacity is not None:
        e_cap = max(e_cap, entry_capacity)
    fans = [
        build_fanout(rows, num_filters, filter_capacity=f_cap,
                     entry_capacity=e_cap)
        for rows in rows_per_shard
    ]
    return ShardedFanout(
        row_ptr=np.stack([f.row_ptr for f in fans]),
        sub_ids=np.stack([f.sub_ids for f in fans]),
        row_pairs=np.stack([f.row_pairs for f in fans]),
    )


def place_sharded(mesh: Mesh, sharded: NamedTuple):
    """Put stacked shard arrays onto the mesh: leading axis on 'trie',
    replicated over 'data'."""
    spec = NamedSharding(mesh, P("trie"))
    return type(sharded)(*[jax.device_put(x, spec) for x in sharded])


def place_batch(mesh: Mesh, word_ids, n_words, sys_mask):
    spec = NamedSharding(mesh, P("data"))
    return (jax.device_put(word_ids, spec),
            jax.device_put(n_words, spec),
            jax.device_put(sys_mask, spec))


def _local_auto(auto_t: ShardedAutomaton) -> Automaton:
    """This shard's walkable Automaton view inside shard_map (the
    leading shard axis is length 1 locally)."""
    return Automaton(
        row_ptr=None, edge_word=None, edge_child=None,
        plus_child=None, hash_filter=None, end_filter=None,
        n_states=0, n_edges=0,
        wt=auto_t.wt[0], wt_seed=auto_t.wt_seed[0],
        node2=auto_t.node2[0])


@functools.partial(
    jax.jit,
    static_argnames=("mesh", "k", "m", "d", "mb", "with_fanout",
                     "steps", "slots", "take"))
def publish_step(
    mesh: Mesh,
    auto: ShardedAutomaton,
    fan: ShardedFanout,
    word_ids: jax.Array,   # [B, L] sharded over 'data'
    n_words: jax.Array,    # [B]
    sys_mask: jax.Array,   # [B]
    bmt: ShardedBitmaps | None = None,
    *,
    k: int = 64,
    m: int = 128,
    d: int = 128,
    mb: int = 16,
    with_fanout: bool = True,
    steps: int | None = None,
    slots: int = 2,
    take: int = 1,
):
    """The full multi-chip publish step.

    Returns ``(match_ids [B, T*m], sub_ids [B, T*d], src_ids [B, T*d],
    bm [(union [B, W], has_big [B], bovf [B]) | None],
    overflow [B], match_overflow [B], stats)``:

    - ``src_ids`` carries the source filter id per gathered subscriber
      slot (the delivery tail resolves per-subscription options by
      matched filter, the reference's ``{Topic, SubPid}`` dispatch
      pairs);
    - with a :class:`ShardedBitmaps` table, each trie shard ORs its
      matched big filters' bitmap rows (the >d regime,
      src/emqx_broker_helper.erl:82-92) and the per-topic unions
      OR-combine over ICI — ``bovf`` flags topics matching more than
      ``mb`` big filters on some shard (host fallback, like the
      single-chip bitmap path);
    - per-row ``overflow`` marks topics whose match or CSR fan-out
      exceeded a kernel bound on ANY trie shard (the caller resolves
      those host-side — same contract as the single-chip
      ``match_batch``), while ``match_overflow`` isolates the match
      (active-set/m) bound — the only overflow a ``boost_k`` grow can
      help with (a fan-out ``d`` overflow must not trigger k
      recompiles). ``stats`` is a dict of mesh-summed counters
      (matches, deliveries, overflows) — the device metric
      accumulator.

    A 1×1 mesh runs the SAME local computation as a plain jit program
    (every collective is the identity on one device, and a
    single-device mesh has nothing to exchange). The multi-device
    path is byte-identical modulo the collectives and stays exercised
    by the 8-device dryrun.
    """
    with_bitmap = bmt is not None
    # Pallas manual-DMA when the MESH's devices are TPUs (the mesh is
    # what the program runs on, and it is a static argument); the scan
    # fallback on the virtual CPU mesh (interpret-mode Pallas inside
    # shard_map is not supported).
    use_dma = mesh.devices.flat[0].platform == "tpu"
    single = mesh.shape["data"] == 1 and mesh.shape["trie"] == 1

    class _NullAxes:
        """Collective ops on a 1-device mesh: identities/local sums."""
        @staticmethod
        def ag_tiled(x):
            return x

        @staticmethod
        def or_over_trie(union):
            return union

        @staticmethod
        def any_over_trie(x):
            return x

        @staticmethod
        def sum_over_mesh(x):
            return x

        @staticmethod
        def sum_over_data(x):
            return x

    class _MeshAxes:
        @staticmethod
        def ag_tiled(x):
            return jax.lax.all_gather(x, "trie", axis=1, tiled=True)

        @staticmethod
        def or_over_trie(union):
            ug = jax.lax.all_gather(union, "trie")       # [T, b, W]
            return jax.lax.reduce(
                ug, jnp.uint32(0), jax.lax.bitwise_or, (0,))

        @staticmethod
        def any_over_trie(x):
            return jax.lax.psum(x.astype(jnp.int32), "trie") > 0

        @staticmethod
        def sum_over_mesh(x):
            return jax.lax.psum(x, ("data", "trie"))

        @staticmethod
        def sum_over_data(x):
            return jax.lax.psum(x, "data")

    def local(auto_t, fan_t, ids, n, sysm, bmt_t=None, C=_MeshAxes):
        from emqx_tpu.ops.bitmap import (BitmapTable, or_bitmaps_dma,
                                         or_bitmaps_xla,
                                         rows_for_matches)

        a = _local_auto(auto_t)
        res = match_batch(a, ids, n, sysm, k=k, m=m, steps=steps,
                          slots=slots, take=take)
        if with_fanout:
            f = FanoutTable(
                fan_t.row_ptr[0], fan_t.sub_ids[0], 0, 0,
                row_pairs=(None if fan_t.row_pairs is None
                           else fan_t.row_pairs[0]))
            subs, src, dcount, dovf = gather_subscribers_src(
                f, res.ids, d=d)
        else:
            subs = jnp.zeros((ids.shape[0], d), jnp.int32)
            src = jnp.full((ids.shape[0], d), -1, jnp.int32)
            dcount = jnp.zeros((ids.shape[0],), jnp.int32)
            dovf = jnp.zeros((ids.shape[0],), bool)
        # exchange shard-local matches over ICI: every data shard gets
        # the union of all trie shards' match ids
        all_ids = C.ag_tiled(res.ids)
        all_subs = C.ag_tiled(subs)
        all_src = C.ag_tiled(src)
        bm_out = None
        big_deliv = None
        if with_bitmap:
            bt = BitmapTable(bmt_t.bitmaps[0], bmt_t.big_row[0], 0, 0)
            rows_b, b_ovf = rows_for_matches(bt, res.ids, mb=mb)
            union = (or_bitmaps_dma(bt.bitmaps, rows_b) if use_dma
                     else or_bitmaps_xla(bt.bitmaps, rows_b))
            # per-topic union OR-combined over the trie axis (each
            # shard contributes its own big filters' members)
            union = C.or_over_trie(union)
            has_big = C.any_over_trie((rows_b >= 0).any(axis=1))
            bovf = C.any_over_trie(b_ovf)
            big_deliv = jnp.sum(
                jax.lax.population_count(union), dtype=jnp.int32)
            bm_out = (union, has_big, bovf)
        # per-row overflow, OR-reduced over the trie axis: one shard
        # overflowing means the row's union is incomplete
        row_movf = C.any_over_trie(res.overflow)
        row_ovf = row_movf | C.any_over_trie(dovf)
        deliv = C.sum_over_mesh(jnp.sum(dcount))
        if big_deliv is not None:
            # the OR-reduced union is IDENTICAL on every trie shard —
            # sum it over 'data' only (a trie psum would count each
            # big delivery T times)
            deliv = deliv + C.sum_over_data(big_deliv)
        stats = {
            "matches": C.sum_over_mesh(jnp.sum(res.count)),
            "deliveries": deliv,
            "overflows": C.sum_over_mesh(jnp.sum(res.overflow | dovf)),
        }
        return all_ids, all_subs, all_src, bm_out, row_ovf, row_movf, stats

    args = [auto, fan, word_ids, n_words, sys_mask]
    if with_bitmap:
        args.append(bmt)
    if single:
        out = local(*args, C=_NullAxes)
        # the 1×1 outputs already carry the T=1 global shapes; cast
        # the bool reductions to match the mesh path's dtypes
        return out
    in_specs = [P("trie"), P("trie"), P("data"), P("data"), P("data")]
    bm_spec = (P("data"), P("data"), P("data")) if with_bitmap else None
    if with_bitmap:
        in_specs.append(P("trie"))
    return _shard_map(
        local, mesh,
        tuple(in_specs),
        (P("data"), P("data"), P("data"), bm_spec,
         P("data"), P("data"), P()),
    )(*args)


@functools.partial(
    jax.jit,
    static_argnames=("mesh", "lay", "k", "m", "d", "mb", "steps",
                     "slots", "take"))
def publish_step_insert(
    mesh: Mesh,
    auto: ShardedAutomaton,
    fan: ShardedFanout,
    table: jax.Array,      # the mesh match cache's table, replicated
    buf: jax.Array,        # the batch's one int32 buffer, replicated
    *,
    lay,                   # ops.match_cache.BatchLayout with hit = 0
    k: int, m: int, d: int, mb: int, steps: int | None, slots: int,
    take: int,
):
    """The cache-split mesh dispatch's step as ONE program: slice the
    misses' operands out of the batch buffer (a replicated operand
    under ``P("data")`` is cut locally, no collective), run
    :func:`publish_step` on them, lay each topic's ``flag | ids | subs
    | src`` cache row, gather the rows over ``data`` once, and scatter
    them into the cache's table (functionally: the old table stays
    whole for the probes that hold it).

    Returns ``(new_table, (miss_vals [MB, 1 + width], stats))``, rows
    and table replicated; keyed by the miss bucket (and the buffer's
    capacity), never by the batch's hits."""
    from emqx_tpu.ops.match_cache import flag_rows, insert_rows

    rep = NamedSharding(mesh, P())
    # held replicated up to the shard_map's edge, where each chip cuts
    # its own rows out (left to itself the partitioner shards the
    # slices of the buffer and moves rows between chips)
    word_ids, n_words, sys_mask, slots_ = (
        jax.lax.with_sharding_constraint(x, rep)
        for x in lay.step_sections(buf))
    ids, subs, src, _bm, ovf, movf, stats = publish_step(
        mesh, auto, fan, word_ids, n_words, sys_mask, None, k=k, m=m,
        d=d, mb=mb, with_fanout=True, steps=steps, slots=slots,
        take=take)
    vals = jax.lax.with_sharding_constraint(
        flag_rows(jnp.concatenate([ids, subs, src], axis=1), ovf, movf),
        rep)
    return insert_rows(table, slots_, vals), (vals, stats)


@functools.partial(jax.jit, static_argnames=("mesh", "k", "m", "steps",
                                             "slots", "take"))
def shared_pick_step(
    mesh: Mesh,
    auto: ShardedAutomaton,
    gfan: ShardedFanout,     # per-shard GROUP membership CSR
    word_ids: jax.Array,     # [B, L] sharded over 'data'
    n_words: jax.Array,
    sys_mask: jax.Array,
    seeds: jax.Array,        # int32[B] per-message pick seed
    *,
    k: int = 16,
    m: int = 32,
    steps: int | None = None,
    slots: int = 2,
    take: int = 1,
):
    """Multi-chip $share dispatch: match + the device hash-strategy
    member pick (src/emqx_shared_sub.erl:229-275) in one collective
    step. Each trie shard picks members for ITS groups' matches
    (``gfan`` rows live with their filter's shard — same stable
    assignment as the automaton); picks are all-gathered over ICI.

    Returns ``(picks [B, T*m], match_ids [B, T*m], overflow [B])``;
    picks are subscriber ids aligned with ``match_ids`` slots (-1 =
    slot empty or group not on that shard). The pick is stateless
    (hash strategy); round-robin/sticky keep host state and stay
    host-side, exactly as on one chip."""
    from emqx_tpu.ops.fanout import pick_shared

    def local(auto_t, gfan_t, ids, n, sysm, s):
        a = _local_auto(auto_t)
        res = match_batch(a, ids, n, sysm, k=k, m=m, steps=steps,
                          slots=slots, take=take)
        f = FanoutTable(
            gfan_t.row_ptr[0], gfan_t.sub_ids[0], 0, 0,
            row_pairs=(None if gfan_t.row_pairs is None
                       else gfan_t.row_pairs[0]))
        picks = pick_shared(f, res.ids, s)
        all_picks = jax.lax.all_gather(picks, "trie", axis=1, tiled=True)
        all_ids = jax.lax.all_gather(res.ids, "trie", axis=1, tiled=True)
        ovf = jax.lax.psum(res.overflow.astype(jnp.int32), "trie") > 0
        return all_picks, all_ids, ovf

    return _shard_map(
        local, mesh,
        (P("trie"), P("trie"), P("data"), P("data"), P("data"),
         P("data")),
        (P("data"), P("data"), P("data")),
    )(auto, gfan, word_ids, n_words, sys_mask, seeds)
