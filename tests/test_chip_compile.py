"""Ask the TPU v5e's compiler, without a chip, whether it accepts the
device programs of the served publish path at deployment widths.

The tier-1 suite runs on the CPU, where a Pallas kernel runs in
interpret mode and XLA never sees the chip's tiling or memory rules.
The TPU compiler is installed here all the same and compiles for a
*described* ``v5e:2x2`` topology: whatever it refuses in these tests
it refuses on the chip. A compile that passes is not a chip run — it
says nothing about results or times (``chip_smoke.py`` does that).

The topology is described inside a module-scoped fixture, never at
import: only one process may hold the TPU library, and under xdist
every worker imports every test file.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from emqx_tpu.ops.csr import (NARROW_SLOT, NARROW_SLOTS, WIDE_SLOT,
                              WIDE_SLOTS, Automaton)

# deployment widths (1M mixed filters, BASELINE config 2/3): the walk
# tables of that population are ~2^21 buckets / ~2^21 states
_NB = 1 << 21
_S2 = 1 << 21
_FCAP = 1 << 20        # filter-id capacity of the fan tables
_B = 1024              # ingress batch bucket
_K = 16                # MatcherConfig.active_k
_M = 64                # MatcherConfig.max_matches


@pytest.fixture(scope="module")
def topo():
    import os

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def _no_persistent_cache():
    """A compile for a described device is written to the persistent
    cache but cannot be read back without a chip; keep these compiles
    out of it (and silent)."""
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _s(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _auto_shapes(sh, wide: bool) -> Automaton:
    slots, sw = ((WIDE_SLOTS, WIDE_SLOT) if wide
                 else (NARROW_SLOTS, NARROW_SLOT))
    return Automaton(
        row_ptr=None, edge_word=None, edge_child=None,
        plus_child=None, hash_filter=None, end_filter=None,
        n_states=0, n_edges=0,
        wt=_s((_NB, slots * sw), jnp.int32, sh),
        wt_seed=_s((1,), jnp.uint32, sh),
        node2=_s((_S2, 4), jnp.int32, sh))


def _batch_shapes(sh, B, L):
    return (_s((B, L), jnp.int32, sh), _s((B,), jnp.int32, sh),
            _s((B,), jnp.bool_, sh))


@pytest.mark.parametrize("pack_ids", [False, True],
                         ids=["raw", "packed"])
@pytest.mark.parametrize("mode,L,steps", [
    ("narrow", 5, 6),     # the 5-level mixed population
    ("narrow", 16, 17),   # max_levels, full-depth walk
    ("wide", 16, 4),      # chain-compressed deep tries
])
def test_dispatched_walk_compiles(one_chip, mode, L, steps, pack_ids):
    """The walk the router dispatches."""
    from emqx_tpu.ops.match import match_batch

    wide = mode == "wide"
    lowered = match_batch.lower(
        _auto_shapes(one_chip, wide), *_batch_shapes(one_chip, _B, L),
        k=_K, m=_M, steps=steps,
        slots=WIDE_SLOTS if wide else NARROW_SLOTS,
        take=8 if wide else 1, pack_ids=pack_ids)
    compiled = lowered.compile()
    assert compiled.memory_analysis() is not None


def test_fanout_and_pack_compile(one_chip):
    """pack_matches → expand_packed (CSR fan-out) → bitmap row
    translation → pack_union_rows, the tail ``_begin_device`` runs
    after the walk, at the raw-emit width of a 6-step k=16 walk."""
    from emqx_tpu.ops.bitmap import BitmapTable, rows_for_matches
    from emqx_tpu.ops.fanout import FanoutTable, expand_packed
    from emqx_tpu.ops.pack import (bundle_i32, mask_pad_rows,
                                   pack_matches, pack_union_rows)

    sh = one_chip
    ids = _s((_B, 6 * 2 * _K), jnp.int32, sh)
    pm, pq, W = 8192, 16384, 32768
    mask_pad_rows.lower(ids, _s((), jnp.int32, sh)).compile()
    pack_matches.lower(ids, pm=pm).compile()
    fan = FanoutTable(
        row_ptr=_s((_FCAP + 1,), jnp.int32, sh),
        sub_ids=_s((1 << 21,), jnp.int32, sh),
        n_filters=0, n_entries=0,
        row_pairs=_s((_FCAP, 2), jnp.int32, sh))
    expand_packed.lower(fan, _s((_B + 1,), jnp.int32, sh),
                        _s((pm,), jnp.int32, sh), q=pq).compile()

    def rows(big_row, match_ids):
        bt = BitmapTable(None, big_row, 0, 0)
        return rows_for_matches(bt, match_ids, mb=16)

    jax.jit(rows).lower(_s((_FCAP,), jnp.int32, sh), ids).compile()
    pack_union_rows.lower(_s((_B, W), jnp.uint32, sh),
                          _s((_B,), jnp.bool_, sh), pr=8).compile()
    bundle_i32.lower(_s((_B + 1,), jnp.int32, sh),
                     _s((pm,), jnp.int32, sh),
                     _s((_B,), jnp.bool_, sh),
                     _s((8, W), jnp.uint32, sh)).compile()


@pytest.mark.parametrize("B", [256, 1024, 8192])
def test_or_bitmaps_dma_compiles(one_chip, B):
    """The manual-DMA bitmap OR (``or_bitmaps_auto`` on a TPU):
    4096 big filters × 1M subscribers, B topics × 16 rows. B = 8192
    is the batch the chip refused in PR 21 (its scalar-prefetched row
    ids did not fit the 1 MiB of SMEM)."""
    from emqx_tpu.ops.bitmap import or_bitmaps_dma

    compiled = or_bitmaps_dma.lower(
        _s((4096, 32768), jnp.uint32, one_chip),
        _s((B, 16), jnp.int32, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_retained_match_compiles_at_1m_names(one_chip):
    """The retained-replay match the retainer dispatches, at the
    index's deployment capacity (2^20 names)."""
    from emqx_tpu.ops import retained_match as rm

    F, L, cap = 64, 8, 1 << 20
    sh = one_chip
    compiled = rm.match_names_many.lower(
        _s((F, L), jnp.int32, sh), _s((F,), jnp.int32, sh),
        _s((F,), jnp.bool_, sh), _s((cap, L), jnp.int32, sh),
        _s((cap,), jnp.int32, sh), _s((cap,), jnp.bool_, sh)).compile()
    out_bytes = compiled.memory_analysis().output_size_in_bytes
    assert out_bytes >= F * cap  # the [F, cap] hit matrix


def test_match_cache_and_delta_compile(one_chip):
    """The match-cache merge (its gather and scatter; with misses and
    fully hit) and the delta two-probe merge."""
    from emqx_tpu.ops.delta import _mask_ids, _union_packed
    from emqx_tpu.ops.match_cache import (BATCH_BUF_FLOOR, BatchLayout,
                                          _mesh_merge_jit)

    sh = one_chip
    table = _s((65536, _M + 1), jnp.int32, sh)
    i32 = lambda *shape: _s(shape, jnp.int32, sh)  # noqa: E731
    b1 = lambda *shape: _s(shape, jnp.bool_, sh)   # noqa: E731
    lay = BatchLayout(0, _B, _B, BATCH_BUF_FLOOR)
    _mesh_merge_jit.lower(table, i32(lay.size), i32(_B, _M + 1), lay=lay,
                          b_pad=_B, splits=None).compile()
    _mesh_merge_jit.lower(table, i32(lay.size), None,
                          lay=lay._replace(miss=0), b_pad=_B,
                          splits=None).compile()
    _union_packed.lower(i32(_B, _M), i32(_B, _M), m=_M).compile()
    _mask_ids.lower(i32(_B, _M), b1(_FCAP)).compile()


@pytest.mark.parametrize("delta", ["no_delta", "live", "live_with_plus",
                                   "live_grown"])
def test_fused_chip_dispatch_compiles(one_chip, delta):
    """The two programs a served one-chip batch enqueues
    (``Router._match_dispatch_cached``, ``Broker._begin_device``), at
    the cells' widths and the ingress's largest batch: the match (the
    walk with a live delta snapshot's two-probe folded in, the cache
    insert, the gather of the hits and the merge with the pad mask:
    ``walk_merge``, keyed by the batch's bucket and the depth, hits
    and misses both laid at that bucket) or, for a batch that fully
    hit, the merge alone; and the packers with the fetch's bundle. The
    table is an argument the insert does not donate, and the probe's
    snapshot a second one.

    A live delta is one variant of the match whatever it holds, adds,
    tombstones or both (``ops/delta.py``: side tables at the capacity
    ``delta_max_filters`` gives, the mask always there, the side
    walk's steps from the batch's depth): ``live``. What is left to
    vary is a pending '+' (the side walk's lanes) and tables grown
    past their floor."""
    from emqx_tpu.ops.csr import buckets_for_capacity, capacity_for
    from emqx_tpu.ops.delta import DeltaSnapshot
    from emqx_tpu.ops.fanout import FanoutTable
    from emqx_tpu.ops.match_cache import (BATCH_BUF_FLOOR, BatchLayout,
                                          _mesh_merge_jit, walk_merge)
    from emqx_tpu.ops.pack import pack_chip
    from emqx_tpu.router import MatcherConfig

    sh = one_chip
    L = 5
    lay = BatchLayout(L, _B, _B, BATCH_BUF_FLOOR)
    assert BatchLayout.need(16, _B, _B) <= lay.size
    table = _s((65536, _M + 1), jnp.int32, sh)
    buf = _s((lay.size,), jnp.int32, sh)
    side, dkw = None, {"dk": 0, "dsteps": 0}
    if delta != "no_delta":
        # the capacity Router._ensure_delta gives the side tables
        states = capacity_for(4 * MatcherConfig().delta_max_filters)
        if delta == "live_grown":
            states *= 2
        side_auto = Automaton(
            row_ptr=None, edge_word=None, edge_child=None,
            plus_child=None, hash_filter=None, end_filter=None,
            n_states=0, n_edges=0,
            wt=_s((buckets_for_capacity(states, NARROW_SLOTS),
                   NARROW_SLOTS * NARROW_SLOT), jnp.int32, sh),
            wt_seed=_s((1,), jnp.uint32, sh),
            node2=_s((states, 4), jnp.int32, sh))
        side = (side_auto, _s((_FCAP,), jnp.bool_, sh))
        dkw = {"dk": _K if delta == "live_with_plus" else 1,
               "dsteps": DeltaSnapshot.steps_for(L)}
    match = walk_merge.lower(
        _auto_shapes(sh, False), side, table, table, buf, lay=lay, k=_K,
        m=_M, steps=L + 1, slots=NARROW_SLOTS, take=1, **dkw).compile()
    ma = match.memory_analysis()
    # the new table beside the old: not donated (a probe holds it);
    # and the batch's ids and flags
    assert ma.output_size_in_bytes >= (65536 * (_M + 1) + _B * _M) * 4
    if delta != "no_delta":
        return
    _mesh_merge_jit.lower(
        table, buf, None, lay=BatchLayout(0, 0, _B, lay.size), b_pad=_B,
        splits=None).compile()
    fan = FanoutTable(
        row_ptr=_s((_FCAP + 1,), jnp.int32, sh),
        sub_ids=_s((1 << 21,), jnp.int32, sh),
        n_filters=_s((), jnp.int32, sh), n_entries=_s((), jnp.int32, sh),
        row_pairs=_s((_FCAP, 2), jnp.int32, sh))
    ids = _s((_B, _M), jnp.int32, sh)
    ovf = _s((_B,), jnp.bool_, sh)
    for f in (fan, None):
        pack_chip.lower(f, ids, ovf, pm=8192, pq=16384).compile()


@pytest.mark.parametrize("B,L", [(8, 2), (64, 3), (256, 16)])
def test_the_match_program_compiles_at_every_end_of_its_keys(one_chip,
                                                             B, L):
    """Its keys are the batch's bucket and the depth: the smallest
    bucket at the shallowest depth, a paced batch's, and a flood
    batch's at ``max_levels``, each beside the bucket's walk-free
    merge. No key holds a count of hits or misses or a pack budget:
    the program's only static shapes are the layout's."""
    import inspect

    from emqx_tpu.ops.match_cache import (BATCH_BUF_FLOOR, BatchLayout,
                                          _mesh_merge_jit, walk_merge)

    sh = one_chip
    lay = BatchLayout(L, B, B, BATCH_BUF_FLOOR)
    table = _s((65536, _M + 1), jnp.int32, sh)
    buf = _s((lay.size,), jnp.int32, sh)
    compiled = walk_merge.lower(
        _auto_shapes(sh, False), None, table, table, buf, lay=lay, k=_K,
        m=_M, steps=L + 1, slots=NARROW_SLOTS, take=1).compile()
    assert compiled.memory_analysis().output_size_in_bytes >= \
        65536 * (_M + 1) * 4
    _mesh_merge_jit.lower(
        table, buf, None, lay=BatchLayout(0, 0, B, lay.size), b_pad=B,
        splits=None).compile()
    names = set(inspect.signature(
        walk_merge.__wrapped__).parameters)
    assert not names & {"pm", "pq", "hit", "miss", "b_pad"}


@pytest.mark.parametrize("n_data,n_trie", [(4, 1), (2, 2)])
def test_sharded_publish_step_compiles(topo, n_data, n_trie):
    """BASELINE config 5's step on the four described chips: the
    shard_map program with per-shard fan tables and bitmaps — the
    collectives must be in the program and the bitmap OR must be the
    Pallas kernel (``use_dma`` follows the mesh's devices)."""
    from emqx_tpu.parallel.sharded import (ShardedAutomaton,
                                           ShardedBitmaps,
                                           ShardedFanout, publish_step)

    mesh = Mesh(np.array(topo.devices[:4]).reshape(n_data, n_trie),
                ("data", "trie"))
    tr = NamedSharding(mesh, P("trie"))
    da = NamedSharding(mesh, P("data"))
    T = n_trie
    nb, s2, fcap = _NB // T, _S2 // T, _FCAP
    auto = ShardedAutomaton(
        wt=_s((T, nb, NARROW_SLOTS * NARROW_SLOT), jnp.int32, tr),
        wt_seed=_s((T, 1), jnp.uint32, tr),
        node2=_s((T, s2, 4), jnp.int32, tr))
    fan = ShardedFanout(
        row_ptr=_s((T, fcap + 1), jnp.int32, tr),
        sub_ids=_s((T, 1 << 20), jnp.int32, tr),
        row_pairs=_s((T, fcap, 2), jnp.int32, tr))
    bmt = ShardedBitmaps(
        bitmaps=_s((T, 16, 32768), jnp.uint32, tr),
        big_row=_s((T, fcap), jnp.int32, tr))
    compiled = publish_step.lower(
        mesh, auto, fan, *_batch_shapes(da, _B, 5), bmt,
        k=_K, m=_M, d=128, mb=16, with_fanout=True, steps=6,
        slots=NARROW_SLOTS, take=1).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text          # Pallas bitmap OR
    assert "all-reduce" in text               # mesh-summed stats
    if n_trie > 1:
        assert "all-gather" in text           # trie-axis exchange
    ma = compiled.memory_analysis()
    # tables are split over the trie axis, not replicated at full size
    assert ma.argument_size_in_bytes < 4 * (
        _NB * 8 + _S2 * 4 + 3 * fcap + (1 << 20) + 16 * 32768
        + fcap) * 2


@pytest.mark.parametrize("n_data,n_trie", [(4, 1), (2, 2)])
def test_fused_mesh_dispatch_compiles(topo, n_data, n_trie):
    """The three programs a served mesh batch enqueues
    (``Router._dispatch_fused``), at the cell's widths: the step with
    the cache insert keeps ``publish_step``'s collectives and adds ONE
    all-gather (the fresh rows over ``data``) and no other kind; the
    merge and the packer run replicated, with no collective at all."""
    import re

    from emqx_tpu.ops.match_cache import (BATCH_BUF_FLOOR, BatchLayout,
                                          _mesh_merge_jit)
    from emqx_tpu.ops.pack import pack_mesh
    from emqx_tpu.parallel.sharded import (ShardedAutomaton,
                                           ShardedFanout, publish_step,
                                           publish_step_insert)

    def collectives(compiled):
        return sorted(m.group(1) for m in re.finditer(
            r"\b(all-gather|all-reduce|collective-permute|all-to-all|"
            r"reduce-scatter)(?:-start)?\(", compiled.as_text()))

    mesh = Mesh(np.array(topo.devices[:4]).reshape(n_data, n_trie),
                ("data", "trie"))
    tr = NamedSharding(mesh, P("trie"))
    da = NamedSharding(mesh, P("data"))
    rep = NamedSharding(mesh, P())
    T, L, MB, HB, B, d = n_trie, 16, 32, 256, 256, 128
    width = T * _M + 2 * T * d
    auto = ShardedAutomaton(
        wt=_s((T, _NB // T, NARROW_SLOTS * NARROW_SLOT), jnp.int32, tr),
        wt_seed=_s((T, 1), jnp.uint32, tr),
        node2=_s((T, _S2 // T, 4), jnp.int32, tr))
    fan = ShardedFanout(
        row_ptr=_s((T, _FCAP + 1), jnp.int32, tr),
        sub_ids=_s((T, 1 << 20), jnp.int32, tr),
        row_pairs=_s((T, _FCAP, 2), jnp.int32, tr))
    kw = dict(k=_K, m=_M, d=d, mb=16, steps=6, slots=NARROW_SLOTS, take=1)
    lay = BatchLayout(L, MB, HB, BATCH_BUF_FLOOR)
    table = _s((65536, 1 + width), jnp.int32, rep)
    buf = _s((lay.size,), jnp.int32, rep)
    step = publish_step.lower(
        mesh, auto, fan, *_batch_shapes(da, MB, L), None,
        with_fanout=True, **kw).compile()
    fused = publish_step_insert.lower(
        mesh, auto, fan, table, buf, lay=lay._replace(hit=0),
        **kw).compile()
    assert collectives(fused) == sorted(collectives(step) + ["all-gather"])
    vals = _s((MB, 1 + width), jnp.int32, rep)
    for miss_vals, miss in ((vals, MB), (None, 0)):
        merge = _mesh_merge_jit.lower(
            table, buf, miss_vals,
            lay=lay._replace(levels=0, miss=miss), b_pad=B,
            splits=(T * _M, T * d)).compile()
        assert collectives(merge) == []
    i32 = lambda *shape: _s(shape, jnp.int32, rep)  # noqa: E731
    flags = _s((B,), jnp.bool_, rep)
    pack = pack_mesh.lower(i32(B, T * _M), i32(B, T * d), i32(B, T * d),
                           flags, flags, pm=8192, pq=8192).compile()
    assert collectives(pack) == []
