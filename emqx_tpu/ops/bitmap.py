"""Subscriber-bitmap fan-out for huge-fan-out filters — Pallas kernel.

The reference bounds per-dispatch work by sharding a topic's
subscribers once they exceed 1024 (src/emqx_broker_helper.erl:55,
82-92; dispatch walks ``{shard, Topic, I}`` records,
src/emqx_broker.erl:305-309). The TPU analogue (SURVEY §2.2): filters
past the threshold store their subscriber set as a *bitmap row* in
HBM (bit i = subscriber id i), and fan-out for a publish batch is a
bitwise OR of its matched rows:

    out[b, :] = OR over m of bitmaps[row(match_ids[b, m]), :]

This is pure HBM bandwidth (the OR is trivial), so the kernel is a
streaming Pallas program: grid ``(B, W_tiles)``; each program loops
over the topic's matched rows, DMA-ing the row's tile HBM→VMEM with
double buffering and OR-accumulating in registers. Matched ids are
per-topic scalars in SMEM driving the DMA source index — the
data-dependent gather XLA would materialize as a ``[B, M, W]``
intermediate never exists.

Small-fan-out filters stay on the CSR id-gather path
(:mod:`emqx_tpu.ops.fanout`); the broker routes each matched filter
by class, mirroring the reference's flat-bag / sharded split.
"""

from __future__ import annotations

import functools
from typing import Dict, NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np

_LANES = 128          # last-dim tile unit (uint32 words)
_DEFAULT_TILE = 2048  # words per DMA tile (8 KB)


class BitmapTable(NamedTuple):
    """Per-filter subscriber bitmaps for 'big' filters.

    ``big_row[fid]`` maps a global filter id to its bitmap row
    (-1 = filter is small / unknown → CSR path).
    """

    bitmaps: np.ndarray  # uint32[R_cap, W] — W padded to the tile size
    big_row: np.ndarray  # int32[F_cap]
    n_rows: int
    n_subs: int


def words_for(n_subs: int, tile: int = _DEFAULT_TILE) -> int:
    """Row width in uint32 words: next power of two ≥ the bit count
    (min one tile). Pow2 keeps the kernel's row-chunk size an exact
    divisor of the row for any capacity."""
    w = (n_subs + 31) // 32
    out = max(tile, 1024)
    while out < w:
        out *= 2
    return out


def build_bitmaps(
    rows: Dict[int, Sequence[int]],
    num_filters: int,
    n_subs: int,
    row_capacity: int | None = None,
    tile: int = _DEFAULT_TILE,
) -> BitmapTable:
    """Pack ``{filter_id: [subscriber ids]}`` into bitmap rows."""
    from emqx_tpu.ops.csr import capacity_for

    W = words_for(n_subs, tile)
    f_cap = capacity_for(num_filters)
    r_cap = capacity_for(max(1, len(rows)), row_capacity)
    bitmaps = np.zeros((r_cap, W), dtype=np.uint32)
    big_row = np.full((f_cap,), -1, dtype=np.int32)
    for r, (fid, subs) in enumerate(sorted(rows.items())):
        big_row[fid] = r
        ids = np.asarray(list(subs), dtype=np.int64)
        np.bitwise_or.at(bitmaps[r], ids // 32,
                         np.uint32(1) << (ids % 32).astype(np.uint32))
    return BitmapTable(bitmaps=bitmaps, big_row=big_row,
                       n_rows=len(rows), n_subs=n_subs)


def rows_for_matches(table: BitmapTable, match_ids: jax.Array,
                     mb: int = 16) -> tuple[jax.Array, jax.Array]:
    """Translate matched filter ids [B, M] to bitmap rows [B, mb]
    (-1 padded, packed to the front; small/unmatched filters drop
    out). ``mb`` bounds the number of big filters one topic can
    match; the overflow flag [B] marks topics that exceeded it
    (host fallback, as in ops.match)."""
    # ids at/above the table's filter capacity (patched into the
    # automaton after this table was built) have no row; clamping
    # would alias them onto the LAST filter's bitmap — an entire
    # unrelated subscriber set
    in_range = (match_ids >= 0) & (match_ids < table.big_row.shape[0])
    safe = jnp.where(in_range, match_ids, 0)
    rows = jnp.where(in_range, table.big_row[safe], -1)
    # pack valid rows to the front (cumsum+scatter, as in ops.match)
    valid = rows >= 0
    pos = jnp.cumsum(valid, axis=1) - 1
    out = jnp.full((rows.shape[0], mb), -1, dtype=jnp.int32)
    out = out.at[
        jnp.arange(rows.shape[0])[:, None],
        jnp.where(valid, jnp.minimum(pos, mb), mb)].set(rows, mode="drop")
    overflow = jnp.sum(valid, axis=1) > mb
    return out, overflow


# -- XLA reference implementation ------------------------------------------

@jax.jit
def or_bitmaps_xla(bitmaps: jax.Array, rows: jax.Array) -> jax.Array:
    """OR of bitmap rows per topic — lax.scan over the row slots (the
    no-Pallas fallback; materializes one [B, W] gather per slot)."""
    B = rows.shape[0]
    W = bitmaps.shape[1]

    def step(acc, r):
        tile = jnp.where(r[:, None] >= 0, bitmaps[jnp.maximum(r, 0)],
                         jnp.zeros((1, W), jnp.uint32))
        return acc | tile, None

    acc0 = jnp.zeros((B, W), dtype=jnp.uint32)
    acc, _ = jax.lax.scan(step, acc0, jnp.swapaxes(rows, 0, 1))
    return acc


# -- Pallas kernel ----------------------------------------------------------

_SUB = 8          # sublanes per block
_TILE2D = _SUB * _LANES  # 1024 words per (8, 128) block


def _or_kernel(ids_ref, bm_ref, out_ref):
    """One program = one (topic, tile, match-slot). The match slot is
    the innermost grid dim, so the output block stays resident in
    VMEM across the reduction; the input block for each slot is the
    matched row's tile, selected by the scalar-prefetched ids in the
    index_map (Pallas pipelines those HBM→VMEM streams)."""
    import jax.experimental.pallas as pl

    b = pl.program_id(0)
    m = pl.program_id(2)

    @pl.when(m == 0)
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when(ids_ref[b, m] >= 0)
    def _():
        out_ref[...] = out_ref[...] | bm_ref[...]


@functools.partial(jax.jit, static_argnames=("interpret",))
def or_bitmaps(bitmaps: jax.Array, rows: jax.Array,
               interpret: bool = False) -> jax.Array:
    """``out[b] = OR of bitmaps[rows[b, m]] for rows[b, m] >= 0``.

    ``rows`` is [B, mb] from :func:`rows_for_matches` (packed, -1
    padded; -1 slots are skipped). ``bitmaps`` is [R, W] with W a
    multiple of 1024 words (words_for guarantees this).
    """
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, mb = rows.shape
    R, W = bitmaps.shape
    assert W % _TILE2D == 0, (W, _TILE2D)
    wt = W // _TILE2D
    # chunk several (8, 128) tiles per program: per-program overhead
    # dominated at 1-tile blocks (measured 65ms → see commit); 64
    # tiles = 256 KB per stream block, and pow2 widths divide evenly
    blk = min(wt, 64)
    assert wt % blk == 0, (wt, blk)
    bm4 = bitmaps.reshape(R, wt, _SUB, _LANES)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, wt // blk, mb),
        in_specs=[
            pl.BlockSpec(
                (1, blk, _SUB, _LANES),
                lambda b, j, m, ids: (jnp.maximum(ids[b, m], 0), j, 0, 0)),
        ],
        out_specs=pl.BlockSpec(
            (1, blk, _SUB, _LANES), lambda b, j, m, ids: (b, j, 0, 0)),
    )
    out = pl.pallas_call(
        _or_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, wt, _SUB, _LANES), jnp.uint32),
        interpret=interpret,
    )(rows, bm4)
    return out.reshape(B, W)


def _or_kernel_dma(ids_ref, bm_ref, out_ref, buf, sem):
    """Manual double-buffered variant: the whole match-row loop runs
    inside one program; row tiles are DMA'd HBM→VMEM with two slots
    so slot m+1 streams while slot m is OR'd."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b = pl.program_id(0)
    j = pl.program_id(1)
    mb = ids_ref.shape[1]
    blk = out_ref.shape[1]

    nbuf = buf.shape[0]

    def dma(slot, m):
        row = jnp.maximum(ids_ref[b, m], 0)
        return pltpu.make_async_copy(
            bm_ref.at[row, pl.ds(j * blk, blk)],
            buf.at[slot], sem.at[slot])

    for w in range(min(nbuf - 1, mb)):
        @pl.when(ids_ref[b, w] >= 0)
        def _(w=w):
            dma(w, w).start()

    def body(m, acc):
        live = ids_ref[b, m] >= 0
        nxt = jnp.minimum(m + nbuf - 1, mb - 1)

        @pl.when(live & (m + nbuf - 1 < mb) & (ids_ref[b, nxt] >= 0))
        def _():
            dma((m + nbuf - 1) % nbuf, m + nbuf - 1).start()

        @pl.when(live)
        def _():
            dma(m % nbuf, m).wait()
        return jnp.where(live, acc | buf[m % nbuf], acc)

    acc = jax.lax.fori_loop(
        0, mb, body,
        jnp.zeros((blk, out_ref.shape[2], out_ref.shape[3]), jnp.uint32))
    out_ref[0] = acc


#: bytes of SMEM one call's scalar-prefetched ``rows`` may take. The
#: v5e has 1 MiB of SMEM and a 2-D int32 operand pads its minor dim
#: to 128 lanes there, so ``[B, 16]`` costs ``B × 512`` bytes: an
#: 8192-row batch asked for 4 MiB and was refused on the chip
#: (``RESOURCE_EXHAUSTED … prefetched SMEM operand 0``, PR 21).
#: Bigger batches are fed to the kernel in chunks of this many bytes.
_PREFETCH_BYTES = 512 * 1024


def _or_dma_call(bm4: jax.Array, rows: jax.Array, blk: int,
                 interpret: bool) -> jax.Array:
    """One ``pallas_call`` over ``rows`` ([B, mb], B small enough for
    SMEM) → ``[B, wt, 8, 128]``."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B = rows.shape[0]
    wt = bm4.shape[1]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, wt // blk),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(
            (1, blk, _SUB, _LANES), lambda b, j, ids: (b, j, 0, 0)),
        scratch_shapes=[
            # 2 slots measured best on v5e (4 slots regressed ~8x —
            # deeper in-flight DMA windows serialize on this part)
            pltpu.VMEM((2, blk, _SUB, _LANES), jnp.uint32),
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )
    return pl.pallas_call(
        _or_kernel_dma,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, wt, _SUB, _LANES), jnp.uint32),
        interpret=interpret,
    )(rows, bm4)


@functools.partial(jax.jit, static_argnames=("interpret",))
def or_bitmaps_dma(bitmaps: jax.Array, rows: jax.Array,
                   interpret: bool = False) -> jax.Array:
    """Same contract as :func:`or_bitmaps`, manual-DMA variant; any
    batch size (batches past the SMEM budget run the kernel once per
    chunk of rows)."""
    B, mb = rows.shape
    R, W = bitmaps.shape
    assert W % _TILE2D == 0, (W, _TILE2D)
    wt = W // _TILE2D
    blk = min(wt, 64)
    assert wt % blk == 0, (wt, blk)
    bm4 = bitmaps.reshape(R, wt, _SUB, _LANES)
    chunk = _PREFETCH_BYTES // (4 * _LANES * -(-mb // _LANES))
    if B <= chunk:
        return _or_dma_call(bm4, rows, blk, interpret).reshape(B, W)
    nc = -(-B // chunk)
    pad = nc * chunk - B
    if pad:  # -1 rows are skipped by the kernel
        rows = jnp.concatenate(
            [rows, jnp.full((pad, mb), -1, rows.dtype)])
    out = jax.lax.map(
        lambda r: _or_dma_call(bm4, r, blk, interpret),
        rows.reshape(nc, chunk, mb))
    return out.reshape(nc * chunk, W)[:B]


def or_bitmaps_auto(bitmaps: jax.Array, rows: jax.Array) -> jax.Array:
    """Manual-DMA Pallas on TPU; interpret-mode elsewhere (CPU tests)."""
    interp = jax.default_backend() != "tpu"
    return or_bitmaps_dma(bitmaps, rows, interpret=interp)
