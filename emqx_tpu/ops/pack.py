"""Device-side compaction of match + fan-out results for transfer.

The product publish path ends with a device→host hand-off: the host
delivery tail needs each message's matched filter ids and gathered
subscriber ids. Fetching the *dense* kernel outputs (``ids[B, M]``,
``subs/src[B, d]`` with d=1024) moves megabytes of ``-1`` padding per
batch — pure waste on the host link, which is the classic accelerator
serving bottleneck (and the reference never materializes padding at
all: its trie match returns exactly the matched set,
``src/emqx_trie.erl:161-186``).

So the last device step packs the sparse results into CSR-style
buffers sized by a static *budget*: a global cumsum assigns each valid
element its output slot, a drop-mode scatter writes them, and the
per-row counts become a row-pointer array. The host then transfers

    m_ptr[B+1], packed_ids[PM], f_ptr[B+1], packed_subs[PQ],
    packed_src[PQ]

— tens of kilobytes instead of megabytes. Budgets are power-of-two
bucketed (one compiled program per bucket, like the batch buckets);
when a batch's true totals exceed the budget the caller re-packs with
the next bucket (the totals are ``m_ptr[-1]``/``f_ptr[-1]``, so
detection costs nothing extra).

Big-filter (bitmap) fan-out rows compact the same way: only rows that
actually matched a big filter transfer (``pack_union_rows``), so a
batch with no big-fan-out traffic moves zero bitmap bytes.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from emqx_tpu.ops.fanout import expand_packed


@jax.jit
def mask_pad_rows(ids: jax.Array, n_rows: jax.Array) -> jax.Array:
    """Blank the batch's padding rows (row index ≥ ``n_rows``) to -1.

    The matcher pads batches to a power-of-two bucket with a dummy
    topic; wildcard filters (``#``) can match it, and without this
    mask those phantom rows inflate the packed totals — and the
    learned budgets — by (bucket − B) × fan-out. ``n_rows`` is a
    traced scalar so every batch size in a bucket shares one compile.
    """
    row = jnp.arange(ids.shape[0], dtype=jnp.int32)
    return jnp.where((row < n_rows)[:, None], ids, -1)


@jax.jit
def mask_pad_flags(flags: jax.Array, n_rows: jax.Array) -> jax.Array:
    """Clear per-row bool flags on the batch's padding rows (the
    bool analogue of :func:`mask_pad_rows`)."""
    row = jnp.arange(flags.shape[0], dtype=jnp.int32)
    return flags & (row < n_rows)


def budget_for(n_rows: int, per_row: int, floor: int = 64) -> int:
    """Power-of-two packed-buffer budget for ``n_rows`` rows at an
    expected ``per_row`` average occupancy."""
    need = max(floor, n_rows * per_row)
    out = floor
    while out < need:
        out *= 2
    return out


@functools.partial(jax.jit, static_argnames=("pm",))
def pack_matches(ids: jax.Array, *, pm: int):
    """Compact ``ids[B, M]`` (-1 padded) into a CSR pair.

    Returns ``(m_ptr[B+1], packed_ids[pm])``; ``m_ptr[-1]`` is the
    true total — if it exceeds ``pm`` the tail was dropped and the
    caller must re-pack with a larger budget.
    """
    flat = ids.reshape(-1)
    valid = flat >= 0
    cnt = (ids >= 0).sum(axis=1, dtype=jnp.int32)
    m_ptr = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), jnp.cumsum(cnt, dtype=jnp.int32)])
    pos = jnp.cumsum(valid.astype(jnp.int32)) - 1
    tgt = jnp.where(valid, pos, pm)  # pm = out of range → dropped
    packed = jnp.full((pm,), -1, jnp.int32).at[tgt].set(flat, mode="drop")
    return m_ptr, packed


@functools.partial(jax.jit, static_argnames=("pq",))
def pack_fanout(subs: jax.Array, src: jax.Array, *, pq: int):
    """Compact the gathered ``(subs, src)[B, d]`` pair (same -1
    padding positions in both) into one CSR triple.

    Returns ``(f_ptr[B+1], packed_subs[pq], packed_src[pq])`` with the
    same overflow contract as :func:`pack_matches`.
    """
    flat_subs = subs.reshape(-1)
    flat_src = src.reshape(-1)
    valid = flat_subs >= 0
    cnt = (subs >= 0).sum(axis=1, dtype=jnp.int32)
    f_ptr = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), jnp.cumsum(cnt, dtype=jnp.int32)])
    pos = jnp.cumsum(valid.astype(jnp.int32)) - 1
    tgt = jnp.where(valid, pos, pq)
    packed_subs = jnp.full((pq,), -1, jnp.int32).at[tgt].set(
        flat_subs, mode="drop")
    packed_src = jnp.full((pq,), -1, jnp.int32).at[tgt].set(
        flat_src, mode="drop")
    return f_ptr, packed_subs, packed_src


@jax.jit
def bundle_i32(*parts: jax.Array) -> jax.Array:
    """Concatenate heterogeneous packed outputs into ONE int32 vector.

    A device→host fetch pays per-buffer round-trip latency on the
    host link; bundling the whole packed result set (row pointers,
    packed ids/subs/src, overflow flags, bitmap rows — bools widen,
    uint32 bitcasts) into a single buffer makes the publish path's
    fetch exactly one transfer. The host slices it apart with the
    statically known section sizes (see ``Broker.publish_fetch``).
    """
    flat = []
    for p in parts:
        if p.dtype == jnp.uint32:
            p = jax.lax.bitcast_convert_type(p, jnp.int32)
        elif p.dtype != jnp.int32:
            p = p.astype(jnp.int32)
        flat.append(p.reshape(-1))
    return jnp.concatenate(flat)


@functools.partial(jax.jit, static_argnames=("pm", "pq"))
def pack_mesh(ids: jax.Array, subs: jax.Array, src: jax.Array,
              ovf: jax.Array, movf: jax.Array, *, pm: int, pq: int):
    """:func:`pack_matches`, :func:`pack_fanout` and the fetch's
    :func:`bundle_i32` of one mesh batch as ONE program, keyed by
    (batch bucket, ``pm``, ``pq``): the event loop enqueues one packer
    a batch, and a grown budget costs one new program a bucket.
    Returns ``(m_ptr, packed_ids, f_ptr, packed_subs, packed_src,
    bundle)``, ``bundle`` in ``Broker._fetch_device``'s order for a
    batch without bitmap rows; the fetch's re-pack on overflow still
    calls the two packers apart."""
    m_ptr, packed_ids = pack_matches(ids, pm=pm)
    f_ptr, packed_subs, packed_src = pack_fanout(subs, src, pq=pq)
    return (m_ptr, packed_ids, f_ptr, packed_subs, packed_src,
            bundle_i32(m_ptr, packed_ids, ovf, movf, f_ptr, packed_subs,
                       packed_src))


@functools.partial(jax.jit, static_argnames=("pm", "pq"))
def pack_chip(fan, ids: jax.Array, ovf: jax.Array, *, pm: int, pq: int):
    """:func:`pack_matches`, ``ops/fanout.expand_packed`` and the
    fetch's :func:`bundle_i32` of one one-chip batch as ONE program
    (the twin of :func:`pack_mesh`), keyed by (batch bucket, ``pm``,
    ``pq``) with the fan-out table ``fan`` an argument (None = no
    local subscriber: matches only, ``pq`` unused). Returns ``(m_ptr,
    packed_ids, f_ptr, packed_subs, packed_src, bundle)``, ``bundle``
    in ``Broker._fetch_device``'s order for a batch without bitmap
    rows, so the fetch's thread launches nothing before its transfer;
    the fetch's re-pack on overflow still calls the packers apart."""
    m_ptr, packed_ids = pack_matches(ids, pm=pm)
    if fan is None:
        return (m_ptr, packed_ids, None, None, None,
                bundle_i32(m_ptr, packed_ids, ovf))
    f_ptr, packed_subs, packed_src, _total = expand_packed(
        fan, m_ptr, packed_ids, q=pq)
    return (m_ptr, packed_ids, f_ptr, packed_subs, packed_src,
            bundle_i32(m_ptr, packed_ids, ovf, f_ptr, packed_subs,
                       packed_src))


@functools.partial(jax.jit, static_argnames=("pr",))
def pack_union_rows(union: jax.Array, has_big: jax.Array, *, pr: int):
    """Compact the bitmap-union rows: only rows with ``has_big`` set
    (the row matched ≥1 big filter) are materialized.

    Returns ``(sel[B], rows[pr, W], total)`` where ``sel[b]`` is the
    packed row index for message ``b`` (-1 = no big match) and
    ``total`` > ``pr`` signals budget overflow (re-pack bigger).
    """
    hb = has_big.astype(jnp.int32)
    pos = jnp.cumsum(hb) - 1
    sel = jnp.where(has_big, pos, -1).astype(jnp.int32)
    tgt = jnp.where(has_big, pos, pr)
    rows = jnp.zeros((pr, union.shape[1]), union.dtype).at[tgt].set(
        union, mode="drop")
    return sel, rows, jnp.sum(hb)
