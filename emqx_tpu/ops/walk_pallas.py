"""VMEM-resident NFA walk — the Pallas variant of :mod:`ops.match`.

The lax.scan walk (:func:`emqx_tpu.ops.match.match_batch`) carries the
active-state frontier through the scan carry: every hop ends in a
fresh XLA op whose operands round-trip HBM, so a deep topic pays one
HBM latency per hop *on top of* the probe gathers (docs/PERF_NOTES.md
"gather-op count governs throughput"). This kernel runs the whole
walk for one topic inside a single Pallas program:

  - the frontier (≤ K packed lanes) lives in **VMEM scratch** across
    hops — between-hop state never leaves the chip;
  - the walk tables stay in HBM (``pl.ANY``) sized for 10M-sub scale;
    each hop DMAs exactly the probed rows (2 buckets + 1 ``node2``
    row per live lane) into VMEM scratch — the same rows the lax
    walk gathers, minus the per-hop dispatch/HBM-carry overhead;
  - the hop loop is **unrolled** (``steps`` is static, ≤ L+1), so
    emit stores use static indices and Mosaic sees straight-line
    vector code.

Byte-exact parity with ``match_batch`` is the contract (pinned by
tests/test_walk_pallas.py on CPU interpret mode): same probe math
(:func:`~emqx_tpu.ops.csr.hash_mix`), same exact inline chain-word
verify, same compaction order, same overflow semantics.

**Not selected by dispatch.** The TPU v5e compiler refuses this
kernel as written (PR 21, ROADMAP C2: ``(1, L)`` / ``(1, L, take)`` /
1-D ``(1,)`` blocks are not (8, 128)-tileable, and the body indexes
DMAs with scalars read out of VMEM refs), so it has only ever run in
interpret mode. :func:`match_batch_auto` therefore takes the lax walk
on every backend; ``EMQX_TPU_WALK=pallas`` forces this kernel for the
parity suite (interpreted off-TPU, compiled — and today refused — on
one).
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np

from emqx_tpu.ops.csr import (NARROW_SLOT, WIDE_SLOT, Automaton,
                              hash_mix)
from emqx_tpu.ops.match import (_LVL_BITS, _LVL_MASK, MatchResult,
                                match_batch)

#: probe-row gathers per live lane per hop: two 2-choice buckets +
#: one node2 terminal row (the bench's ``gathers_per_topic`` model)
GATHERS_PER_HOP = 3

#: env override for dispatch: "lax" (the default) or "pallas"
_WALK_ENV = "EMQX_TPU_WALK"


def walk_variant() -> str:
    """The walk implementation dispatch would select right now:
    ``"lax"`` — the walk the chip's compiler accepts — on every
    backend, unless the ``EMQX_TPU_WALK=pallas`` override forces the
    kernel (surfaces in ``ctl cache`` as the ``walk`` tag). The
    backend's name is deliberately not consulted: a kernel the
    compiler refuses is never the default."""
    return ("pallas" if os.environ.get(_WALK_ENV) == "pallas"
            else "lax")


def _compact_lanes(cands: jax.Array, k: int):
    """Kernel-side mirror of ``match._compact``: candidates ``[n]``
    (-1 invalid) → packed ``[k]`` + overflow scalar.

    ``match._compact`` sorts small sets (n ≤ 32) descending on a
    Batcher network and order-preserving-packs larger ones. Trie
    children are unique, so both reduce to a rank-select: descending
    value rank for the sorted branch, valid-prefix rank for the
    scatter branch — each implemented as a one-hot max (pure VPU
    compares, no dynamic scatter for Mosaic to choke on)."""
    n = cands.shape[0]
    valid = cands >= 0
    count = jnp.sum(valid)
    if n <= 32:
        # rank = number of strictly-larger candidates; valid values
        # are unique so this is exactly the descending sort position
        rank = jnp.sum(cands[:, None] > cands[None, :], axis=0)
    else:
        rank = jnp.cumsum(valid) - 1
    lane = jax.lax.broadcasted_iota(jnp.int32, (k, n), 0)
    sel = valid[None, :] & (rank[None, :] == lane)
    packed = jnp.max(jnp.where(sel, cands[None, :], -1), axis=1)
    return packed, count > k


def _walk_kernel(words_ref, win_ref, n_ref, sys_ref, seed_ref,
                 wt_ref, node2_ref, emits_ref, ovf_ref,
                 active_ref, sidx_ref, bb_ref, lvl_ref,
                 node_buf, row_buf, sem,
                 *, k, steps, slots, take, L, nb):
    """One program = one topic's full walk. See module doc."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    wide = take > 1
    sw = WIDE_SLOT if wide else NARROW_SLOT
    seed = seed_ref[0]
    n = n_ref[0]
    is_sys = sys_ref[0] > 0

    # frontier init: lane 0 at the root, packed lvl 0
    lane_iota = jax.lax.broadcasted_iota(jnp.int32, (1, k), 1)
    active_ref[...] = jnp.where(lane_iota == 0, 0, -1)
    ovf = jnp.zeros((), jnp.bool_)

    for s in range(steps):
        active = active_ref[0, :]
        if wide:
            state = jnp.where(active >= 0, active >> _LVL_BITS, -1)
            lvl = active & _LVL_MASK
            lvl_ref[...] = jnp.minimum(lvl, L - 1)[None, :]
        else:
            state = active
            w_s = words_ref[0, s] if s < L else jnp.int32(-2)
        alive = state >= 0
        s_idx = jnp.maximum(state, 0)
        sidx_ref[...] = s_idx[None, :]
        if wide:
            w0_probe = None  # per-lane window word, loaded below
        else:
            w0 = jnp.broadcast_to(w_s, state.shape)
        # bucket pair per lane — the same mix the builder placed with
        h1, h2 = hash_mix(
            state, w0 if not wide else jnp.zeros_like(state), seed)
        if not wide:
            bb_ref[0, :] = (h1 & jnp.uint32(nb - 1)).astype(jnp.int32)
            bb_ref[1, :] = (h2 & jnp.uint32(nb - 1)).astype(jnp.int32)

        win = None
        if wide:
            # per-lane word window [k, take] (dynamic level start)
            rows = []
            for i in range(k):
                li = lvl_ref[0, i]
                rows.append(win_ref[0, pl.ds(li, 1), :])
            win = jnp.concatenate(rows, axis=0)  # [k, take]
            w0 = win[:, 0]
            h1, h2 = hash_mix(state, w0, seed)
            bb_ref[0, :] = (h1 & jnp.uint32(nb - 1)).astype(jnp.int32)
            bb_ref[1, :] = (h2 & jnp.uint32(nb - 1)).astype(jnp.int32)

        # stream exactly the probed rows HBM→VMEM: 2 bucket rows + 1
        # node2 row per lane, all copies in flight before one wait
        copies = []
        for i in range(k):
            copies.append(pltpu.make_async_copy(
                node2_ref.at[sidx_ref[0, i]], node_buf.at[i],
                sem.at[i]))
            copies.append(pltpu.make_async_copy(
                wt_ref.at[bb_ref[0, i]], row_buf.at[2 * i],
                sem.at[k + 2 * i]))
            copies.append(pltpu.make_async_copy(
                wt_ref.at[bb_ref[1, i]], row_buf.at[2 * i + 1],
                sem.at[k + 2 * i + 1]))
        for c in copies:
            c.start()
        for c in copies:
            c.wait()

        node = node_buf[...]                       # [k, 4]
        plus_col, hashf_col, endf_col = (
            node[:, 0], node[:, 1], node[:, 2])
        if wide:
            at_root_sys = (active == 0) & is_sys
            walking = alive & (lvl < n)
            ending = alive & (lvl == n)
        else:
            at_root_sys = ((jnp.int32(s) == 0) & is_sys) & alive
            walking = alive & (s < n)
            ending = alive & (s == n)
        emit_h = jnp.where(
            (walking | ending) & ~at_root_sys, hashf_col, -1)
        emit_e = jnp.where(ending, endf_col, -1)

        # probe both buckets' rows as one [k, 2*slots] candidate set
        # (max over the union ≡ match_batch's max of per-bucket maxes)
        row = row_buf[...].reshape((k, 2 * slots, sw))
        if wide:
            stake = row[..., 2]
            hit = (row[..., 0] == state[:, None]) & (
                row[..., 1] == win[:, None, 0])
            for i in range(take - 1):
                hit &= (stake <= i + 1) | (
                    row[..., 4 + i] == win[:, None, 1 + i])
            hit &= lvl[:, None] + stake <= n
            child = jnp.max(jnp.where(hit, row[..., 3], -1), axis=1)
            adv = jnp.max(jnp.where(hit, stake, 0), axis=1)
            lit_ok = walking & (w0 >= 0) & (child >= 0)
            lit = jnp.where(
                lit_ok, (child << _LVL_BITS) | (lvl + adv), -1)
            plus_ok = walking & ~at_root_sys & (plus_col >= 0)
            plus = jnp.where(
                plus_ok,
                (jnp.maximum(plus_col, 0) << _LVL_BITS) | (lvl + 1),
                -1)
        else:
            hit = (row[..., 0] == state[:, None]) & (
                row[..., 1] == w0[:, None])
            lit = jnp.max(jnp.where(hit, row[..., 2], -1), axis=1)
            lit = jnp.where(walking & (w0 >= 0), lit, -1)
            plus = jnp.where(walking & ~at_root_sys, plus_col, -1)

        nxt, over = _compact_lanes(jnp.concatenate([lit, plus]), k)
        ovf = ovf | over
        active_ref[...] = nxt[None, :]
        emits_ref[0, s, :] = jnp.concatenate([emit_h, emit_e])

    # residue: lanes alive after the last hop were never processed —
    # flag for the exact host fallback (match_batch's check, verbatim)
    residue = active_ref[0, :]
    if wide:
        r_lvl = residue & _LVL_MASK
        ovf = ovf | jnp.any((residue >= 0) & (r_lvl <= n))
    else:
        ovf = ovf | jnp.any((residue >= 0) & (steps <= n))
    ovf_ref[0, 0] = ovf.astype(jnp.int32)


@functools.partial(jax.jit,
                   static_argnames=("k", "m", "steps", "slots", "take",
                                    "pack_ids", "interpret"))
def match_batch_pallas(
    auto: Automaton,
    word_ids: jax.Array,   # int32[B, L]
    n_words: jax.Array,    # int32[B]
    sys_mask: jax.Array,   # bool[B]
    *,
    k: int = 16,
    m: int = 64,
    steps: int | None = None,
    slots: int = 2,
    take: int = 1,
    pack_ids: bool = True,
    interpret: bool = False,
) -> MatchResult:
    """Drop-in replacement for :func:`ops.match.match_batch` — same
    signature, same ``MatchResult``, byte-identical output."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, L = word_ids.shape
    if steps is None:
        steps = L + 1
    wide = take > 1
    if wide and L > _LVL_MASK:
        raise ValueError(
            f"wide walk supports at most {_LVL_MASK} levels, got {L}")
    sw = WIDE_SLOT if wide else NARROW_SLOT
    nb = auto.wt.shape[0]

    # word windows [B, L, take]: win[b, l] = words[l : l+take] padded
    # with -2 beyond the topic (the same construction match_batch's
    # wide path builds per topic)
    wp = jnp.concatenate(
        [word_ids, jnp.full((B, take), -2, jnp.int32)], axis=1)
    win_mat = jnp.stack([wp[:, l:l + take] for l in range(L)], axis=1)

    kern = functools.partial(
        _walk_kernel, k=k, steps=steps, slots=slots, take=take,
        L=L, nb=nb)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=0,
        grid=(B,),
        in_specs=[
            pl.BlockSpec((1, L), lambda b: (b, 0)),
            pl.BlockSpec((1, L, take), lambda b: (b, 0, 0)),
            pl.BlockSpec((1,), lambda b: (b,)),
            pl.BlockSpec((1,), lambda b: (b,)),
            pl.BlockSpec((1,), lambda b: (0,)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=[
            pl.BlockSpec((1, steps, 2 * k), lambda b: (b, 0, 0)),
            pl.BlockSpec((1, 1), lambda b: (b, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((1, k), jnp.int32),       # frontier
            pltpu.VMEM((1, k), jnp.int32),       # node2 row indices
            pltpu.VMEM((2, k), jnp.int32),       # bucket pair
            pltpu.VMEM((1, k), jnp.int32),       # clamped levels
            pltpu.VMEM((k, 4), jnp.int32),       # node2 rows
            pltpu.VMEM((2 * k, slots * sw), jnp.int32),  # probe rows
            pltpu.SemaphoreType.DMA((3 * k,)),
        ],
    )
    emits, ovf_i = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, steps, 2 * k), jnp.int32),
            jax.ShapeDtypeStruct((B, 1), jnp.int32),
        ],
        interpret=interpret,
    )(word_ids, win_mat, n_words,
      sys_mask.astype(jnp.int32), auto.wt_seed, auto.wt, auto.node2)

    # tail identical to match_batch (packing / overflow composition)
    ovf = ovf_i[:, 0] > 0
    flat = emits.reshape(B, -1)
    valid = flat >= 0
    cnt = jnp.sum(valid, axis=1)
    too_long = n_words < 0
    if pack_ids:
        pos = jnp.cumsum(valid, axis=1) - 1
        ids = jnp.full((B, m), -1, dtype=flat.dtype).at[
            jnp.arange(B)[:, None],
            jnp.where(valid, pos, m)].set(flat, mode="drop")
        return MatchResult(
            ids=jnp.where(too_long[:, None], -1, ids),
            count=jnp.where(too_long, 0,
                            jnp.minimum(cnt, m)).astype(jnp.int32),
            overflow=ovf | (cnt > m) | too_long,
        )
    return MatchResult(
        ids=jnp.where(too_long[:, None], -1, flat),
        count=jnp.where(too_long, 0, cnt).astype(jnp.int32),
        overflow=ovf | too_long,
    )


def match_batch_auto(auto, word_ids, n_words, sys_mask, *, k=16, m=64,
                     steps=None, slots=2, take=1,
                     pack_ids=True) -> MatchResult:
    """Dispatch seam the router and delta probes call: the lax.scan
    walk, unless ``EMQX_TPU_WALK=pallas`` forces the kernel (see
    :func:`walk_variant`). Byte parity between the two is pinned."""
    if walk_variant() == "pallas":
        # the forced override on a non-TPU backend runs the kernel in
        # interpret mode: slow, but byte-exact — how the parity suite
        # drives this dispatch path on CPU. On a TPU it is compiled,
        # never interpreted
        interp = jax.default_backend() != "tpu"
        return match_batch_pallas(
            auto, word_ids, n_words, sys_mask, k=k, m=m, steps=steps,
            slots=slots, take=take, pack_ids=pack_ids,
            interpret=interp)
    return match_batch(
        auto, word_ids, n_words, sys_mask, k=k, m=m, steps=steps,
        slots=slots, take=take, pack_ids=pack_ids)


def fetch_walk_result(res: MatchResult):
    """The walk's coalesced device→host transfer seam (parity suites,
    deep_smoke): ONE fetch materializing all three result arrays —
    the only sanctioned sync on the walk path (DP301 whitelist)."""
    ids, cnt, ovf = jax.device_get((res.ids, res.count, res.overflow))
    return np.asarray(ids), np.asarray(cnt), np.asarray(ovf)
