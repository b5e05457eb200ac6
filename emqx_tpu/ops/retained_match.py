"""Batched retained-name matching — the subscribe-path analogue of
the publish walk.

The retained index (:class:`emqx_tpu.modules.retainer.RetainIndex`)
keeps stored topic NAMES as a persistent ``[cap, L]`` word-id matrix;
a wildcard subscribe matches a filter against every stored name with
a pure elementwise program (per level: equality or ``+``, a ``#``
suffix relaxing the depth check, the ``$``-root rule masking system
topics — no automaton walk, no gathers). Until PR 19 that kernel took
ONE filter per dispatch, so a subscribe burst — session resume,
reconnect storm, shared-group rebalance — paid one device round-trip
per resumed subscription. This module batches the filter side too:

  ``[F, L]`` encoded filters × ``[cap, L]`` stored names → ``[F, cap]``
  hit matrix, one dispatch per burst.

Two implementations, byte-parity pinned (tests/test_retained_replay):

  - :func:`match_names_many` — the jitted lax baseline. The level
    loop is unrolled (``L`` static), carrying one ``[F, cap]`` bool
    accumulator, so peak memory never materializes ``[F, cap, L]``.
  - :func:`match_names_many_pallas` — the Pallas variant: grid over
    (filter-block × name-block) tiles, each program ANDing its
    ``[BF, BN]`` tile entirely in VMEM. Elementwise and HBM-bandwidth
    bound, like the publish fan-out kernels.

Dispatch (:func:`match_names_auto`) follows the walk seam
(:func:`~emqx_tpu.ops.walk_pallas.walk_variant`): the lax body on
every backend — at the index's deployment capacity (2^20 names) the
v5e compiler refuses the Pallas tiles (``Scoped allocation with size
17.19M and limit 16.00M exceeded scoped vmem limit``: the ``(512, 8)``
/ ``(512, 1)`` int32 blocks pad to full 128-lane tiles, PR 21,
ROADMAP C2) — with ``EMQX_TPU_WALK=pallas`` forcing the kernel for
the parity suite (interpret mode off-TPU, slow and byte-exact).

Unlike the publish side there is no ``has_hash`` static argument:
the batch mixes ``#``- and non-``#`` filters, so the flag rides as an
array input and compile count depends only on the (padded) shapes.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from emqx_tpu.ops.walk_pallas import walk_variant

#: '+' sentinel in an encoded FILTER row — never collides with real
#: word ids (≥0) or the topic-side UNKNOWN (-1) / PAD (-2); mirrors
#: retainer's encoding (the index owns the tokenization)
PLUS_ID = -3

#: Pallas tile: filters per program × names per program. Elementwise
#: work, so the tile only has to amortize grid overhead; BN spans
#: whole VPU lanes, BF keeps a burst's worth of filters per program.
_BF = 8
_BN = 512


def _match_many_body(fw, fn, has_hash, topic_ids, n_words, sys_mask):
    """``[F, L]`` filters vs ``[cap, L]`` names → ``[F, cap]`` bool.

    ``fw`` filter word ids (``PLUS_ID`` for ``+``, PAD beyond ``fn``);
    ``fn`` per-filter word count excluding a trailing ``#``;
    ``has_hash`` the trailing-``#`` flag per filter. Semantics =
    emqx_topic:match/2 exactly as the old one-filter kernel: per-level
    equality with ``+`` wildcards; a ``#`` suffix matches the parent
    itself and anything deeper (src/emqx_topic.erl:64-87); root
    wildcards never match ``$``-topics (src/emqx_trie.erl:162-163).
    Dead rows have ``n_words == 0`` — excluded by the ``n > 0`` live
    gate. A padding filter row (``fn == 0``, no ``#``) matches
    nothing for the same reason."""
    L = topic_ids.shape[1]
    fnc = fn[:, None]                                    # [F, 1]
    ok = jnp.ones((fw.shape[0], topic_ids.shape[0]), dtype=jnp.bool_)
    for lvl in range(L):                                 # L static
        w = fw[:, lvl][:, None]                          # [F, 1]
        ok &= ((topic_ids[:, lvl][None, :] == w) | (w == PLUS_ID)
               | (lvl >= fnc))
    nw = n_words[None, :]                                # [1, cap]
    exact = ok & (nw == fnc)
    deeper = has_hash[:, None] & ok & (nw >= fnc)
    hit = (exact | deeper) & (nw > 0)
    root_wild = (fw[:, 0] == PLUS_ID) | (has_hash & (fn == 0))
    return hit & ~(sys_mask[None, :] & root_wild[:, None])


# jit once; shapes vary only with the padded burst size and the index
# capacity (both power-of-two) so compile count stays log² bounded
match_names_many = jax.jit(_match_many_body)


def _retained_kernel(fw_ref, fn_ref, hh_ref, ids_ref, n_ref, sys_ref,
                     out_ref, *, L):
    """One program = one ``[BF, BN]`` tile of the hit matrix; the
    same elementwise math as :func:`_match_many_body`, all operands
    block-copied into VMEM by the BlockSpecs."""
    fw = fw_ref[...]                                     # [BF, L]
    fn = fn_ref[...][:, 0]                               # [BF]
    hh = hh_ref[...][:, 0] > 0
    ids = ids_ref[...]                                   # [BN, L]
    nw = n_ref[...][:, 0][None, :]                       # [1, BN]
    sysm = sys_ref[...][:, 0] > 0
    fnc = fn[:, None]
    ok = jnp.ones((fw.shape[0], ids.shape[0]), dtype=jnp.bool_)
    for lvl in range(L):
        w = fw[:, lvl][:, None]
        ok &= ((ids[:, lvl][None, :] == w) | (w == PLUS_ID)
               | (lvl >= fnc))
    exact = ok & (nw == fnc)
    deeper = hh[:, None] & ok & (nw >= fnc)
    hit = (exact | deeper) & (nw > 0)
    root_wild = (fw[:, 0] == PLUS_ID) | (hh & (fn == 0))
    out_ref[...] = (hit & ~(sysm[None, :] & root_wild[:, None])
                    ).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("interpret",))
def match_names_many_pallas(fw, fn, has_hash, topic_ids, n_words,
                            sys_mask, *, interpret: bool = False):
    """Pallas twin of :func:`match_names_many` — same arguments, same
    ``[F, cap]`` bool result, byte-identical output."""
    import jax.experimental.pallas as pl

    F, L = fw.shape
    N = topic_ids.shape[0]
    Fp = -(-F // _BF) * _BF
    Np = -(-N // _BN) * _BN
    if Fp != F:
        # padding filter rows: fn=0 without '#' matches nothing
        pad = Fp - F
        fw = jnp.concatenate([fw, jnp.full((pad, L), -2, fw.dtype)])
        fn = jnp.concatenate([fn, jnp.zeros((pad,), fn.dtype)])
        has_hash = jnp.concatenate(
            [has_hash, jnp.zeros((pad,), has_hash.dtype)])
    if Np != N:
        # padding name rows: n_words=0 fails the live gate
        pad = Np - N
        topic_ids = jnp.concatenate(
            [topic_ids, jnp.full((pad, L), -2, topic_ids.dtype)])
        n_words = jnp.concatenate(
            [n_words, jnp.zeros((pad,), n_words.dtype)])
        sys_mask = jnp.concatenate(
            [sys_mask, jnp.zeros((pad,), sys_mask.dtype)])
    out = pl.pallas_call(
        functools.partial(_retained_kernel, L=L),
        grid=(Fp // _BF, Np // _BN),
        in_specs=[
            pl.BlockSpec((_BF, L), lambda f, t: (f, 0)),
            pl.BlockSpec((_BF, 1), lambda f, t: (f, 0)),
            pl.BlockSpec((_BF, 1), lambda f, t: (f, 0)),
            pl.BlockSpec((_BN, L), lambda f, t: (t, 0)),
            pl.BlockSpec((_BN, 1), lambda f, t: (t, 0)),
            pl.BlockSpec((_BN, 1), lambda f, t: (t, 0)),
        ],
        out_specs=pl.BlockSpec((_BF, _BN), lambda f, t: (f, t)),
        out_shape=jax.ShapeDtypeStruct((Fp, Np), jnp.int32),
        interpret=interpret,
    )(fw, fn[:, None].astype(jnp.int32),
      has_hash[:, None].astype(jnp.int32),
      topic_ids, n_words[:, None].astype(jnp.int32),
      sys_mask[:, None].astype(jnp.int32))
    return out[:F, :N] > 0


def match_names_auto(fw, fn, has_hash, topic_ids, n_words, sys_mask):
    """Dispatch seam the retained index calls: the lax body, unless
    ``EMQX_TPU_WALK=pallas`` forces the Pallas tiles (module doc).
    Byte parity between the two is pinned."""
    if walk_variant() == "pallas":
        interp = jax.default_backend() != "tpu"
        return match_names_many_pallas(
            fw, fn, has_hash, topic_ids, n_words, sys_mask,
            interpret=interp)
    return match_names_many(fw, fn, has_hash, topic_ids, n_words,
                            sys_mask)
