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

One implementation, :func:`match_names_many`, jitted lax. The level
loop is unrolled (``L`` static), carrying one ``[F, cap]`` bool
accumulator, so peak memory never materializes ``[F, cap, L]``.

Unlike the publish side there is no ``has_hash`` static argument:
the batch mixes ``#``- and non-``#`` filters, so the flag rides as an
array input and compile count depends only on the (padded) shapes.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

#: '+' sentinel in an encoded FILTER row — never collides with real
#: word ids (≥0) or the topic-side UNKNOWN (-1) / PAD (-2); mirrors
#: retainer's encoding (the index owns the tokenization)
PLUS_ID = -3


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
