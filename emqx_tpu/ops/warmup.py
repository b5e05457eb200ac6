"""Kernel re-warm planning for device-loss recovery.

After :meth:`Router.rebuild_device_state` publishes fresh tables on a
fresh backend, the walk/fetch jit kernels for the batch shapes live
traffic actually uses must be executed once OFF the hot path — the
first post-recovery publish batch must pay zero compile
(docs/ROBUSTNESS.md "Device-loss recovery").

This module is pure host planning (no jax imports, nothing to sync —
the device work happens in ``Broker.warm_device_path``, which drives
the REAL ``_begin_device``/``_fetch_device`` seams over the batches
planned here, so exactly the production kernel set compiles: encode →
walk (cache-miss shape) → pack → fan-out expand → bundle → fetch).

Synthetic warm topics are rooted at ``"\\x00devloss"`` — no real
filter matches them (MQTT topics cannot contain NUL), so a warm batch
delivers nothing, and their match-cache entries are ordinary slots
that age out under the clock sweep.
"""

from __future__ import annotations

from typing import Iterable, List, Tuple

#: bound on warm batches per recovery: the floor bucket plus the
#: largest observed live buckets (each is one compile family)
MAX_WARM_BUCKETS = 4


def warm_buckets(observed: Iterable[int], min_batch: int,
                 cap: int = MAX_WARM_BUCKETS) -> List[int]:
    """The padded-batch buckets worth warming: the configured floor
    bucket (every small batch lands there) plus the largest buckets
    live traffic was actually seen using (``Broker._pack_budgets``
    keys — the budget table is learned per bucket, so its key set IS
    the observed shape set)."""
    buckets = sorted({int(b) for b in observed if int(b) > 0}
                     | {int(min_batch)})
    return buckets[-max(1, cap):]


def warm_topics(bucket: int, min_batch: int,
                levels: int = 4) -> List[str]:
    """A unique-topic list whose padded dispatch lands exactly in
    ``bucket``: the dispatch pads to the smallest power-of-two bucket
    ≥ the topic count (floored at ``min_batch``), so ``bucket//2 + 1``
    topics select ``bucket`` for any bucket above the floor.

    ``levels`` pins the batch's level-bucket shape: the walk slices
    its level axis to the batch's deepest topic (``depth_bucket``)
    and compiles per resulting depth, so the FIRST topic carries
    exactly ``levels`` levels — one deep spine is enough to select
    the compile family, the rest stay short."""
    n = 1 if bucket <= min_batch else bucket // 2 + 1
    out = ["\x00devloss/warm/%d/%d" % (bucket, i) for i in range(n)]
    spine = ["\x00devloss", "warm", str(bucket), "0"][:max(2, levels)]
    spine += ["d"] * (max(2, levels) - len(spine))
    out[0] = "/".join(spine)
    return out


def warm_plan(observed: Iterable[int], min_batch: int,
              cap: int = MAX_WARM_BUCKETS,
              levels: Iterable[int] = ()
              ) -> List[Tuple[int, List[str]]]:
    """``(bucket, topics)`` warm batches, smallest bucket first (the
    floor bucket compiles fastest — recovery reaches "some shape is
    warm" as early as possible). ``levels`` is the set of observed
    level-bucket shapes (``Router.observed_levels``) — each is its
    own compile family, so every bucket replays every depth; the
    compressed-walk deep buckets (16-level spines, ISSUE 16) warm
    here exactly like the shallow ones. Empty = the historical
    4-level shape only."""
    lvls = sorted({int(l) for l in levels if int(l) >= 2}) or [4]
    return [(b, warm_topics(b, min_batch, lv))
            for b in warm_buckets(observed, min_batch, cap)
            for lv in lvls]
