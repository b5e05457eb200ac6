"""Arithmetic the metrics share: the percentile rule and the union of
intervals. Kept with the benchmark so that no later PR can move it."""

from __future__ import annotations

import math


def percentile(values, q: float) -> float:
    """The ``q``-th percentile (nearest rank) of ``values``. Refuses a
    percentile that has fewer than ten samples beyond it: such a tail
    is a reading of a handful of requests, not of the system."""
    n = len(values)
    if not 0.0 < q < 100.0:
        raise ValueError(f"percentile {q} is not inside (0, 100)")
    beyond = n - math.ceil(n * q / 100.0)
    if beyond < 10:
        raise ValueError(
            f"p{q:g} of {n} samples has {beyond} beyond it; ten are needed")
    xs = sorted(values)
    return float(xs[math.ceil(n * q / 100.0) - 1])


def union_seconds(intervals) -> float:
    """Total length covered by ``(start, duration)`` pairs; where they
    overlap the time counts once."""
    total = 0.0
    end = None
    for start, dur in sorted(intervals):
        stop = start + dur
        if end is None or start > end:
            total += dur
            end = stop
        elif stop > end:
            total += stop - end
            end = stop
    return total


def gaps(intervals) -> list:
    """The idle stretches between the covered ones, as ``(start,
    duration, index of the interval that ends the gap)``; ``intervals``
    as for :func:`union_seconds`."""
    out = []
    end = None
    order = sorted(range(len(intervals)), key=lambda i: intervals[i])
    for i in order:
        start, dur = intervals[i]
        if end is not None and start > end:
            out.append((end, start - end, i))
        end = start + dur if end is None else max(end, start + dur)
    return out


def short_op_name(name: str) -> str:
    """The trace names an op by its whole HLO line (``%fusion.12 =
    s32[...] fusion(...)``); keep the result's name."""
    return name.partition(" = ")[0].strip()[:80]
