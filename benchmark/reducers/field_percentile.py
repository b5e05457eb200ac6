"""A percentile of one of the run's sample arrays (seconds), by the
rule of ``stats.percentile``; ``scale`` turns seconds into the unit.
Prints the sample count, which the reader of a tail has to know."""

import stats


def reduce(run: dict, field: str, q: float, scale: float = 1000.0):
    xs = run.get(field)
    if xs is None or len(xs) == 0:
        return None
    print(f"samples: {field} n={len(xs)} for p{q:g}", flush=True)
    return stats.percentile(xs, q) * scale
