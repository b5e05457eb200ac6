#!/usr/bin/env python3
"""_spread.py <tag>... — the spreads of the two sets of runs that
``_sets.sh`` left under chiprun_out/: for each metric the distance
between the quartiles as a share of the median
(``statistics.quantiles(values, n=4)``), per set, and the wider."""

import glob
import json
import statistics
import sys


def spread(values):
    q1, _q2, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


for tag in sys.argv[1:]:
    sets = {}
    for path in sorted(glob.glob(f"chiprun_out/{tag}_s[12]_*.log")):
        which = path.split(f"{tag}_s")[1][0]
        with open(path) as f:
            last = f.read().strip().splitlines()[-1]
        try:
            out = json.loads(last)
        except ValueError:
            print(f"{path}: no result line")
            continue
        if not out["correct"]:
            print(f"{path}: correct is false")
        for name, m in out["metrics"].items():
            sets.setdefault(name, {}).setdefault(which, []).append(m["value"])
    for name, by_set in sets.items():
        row = []
        for which, vals in sorted(by_set.items()):
            row.append(f"set {which}: n={len(vals)} median "
                       f"{statistics.median(vals):.6g} spread "
                       f"{100 * spread(vals):.2f}% "
                       f"[{min(vals):.6g} .. {max(vals):.6g}]")
        wider = max(spread(v) for v in by_set.values() if len(v) >= 2)
        meds = [statistics.median(v) for _k, v in sorted(by_set.items())]
        drift = (meds[-1] / meds[0] - 1) if len(meds) == 2 else 0.0
        print(f"{tag} {name}: " + " | ".join(row)
              + f" | wider {100 * wider:.2f}% -> 5x = {500 * wider:.1f}%"
              + f" | set 2 median vs set 1: {100 * drift:+.2f}%")
