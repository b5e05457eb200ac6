"""The device operations that took most time in the traced slice, by
the names the trace prints: ``[[name, seconds], ...]``."""

from collections import defaultdict

import stats


def reduce(run: dict, n: int = 10):
    if run.get("device_ops") is None:
        return None
    total = defaultdict(float)
    for name, _start, dur in run["device_ops"]:
        total[stats.short_op_name(name)] += dur
    top = sorted(total.items(), key=lambda kv: -kv[1])[:n]
    return [[name, secs] for name, secs in top]
