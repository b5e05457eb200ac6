"""Share of the traced slice in which no operation ran on the device:
100 * (1 - union of the device-op intervals / slice). Ops that overlap
count once. A slice without a length, or a trace without a device op,
is an error and never "100 % idle"."""

import stats


def busy_seconds(run: dict) -> float:
    ops = run["device_ops"]
    if not ops:
        raise ValueError("the trace holds no device operation")
    return stats.union_seconds([(s, d) for _n, s, d in ops])


def reduce(run: dict):
    if run.get("device_ops") is None:
        return None
    window = run["trace_window_s"]
    if not window > 0:
        raise ValueError(f"traced slice of {window!r} s")
    return 100.0 * (1.0 - busy_seconds(run) / window)
