"""The longest stretches of the traced slice in which nothing ran on
the device, each named by the operation that ended it (the trace has
no host spans on its clock yet): ``[[name, seconds], ...]``."""

import stats


def reduce(run: dict, n: int = 10):
    ops = run.get("device_ops")
    if not ops:
        return None
    found = stats.gaps([(s, d) for _n, s, d in ops])
    found.sort(key=lambda g: -g[1])
    return [["before " + stats.short_op_name(ops[i][0]), dur] for _s, dur, i in found[:n]]
