"""The change of some ``Metrics`` counters over the measured window,
summed, as a share of the summed change of the counters in ``of`` (a
numerator counter may be among them). A program that does not register
every counter named has nothing to read here, and neither has a window
in which none of ``of`` moved: the metric is left out, never reported
as 0."""


def reduce(run: dict, counters: list, of: list, scale: float = 1.0):
    have = run.get("counters") or {}
    if any(name not in have for name in counters + of):
        return None
    denom = sum(have[name] for name in of)
    if not denom:
        return None
    return sum(have[name] for name in counters) * scale / denom
