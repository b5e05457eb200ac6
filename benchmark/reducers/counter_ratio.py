"""The change of some ``Metrics`` counters over the measured window,
summed, divided by ``per``: ``"counter:<name>"`` (that counter's
change), ``"window"`` (the window's seconds) or ``"one"``. A program
that does not register every one of ``counters`` has nothing to read
here: the metric is left out, never reported as 0."""


def reduce(run: dict, counters: list, per: str = "one",
           scale: float = 1.0):
    have = run.get("counters") or {}
    if any(name not in have for name in counters):
        return None
    total = sum(have[name] for name in counters)
    kind, _, name = per.partition(":")
    if kind == "one":
        denom = 1
    elif kind == "window":
        denom = run["window_s"]
    elif kind == "counter":
        denom = have.get(name, 0)
    else:
        raise ValueError(f"unknown denominator {per!r}")
    if not denom:
        return None
    return total * scale / denom
