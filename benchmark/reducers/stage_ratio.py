"""Host time of some span stages (milliseconds on the host's clock,
summed over the window's publish spans), divided by a count of work,
for stages that not every program records: where no span of the
window carries any of ``stages`` (a program from before the stage
existed) there is nothing to read and the metric is left out, never
reported as 0. ``per`` is ``"spans"``, ``"field:<span field>"``
(summed) or ``"counter:<Metrics counter>"`` (its change over the
window)."""


def reduce(run: dict, stages: list, per: str, scale: float = 1.0):
    spans = run.get("spans")
    if not spans:
        return None
    if not any(st in s["stages"] for s in spans for st in stages):
        return None
    ms = sum(s["stages"].get(st, 0.0) for s in spans for st in stages)
    kind, _, name = per.partition(":")
    if kind == "spans":
        denom = len(spans)
    elif kind == "field":
        denom = sum(s[name] for s in spans)
    elif kind == "counter":
        denom = (run.get("counters") or {}).get(name, 0)
    else:
        raise ValueError(f"unknown denominator {per!r}")
    if not denom:
        return None
    return ms * scale / denom
