"""The change of one ``Metrics`` counter over the measured window."""


def reduce(run: dict, counter: str):
    return run.get("counters", {}).get(counter)
