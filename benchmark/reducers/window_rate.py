"""A count over the whole measured window, per second of the window."""


def reduce(run: dict, field: str):
    if run.get(field) is None:
        return None
    return run[field] / run["window_s"]
