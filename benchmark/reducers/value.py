"""A reading the harness took itself, as it stands (``setup_s``)."""


def reduce(run: dict, field: str):
    return run.get(field)
