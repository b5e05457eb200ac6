"""Mean of one field of the window's publish spans."""


def reduce(run: dict, field: str):
    spans = run.get("spans")
    if not spans:
        return None
    return sum(s[field] for s in spans) / len(spans)
