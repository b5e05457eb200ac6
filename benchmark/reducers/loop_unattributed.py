"""Share of the event loop's busy time in which it did something that
has no name: 100 * (busy - named) / busy. Busy is the program's own
wall-clock counter ``wall`` less ``idle``, the time inside the
selector; named is the publish spans' on-loop ``stages``
(milliseconds, less the ``nested`` stage: collections inside them,
which the collector's own counter holds) plus the change of the
``counters`` (nanoseconds, each exclusive of what nested in it: read
chunks, flush wake-ups, collections). Of busy time and not of the
window, because the harness reads the counters from a little before
the window's first message until the run's side work is done — a
traced run waits there, in the selector, for the profiler to stop —
and the spans it keeps are those of the same stretch. A program
without those counters has nothing to read here."""


def reduce(run: dict, stages: list, nested: str, counters: list,
           wall: str, idle: str):
    spans = run.get("spans")
    have = run.get("counters") or {}
    if not spans or any(name not in have
                        for name in counters + [wall, idle]):
        return None
    busy_ns = have[wall] - have[idle]
    if not busy_ns > 0:
        raise ValueError(f"{wall} - {idle} = {busy_ns}: no busy time")
    on_loop_ms = sum(s["stages"].get(st, 0.0)
                     for s in spans for st in stages)
    on_loop_ms -= sum(s["stages"].get(nested, 0.0) for s in spans)
    named_ns = on_loop_ms * 1e6 + sum(have[n] for n in counters)
    return 100.0 * (busy_ns - named_ns) / busy_ns
