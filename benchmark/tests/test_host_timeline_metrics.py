"""The reducers behind the host-timeline metrics (stage waits, loop
counters, the loop's unattributed share), on small hand-made inputs,
and against a program that lacks what they read."""

import importlib.util
import json
import os

import pytest

_BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_NEW = ("ingress_wait_ms.paced", "pipeline_wait_ms.paced",
        "stall_ms.paced", "prepare_us_per_msg", "read_us_per_msg",
        "flush_us_per_delivery", "gc_share", "loop_unattributed_share",
        "loop_select_share", "flush_wait_ms.paced")
#: PR 46: readings of stages and counters the program already had, which
#: waited for room in ``per_layer``
_WAITED = ("programs_per_batch", "grown_batch_share", "pack_ms_per_batch",
           "dispatch_us_per_delivery", "stats_share")


def reducer(name):
    spec = importlib.util.spec_from_file_location(
        "red_" + name, os.path.join(_BENCH, "reducers", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.reduce


def entry(name):
    with open(os.path.join(_BENCH, "layer_metrics", name + ".json"),
              encoding="utf-8") as f:
        return json.load(f)


def run_entry(name, run):
    e = entry(name)
    return reducer(e["reducer"])(run, **e["args"])


def _span(batch, **stages):
    return {"batch": batch, "stages": stages}


#: a window as the change's program fills it: two batches, counters
_RUN = {
    "window_s": 2.0,
    "spans": [
        _span(100, ingress_wait=4.0, prepare=1.0, match=2.0,
              cache_gather=0.5, pack=0.5, executor_wait=0.2,
              fetch=3.0, chain_wait=1.0, loop_wait=0.8, dispatch=6.0,
              gc_inside=1.5, end_to_end=20.0),
        _span(300, ingress_wait=2.0, prepare=3.0, match=2.0,
              pack=1.0, executor_wait=0.4, fetch=5.0, loop_wait=0.6,
              dispatch=4.0, end_to_end=18.0),
    ],
    "counters": {
        "messages.received": 400, "messages.delivered": 2000,
        "loop.read.ns": 40_000_000, "loop.flush.ns": 10_000_000,
        "loop.flush.calls": 50, "loop.flush.wait_ns": 100_000_000,
        "loop.select.ns": 1_000_000_000,
        "gc.ns.gen0": 30_000_000, "gc.ns.gen1": 20_000_000,
        "gc.ns.gen2": 150_000_000,
        "loop.stall.ns": 250_000_000,
        "loop.stats.ns": 50_000_000,
        "dispatch.batches": 2, "dispatch.programs": 4,
        "ingress.flush.grown": 1,
        # the counters were read over 2.5 s around the 2 s window
        "loop.wall.ns": 2_500_000_000,
    },
}

#: the same window from a program that has neither the stages nor the
#: counters (the parent commit)
_OLD = {
    "window_s": 2.0,
    "spans": [_span(100, match=2.0, fetch=3.0, dispatch=6.0,
                    end_to_end=12.0)],
    "counters": {"messages.received": 400, "messages.delivered": 2000},
}


@pytest.mark.parametrize("name,want", [
    ("ingress_wait_ms.paced", 3.0),                  # (4 + 2) / 2
    ("pipeline_wait_ms.paced", 1.5),     # (.2+1+.8 + .4+.6) / 2
    ("stall_ms.paced", 250.0),
    ("prepare_us_per_msg", 10.0),        # 4 ms / 400 msgs
    ("read_us_per_msg", 100.0),          # 40 ms / 400
    ("flush_us_per_delivery", 5.0),      # 10 ms / 2000
    # shares are of the program's own wall clock over the counters'
    # stretch (2.5 s), not of the window's nominal 2 s
    ("gc_share", 8.0),                   # 0.2 s / 2.5 s
    ("loop_select_share", 40.0),
    ("flush_wait_ms.paced", 2.0),        # 100 ms / 50 wake-ups
    # busy = 2.5 s - 1 s in the selector; named = on-loop stages
    # 20 ms - 1.5 ms inside gc = 18.5 ms, + read, flush, gc 0.25 s
    ("loop_unattributed_share", 100.0 * (1.5 - 0.2685) / 1.5),
    ("programs_per_batch", 2.0),         # 4 launches / 2 batches
    ("grown_batch_share", 0.5),          # 1 take over batch_size of 2
    ("pack_ms_per_batch", 0.75),         # (0.5 + 1) / 2
    ("dispatch_us_per_delivery", 5.0),   # 10 ms / 2000
    ("stats_share", 2.0),                # 50 ms / 2.5 s
])
def test_metric_from_a_hand_made_window(name, want):
    assert run_entry(name, _RUN) == pytest.approx(want)


@pytest.mark.parametrize("name", _NEW + tuple(
    n for n in _WAITED if n != "dispatch_us_per_delivery"))  # an old stage
def test_metric_is_left_out_for_a_program_without_it(name):
    """The parent has no such stage or counter: nothing, not 0 and
    not an error — with spans, without spans, with no counters."""
    assert run_entry(name, _OLD) is None
    assert run_entry(name, dict(_OLD, spans=None)) is None
    assert run_entry(name, {"window_s": 2.0, "spans": None,
                            "counters": None}) is None


def test_a_stall_free_window_reads_zero_not_nothing():
    quiet = dict(_RUN, counters=dict(_RUN["counters"],
                                     **{"loop.stall.ns": 0}))
    assert run_entry("stall_ms.paced", quiet) == 0.0


def test_stage_ratio_denominators_and_partial_stages():
    red = reducer("stage_ratio")
    # a stage only some spans carry still counts
    assert red(_RUN, ["chain_wait"], "spans") == pytest.approx(0.5)
    assert red(_RUN, ["dispatch"], "counter:messages.delivered",
               1000.0) == pytest.approx(5.0)
    assert red(_RUN, ["dispatch"], "counter:nope") is None
    assert red(_RUN, ["nope"], "spans") is None
    with pytest.raises(ValueError, match="denominator"):
        red(_RUN, ["dispatch"], "hours")


def test_counter_ratio_needs_every_counter_and_a_denominator():
    red = reducer("counter_ratio")
    assert red(_RUN, ["gc.ns.gen0", "gc.ns.gen9"], "window") is None
    assert red(_RUN, ["loop.read.ns"], "counter:nope") is None
    idle = dict(_RUN, counters=dict(_RUN["counters"],
                                    **{"messages.received": 0}))
    assert red(idle, ["loop.read.ns"],
               "counter:messages.received") is None
    with pytest.raises(ValueError, match="denominator"):
        red(_RUN, ["loop.read.ns"], "hours")


def test_loop_unattributed_refuses_a_loop_that_was_never_busy():
    e = entry("loop_unattributed_share")
    still = dict(_RUN, counters=dict(_RUN["counters"],
                                     **{"loop.wall.ns": 0}))
    with pytest.raises(ValueError, match="no busy time"):
        reducer(e["reducer"])(still, **e["args"])


def test_loop_unattributed_ignores_an_idle_tail():
    """A traced run's counters run on while the profiler stops and
    the loop sits in its selector: the share must not move."""
    e = entry("loop_unattributed_share")
    c = _RUN["counters"]
    tail = dict(_RUN, counters=dict(
        c, **{"loop.wall.ns": c["loop.wall.ns"] + 10_000_000_000,
              "loop.select.ns": c["loop.select.ns"] + 10_000_000_000}))
    red = reducer(e["reducer"])
    assert red(tail, **e["args"]) == pytest.approx(
        red(_RUN, **e["args"]))


def test_benchmark_json_and_the_metric_files_agree():
    with open(os.path.join(os.path.dirname(_BENCH), "BENCHMARK.json"),
              encoding="utf-8") as f:
        spec = json.load(f)
    by_name = {m["name"]: m for m in spec["per_layer"]}
    cells = {w["name"] for w in spec["workloads"]}
    reports = {e["name"]: set(e.get("workloads", cells))
               for e in spec["end_to_end"]}
    for name in _NEW + _WAITED:
        m, e = by_name[name], entry(name)
        for key in ("unit", "better", "source", "layer", "moves"):
            assert m[key] == e[key], (name, key)
        assert os.path.exists(os.path.join(
            _BENCH, "reducers", e["reducer"] + ".py"))
        # each listed cell reports the end-to-end metric it moves
        assert set(m["workloads"]) <= reports[m["moves"]], name
    # no position is pinned: which entry reports where is
    # test_layer_entries.py's, for every entry and cell at once
