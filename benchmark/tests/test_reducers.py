"""The reduction from spans, counters and the trace to metrics, on
small hand-made inputs."""

import importlib.util
import os

import pytest

import stats

_RED = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "reducers")


def reducer(name):
    spec = importlib.util.spec_from_file_location(
        "red_" + name, os.path.join(_RED, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.reduce


# -- the percentile rule -----------------------------------------------------


def test_percentile_nearest_rank():
    xs = list(range(1, 1001))
    assert stats.percentile(xs, 50) == 500
    assert stats.percentile(xs, 99) == 990
    assert stats.percentile(list(reversed(xs)), 99) == 990


@pytest.mark.parametrize("n,q", [(999, 99), (19, 50), (100, 95)])
def test_percentile_refuses_a_thin_tail(n, q):
    with pytest.raises(ValueError, match="beyond"):
        stats.percentile(list(range(n)), q)


def test_percentile_with_exactly_ten_beyond():
    assert stats.percentile(list(range(1000)), 99) == 989
    assert stats.percentile(list(range(20)), 50) == 9


# -- intervals -----------------------------------------------------------------


def test_union_counts_overlap_once():
    # [0,2) and [1,3) overlap; [5,6) apart; [5.2,5.4) inside
    iv = [(0.0, 2.0), (1.0, 2.0), (5.0, 1.0), (5.2, 0.2)]
    assert stats.union_seconds(iv) == pytest.approx(4.0)
    assert stats.union_seconds([]) == 0.0


def test_gaps_name_the_interval_that_ends_them():
    iv = [(5.0, 1.0), (0.0, 2.0), (1.0, 2.0)]
    assert stats.gaps(iv) == [(3.0, 2.0, 0)]


_OPS = [("%fusion.1 = s32[8]{0} fusion(%p0), kind=kLoop", 0.0, 0.5),
        ("%fusion.1 = s32[8]{0} fusion(%p0), kind=kLoop", 0.25, 0.5),
        ("%while.2 = (s32[], s32[8]) while(%t), body=%b", 2.0, 0.25)]


def test_trace_idle_share():
    run = {"device_ops": _OPS, "trace_window_s": 4.0}
    # busy: [0, 0.75) + [2, 2.25) = 1.0 of 4.0
    assert reducer("trace_idle_share")(run) == pytest.approx(75.0)


def test_trace_idle_share_refuses_an_empty_slice():
    idle = reducer("trace_idle_share")
    with pytest.raises(ValueError, match="no device operation"):
        idle({"device_ops": [], "trace_window_s": 3.0})
    with pytest.raises(ValueError, match="slice"):
        idle({"device_ops": _OPS, "trace_window_s": 0.0})
    # no trace at all: nothing to read, the metric is left out
    assert idle({}) is None


def test_trace_top_ops_and_gaps():
    run = {"device_ops": _OPS, "trace_window_s": 4.0}
    assert reducer("trace_top_ops")(run, n=10) == [
        ["%fusion.1", 1.0], ["%while.2", 0.25]]
    assert reducer("trace_top_ops")(run, n=1) == [["%fusion.1", 1.0]]
    assert reducer("trace_idle_gaps")(run) == [["before %while.2", 1.25]]
    assert reducer("trace_top_ops")({}) is None


# -- spans and counters --------------------------------------------------------


_SPANS = [
    {"batch": 100, "n_uniq": 60, "stages": {
        "match": 2.0, "cache_gather": 1.0, "fetch": 4.0, "dispatch": 3.0}},
    {"batch": 300, "n_uniq": 90, "stages": {
        "match": 5.0, "fetch": 2.0, "dispatch_plan": 1.0, "dispatch": 2.0}},
]


def test_span_reducers():
    run = {"spans": _SPANS, "counters": {"messages.delivered": 1200}}
    assert reducer("span_field_mean")(run, field="batch") == 200
    ratio = reducer("span_stage_ratio")
    # (2 + 1 + 5) ms over 400 messages, in microseconds
    assert ratio(run, stages=["match", "cache_gather"],
                 per="field:batch", scale=1000.0) == pytest.approx(20.0)
    assert ratio(run, stages=["fetch"], per="spans") == pytest.approx(3.0)
    assert ratio(run, stages=["dispatch_plan", "serialize", "dispatch"],
                 per="counter:messages.delivered",
                 scale=1000.0) == pytest.approx(5.0)
    with pytest.raises(ValueError):
        ratio(run, stages=["fetch"], per="nonsense")
    # nothing to read -> nothing reported
    assert ratio({"spans": None}, stages=["fetch"], per="spans") is None
    assert ratio({"spans": _SPANS, "counters": {}}, stages=["fetch"],
                 per="counter:messages.delivered") is None


def test_plain_reducers(capsys):
    run = {"window_s": 4.0, "socket_deliveries_in_window": 1000,
           "setup_s": 71.5, "counters": {"x": 3},
           "latency_s": [i / 1000.0 for i in range(1, 1001)]}
    assert reducer("window_rate")(
        run, field="socket_deliveries_in_window") == 250.0
    assert reducer("value")(run, field="setup_s") == 71.5
    assert reducer("counter_delta")(run, counter="x") == 3
    pct = reducer("field_percentile")
    assert pct(run, field="latency_s", q=99) == pytest.approx(990.0)
    assert "n=1000" in capsys.readouterr().out  # the sample count
    assert pct(run, field="gen_late_s", q=99) is None
