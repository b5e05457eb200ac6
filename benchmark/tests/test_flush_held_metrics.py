"""The two readings of ``ingress.flush.held`` (PR 41: flushes short of
``batch_size`` that found a batch on the device path and began
nothing), on a small hand-made window, against a program without the
counter, and against ``BENCHMARK.json``."""

import json
import os

import pytest

from test_host_timeline_metrics import entry, run_entry

_BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
#: metric -> (the end-to-end metric it moves, the cells that list it).
#: One entry a reading since PR 46: the paced cell reports the bare name
#: (``held_ticks_per_batch.paced`` went; its file said the same reducer
#: and arguments), and there it moves the median, as its ``what`` says.
_NEW = {
    "held_ticks_per_batch": ("delivered_rate",
                             ["fleet_1m.flood", "fanout_1k.flood",
                              "fleet_1m.paced"]),
}

#: a window as the change's program counts it: 2,400 device batches,
#: 1,800 ticks that met an occupied path
_RUN = {"window_s": 20.0, "spans": None,
        "counters": {"ingress.flush.held": 1800,
                     "dispatch.batches": 2400}}
#: the parent registers ``dispatch.batches`` and no ``ingress.flush.held``
_OLD = {"window_s": 20.0, "spans": None,
        "counters": {"dispatch.batches": 2400}}


@pytest.mark.parametrize("name", sorted(_NEW))
def test_held_ticks_over_device_batches(name):
    assert run_entry(name, _RUN) == pytest.approx(0.75)


@pytest.mark.parametrize("name", sorted(_NEW))
def test_a_window_with_no_held_tick_reads_zero_not_nothing(name):
    free = dict(_RUN, counters=dict(_RUN["counters"],
                                    **{"ingress.flush.held": 0}))
    assert run_entry(name, free) == 0.0


@pytest.mark.parametrize("name", sorted(_NEW))
def test_left_out_by_a_program_without_the_counter(name):
    assert run_entry(name, _OLD) is None
    assert run_entry(name, dict(_OLD, counters=None)) is None
    # no device batch in the window: nothing to divide by
    assert run_entry(name, dict(_RUN, counters={
        "ingress.flush.held": 0, "dispatch.batches": 0})) is None


@pytest.mark.parametrize("name", sorted(_NEW))
def test_the_file_and_its_entry_agree(name):
    with open(os.path.join(os.path.dirname(_BENCH), "BENCHMARK.json"),
              encoding="utf-8") as f:
        spec = json.load(f)
    listed = [m for m in spec["per_layer"] if m["name"] == name]
    assert len(listed) == 1
    m, e = listed[0], entry(name)
    moves, cells = _NEW[name]
    assert set(m) == {"name", "unit", "better", "source", "layer",
                      "moves", "workloads"}
    for key in ("unit", "better", "source", "layer", "moves"):
        assert m[key] == e[key], key
    assert (m["moves"], m["workloads"]) == (moves, cells)
    assert m["layer"] == "batch pipeline hand-offs" == next(
        x["layer"] for x in spec["per_layer"]
        if x["name"] == "pipeline_wait_ms.paced")
    assert e["reducer"] == "counter_ratio" and e["args"] == {
        "counters": ["ingress.flush.held"],
        "per": "counter:dispatch.batches"}
    # no position is pinned. The mesh's path counts mesh.batches and
    # stamps no dispatch.batches: its cell cannot form the ratio
    names = [x["name"] for x in spec["per_layer"]]
    assert "held_ticks_per_batch.paced" not in names
    assert "paced cells" in e["what"]
    assert "fleet_10m_mesh.flood" not in m["workloads"]
