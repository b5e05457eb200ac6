"""``BENCHMARK.json``'s per-layer entries against the files under
``layer_metrics/``, for every entry and every cell that reports it, as
one parametrised test (PR 46): what ~110 cases in eight files of
``tests/`` say of one cell's copies each, said once of all of them. It
holds before and after a fold: it names no metric and no position.

It sits here and not under ``tests/`` because a ``benchmark`` PR may
touch no file outside the benchmark's own; a later PR moves it (PERF.md
section 7)."""

import json
import os

import pytest

_BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_ROOT = os.path.dirname(_BENCH)
KEYS = ("unit", "better", "source", "layer", "moves")


def _json(*path):
    with open(os.path.join(*path), encoding="utf-8") as f:
        return json.load(f)


SPEC = _json(_ROOT, "BENCHMARK.json")
CELLS = [w["name"] for w in SPEC["workloads"]]
REPORTS = {e["name"]: set(e.get("workloads", CELLS))
           for e in SPEC["end_to_end"]}
#: every (entry, cell) pair the benchmark reports in a traced run
PAIRS = [(m["name"], cell) for m in SPEC["per_layer"]
         for cell in m.get("workloads", CELLS)]


def _file(name):
    return _json(_BENCH, "layer_metrics", name + ".json")


@pytest.mark.parametrize("name,cell", PAIRS)
def test_entry_and_file_agree_in_a_cell_that_exists(name, cell):
    entry = next(m for m in SPEC["per_layer"] if m["name"] == name)
    data = _file(name)
    for key in KEYS:
        assert data[key] == entry[key], key
    assert set(entry) <= {"name", "workloads", *KEYS}
    assert data["what"]
    assert os.path.exists(os.path.join(
        _BENCH, "reducers", data["reducer"] + ".py"))
    assert entry["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    # the cell exists, and reports the end-to-end metric this one moves
    assert cell in CELLS
    assert cell in REPORTS[entry["moves"]]


def test_one_file_an_entry_and_no_other():
    names = [m["name"] for m in SPEC["per_layer"]]
    assert len(names) == len(set(names)) <= 128
    files = {f[:-len(".json")]
             for f in os.listdir(os.path.join(_BENCH, "layer_metrics"))}
    assert files == set(names)


def test_no_cell_reads_one_reading_under_two_names():
    """Two entries may share a reducer and its arguments only while no
    cell reports both (the copies a tier-1 test still pins, PERF.md
    section 7); a cell that read one number under two names would put
    it twice on its result line."""
    seen = {}
    for m in SPEC["per_layer"]:
        data = _file(m["name"])
        key = (data["reducer"], json.dumps(data.get("args", {}),
                                           sort_keys=True))
        for cell in m.get("workloads", CELLS):
            assert (key, cell) not in seen, (m["name"], seen[key, cell])
            seen[key, cell] = m["name"]


def test_layers_are_named_letter_for_letter():
    """A layer's name is a few words on one line; metrics of one layer
    give the same, so no two names differ by case or spacing alone."""
    layers = {m["layer"] for m in SPEC["per_layer"]}
    assert len({" ".join(x.lower().split()) for x in layers}) == len(layers)
    assert all("\n" not in x and len(x) <= 200 for x in layers)


def test_every_cell_reports_a_layer_metric_and_a_second_end_to_end():
    for cell in CELLS:
        assert any(cell in m.get("workloads", CELLS)
                   for m in SPEC["per_layer"]), cell
        assert {n for n, cells in REPORTS.items() if cell in cells} \
            > {"setup_s"}, cell


@pytest.mark.parametrize("cell", CELLS)
def test_a_cells_file_is_its_entry(cell):
    entry = next(w for w in SPEC["workloads"] if w["name"] == cell)
    wl = _json(_BENCH, "workloads", cell + ".json")
    assert entry == {"name": cell, "config": wl["config"],
                     "traffic": wl["traffic"], "chips": wl["chips"],
                     "why": wl["why"]}
    assert len(wl["why"]) <= 200 and "\n" not in wl["why"]
    assert os.path.exists(os.path.join(
        _BENCH, "configs", wl["config"] + ".json"))
    assert os.path.exists(os.path.join(
        _BENCH, "traffic", wl["traffic"] + ".json"))
