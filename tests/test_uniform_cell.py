"""The files of benchmark cell ``fleet_1m_uniform.flood`` (PR 33): the
configuration is ``fleet_1m`` with the skew taken out and nothing else
changed, every layer-metric file says what its ``BENCHMARK.json``
entry says and names a reducer that exists, and the topic law is a
function of the seed that gives every word of a level its equal
share. Data and one pure function: nothing here touches a device."""

import collections
import importlib.util
import json
import os

import pytest

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_BENCH = os.path.join(_ROOT, "benchmark")
CELL = "fleet_1m_uniform.flood"


def _json(*path):
    with open(os.path.join(_ROOT, *path), encoding="utf-8") as f:
        return json.load(f)


SPEC = _json("BENCHMARK.json")
METRICS = [m for m in SPEC["per_layer"] if m.get("workloads") == [CELL]]


def _law():
    spec = importlib.util.spec_from_file_location(
        "_uniform_levels",
        os.path.join(_BENCH, "topic_laws", "uniform_levels.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_the_configuration_is_fleet_1m_but_for_its_topic_law():
    base = _json("benchmark", "configs", "fleet_1m.json")
    cfg = _json("benchmark", "configs", "fleet_1m_uniform.json")
    own = {"name", "title", "source", "publish_topics", "warmers", "assumed"}
    assert set(cfg) == set(base)
    for key in set(base) - own:
        assert cfg[key] == base[key], key
    assert cfg["name"] == "fleet_1m_uniform"
    assert cfg["publish_topics"] == {
        "law": "uniform_levels", "depth": base["publish_topics"]["depth"],
        "pool": base["publish_topics"]["pool"]}
    assert cfg["warmers"] == ["dispatch_shapes"]
    # of `assumed`, what the law changes and no other line
    changed = {"source", "publish_topics.law", "publish_topics.depth",
               "publish_topics.pool", "sockets"}
    assert set(cfg["assumed"]) == \
        (set(base["assumed"]) - {"publish_topics.a"}) | {"publish_topics.law"}
    for key in set(base["assumed"]) - changed - {"publish_topics.a"}:
        assert cfg["assumed"][key] == base["assumed"][key], key
    entry = next(c for c in SPEC["configs"] if c["name"] == cfg["name"])
    assert entry["source"] == cfg["source"] and len(cfg["source"]) <= 200
    assert entry["file"] == "benchmark/configs/fleet_1m_uniform.json"
    assert sorted(entry["reduced"]) == sorted(cfg["reduced"])


def test_the_cell_is_one_chip_of_flood_with_no_override():
    cell = next(w for w in SPEC["workloads"] if w["name"] == CELL)
    wl = _json("benchmark", "workloads", CELL + ".json")
    assert cell == {"name": CELL, "config": "fleet_1m_uniform",
                    "traffic": "flood", "chips": 1, "why": wl["why"]}
    assert wl["overrides"] == {} and wl["chips"] == 1
    assert [w["name"] for w in SPEC["workloads"]
            if w["config"] == "fleet_1m_uniform"] == [CELL]
    # it reports both end-to-end metrics a flood cell has
    assert {m["name"] for m in SPEC["end_to_end"]
            if CELL in m.get("workloads", [CELL])} == {
                "delivered_rate", "setup_s"}
    for name in ("dispatch_shapes",):
        assert os.path.exists(os.path.join(_BENCH, "warmers", name + ".py"))


def test_the_cells_per_layer_list():
    assert {m["name"] for m in METRICS} == {
        "walked_topic_share.uniform", "uniq_per_batch.uniform",
        "walk_busy_share.uniform", "batch_fill.uniform",
        "match_us_per_msg.uniform", "fetch_ms_per_batch.uniform",
        "tail_us_per_delivery.uniform", "read_us_per_msg.uniform",
        "prepare_us_per_msg.uniform", "device_idle_share.uniform",
        "warmers_s.uniform",
        # PR 34: the delivery walk's resolutions a delivery
        "plan_resolve_share.uniform",
        # PR 37: who waits for whom, and the device path's occupancy
        "select_wait_device_share.uniform", "device_path_share.uniform",
        "device_path_depth.uniform",
        # PR 38: batches that left the loop as one transfer
        "fused_batch_share.uniform"}
    # no accepted metric's list was touched: none names the new cell
    assert all(CELL not in m["workloads"] for m in SPEC["per_layer"]
               if m not in METRICS)


@pytest.mark.parametrize("name", [m["name"] for m in METRICS])
def test_layer_metric_file_equals_its_entry(name):
    entry = next(m for m in METRICS if m["name"] == name)
    data = _json("benchmark", "layer_metrics", name + ".json")
    for key in ("unit", "better", "source", "layer", "moves"):
        assert data[key] == entry[key], key
    assert entry["moves"] == ("setup_s" if name.startswith("warmers_s")
                              else "delivered_rate")
    assert os.path.exists(os.path.join(
        _BENCH, "reducers", data["reducer"] + ".py"))
    twin = name[:-len(".uniform")]
    if os.path.exists(os.path.join(_BENCH, "layer_metrics", twin + ".json")):
        # a twin reads what the accepted metric reads
        base = _json("benchmark", "layer_metrics", twin + ".json")
        assert (data["reducer"], data["args"]) == (
            base["reducer"], base["args"])
        assert [data[k] for k in ("unit", "better", "source", "layer")] \
            == [base[k] for k in ("unit", "better", "source", "layer")]


def test_the_walks_share_reads_the_while_ops_by_opcode():
    import sys

    sys.path.insert(0, _BENCH)
    try:
        spec = importlib.util.spec_from_file_location(
            "_trace_opcode_share",
            os.path.join(_BENCH, "reducers", "trace_opcode_share.py"))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
    finally:
        sys.path.remove(_BENCH)
    args = _json("benchmark", "layer_metrics",
                 "walk_busy_share.uniform.json")["args"]
    ops = [("%while.2 = (s32[8]) while(%tuple.1), body=%b", 0.0, 0.3),
           ("%fusion.4 = s32[8] fusion(%p)", 0.1, 0.1),   # inside it
           ("%fusion.9 = s32[8] fusion(%while.2)", 0.5, 0.2),
           ("%copy.1", 0.8, 0.1)]
    assert mod.reduce({"device_ops": ops}, **args) == pytest.approx(50.0)
    assert mod.reduce({"device_ops": [("%while.7", 0.0, 1.0)]},
                      **args) == pytest.approx(100.0)
    # nothing to read is nothing, never 0
    assert mod.reduce({"device_ops": ops[1:]}, **args) is None
    assert mod.reduce({}, **args) is None


def test_uniform_levels_is_a_function_of_the_seed_with_equal_shares():
    law = _law()
    vocab = [[f"w{lvl}_{i}" for i in range(60)] for lvl in range(5)]
    params = {"law": "uniform_levels", "depth": [2, 5], "pool": 1 << 20}
    pool = law.pool(params, vocab, 1234)
    assert pool == law.pool(params, vocab, 1234)
    assert pool != law.pool(params, vocab, 1235)
    assert len(pool) == params["pool"]
    depth = collections.Counter(t.count("/") + 1 for t in pool)
    assert sorted(depth) == [2, 3, 4, 5]
    for n in depth.values():
        assert abs(n - len(pool) / 4) < 0.05 * len(pool) / 4
    for lvl in range(5):
        words = collections.Counter(
            t.split("/")[lvl] for t in pool if t.count("/") >= lvl)
        assert set(words) == set(vocab[lvl])
        share = sum(words.values()) / 60
        assert all(abs(n - share) < 0.05 * share for n in words.values())
    # far more distinct topics than the match cache has slots, and next
    # to no duplicate inside a batch's worth of consecutive draws
    assert len(set(pool)) == 677784 > 10 * 65536  # the file's `assumed`
    assert min(len(set(pool[i:i + 600]))
               for i in range(0, len(pool) - 600, 24000)) >= 590
