"""The files of benchmark cell ``p2p_2k.flood`` (PR 35): the
configuration is ``fanout_1k`` with the connections turned round — as
many publisher and subscriber sockets as devices, each on a topic and
a filter of its own — and nothing else changed; the topic law puts
every position of a publisher's sector on that publisher's topic;
every layer-metric file says what its ``BENCHMARK.json`` entry says,
and a twin reads what the accepted metric reads. Data and one pure
function: nothing here touches a device."""

import importlib.util
import json
import os
import sys

import pytest

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_BENCH = os.path.join(_ROOT, "benchmark")
CELL = "p2p_2k.flood"
#: twin -> the accepted metric whose reducer and arguments it takes
TWINS = {
    "batch_fill.p2p": "batch_fill",
    "match_us_per_msg.p2p": "match_us_per_msg",
    "fetch_ms_per_batch.p2p": "fetch_ms_per_batch",
    "tail_us_per_delivery.p2p": "tail_us_per_delivery",
    "read_us_per_msg.p2p": "read_us_per_msg",
    "flush_us_per_delivery.p2p": "flush_us_per_delivery",
    "plan_resolve_share.p2p": "plan_resolve_share",
    "publish_run_share.p2p": "publish_run_share",
    "walked_topic_share.p2p": "walked_topic_share.uniform",
    "loop_select_share.p2p": "loop_select_share",
    "device_idle_share.p2p": "device_idle_share",
    "warmers_s.p2p": "warmers_s",
    # PR 37: who waits for whom, and the device path's occupancy
    "select_poll_share.p2p": "select_poll_share",
    "select_wait_device_share.p2p": "select_wait_device_share",
    "select_wait_clients_share.p2p": "select_wait_clients_share",
    "device_path_share.p2p": "device_path_share",
    "device_path_depth.p2p": "device_path_depth",
    # PR 38: batches that left the loop as one transfer
    "fused_batch_share.p2p": "fused_batch_share",
}
#: the cell's own readings: counters over counters
OWN = {
    "msgs_per_read.p2p": (["messages.received"], "loop.read.calls"),
    "frames_per_flush.p2p": (["messages.sent"], "loop.flush.calls"),
    "parks_per_msg.p2p": (["ingress.parks"], "messages.received"),
    "park_wait_ms.p2p": (["ingress.park.ns"], "ingress.parks"),
    "wakes_per_park.p2p": (["ingress.wakes"], "ingress.parks"),  # PR 36
}


def _json(*path):
    with open(os.path.join(_ROOT, *path), encoding="utf-8") as f:
        return json.load(f)


def _module(kind, name):
    spec = importlib.util.spec_from_file_location(
        f"_p2p_{kind}_{name}", os.path.join(_BENCH, kind, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


SPEC = _json("BENCHMARK.json")
METRICS = [m for m in SPEC["per_layer"] if m.get("workloads") == [CELL]]
CFG = _json("benchmark", "configs", "p2p_2k.json")
WL = _json("benchmark", "workloads", CELL + ".json")


def test_the_configuration_is_fanout_1k_with_the_connections_turned_round():
    base = _json("benchmark", "configs", "fanout_1k.json")
    own = {"name", "title", "source", "sockets", "publish_topics",
           "warmers", "guarantees", "layout", "reduced", "assumed"}
    assert set(CFG) == set(base)
    for key in set(base) - own:
        assert CFG[key] == base[key], key
    assert "broker" not in CFG  # the default node
    assert CFG["name"] == "p2p_2k"
    assert CFG["sockets"] == [{"count": 2048, "filters": ["dev/{i}/#"]}]
    assert CFG["publish_topics"] == {
        "law": "interleave", "every": base["publish_topics"]["every"],
        "main": {"law": "own_topic", "owners": 2048,
                 "topic": "dev/{i}/state"},
        "background": base["publish_topics"]["background"],
        "pool": 262144}
    assert CFG["warmers"] == ["dispatch_shapes"]
    # of the nested groups, the one line that says who gets what
    for group, changed in (("guarantees", {"delivery"}),
                           ("layout", {"on_device", "deployment"})):
        assert set(CFG[group]) == set(base[group])
        for key in set(base[group]) - changed:
            assert CFG[group][key] == base[group][key], key
    assert CFG["layout"].get("path", "device") == "device"
    assert set(CFG["reduced"]) == set(base["reduced"]) | {"connections"}
    for key in base["reduced"]:
        assert CFG["reduced"][key] == base["reduced"][key], key
    changed = {"source", "sockets.filters", "publish_topics.pool", "traffic"}
    assert set(CFG["assumed"]) == \
        set(base["assumed"]) | {"publish_topics.main"}
    for key in set(base["assumed"]) - changed:
        assert CFG["assumed"][key] == base["assumed"][key], key
    entry = next(c for c in SPEC["configs"] if c["name"] == "p2p_2k")
    assert entry["source"] == CFG["source"] and len(CFG["source"]) <= 200
    assert entry["file"] == "benchmark/configs/p2p_2k.json"
    assert sorted(entry["reduced"]) == sorted(CFG["reduced"])
    # appended: after everything the benchmark had
    names = [c["name"] for c in SPEC["configs"]]
    assert names.index("p2p_2k") > names.index("fleet_1m_uniform")


def test_the_cell_pairs_every_publisher_with_one_socket():
    cell = next(w for w in SPEC["workloads"] if w["name"] == CELL)
    assert cell == {"name": CELL, "config": "p2p_2k", "traffic": "flood",
                    "chips": 1, "why": WL["why"]}
    names = [w["name"] for w in SPEC["workloads"]]
    assert names.index(CELL) > names.index("fleet_1m_uniform.flood")
    assert len(WL["why"]) <= 200
    assert WL["overrides"] == {"publishers": 2048, "burst": 4,
                               "subscriber_procs": 8}
    assert WL["chips"] == 1 and WL["traffic"] == "flood"
    # one publisher a device, one consumer socket a device
    assert WL["overrides"]["publishers"] \
        == CFG["publish_topics"]["main"]["owners"] \
        == sum(g["count"] for g in CFG["sockets"])
    assert {m["name"] for m in SPEC["end_to_end"]
            if CELL in m.get("workloads", [CELL])} == {
                "delivered_rate", "setup_s"}
    for kind, name in (("traffic", "flood.json"), ("loops", "flood.py"),
                       ("warmers", "dispatch_shapes.py"),
                       ("topic_laws", "own_topic.py")):
        assert os.path.exists(os.path.join(_BENCH, kind, name))


def test_the_cells_per_layer_list():
    assert {m["name"] for m in METRICS} == set(TWINS) | set(OWN)
    # appended in one stretch after everything the benchmark had; no
    # accepted metric's list names the new cell
    # (PR 38's one behind PR 37's; what later PRs appended follows)
    names = [m["name"] for m in SPEC["per_layer"]]
    at = names.index("plan_resolve_share.uniform") + 1
    assert SPEC["per_layer"][at:at + len(METRICS) - 1] == METRICS[:-1]
    assert METRICS[-1] == SPEC["per_layer"][
        names.index("fused_batch_share.p2p")]
    assert names.index("fused_batch_share.p2p") > at
    assert all(CELL not in m["workloads"] for m in SPEC["per_layer"]
               if m not in METRICS)


@pytest.mark.parametrize("name", sorted(TWINS) + sorted(OWN))
def test_layer_metric_file_equals_its_entry(name):
    entry = next(m for m in METRICS if m["name"] == name)
    data = _json("benchmark", "layer_metrics", name + ".json")
    assert set(entry) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    for key in ("unit", "better", "source", "layer", "moves"):
        assert data[key] == entry[key], key
    assert entry["moves"] == ("setup_s" if name.startswith("warmers_s")
                              else "delivered_rate")
    assert os.path.exists(os.path.join(
        _BENCH, "reducers", data["reducer"] + ".py"))
    if name in TWINS:
        # a twin reads what the accepted metric reads
        base = _json("benchmark", "layer_metrics", TWINS[name] + ".json")
        assert (data["reducer"], data["args"]) == (
            base["reducer"], base["args"])
        assert [data[k] for k in ("unit", "better", "source", "layer")] \
            == [base[k] for k in ("unit", "better", "source", "layer")]
    else:
        counters, per = OWN[name]
        assert data["reducer"] == "counter_ratio"
        assert data["args"]["counters"] == counters
        assert data["args"]["per"] == "counter:" + per
        assert data["source"] == "program_counter"
        # a layer the benchmark already names
        assert data["layer"] in {m["layer"] for m in SPEC["per_layer"]
                                 if m not in METRICS}


def test_the_park_metrics_read_nothing_from_a_program_without_the_counters():
    reduce = _module("reducers", "counter_ratio").reduce
    wait = _json("benchmark", "layer_metrics", "park_wait_ms.p2p.json")
    share = _json("benchmark", "layer_metrics", "parks_per_msg.p2p.json")
    parent = {"counters": {"messages.received": 1000, "loop.read.calls": 9}}
    assert reduce(parent, **wait["args"]) is None
    assert reduce(parent, **share["args"]) is None
    run = {"counters": {"messages.received": 1000, "ingress.parks": 50,
                        "ingress.park.ns": 50 * 3_000_000}}
    assert reduce(run, **wait["args"]) == pytest.approx(3.0)  # ms a park
    assert reduce(run, **share["args"]) == pytest.approx(0.05)
    # PR 36's counter: PR 35's program parks and counts no wake-up
    wakes = _json("benchmark", "layer_metrics", "wakes_per_park.p2p.json")
    assert reduce(parent, **wakes["args"]) is None
    assert reduce(run, **wakes["args"]) is None
    run["counters"]["ingress.wakes"] = 50
    assert reduce(run, **wakes["args"]) == 1.0
    assert wakes["better"] == "lower"  # a herd reads many


def _plan(n_pool, n_pubs):
    sys.path.insert(0, _BENCH)
    try:
        from loadgen import Plan
    finally:
        sys.path.remove(_BENCH)
    plan = Plan.__new__(Plan)
    plan.n_pool, plan.n_pubs = n_pool, n_pubs
    return plan


@pytest.mark.parametrize("n_pool,owners", [
    (262144, 2048),  # the cell
    (4096, 2048),    # the rehearsal's cut
    (4096, 40), (100, 7), (10, 3),  # sectors of unequal length
])
def test_own_topic_gives_every_sector_to_its_publisher(n_pool, owners):
    law = _module("topic_laws", "own_topic")
    params = {"law": "own_topic", "owners": owners, "pool": n_pool,
              "topic": "dev/{i}/state"}
    pool = law.pool(params, None, 1234)
    assert len(pool) == n_pool
    assert pool == law.pool(params, None, 99)  # nothing is drawn
    plan = _plan(n_pool, owners)
    idle = [0] * owners
    for i in range(owners):
        lo = plan.base(i, idle)
        hi = plan.base(i + 1, idle) if i + 1 < owners else n_pool
        assert lo < hi
        assert set(pool[lo:hi]) == {f"dev/{i}/state"}, i
    assert len(set(pool)) == owners
    if n_pool % owners == 0:
        assert all(pool[p] == f"dev/{p * owners // n_pool}/state"
                   for p in range(n_pool))


def test_own_topic_composes_under_interleave():
    sys.path.insert(0, _BENCH)
    try:
        interleave = _module("topic_laws", "interleave")
        params = dict(CFG["publish_topics"], pool=4096)
        params["main"] = dict(params["main"], owners=32)
        vocab = _module("populations", "mixed_tree").vocab(
            dict(CFG["population"], words_per_level=12))
        pool = interleave.pool(params, vocab, 1234)
    finally:
        sys.path.remove(_BENCH)
    every = params["every"]
    for p, topic in enumerate(pool):
        if p % every == every - 1:
            assert topic.startswith("w0_"), p  # the resident tree's
        else:
            assert topic == f"dev/{p * 32 // 4096}/state", p
    # no filter of one plane matches a topic of the other
    reference = _module("", "reference")
    assert not any(reference.matches(t, "dev/3/#") for t in pool
                   if t.startswith("w0_"))
    assert [i for i in range(32)
            if reference.matches("dev/3/state", f"dev/{i}/#")] == [3]
