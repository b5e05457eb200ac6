"""The files of benchmark cell ``p2p_flap_2k.churn`` (PR 40): the
configuration is ``p2p_2k`` with links that flap — every device holds
a command filter of its own and reconnects every ten seconds — and
nothing else changed; the loop's schedule is a pure function; every
layer-metric file says what its ``BENCHMARK.json`` entry says, a twin
reads what ``p2p_2k.flood``'s metric reads, and the readers of what
this PR adds to the program read nothing from a program without it.
Data and pure functions: nothing here touches a device."""

import asyncio
import importlib.util
import json
import os
import sys
import time
import types

import pytest

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_BENCH = os.path.join(_ROOT, "benchmark")
CELL = "p2p_flap_2k.churn"
SPEC = json.load(open(os.path.join(_ROOT, "BENCHMARK.json")))
#: the 23 metrics that list ``p2p_2k.flood``, each with a twin here
ORIGINALS = [m["name"] for m in SPEC["per_layer"]
             if m.get("workloads") == ["p2p_2k.flood"]]
TWINS = {name[:-len(".p2p")] + ".flap": name for name in ORIGINALS}
#: the cell's own readings -> (reducer, its arguments)
OWN = {
    "sessions_per_s.flap": ("counter_ratio", {
        "counters": ["client.connected"], "per": "window"}),
    "session_open_us.flap": ("counter_ratio", {
        "counters": ["loop.session.open.ns"],
        "per": "counter:loop.session.open.calls", "scale": 0.001}),
    "session_close_us.flap": ("counter_ratio", {
        "counters": ["loop.session.close.ns"],
        "per": "counter:loop.session.close.calls", "scale": 0.001}),
    "fan_sync_ms_per_batch.flap": ("span_stage_ratio", {
        "stages": ["fan_sync"], "per": "spans"}),
    "fan_rebuild_share.flap": ("counter_share", {
        "counters": ["fanout.rebuilds"],
        "of": ["fanout.rebuilds", "fanout.patches"]}),
    "delta_probe_share.flap": ("counter_ratio", {
        "counters": ["automaton.delta.probes"],
        "per": "counter:dispatch.batches"}),
    "cache_stale_share.flap": ("counter_share", {
        "counters": ["cache.match.stale"],
        "of": ["cache.match.hit", "cache.match.miss"]}),
    "delta_merges.flap": ("counter_delta", {
        "counter": "automaton.delta.merges"}),
}


def _json(*path):
    with open(os.path.join(_ROOT, *path), encoding="utf-8") as f:
        return json.load(f)


def _module(kind, name):
    sys.path.insert(0, _BENCH)   # the loop imports loadgen, reference
    try:
        spec = importlib.util.spec_from_file_location(
            f"_flap_{kind}_{name}",
            os.path.join(_BENCH, kind, name + ".py"))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
    finally:
        sys.path.remove(_BENCH)
    return mod


METRICS = [m for m in SPEC["per_layer"] if m.get("workloads") == [CELL]]
CFG = _json("benchmark", "configs", "p2p_flap_2k.json")
WL = _json("benchmark", "workloads", CELL + ".json")
TRAFFIC = _json("benchmark", "traffic", "churn.json")


def test_the_configuration_is_p2p_2k_with_links_that_flap():
    base = _json("benchmark", "configs", "p2p_2k.json")
    own = {"name", "title", "source", "sockets", "guarantees", "layout",
           "reduced", "assumed"}
    assert set(CFG) == set(base) | {"devices"}
    for key in set(base) - own:
        assert CFG[key] == base[key], key
    assert "broker" not in CFG  # the default node
    assert CFG["name"] == "p2p_flap_2k"
    assert CFG["warmers"] == ["dispatch_shapes"]
    # a consumer does not match its device's command topics
    assert CFG["sockets"] == [{"count": 2048,
                               "filters": ["dev/{i}/state/#"]}]
    assert CFG["devices"] == {"filters": ["dev/{i}/cmd/#"],
                              "probe": "dev/{i}/cmd/probe", "qos": 0}
    g, bg = CFG["guarantees"], base["guarantees"]
    assert set(g) == set(bg) | {"subscription", "fence", "takeover"}
    for key in bg:               # nothing is weakened
        assert g[key] == bg[key], key
    assert "SUBACK is live" in g["subscription"]
    lay, blay = CFG["layout"], base["layout"]
    assert set(lay) == set(blay)
    for key in set(blay) - {"on_device", "deployment"}:
        assert lay[key] == blay[key], key
    assert "delta automaton with up to 2,048 command filters" \
        in lay["on_device"]
    assert lay.get("path", "device") == "device"
    assert set(CFG["reduced"]) == set(base["reduced"]) | {"connect_rate"}
    for key in base["reduced"]:
        assert CFG["reduced"][key] == base["reduced"][key], key
    new = {"devices.filters", "traffic.session_s",
           "traffic.takeover_share"}
    assert set(CFG["assumed"]) == set(base["assumed"]) | new
    for key in set(base["assumed"]) - {"source", "sockets.filters"}:
        assert CFG["assumed"][key] == base["assumed"][key], key
    assert CFG["assumed"]["source"].startswith(base["assumed"]["source"])


def test_the_configurations_entry():
    entry = next(c for c in SPEC["configs"] if c["name"] == "p2p_flap_2k")
    assert entry["source"] == CFG["source"] and len(CFG["source"]) <= 200
    assert "p2p-50K-50K-50K-50K" in entry["source"] \
        and "conn-tcp-1M-5K" in entry["source"]
    assert entry["file"] == "benchmark/configs/p2p_flap_2k.json"
    assert entry["reduced"] == ["connections", "subscriber_connections",
                                "filters", "connect_rate"]
    assert sorted(entry["reduced"]) == sorted(CFG["reduced"])
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    # appended behind what the benchmark had (what later PRs appended
    # follows)
    assert SPEC["configs"].index(entry) == 5
    # and a source of its own
    assert sum(c["source"] == entry["source"]
               for c in SPEC["configs"]) == 1


def test_the_cell_is_p2p_2k_floods_fleet_under_churn():
    cell = next(w for w in SPEC["workloads"] if w["name"] == CELL)
    assert cell == {"name": CELL, "config": "p2p_flap_2k",
                    "traffic": "churn", "chips": 1, "why": WL["why"]}
    assert len(cell["why"]) <= 200 and SPEC["workloads"].index(cell) == 6
    flood = _json("benchmark", "workloads", "p2p_2k.flood.json")
    assert WL["overrides"] == flood["overrides"] == {
        "publishers": 2048, "burst": 4, "subscriber_procs": 8}
    assert WL["overrides"]["publishers"] \
        == CFG["publish_topics"]["main"]["owners"] \
        == sum(g["count"] for g in CFG["sockets"])
    assert {m["name"] for m in SPEC["end_to_end"]
            if CELL in m.get("workloads", [CELL])} == {
                "delivered_rate", "setup_s"}
    assert TRAFFIC["loop"] == "churn"
    assert (TRAFFIC["session_s"], TRAFFIC["takeover_share"],
            TRAFFIC["wait_limit_s"]) == (10, 0.5, 10)
    # the run's first batch flattens, builds and compiles: the rounds
    # before the harness's warmers have a limit of their own
    assert TRAFFIC["cold_rounds"] == 2 \
        and TRAFFIC["cold_wait_limit_s"] > TRAFFIC["wait_limit_s"]
    for kind, name in (("loops", "churn.py"),
                       ("warmers", "dispatch_shapes.py"),
                       ("reducers", "counter_share.py")):
        assert os.path.exists(os.path.join(_BENCH, kind, name))


def test_nothing_the_benchmark_had_names_the_new_cell():
    assert {m["name"] for m in METRICS} == set(TWINS) | set(OWN)
    assert len(TWINS) == 23 and len(METRICS) == 31
    # appended in one stretch behind everything the benchmark had
    # (what later PRs appended follows, and names other cells)
    at = SPEC["per_layer"].index(METRICS[0])
    assert SPEC["per_layer"][at:at + len(METRICS)] == METRICS
    assert SPEC["per_layer"][at - 1]["name"] == "fused_batch_share.p2p"
    assert all(CELL not in m.get("workloads", [])
               for m in SPEC["per_layer"] if m not in METRICS)
    assert all(CELL not in m.get("workloads", [])
               for m in SPEC["end_to_end"])


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
        # a twin is its original: the two fleets are read side by side
        assert data == _json("benchmark", "layer_metrics",
                             TWINS[name] + ".json")
    else:
        assert (data["reducer"], data["args"]) == OWN[name]
        assert data["source"] == ("program_span" if "stages" in data["args"]
                                  else "program_counter")


def _run(**counters):
    return {"counters": counters, "window_s": 20.0, "spans": None}


def test_a_program_without_what_this_pr_adds_gives_the_readers_nothing():
    """The parent's line leaves these out, not 0 (and nothing raises)."""
    parent = _run(**{"client.connected": 4096, "dispatch.batches": 700,
                     "automaton.delta.probes": 650,
                     "automaton.delta.merges": 0,
                     "cache.match.hit": 10, "cache.match.miss": 90,
                     "cache.match.stale": 70})
    parent["spans"] = [{"stages": {"match": 1.0, "pack": 2.0}}]
    got = {}
    for name, (reducer, args) in OWN.items():
        got[name] = _module("reducers", reducer).reduce(parent, **args)
    assert got["session_open_us.flap"] is None
    assert got["session_close_us.flap"] is None
    assert got["fan_rebuild_share.flap"] is None
    # the stage is not there: 0 ms of it a batch, from a parent
    assert got["fan_sync_ms_per_batch.flap"] == 0.0
    # what the parent has, it reports
    assert got["sessions_per_s.flap"] == pytest.approx(204.8)
    assert got["delta_probe_share.flap"] == pytest.approx(650 / 700)
    assert got["cache_stale_share.flap"] == pytest.approx(0.7)
    assert got["delta_merges.flap"] == 0


def test_the_readers_on_a_program_that_has_it():
    run = _run(**{"loop.session.open.ns": 4096 * 150_000,
                  "loop.session.open.calls": 4096,
                  "loop.session.close.ns": 4096 * 90_000,
                  "loop.session.close.calls": 4096,
                  "fanout.rebuilds": 0, "fanout.patches": 640})
    run["spans"] = [{"stages": {"fan_sync": 0.25}},
                    {"stages": {"fan_sync": 0.75}}]
    got = {name: _module("reducers", OWN[name][0]).reduce(
        run, **OWN[name][1]) for name in (
            "session_open_us.flap", "session_close_us.flap",
            "fan_rebuild_share.flap", "fan_sync_ms_per_batch.flap")}
    assert got == {"session_open_us.flap": pytest.approx(150.0),
                   "session_close_us.flap": pytest.approx(90.0),
                   "fan_rebuild_share.flap": 0.0,
                   "fan_sync_ms_per_batch.flap": pytest.approx(0.5)}
    share = _module("reducers", "counter_share").reduce
    # a window in which no sync changed the tables: left out, not 0
    assert share(_run(**{"fanout.rebuilds": 0, "fanout.patches": 0}),
                 ["fanout.rebuilds"],
                 ["fanout.rebuilds", "fanout.patches"]) is None
    assert share(_run(**{"fanout.rebuilds": 1, "fanout.patches": 3}),
                 ["fanout.rebuilds"],
                 ["fanout.rebuilds", "fanout.patches"]) == 0.25


# -- the schedule, a pure function -------------------------------------------

CHURN = _module("loops", "churn")


@pytest.mark.parametrize("phase", [0, 1, 2, 3, 7])
def test_a_window_holds_two_reconnects_a_device(phase):
    n, t0 = 2048, 1000.0
    dues = [CHURN.due(p, n, phase, t0, t0 + 20.0, 10.0) for p in range(n)]
    assert sum(len(d) for d in dues) == 4096
    assert all(len(d) == 2 for d in dues)
    # no device twice within a session, none outside the phase
    assert all(d[1] - d[0] == pytest.approx(10.0) for d in dues)
    assert all(t0 <= t < t0 + 20.0 for d in dues for t in d)
    # spread evenly: 204.8 a second, in every second of the window
    per_s = [0] * 20
    for d in dues:
        for t in d:
            per_s[int(t - t0)] += 1
    assert set(per_s) <= {204, 205}


@pytest.mark.parametrize("phase", [1, 2, 3, 4, 5, 6])
def test_a_warm_round_holds_a_fifth_of_the_fleet_once(phase):
    n, t0 = 2048, 500.0
    dues = [CHURN.due(p, n, phase, t0, t0 + 2.0, 10.0) for p in range(n)]
    assert all(len(d) <= 1 for d in dues)
    assert sum(len(d) for d in dues) in (409, 410)
    # and the next round another fifth: the golden turn
    nxt = [CHURN.due(p, n, phase + 1, t0, t0 + 2.0, 10.0)
           for p in range(n)]
    both = sum(1 for a, b in zip(dues, nxt) if a and b)
    assert both == 0


def test_every_other_reconnect_is_a_takeover():
    assert [CHURN.is_takeover(k, 0.5) for k in range(6)] \
        == [False, True, False, True, False, True]
    assert not any(CHURN.is_takeover(k, 0.0) for k in range(8))
    assert all(CHURN.is_takeover(k, 1.0) for k in range(8))
    assert sum(CHURN.is_takeover(k, 0.25) for k in range(400)) == 100


def test_the_wait_limit_by_phase():
    lim = CHURN.wait_limit
    assert lim(TRAFFIC, 0) == 10.0               # the measured window
    assert lim(TRAFFIC, 1) == lim(TRAFFIC, 2) == 45.0
    assert lim(TRAFFIC, 3) == lim(TRAFFIC, 40) == 10.0
    assert lim({"wait_limit_s": 5}, 1) == 5.0    # no cold rounds named


class _DeadLink:
    """Both ends of a connection the broker never answers on."""

    def write(self, data):
        pass

    async def drain(self):
        pass

    async def readexactly(self, n):
        raise asyncio.IncompleteReadError(b"", n)


def _stranded_fleet():
    link = _DeadLink()
    plan = types.SimpleNamespace(
        config=CFG, n_pubs=4, payload_len=64, base=lambda pub, start: 0,
        traffic=dict(TRAFFIC, burst=4))
    return types.SimpleNamespace(plan=plan, start=[0] * 4, filler=b"x" * 32,
                                 conns=[(link, link)] * 4)


@pytest.mark.parametrize("phase", [1, 2, 3, 9])
def test_a_fleet_lost_in_a_warm_round_ends_the_run(phase):
    """The first device that fails before the window raises what
    ``Publishers.run_phase`` does not count, so the run ends with an
    exit code and no result line, traced or not; the devices after it
    just stop."""
    pubs = _stranded_fleet()
    now = time.monotonic()
    with pytest.raises(CHURN.FleetLost, match="before the window"):
        asyncio.run(CHURN.publisher(pubs, 2, phase, now, now + 2.0, None))
    assert not isinstance(CHURN.FleetLost("x"), (ConnectionError, OSError))
    with pytest.raises(ConnectionError, match="the fleet stopped when "
                                              "device 2 failed"):
        asyncio.run(CHURN.publisher(pubs, 3, phase, now, now + 2.0, None))
    assert pubs.fleet.first_failed == 2 and pubs.fleet.dead[2]


def test_a_device_lost_in_the_window_is_counted():
    """In the measured window the failure is the kind ``run_phase``
    counts: ``connections_failed`` and a result line."""
    pubs = _stranded_fleet()
    now = time.monotonic()
    with pytest.raises(asyncio.IncompleteReadError):
        asyncio.run(CHURN.publisher(pubs, 0, 0, now, now + 2.0, None))
    with pytest.raises(ConnectionError, match="the fleet stopped"):
        asyncio.run(CHURN.publisher(pubs, 1, 0, now, now + 2.0, None))
    assert pubs.fleet.first_failed == 0


def test_the_probe_comes_back_where_the_reference_says_so():
    sys.path.insert(0, _BENCH)
    try:
        from reference import matches
    finally:
        sys.path.remove(_BENCH)
    dev = CFG["devices"]
    for i in (0, 7, 2047):
        probe = dev["probe"].format(i=i)
        assert any(matches(probe, f.format(i=i)) for f in dev["filters"])
        # on its own connection alone: no consumer, no other device
        assert not any(matches(probe, f.format(i=j))
                       for j in (i + 1, i + 10) for f in dev["filters"])
        assert not any(matches(probe, f.format(i=i))
                       for g in CFG["sockets"] for f in g["filters"])
        state = CFG["publish_topics"]["main"]["topic"].format(i=i)
        assert not any(matches(state, f.format(i=i))
                       for f in dev["filters"])
        assert all(matches(state, f.format(i=i))
                   for g in CFG["sockets"] for f in g["filters"])
