"""The files of benchmark cell ``fleet_1m_storm.subscribe_storm``
(PR 42): the configuration is ``fleet_1m`` key for key with a fleet
that comes back through 64 gateways and nothing else changed; the
loop's schedule is a pure function; every layer-metric file says what
its ``BENCHMARK.json`` entry says (the twelve readings of the cell's
own: ``per_layer`` holds 128 entries at most and had 116, so the 22
twins of ``fleet_1m.flood``'s metrics that ISSUE 42 also asked for have
no room), and the readers of what this PR adds
to the program read nothing from a program without it. Data and pure
functions: nothing here touches a device."""

import asyncio
import importlib.util
import json
import os
import struct
import sys
import time
import types

import pytest

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_BENCH = os.path.join(_ROOT, "benchmark")
CELL = "fleet_1m_storm.subscribe_storm"
SPEC = json.load(open(os.path.join(_ROOT, "BENCHMARK.json")))
#: twelve readings of the cell's own -> (reducer, its arguments)
OWN = {
    "delta_merges.storm": ("counter_delta", {
        "counter": "automaton.delta.merges"}),
    "merge_s.storm": ("counter_ratio", {
        "counters": ["automaton.compaction.ns"],
        "per": "counter:automaton.delta.merges", "scale": 1e-9}),
    "rebuild_stall_ms_per_merge.storm": ("counter_ratio", {
        "counters": ["automaton.rebuild.stall_ms"],
        "per": "counter:automaton.delta.merges"}),
    "fan_rebuild_share.storm": ("counter_share", {
        "counters": ["fanout.rebuilds"],
        "of": ["fanout.rebuilds", "fanout.patches"]}),
    "fan_sync_ms_per_batch.storm": ("span_stage_ratio", {
        "stages": ["fan_sync"], "per": "spans"}),
    "delta_probe_share.storm": ("counter_ratio", {
        "counters": ["automaton.delta.probes"],
        "per": "counter:dispatch.batches"}),
    "cache_stale_share.storm": ("counter_share", {
        "counters": ["cache.match.stale"],
        "of": ["cache.match.hit", "cache.match.miss"]}),
    "walked_topic_share.storm": ("counter_ratio", {
        "counters": ["dispatch.walk.topics"],
        "per": "counter:dispatch.topics"}),
    "delta_grows.storm": ("counter_delta", {
        "counter": "automaton.delta.grows"}),
    "subscribes_per_s.storm": ("counter_ratio", {
        "counters": ["loop.subscribe.filters"], "per": "window"}),
    "subscribe_us.storm": ("counter_ratio", {
        "counters": ["loop.subscribe.ns"],
        "per": "counter:loop.subscribe.filters", "scale": 0.001}),
    "unsubscribe_us.storm": ("counter_ratio", {
        "counters": ["loop.unsubscribe.ns"],
        "per": "counter:loop.unsubscribe.filters", "scale": 0.001}),
}
#: those of them that read what ``p2p_flap_2k.churn``'s metric reads
AS_FLAP = ("delta_merges", "fan_rebuild_share", "fan_sync_ms_per_batch",
           "delta_probe_share", "cache_stale_share", "walked_topic_share")


def _json(*path):
    with open(os.path.join(_ROOT, *path), encoding="utf-8") as f:
        return json.load(f)


def _module(kind, name):
    sys.path.insert(0, _BENCH)   # the loop imports loadgen, reference
    try:
        spec = importlib.util.spec_from_file_location(
            f"_storm_{kind}_{name}",
            os.path.join(_BENCH, kind, name + ".py"))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
    finally:
        sys.path.remove(_BENCH)
    return mod


METRICS = [m for m in SPEC["per_layer"] if m.get("workloads") == [CELL]]
CFG = _json("benchmark", "configs", "fleet_1m_storm.json")
BASE = _json("benchmark", "configs", "fleet_1m.json")
WL = _json("benchmark", "workloads", CELL + ".json")
TRAFFIC = _json("benchmark", "traffic", "subscribe_storm.json")
STORM = _module("loops", "subscribe_storm")


def test_the_configuration_is_fleet_1m_with_a_fleet_that_comes_back():
    own = {"name", "title", "source", "guarantees", "layout", "reduced",
           "assumed", "warmers"}
    assert set(CFG) == set(BASE) | {"gateways"}
    for key in set(BASE) - own:      # population, sockets, law, payload
        assert CFG[key] == BASE[key], key
    assert "broker" not in CFG       # the default node
    assert CFG["name"] == "fleet_1m_storm"
    assert CFG["warmers"] == ["dispatch_shapes"]
    gw = CFG["gateways"]
    assert set(gw) == {"count", "client_id", "filters", "probes", "qos"}
    assert (gw["count"], gw["client_id"], gw["qos"]) == (
        64, "bench-gw-{g}", 0)
    assert gw["filters"] == ["cmd/s{g}/d{k}/#"] * 3 \
        + ["cmd/s{g}/+/d{k}/ack"]
    assert gw["probes"] == ["cmd/s{g}/d{k}/probe"] * 3 \
        + ["cmd/s{g}/x/d{k}/ack"]
    g, bg = CFG["guarantees"], BASE["guarantees"]
    assert set(g) == set(bg) | {"subscription", "unsubscription",
                                "delivery_across_swaps"}
    for key in bg:                   # nothing is weakened
        assert g[key] == bg[key], key
    assert "acknowledged by SUBACK is live" in g["subscription"]
    assert "after UNSUBACK no message" in g["unsubscription"]
    assert "across every swap" in g["delivery_across_swaps"]
    lay, blay = CFG["layout"], BASE["layout"]
    assert set(lay) == set(blay)
    for key in set(blay) - {"on_device", "deployment"}:
        assert lay[key] == blay[key], key
    assert "delta automaton" in lay["on_device"] \
        and "fan-out tables, carried over a swap" in lay["on_device"]
    assert lay.get("path", "device") == "device"
    assert set(CFG["reduced"]) == set(BASE["reduced"]) | {
        "connections", "subscribe_rate"}
    for key in BASE["reduced"]:
        assert CFG["reduced"][key] == BASE["reduced"][key], key
    assert "5,000" in CFG["reduced"]["subscribe_rate"]
    new = {"gateways.count", "gateways.filters", "gateways.probes",
           "traffic.subscribe_rate", "traffic.filters_per_packet",
           "traffic.unsubscribe_share", "broker.delta_max_filters"}
    assert set(CFG["assumed"]) == set(BASE["assumed"]) | new
    for key in set(BASE["assumed"]) - {"source"}:
        assert CFG["assumed"][key] == BASE["assumed"][key], key
    assert CFG["assumed"]["source"].startswith(BASE["assumed"]["source"])
    assert "recalled" in CFG["assumed"]["source"]


def test_the_configurations_entry():
    entry = next(c for c in SPEC["configs"]
                 if c["name"] == "fleet_1m_storm")
    assert entry["source"] == CFG["source"] and len(CFG["source"]) <= 200
    assert "conn-tcp-1M-5K" in entry["source"] \
        and "emqtt_bench sub -c N -i <ms> -t <filter>/%i" in entry["source"]
    assert entry["file"] == "benchmark/configs/fleet_1m_storm.json"
    assert entry["reduced"] == ["connections", "subscribe_rate",
                                "subscriber_connections", "filters"]
    assert sorted(entry["reduced"]) == sorted(CFG["reduced"])
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    # appended behind what the benchmark had (what later PRs append
    # follows)
    assert SPEC["configs"].index(entry) == 6
    assert sum(c["source"] == entry["source"]
               for c in SPEC["configs"]) == 1


def test_the_cell_is_fleet_1m_flood_plus_the_storm():
    cell = next(w for w in SPEC["workloads"] if w["name"] == CELL)
    assert cell == {"name": CELL, "config": "fleet_1m_storm",
                    "traffic": "subscribe_storm", "chips": 1,
                    "why": WL["why"]}
    assert len(cell["why"]) <= 200 and SPEC["workloads"].index(cell) == 7
    assert sum(w["chips"] == 4 for w in SPEC["workloads"]) == 1
    flood_wl = _json("benchmark", "workloads", "fleet_1m.flood.json")
    assert WL["overrides"] == flood_wl["overrides"] == {}
    flood = _json("benchmark", "traffic", "flood.json")
    for key in ("publishers", "burst", "subscriber_procs"):
        assert TRAFFIC[key] == flood[key], key   # 8, 512, 1
    churn = _json("benchmark", "traffic", "churn.json")
    for key in ("wait_limit_s", "cold_rounds", "cold_wait_limit_s",
                "busy_retry_s"):
        assert TRAFFIC[key] == churn[key], key   # 10, 2, 45, 0.25
    assert TRAFFIC["loop"] == "subscribe_storm"
    assert (TRAFFIC["filters_per_packet"],
            TRAFFIC["unsubscribe_share"]) == (16, 0.25)
    assert TRAFFIC["subscribe_rate"] in (256, 512, 1024, 2048)
    assert TRAFFIC["subscribe_rate_fleet"] \
        == CFG["population"]["filters"] == 1_000_000
    assert {m["name"] for m in SPEC["end_to_end"]
            if CELL in m.get("workloads", [CELL])} == {
                "delivered_rate", "setup_s"}
    for kind, name in (("loops", "subscribe_storm.py"),
                       ("loops", "churn.py"), ("loops", "flood.py"),
                       ("warmers", "dispatch_shapes.py")):
        assert os.path.exists(os.path.join(_BENCH, kind, name))


def test_nothing_the_benchmark_had_names_the_new_cell():
    assert [m["name"] for m in METRICS] == list(OWN)
    # the list is full: what it had and the twelve (the driver refuses
    # a file with more than 128 per-layer metrics before any run)
    assert len(METRICS) == 12 and len(SPEC["per_layer"]) <= 128
    # appended in one stretch behind everything the benchmark had
    at = SPEC["per_layer"].index(METRICS[0])
    assert SPEC["per_layer"][at:at + len(METRICS)] == METRICS
    assert SPEC["per_layer"][at - 1]["name"] == "held_ticks_per_batch"
    assert all(CELL not in m.get("workloads", [])
               for m in SPEC["per_layer"] if m not in METRICS)
    assert all(CELL not in m.get("workloads", [])
               for m in SPEC["end_to_end"])


@pytest.mark.parametrize("name", sorted(OWN))
def test_layer_metric_file_equals_its_entry(name):
    entry = next(m for m in METRICS if m["name"] == name)
    data = _json("benchmark", "layer_metrics", name + ".json")
    assert set(entry) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    for key in ("unit", "better", "source", "layer", "moves"):
        assert data[key] == entry[key], key
    assert entry["moves"] == "delivered_rate"
    assert os.path.exists(os.path.join(
        _BENCH, "reducers", data["reducer"] + ".py"))
    # a layer PERF.md's list and the accepted benchmark name
    assert entry["layer"] in {m["layer"] for m in SPEC["per_layer"]
                              if m not in METRICS}
    assert (data["reducer"], data["args"]) == OWN[name]
    assert data["source"] == ("program_span" if "stages" in data["args"]
                              else "program_counter")
    stem = name[:-len(".storm")]
    if stem in AS_FLAP:
        assert data == _json("benchmark", "layer_metrics",
                             stem + ".flap.json")


def _run(**counters):
    return {"counters": counters, "window_s": 20.0, "spans": None}


def test_a_program_without_what_this_pr_adds_gives_the_readers_nothing():
    """The parent's line leaves these out, not 0 (and nothing raises)."""
    parent = _run(**{"dispatch.batches": 1500, "dispatch.topics": 90000,
                     "dispatch.walk.topics": 30000,
                     "automaton.delta.probes": 1400,
                     "automaton.delta.merges": 5,
                     "automaton.rebuild.stall_ms": 150,
                     "fanout.rebuilds": 5, "fanout.patches": 1200,
                     "cache.match.hit": 60, "cache.match.miss": 40,
                     "cache.match.stale": 30})
    parent["spans"] = [{"stages": {"match": 1.0, "fan_sync": 2.5}}]
    got = {name: _module("reducers", reducer).reduce(parent, **args)
           for name, (reducer, args) in OWN.items()}
    for name in ("merge_s.storm", "delta_grows.storm",
                 "subscribes_per_s.storm", "subscribe_us.storm",
                 "unsubscribe_us.storm"):
        assert got[name] is None, name
    # what the parent has, it reports
    assert got["delta_merges.storm"] == 5
    assert got["rebuild_stall_ms_per_merge.storm"] == pytest.approx(30.0)
    assert got["fan_rebuild_share.storm"] == pytest.approx(5 / 1205)
    assert got["fan_sync_ms_per_batch.storm"] == pytest.approx(2.5)
    assert got["delta_probe_share.storm"] == pytest.approx(1400 / 1500)
    assert got["cache_stale_share.storm"] == pytest.approx(0.3)
    assert got["walked_topic_share.storm"] == pytest.approx(1 / 3)


def test_the_readers_on_a_program_that_has_it():
    run = _run(**{"automaton.delta.merges": 5,
                  "automaton.compaction.ns": 5 * 3_200_000_000,
                  "automaton.rebuild.stall_ms": 400,
                  "automaton.delta.grows": 0,
                  "loop.subscribe.filters": 20480,
                  "loop.subscribe.ns": 20480 * 45_000,
                  "loop.unsubscribe.filters": 5120,
                  "loop.unsubscribe.ns": 5120 * 60_000})
    got = {name: _module("reducers", OWN[name][0]).reduce(
        run, **OWN[name][1]) for name in (
            "merge_s.storm", "rebuild_stall_ms_per_merge.storm",
            "delta_grows.storm", "subscribes_per_s.storm",
            "subscribe_us.storm", "unsubscribe_us.storm")}
    assert got == {"merge_s.storm": pytest.approx(3.2),
                   "rebuild_stall_ms_per_merge.storm": pytest.approx(80.0),
                   "delta_grows.storm": 0,
                   "subscribes_per_s.storm": pytest.approx(1024.0),
                   "subscribe_us.storm": pytest.approx(45.0),
                   "unsubscribe_us.storm": pytest.approx(60.0)}
    # a window in which no merge ended: left out, not a division by 0
    quiet = _run(**{"automaton.delta.merges": 0,
                    "automaton.compaction.ns": 0,
                    "automaton.rebuild.stall_ms": 0})
    ratio = _module("reducers", "counter_ratio").reduce
    assert ratio(quiet, **OWN["merge_s.storm"][1]) is None
    assert ratio(quiet, **OWN["rebuild_stall_ms_per_merge.storm"][1]) is None


# -- the schedule, a pure function -------------------------------------------


def test_the_period_follows_the_fleets_size():
    assert STORM.period(TRAFFIC, CFG) == pytest.approx(
        64 * 16 / TRAFFIC["subscribe_rate"])
    half = dict(CFG, population=dict(CFG["population"], filters=500_000))
    assert STORM.period(TRAFFIC, half) == pytest.approx(
        2 * STORM.period(TRAFFIC, CFG))
    # a toy copy still sees a trickle: never under the floor
    small = dict(CFG, population=dict(CFG["population"], filters=3000))
    assert TRAFFIC["subscribe_rate_floor"] == 32
    assert STORM.period(TRAFFIC, small) == pytest.approx(64 * 16 / 32)


@pytest.mark.parametrize("phase", [0, 1, 2, 3, 7])
def test_a_window_holds_the_rates_packets(phase):
    n, t0, every = 64, 1000.0, STORM.period(TRAFFIC, CFG)
    dues = [STORM.due(g, n, phase, t0, t0 + 20.0, every) for g in range(n)]
    packets = sum(len(d) for d in dues)
    assert packets * 16 == pytest.approx(TRAFFIC["subscribe_rate"] * 20,
                                         rel=0.01)
    assert all(t0 <= t < t0 + 20.0 for d in dues for t in d)
    assert all(b - a == pytest.approx(every)
               for d in dues for a, b in zip(d, d[1:]))
    # spread evenly over the gateways and over every second
    per_s = [0] * 20
    for d in dues:
        for t in d:
            per_s[int(t - t0)] += 1
    assert max(per_s) - min(per_s) <= 2
    assert max(len(d) for d in dues) - min(len(d) for d in dues) <= 1


def test_the_schedule_is_the_issues_formula():
    n, every = 64, 1.0
    for g, phase in ((0, 0), (5, 1), (63, 4)):
        frac = (g / n + 0.381966 * phase) % 1.0
        assert STORM.due(g, n, phase, 10.0, 12.5, every) \
            == pytest.approx([10.0 + (j + frac) * every
                              for j in range(3)
                              if 10.0 + (j + frac) * every < 12.5])
    # the golden turn: another phase, other instants
    assert STORM.due(7, n, 1, 0.0, 2.0, every) \
        != STORM.due(7, n, 2, 0.0, 2.0, every)


def test_arrival_k_takes_template_k_mod_4():
    pubs = _fleet()
    st = STORM.Storm(pubs)
    got = [st.arrival(3) for _ in range(8)]
    assert [f for f, _t in got] == [
        "cmd/s3/d0/#", "cmd/s3/d1/#", "cmd/s3/d2/#", "cmd/s3/+/d3/ack",
        "cmd/s3/d4/#", "cmd/s3/d5/#", "cmd/s3/d6/#", "cmd/s3/+/d7/ack"]
    assert [t for _f, t in got][2:4] == ["cmd/s3/d2/probe",
                                         "cmd/s3/x/d3/ack"]
    assert st.arrival(4)[0] == "cmd/s4/d0/#"     # counted a gateway
    assert st.drop == 4 and st.per_packet == 16


def test_no_pool_topic_meets_the_storm_and_no_probe_a_socket():
    sys.path.insert(0, _BENCH)
    try:
        from reference import matches
    finally:
        sys.path.remove(_BENCH)
    gw = CFG["gateways"]
    sockets = [f.format(i=i + grp.get("first", 0))
               for grp in CFG["sockets"] for i in range(grp["count"])
               for f in grp["filters"]]
    for g, k in ((0, 0), (9, 3), (63, 1201), (63, 1203)):
        flt = gw["filters"][k % 4].format(g=g, k=k)
        probe = gw["probes"][k % 4].format(g=g, k=k)
        assert matches(probe, flt)
        # its own filter alone: no other arrival's, no other gateway's
        for g2, k2 in ((g, k + 4), (g, k + 1), ((g + 1) % 64, k)):
            assert not matches(probe, gw["filters"][k2 % 4].format(
                g=g2, k=k2))
        assert not any(matches(probe, f) for f in sockets)
        # the tree's words are w<level>_<n>: no topic of the pool
        # starts under cmd
        assert not matches("w0_3/w1_4/w2_5", flt)


# -- failures ----------------------------------------------------------------


class _Link:
    """A gateway's connection to a broker played by a script: what it
    reads is what the test queued."""

    def __init__(self, replies=()):
        self.replies = list(replies)
        self.wrote = []
        self.buf = b""

    def write(self, data):
        self.wrote.append(data)
        if self.replies:
            nxt = self.replies.pop(0)
            self.buf += nxt(data) if callable(nxt) else nxt

    async def drain(self):
        pass

    def close(self):
        pass

    def get_extra_info(self, _what):
        return ("127.0.0.1", 1)

    async def readexactly(self, n):
        if len(self.buf) < n:
            raise asyncio.IncompleteReadError(self.buf, n)
        out, self.buf = self.buf[:n], self.buf[n:]
        return out


def _fleet(n_pubs=2):
    link = _Link()
    plan = types.SimpleNamespace(
        config=CFG, n_pubs=n_pubs, payload_len=64,
        base=lambda pub, start: 0, traffic=dict(TRAFFIC, burst=4))
    pubs = types.SimpleNamespace(
        plan=plan, start=[0] * n_pubs, filler=b"x" * 44,
        conns=[(link, link)] * n_pubs)
    pubs.frames = lambda *a: b""

    async def await_fence(pub, seq):
        await asyncio.sleep(0.01)

    pubs.await_fence = await_fence
    return pubs


def _dead_gateways(pubs):
    """Every gateway's connection is one the broker never answers on."""
    st = pubs.storm = STORM.Storm(pubs)
    st.every = 0.05
    dead = _Link()
    st.conns = [(dead, dead)] * st.n
    return st


@pytest.mark.parametrize("phase", [1, 2, 3, 9])
def test_a_warm_rounds_first_failure_is_fleet_lost(phase):
    """The first publisher to see a gateway fail before the window
    raises what ``Publishers.run_phase`` does not count, so the run
    ends with an exit code and no result line, traced or not; the
    publishers after it are counted."""
    pubs = _fleet()
    st = _dead_gateways(pubs)
    now = time.monotonic()
    with pytest.raises(STORM.FleetLost, match="before the window"):
        asyncio.run(STORM.publisher(pubs, 0, phase, now, now + 0.3, None))
    assert not isinstance(STORM.FleetLost("x"), (ConnectionError, OSError))
    with pytest.raises(ConnectionError, match="the fleet stopped when "
                                              "gateway"):
        asyncio.run(STORM.publisher(pubs, 1, phase, now, now + 0.3, None))
    assert st.first_failed is not None


def test_a_gateway_lost_in_the_window_is_counted():
    pubs = _fleet()
    st = _dead_gateways(pubs)
    now = time.monotonic()
    for pub in (0, 1):
        with pytest.raises(ConnectionError, match="the fleet stopped"):
            asyncio.run(STORM.publisher(pubs, pub, 0, now, now + 0.3,
                                        None))
    assert st.first_failed is not None and not st.told


def _suback(data):
    pid = data[2 if data[1] < 128 else 3:][:2]
    return bytes([0x90, 2 + 16]) + pid + bytes(16)


def _unsuback(data):
    pid = data[2 if data[1] < 128 else 3:][:2]
    return b"\xb0\x02" + pid


def _echo(data):
    """A PUBLISH comes back as it was sent."""
    return data


def _one_gateway(replies):
    pubs = _fleet()
    st = STORM.Storm(pubs)
    st.limit = 1.0
    link = _Link(replies)
    st.conns[0] = (link, link)
    return pubs, st, link


def test_a_packets_three_probes():
    pubs, st, link = _one_gateway(
        [_suback, _echo, _unsuback, b"", _echo])
    asyncio.run(STORM._one_packet(pubs, st, 0))
    assert (st.subscribed, st.unsubscribed) == (16, 4)
    assert (st.answered, st.withheld) == (2, 1)
    assert [f for f, _t in st.live[0]][0] == "cmd/s0/d4/#"
    assert len(st.live[0]) == 12
    # SUBSCRIBE (16 filters), probe, UNSUBSCRIBE (4), two probes: the
    # removed filter's first, then the oldest live one's
    sub, newest, unsub, removed, oldest = link.wrote
    assert sub[0] == 0x82 and sub.count(b"cmd/s0/") == 16
    assert unsub[0] == 0xa2 and unsub.count(b"cmd/s0/") == 4
    assert b"cmd/s0/x/d15/ack" in newest
    assert b"cmd/s0/d0/probe" in removed and b"cmd/s0/d4/probe" in oldest


def test_a_returned_probe_on_an_unsubscribed_filter_is_a_failure():
    # the broker sends back both probes: the removed filter still
    # delivers, and the first PUBLISH on the connection is the wrong one
    pubs, st, _link = _one_gateway(
        [_suback, _echo, _unsuback, _echo, _echo])
    with pytest.raises(ConnectionError, match="expected the probe on "
                                              "cmd/s0/d4/probe back"):
        asyncio.run(STORM._one_packet(pubs, st, 0))
    assert st.withheld == 0


def test_a_suback_that_refuses_a_filter_is_a_failure():
    def refused(data):
        return _suback(data)[:-1] + b"\x80"

    pubs, st, _link = _one_gateway([refused])
    with pytest.raises(ConnectionError, match="expected SUBACK"):
        asyncio.run(STORM._one_packet(pubs, st, 0))


def test_the_unsubscribe_packet():
    pkt = STORM.build_unsubscribe(7, ["a/b", "c/#"])
    assert pkt[0] == 0xa2 and pkt[1] == len(pkt) - 2
    assert pkt[2:4] == struct.pack(">H", 7)
    assert pkt[4:] == b"\x00\x03a/b\x00\x03c/#"
