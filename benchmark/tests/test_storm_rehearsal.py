"""Cell ``fleet_1m_storm.subscribe_storm`` end to end at toy size on the
CPU (PR 42), through ``run.main(..., allow_platform="cpu")``: no number
printed here is a device number.

``toy.py`` cuts the population to 3,000 filters, and the loop scales
the storm with it (down to its floor of 32 filters a second: the toy's
tables hold little more), so ``test_rehearsal.py`` runs the cell with a
trickle of its storm and no merge. This
file turns the storm up in a copy of its own: 64 filters a second (a
gateway's packet every 16 s, four packets a second fleet-wide) against
a node whose ``[matcher] delta_max_filters`` is 48, so that the delta
is folded into the main tables every ~0.75 s: merges in the warm
rounds and in the window, all three probes of every packet, the twelve
``.storm`` metrics in a traced line, and the control
``sabotage.py::drop_delivery`` turning ``correct`` false on
``sockets_wrong`` alone."""

import json
import os

import pytest

import run
import sabotage
import toy

CELL = "fleet_1m_storm.subscribe_storm"
run.WARM_ROUND_S = 1.0  # a toy round is short


def _edit(path, **keys):
    with open(path, encoding="utf-8") as f:
        d = json.load(f)
    d.update(keys)
    with open(path, "w", encoding="utf-8") as f:
        json.dump(d, f)


@pytest.fixture(scope="module")
def bench_dir(tmp_path_factory):
    bench = toy.make(str(tmp_path_factory.mktemp("toy_storm")))
    # 1,024 a second for a fleet of 48,000 is 64 a second for 3,000
    _edit(os.path.join(bench, "traffic", "subscribe_storm.json"),
          subscribe_rate_fleet=48000)
    _edit(os.path.join(bench, "configs", "fleet_1m_storm.json"),
          broker={"matcher": {"delta_max_filters": 48}})
    return bench


def _run(bench_dir, capsys, trace=0, seed=2147483999, sabotage=None):
    seen = {}

    def look(r):
        seen["before"] = r.node.router.delta_info()
        seen["helper"] = r.node.broker.helper
        seen["router"] = r.node.router
        if sabotage is not None:
            sabotage(r)

    rc = run.main(["--workload", CELL, "--seed", str(seed), "--seconds",
                   "3", "--trace", str(trace)], allow_platform="cpu",
                  sabotage=look, bench_dir=bench_dir)
    cap = capsys.readouterr()
    lines = [ln for ln in cap.out.splitlines() if ln]
    assert rc == 0, "\n".join(lines[-15:])
    return json.loads(lines[-1]), lines, cap.err.splitlines(), seen


def _moved(err):
    return [ln for ln in err if ln.startswith("compared:")
            and "(limit 0): " in ln and " 0 (limit 0): " not in ln]


def test_the_storm_is_served_across_merges(bench_dir, capsys):
    out, lines, err, seen = _run(bench_dir, capsys, trace=1)
    assert out["correct"] is True and out["failed"] == 0, _moved(err)
    assert 'node: {"matcher": {"delta_max_filters": 48}}' in lines
    # merges before the window and in it; the fan-out tables went over
    # every one, none was built whole for a merge
    router, helper = seen["router"], seen["helper"]
    assert seen["before"]["merges"] >= 2
    assert router.delta_info()["merges"] > seen["before"]["merges"]
    assert helper.carries == router.delta_info()["merges"]
    # (the loop's own lines, probes and headroom, go to the generator's
    # standard error, which is not this process's)
    spec = json.load(open(os.path.join(os.path.dirname(bench_dir),
                                       "BENCHMARK.json")))
    want = {m["name"] for m in spec["per_layer"]
            if CELL in m.get("workloads", [])}
    got = set(out["metrics"])
    assert len(want) == 12 and got == want
    val = {k: v["value"] for k, v in out["metrics"].items()}
    assert val["delta_merges.storm"] >= 1
    assert val["fan_rebuild_share.storm"] == 0.0
    assert val["delta_grows.storm"] == 0
    assert val["merge_s.storm"] > 0
    assert val["subscribes_per_s.storm"] == pytest.approx(64.0, rel=0.3)
    assert val["subscribe_us.storm"] > 0 and val["unsubscribe_us.storm"] > 0
    assert 0 < val["delta_probe_share.storm"] <= 1.0


def test_a_dropped_delivery_turns_correct_false(bench_dir, capsys):
    out, _lines, err, _seen = _run(bench_dir, capsys, seed=4242,
                                   sabotage=sabotage.drop_delivery)
    assert out["correct"] is False
    moved = _moved(err)
    assert len(moved) == 1 and "socket deliveries missing" in moved[0]
    assert out["failed"] == 1
