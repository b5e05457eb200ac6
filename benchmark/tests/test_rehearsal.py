"""Every cell end to end at toy size on the CPU, through
``run.main(..., allow_platform="cpu")`` — the command itself refuses
to run without a chip, and no number printed here is a device number.
Shows that each control turns ``correct`` false, and that a cell, a
traffic mix and a per-layer metric arrive as new files alone."""

import json
import os
import subprocess
import sys

import pytest

import run
import sabotage
import toy

KEYS = {"correct", "attempted", "failed", "metrics", "device", "compared"}
run.WARM_ROUND_S = 1.0  # a toy round is short


@pytest.fixture(scope="module")
def bench_dir(tmp_path_factory):
    return toy.make(str(tmp_path_factory.mktemp("toy")))


def _run(bench_dir, capsys, cell, trace=0, seed=2147483999, seconds=3,
         sabotage=None):
    rc = run.main(["--workload", cell, "--seed", str(seed), "--seconds",
                   str(seconds), "--trace", str(trace)],
                  allow_platform="cpu", sabotage=sabotage,
                  bench_dir=bench_dir)
    cap = capsys.readouterr()
    lines = [ln for ln in cap.out.splitlines() if ln]
    assert rc == 0, "\n".join(lines[-15:])
    out = json.loads(lines[-1])
    # each number compared beside its limit: the result's last key and
    # the last lines of standard error; any of them past its limit
    # makes the run not correct
    assert list(out)[-1] == "compared"
    said = [f"compared: {k} {c['value']} (limit {c['limit']}): "
            for k, c in out["compared"].items()]
    err = [ln for ln in cap.err.splitlines() if ln][-len(said):]
    assert [ln[:len(s)] for ln, s in zip(err, said)] == said
    assert all(set(c) == {"value", "limit"} for c in out["compared"].values())
    assert out["correct"] is all(
        c["value"] <= c["limit"] for c in out["compared"].values())
    return out, lines + err


def _moved(lines):
    """The numbers past their limit, as standard error's last lines
    have them: each once, with what it counts."""
    return [ln for ln in lines if ln.startswith("compared:")
            and "(limit 0): " in ln and " 0 (limit 0): " not in ln]


def _spec(bench_dir):
    with open(os.path.join(os.path.dirname(bench_dir),
                           "BENCHMARK.json")) as f:
        return json.load(f)


def _names(spec, group, cell):
    return {m["name"] for m in spec[group]
            if cell in m.get("workloads", [cell])}


@pytest.mark.parametrize("cell", [w["name"] for w in _spec(toy.BENCH)
                                  ["workloads"]])
def test_cell_end_to_end(bench_dir, capsys, cell):
    out, lines = _run(bench_dir, capsys, cell)
    assert set(out) == KEYS
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 0
    assert set(out["metrics"]) == _names(_spec(bench_dir), "end_to_end",
                                         cell)
    assert all(m["value"] > 0 for m in out["metrics"].values())
    assert out["device"]["platform"] == "cpu"  # and says so
    text = "\n".join(lines)
    for what in ("cpu_count=", "subscriber processes", "programs first "
                 "used", "persistent cache", "socket deliveries expected"):
        assert what in text
    # the sink comparison is not empty in any cell
    assert "the trie expects a filter (limit: at least 1)" in text


def test_traced_run_reports_the_layer_metrics(bench_dir, capsys):
    cell = _spec(bench_dir)["workloads"][0]["name"]
    out, _ = _run(bench_dir, capsys, cell, trace=1, seed=77)
    assert set(out) - {"breakdown"} == KEYS
    assert out["correct"] is True
    want = _names(_spec(bench_dir), "per_layer", cell)
    # the CPU backend has no device plane: the trace readers find
    # nothing and their metrics are left out, never invented
    got = set(out["metrics"])
    assert got and got <= want
    assert all(n.startswith("device_idle") for n in want - got)


#: what a toy window may not form: no merge ends in a window in which
#: the pending adds never reach ``delta_max_filters``
_TOY_CANNOT = {"merge_s.storm", "rebuild_stall_ms_per_merge.storm"}


@pytest.mark.parametrize("cell", [w["name"] for w in _spec(toy.BENCH)
                                  ["workloads"] if w["chips"] == 1])
def test_a_traced_run_forms_every_reading_the_cell_lists(bench_dir, capsys,
                                                         cell):
    """An entry whose reducer finds nothing in a cell it lists is a
    fault of the list (a traced run whose line lacks it is refused),
    found by running each cell, not by reading. The four-chip cell's
    is ``test_mesh_cell.py``'s."""
    out, lines = _run(bench_dir, capsys, cell, trace=1, seed=991,
                      seconds=4)
    assert out["correct"] is True, "\n".join(lines[-15:])
    spec = _spec(bench_dir)
    from_trace = {m["name"] for m in spec["per_layer"]
                  if m["source"] == "device_trace"}
    missing = _names(spec, "per_layer", cell) - set(out["metrics"])
    assert missing <= from_trace | _TOY_CANNOT, sorted(missing)
    assert not set(out["metrics"]) - _names(spec, "per_layer", cell)


@pytest.mark.parametrize("name,nth", [(n, 0) for n in sorted(sabotage.ALL)]
                         + [("wrong_filter", 1)])
def test_control_turns_correct_false(bench_dir, capsys, name, nth):
    cell = _spec(bench_dir)["workloads"][nth]["name"]
    try:
        out, lines = _run(bench_dir, capsys, cell, seed=4242,
                          sabotage=sabotage.ALL[name])
    finally:
        sabotage.undo_host_fallback()
    assert out["correct"] is False, "\n".join(lines[-15:])
    moved = _moved(lines)
    if name == "host_fallback":
        # every delivery was right: only the counters and the log tell
        assert out["failed"] == 0
        assert '"breaker.failures": 1' in moved[0]
        assert all("breaker" in ln or "log lines" in ln for ln in moved)
        return
    assert len(moved) == 1, moved  # the one number it is there to move
    if name == "wrong_filter":
        # every socket got its messages: only the trie tells
        assert "filters differ from the trie's" in moved[0]
        assert out["failed"] > 0
    else:
        assert "socket deliveries missing" in moved[0]
        assert out["failed"] == 1


def test_traced_control_turns_correct_false(bench_dir, capsys):
    cell = _spec(bench_dir)["workloads"][0]["name"]
    out, lines = _run(bench_dir, capsys, cell, trace=1, seed=4243,
                      sabotage=sabotage.TRACED["stated_path"])
    assert out["correct"] is False, "\n".join(lines[-15:])
    moved = _moved(lines)
    # every delivery was right and on the device: only the stated path
    assert len(moved) == 1 and out["failed"] == 0, moved
    assert "publish spans off the mesh path" in moved[0]


def test_broker_table_reaches_the_node(bench_dir, capsys):
    """A configuration's ``broker`` table goes through the product's
    loader and the node is built from it; the cells of today get the
    default node."""
    seen = []

    def look(run):
        seen.append(run.node.router.config.match_cache)

    out, lines = _run(bench_dir, capsys, "toy_nocache.flood", sabotage=look)
    assert out["correct"] is True and out["failed"] == 0
    assert 'node: {"matcher": {"match_cache": false}}' in lines
    cell = _spec(bench_dir)["workloads"][0]["name"]
    out, lines = _run(bench_dir, capsys, cell, sabotage=look, seed=6)
    assert out["correct"] is True and "node: {}" in lines
    assert seen == [False, True]


@pytest.mark.parametrize("broker,named", [
    ({"matcher": {"match_cash": False}}, "matcher.match_cash"),
    ({"nodes": {}}, "broker.nodes"),
    ({"loops": 2}, "broker.loops"),
    ({"node": {"cluster_port": 0}}, "node.cluster_port"),
    ({"listeners": [{"type": "tcp", "port": 1883}]}, "listeners"),
    ({"modules": {"retainr": {}}}, "modules.retainr"),
])
def test_broker_table_is_a_closed_schema(bench_dir, capsys, broker, named):
    """An unknown key, or a socket of the harness's, ends the run as
    ``FAILED:`` with the key's name before anything is seeded."""
    cell = toy.add_deployment(
        bench_dir, "toy_bad_" + named.replace(".", "_"), broker)
    rc = run.main(["--workload", cell, "--seed", "3", "--seconds", "1",
                   "--trace", "0"], allow_platform="cpu",
                  bench_dir=bench_dir)
    lines = [ln for ln in capsys.readouterr().out.splitlines() if ln]
    assert rc == 1
    assert lines[-1].startswith("FAILED: the configuration's broker table")
    assert named in lines[-1]
    assert not any(ln.startswith(("seed:", "generator:", "{"))
                   for ln in lines)


def test_new_cell_mix_and_metric_are_new_files(bench_dir, capsys):
    """What a later PR does: new files and new entries, no edit to a
    file that is there."""
    def put(kind, name, obj):
        path = os.path.join(bench_dir, kind, name + ".json")
        assert not os.path.exists(path)
        with open(path, "w") as f:
            json.dump(obj, f)

    put("traffic", "trickle", {
        "name": "trickle", "loop": "flood", "publishers": 2, "burst": 4,
        "subscriber_procs": 1})
    first = _spec(bench_dir)["workloads"][0]
    put("workloads", "toy.trickle", {
        "config": first["config"], "traffic": "trickle", "chips": 1,
        "overrides": {}, "why": "a later PR's cell", "who": "a test"})
    put("layer_metrics", "uniq_per_batch", {
        "reducer": "span_field_mean", "args": {"field": "n_uniq"}})
    spec_path = os.path.join(os.path.dirname(bench_dir), "BENCHMARK.json")
    spec = _spec(bench_dir)
    spec["workloads"].append({
        "name": "toy.trickle", "config": first["config"],
        "traffic": "trickle", "chips": 1, "why": "a later PR's cell"})
    spec["per_layer"].append({
        "name": "uniq_per_batch", "unit": "topics/batch",
        "better": "higher", "source": "program_span",
        "layer": "ingress batch", "moves": "delivered_rate",
        "workloads": ["toy.trickle"]})
    with open(spec_path, "w") as f:
        json.dump(spec, f)
    out, _ = _run(bench_dir, capsys, "toy.trickle", trace=1, seed=5)
    assert out["correct"] is True
    assert out["metrics"]["uniq_per_batch"]["value"] > 0


def test_command_refuses_to_run_without_a_chip(bench_dir):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=toy.ROOT)
    cell = _spec(bench_dir)["workloads"][0]["name"]
    p = subprocess.run(
        [sys.executable, os.path.join(bench_dir, "run.py"), "--workload",
         cell, "--seed", "1", "--seconds", "1", "--trace", "0"],
        env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert not any(ln.startswith("{") for ln in p.stdout.splitlines())
