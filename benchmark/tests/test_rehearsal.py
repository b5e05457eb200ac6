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

KEYS = {"correct", "attempted", "failed", "metrics", "device"}
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
    lines = [ln for ln in capsys.readouterr().out.splitlines() if ln]
    assert rc == 0, "\n".join(lines[-15:])
    return json.loads(lines[-1]), lines


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
    moved = [ln for ln in lines if ln.startswith("check:")
             and "(limit 0)" in ln and not ln.endswith(": 0 (limit 0)")]
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
