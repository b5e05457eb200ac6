"""The four-chip cell at toy size on four virtual CPU devices, its
controls, and the reducer that reads the collectives' share of a trace.

``conftest.py`` here does not ask XLA for more than one CPU device and
a PR that adds a cell may not edit it, so this module asks, at import:
pytest imports every test module before JAX starts a backend, and the
whole of ``pytest benchmark/tests`` then runs on four devices (a cell
of one chip reads the first). Run alone, another module of this
directory still has one device and refuses the four-chip cell."""

import os

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=4").strip()

import importlib.util  # noqa: E402
import json  # noqa: E402

import pytest  # noqa: E402

import run  # noqa: E402
import sabotage  # noqa: E402
import sabotage_mesh  # noqa: E402
import test_planes  # noqa: E402
import toy  # noqa: E402
import tracefile  # noqa: E402
from test_rehearsal import KEYS, _moved, _names, _run, _spec  # noqa: E402

CELL = "fleet_10m_mesh.flood"
#: every entry whose list the cell is on: the mesh's own readings
#: (``.mesh``: they read ``mesh.*`` counters, the collectives, the
#: mesh's warmer) and, since PR 46, the bare names it shares with the
#: one-chip cells (same reducer, same arguments; the copies went)
MESH_METRICS = _names(_spec(toy.BENCH), "per_layer", CELL)
OWN = {"collective_step_share.mesh", "walked_topic_share.mesh",
       "collective_busy_share.mesh", "warmers_s.mesh"}
#: what a trace of the CPU backend cannot give (it has no device plane)
FROM_TRACE = {"collective_busy_share.mesh", "device_idle_share"}
#: readings that are above 0 in any window that served a batch (a
#: share of the loop may stand at 0 in a toy window: no stats flush, no
#: collection; the unattributed share is a difference; the warmer of a
#: module's second run finds every program and may read 0.0 s)
POSITIVE = {"batch_fill", "match_us_per_msg", "fetch_ms_per_batch",
            "tail_us_per_delivery", "read_us_per_msg",
            "flush_us_per_delivery", "pack_ms_per_batch",
            "dispatch_us_per_delivery", "prepare_us_per_msg"}


@pytest.fixture(scope="module")
def bench_dir(tmp_path_factory):
    return toy.make(str(tmp_path_factory.mktemp("toy_mesh")))


# -- the collectives' share of a trace ---------------------------------------

def _reducer():
    path = os.path.join(toy.BENCH, "reducers", "trace_collective_share.py")
    spec = importlib.util.spec_from_file_location("_tcs", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _four_planes(names_on_busiest):
    """Four chips' planes (chip 2 the busiest: 4 ms in three ops, the
    first two overlapping) read as a four-chip cell reads them."""
    data = test_planes._data({0: test_planes.LIGHT, 1: test_planes.LIGHT,
                              2: test_planes.BUSY, 3: test_planes.LIGHT})
    for line in data.planes[2].lines:
        for ev, name in zip(line.events, names_on_busiest):
            ev.name = name
    # chip 0 alone fills a line that must not be read
    data.planes[0].lines.append(test_planes.NS(
        name="Async XLA Ops", events=[test_planes.NS(
            name="%collective-permute-start.1", start_ns=0,
            duration_ns=9_000_000)]))
    per_plane = tracefile.device_ops(data, test_planes.PREFIX, ["XLA Ops"])
    return tracefile.cell_chips(per_plane, test_planes.PREFIX, [0, 1, 2, 3])


def test_collective_share_of_the_busiest_chip():
    red = _reducer()
    found = _four_planes([
        "%all-gather.3 = s32[8,4]{1,0} all-gather(%p), dimensions={0}",
        "%fusion.1 = s32[8]{0} fusion(%all-gather.3), kind=kLoop",
        "%collective-permute-done.1"])
    # BUSY = (0, 2), (1, 2), (10, 1) ms: the gather 2 ms and the done
    # half 1 ms of a busy union of 4 ms; the fusion that only reads a
    # gather's result is no collective
    assert red.reduce(found) == pytest.approx(75.0)
    found = _four_planes(["%all-reduce-start", "%fusion.1", "%copy.4"])
    assert red.reduce(found) == pytest.approx(50.0)


def test_no_collective_is_nothing_to_read_never_zero():
    red = _reducer()
    found = _four_planes(["%fusion", "%while.2", "%copy-start.1"])
    assert red.reduce(found) is None
    assert red.reduce({"device_ops": None}) is None
    assert red.reduce({}) is None          # an untraced run, the CPU


# -- the cell at toy size ------------------------------------------------------

def test_the_cell_is_declared_as_the_issue_words_it():
    spec = _spec(toy.BENCH)
    cell = next(w for w in spec["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "fleet_10m_mesh", "flood", 4)
    cfg = json.load(open(os.path.join(toy.BENCH, "configs",
                                      "fleet_10m_mesh.json")))
    one = json.load(open(os.path.join(toy.BENCH, "configs", "fleet_1m.json")))
    # 4M of BASELINE's 10M: the cut is `reduced.filters` (PERF.md section 4)
    assert cfg["population"] == dict(one["population"], filters=4_000_000)
    assert "filters" in cfg["reduced"]
    for key in ("sockets", "publish_topics", "payload_bytes", "sink_sample"):
        assert cfg[key] == one[key], key
    for key in ("protocol", "publish_qos", "deliver_qos", "delivery"):
        assert cfg["guarantees"][key] == one["guarantees"][key], key
    assert cfg["broker"] == {"matcher": {"mesh": {"data": 2, "trie": 2}}}
    assert cfg["layout"]["path"] == "mesh" and cfg["layout"]["chips"] == 4
    assert cfg["warmers"] == ["mesh_buckets"]
    conf = next(c for c in spec["configs"] if c["name"] == "fleet_10m_mesh")
    assert conf["source"] == cfg["source"] and len(conf["source"]) <= 200
    assert set(conf["reduced"]) == set(cfg["reduced"])
    # its own four, and the common readings under their bare names;
    # what divides by dispatch.batches is not the mesh's to form (its
    # path counts mesh.batches)
    assert OWN <= MESH_METRICS
    assert {m for m in MESH_METRICS if m.endswith(".mesh")} == OWN
    assert {"batch_fill", "match_us_per_msg", "fetch_ms_per_batch",
            "tail_us_per_delivery", "read_us_per_msg", "device_idle_share",
            "flush_us_per_delivery", "pack_ms_per_batch"} <= MESH_METRICS
    assert not MESH_METRICS & {"held_ticks_per_batch", "programs_per_batch",
                               "grown_batch_share", "fused_batch_share"}
    assert _names(spec, "end_to_end", CELL) == {"delivered_rate", "setup_s"}


def test_cell_end_to_end_on_four_devices(bench_dir, capsys):
    out, lines = _run(bench_dir, capsys, CELL)
    assert set(out) == KEYS
    assert out["correct"] is True and out["failed"] == 0
    assert set(out["metrics"]) == {"delivered_rate", "setup_s"}
    assert out["device"]["chips"] == [0, 1, 2, 3]
    assert len(out["device"]["memory_peak_bytes_by_chip"]) == 4
    text = "\n".join(lines)
    assert 'node: {"matcher": {"mesh": {"data": 2, "trie": 2}}}' in text
    assert "warmer mesh_buckets: unit 16, buckets up to 512" in text


def test_traced_run_reports_the_mesh_metrics(bench_dir, capsys):
    out, lines = _run(bench_dir, capsys, CELL, trace=1, seed=78)
    assert out["correct"] is True, "\n".join(lines[-15:])
    # every span bore path "mesh": the check holds them to layout.path
    assert out["compared"]["spans_off_path"]["value"] == 0
    assert set(out["metrics"]) == MESH_METRICS - FROM_TRACE
    m = {k: v["value"] for k, v in out["metrics"].items()}
    # the toy pool (4,096 topics) fits the match cache: by the window
    # every topic may be cached and no step run, so the two shares may
    # read 0 here (tests/test_mesh_node.py holds the counters to the
    # spans; the cell's own pool misses in 7 batches of 10, PERF.md)
    assert 0 <= m["collective_step_share.mesh"] <= 1
    assert 0 <= m["walked_topic_share.mesh"] < 1
    assert all(m[k] > 0 for k in POSITIVE), m


@pytest.mark.parametrize("name", sorted(sabotage.ALL))
def test_control_turns_correct_false_on_the_mesh(bench_dir, capsys, name):
    """``sabotage.py``'s controls reach the mesh path, each for its own
    reason alone."""
    try:
        out, lines = _run(bench_dir, capsys, CELL, seed=4244,
                          sabotage=sabotage.ALL[name])
    finally:
        sabotage.undo_host_fallback()
    assert out["correct"] is False, "\n".join(lines[-15:])
    moved = _moved(lines)
    if name == "host_fallback":
        assert out["failed"] == 0
        assert '"breaker.failures": 1' in moved[0]
        assert all("breaker" in ln or "log lines" in ln for ln in moved)
        return
    assert len(moved) == 1, moved
    if name == "wrong_filter":
        assert "filters differ from the trie's" in moved[0]
        assert out["failed"] > 0
    else:
        assert "socket deliveries missing" in moved[0]
        assert out["failed"] == 1


def test_a_misstated_path_turns_correct_false(bench_dir, capsys):
    out, lines = _run(bench_dir, capsys, CELL, trace=1, seed=4245,
                      sabotage=sabotage_mesh.TRACED["stated_device"])
    assert out["correct"] is False, "\n".join(lines[-15:])
    moved = _moved(lines)
    assert len(moved) == 1 and out["failed"] == 0, moved
    assert "publish spans off the device path" in moved[0]


def test_the_mesh_warmer_refuses_a_node_without_a_mesh(bench_dir):
    import asyncio

    from emqx_tpu.config import build_node, parse_config

    mod = run.Bench(bench_dir).module("warmers", "mesh_buckets")
    with pytest.raises(RuntimeError, match="has no mesh"):
        asyncio.run(mod.warm(build_node(parse_config({})), None, print))
