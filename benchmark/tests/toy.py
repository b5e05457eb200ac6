"""A toy-size copy of the benchmark's files for the CPU rehearsal:
the same cells, mixes, metrics and code, with the deployments cut to
what a test run can hold. Only a copy is ever cut."""

from __future__ import annotations

import json
import os
import shutil

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)


def _edit(path: str, fn) -> None:
    with open(path, encoding="utf-8") as f:
        d = json.load(f)
    fn(d)
    with open(path, "w", encoding="utf-8") as f:
        json.dump(d, f, indent=2)


def make(dst: str) -> str:
    """Copy BENCHMARK.json and benchmark/ under ``dst`` and cut the
    copy to toy size; return the copy's benchmark directory."""
    bench = os.path.join(dst, "benchmark")
    shutil.copytree(BENCH, bench, ignore=shutil.ignore_patterns(
        "__pycache__", "tests"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), dst)

    def small(cfg: dict) -> None:
        cfg["population"]["filters"] = 3000
        cfg["population"]["words_per_level"] = 12
        cfg["publish_topics"]["pool"] = min(
            cfg["publish_topics"]["pool"], 4096)
        cfg["sink_sample"] = min(cfg["sink_sample"], 512)
        for grp in cfg["sockets"]:
            grp["count"] = 40 if grp["count"] > 100 else \
                min(grp["count"], 6)

    for name in os.listdir(os.path.join(bench, "configs")):
        _edit(os.path.join(bench, "configs", name), small)

    def light(tr: dict) -> None:
        if "burst" in tr:
            tr["burst"] = 64
        if "rate" in tr:
            tr["rate"] = 800

    for name in os.listdir(os.path.join(bench, "traffic")):
        _edit(os.path.join(bench, "traffic", name), light)

    def fewer(wl: dict) -> None:
        ov = wl.get("overrides", {})
        if "burst" in ov:
            ov["burst"] = 8
        if "subscriber_procs" in ov:
            ov["subscriber_procs"] = 2

    for name in os.listdir(os.path.join(bench, "workloads")):
        _edit(os.path.join(bench, "workloads", name), fewer)
    return bench
