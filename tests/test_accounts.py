"""The accounts every session reads before it does anything stay
readable, and what the tree names still exists: ``PERF.md`` has a size
and a line a reader's tool returns whole (looser than the target PR 45
rebuilt it to, so the next PRs have room and a regrowth fails), and
nothing that ships or documents the program names a file, a script or
an environment variable that went. ``CHANGES.md``, ``ROADMAP.md``,
``PERF.md`` and ``ISSUE.md`` may: they are history."""

import os

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SCRIPTS_GONE = ("frontdoor_curve.py", "soak_conns.py",
                "soak_stability.py", "gather_probe.py")
# spelled in two halves so that a grep for the name finds no file
GONE = ("PERF_NOTES", "EMQX_TPU_" + "NATIVE_FRAME") + SCRIPTS_GONE


def _shipped_files():
    for top in ("emqx_tpu", "etc", "docs", "scripts"):
        for d, dirs, files in os.walk(os.path.join(ROOT, top)):
            dirs[:] = [x for x in dirs if x != "__pycache__"]
            for f in files:
                if not f.endswith((".pyc", ".so")):
                    yield os.path.join(d, f)
    yield os.path.join(ROOT, "README.md")


def test_perf_md_is_at_most_80_kb():
    assert os.path.getsize(os.path.join(ROOT, "PERF.md")) <= 80 * 1024


def test_perf_md_has_no_line_over_2500_characters():
    with open(os.path.join(ROOT, "PERF.md"), encoding="utf-8") as f:
        long = [(i, len(line)) for i, line in enumerate(f, 1)
                if len(line.rstrip("\n")) > 2500]
    assert not long, long


def test_nothing_shipped_names_what_went():
    hits = []
    for path in _shipped_files():
        with open(path, encoding="utf-8", errors="replace") as f:
            text = f.read()
        hits += [(os.path.relpath(path, ROOT), g) for g in GONE if g in text]
    assert not hits, hits
    for script in SCRIPTS_GONE:
        assert not os.path.exists(os.path.join(ROOT, "scripts", script))
    assert not os.path.exists(os.path.join(ROOT, "docs", "PERF_NOTES.md"))
