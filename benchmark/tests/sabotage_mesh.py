"""The controls of a cell whose ``layout.path`` is ``mesh``, where
``sabotage.py``'s do not say what they are there to say. Its dropped
and duplicated delivery, its failed walk behind the breaker (the fault
point ``device.walk`` fires before the dispatch forks to the mesh) and
its swapped answers (the sharded rebuild publishes ``router._auto_map``
as the snapshot's id map too) reach the mesh path as they are:
``test_mesh_cell.py`` holds each to its own reason there. One does not:
``stated_path`` states ``mesh``, which is this cell's truth. Its mirror
is here. Run by hand at the cell's own size:

    python3 benchmark/tests/sabotage_mesh.py --workload <cell> \
        --seconds 5 --seed 11 --control stated_device
"""

from __future__ import annotations

import os
import sys

_HERE = os.path.dirname(os.path.abspath(__file__))
_BENCH = os.path.dirname(_HERE)


def stated_device(run) -> None:
    """The deployment states one chip's path and the timed path is the
    mesh's: every delivery is right and no breaker moves; only the
    spans of a traced run bear another ``path`` than ``layout.path``."""
    run.cfg = dict(run.cfg, layout=dict(run.cfg.get("layout", {}),
                                        path="device"))


#: what only a ``--trace 1`` run can tell
TRACED = {"stated_device": stated_device}


def main() -> int:
    import argparse

    sys.path[:0] = [_HERE, _BENCH, os.path.dirname(_BENCH)]
    import run
    import sabotage

    controls = {**sabotage.ALL, **TRACED}
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--control", required=True, choices=sorted(controls))
    args = ap.parse_args()
    return run.main(
        ["--workload", args.workload, "--seed", str(args.seed), "--seconds",
         str(args.seconds), "--trace", str(int(args.control in TRACED))],
        sabotage=controls[args.control])


if __name__ == "__main__":
    sys.exit(main())
