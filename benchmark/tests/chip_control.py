#!/usr/bin/env python3
"""The controls at the cell's own size, on the chip: for each seed one
new process runs the cell with a short window and one control of
``sabotage.py`` (they take turns over the seeds) breaking the timed
path underneath, and ``correct`` has to come out false. Not part of a
benchmark run; started by hand:

    python3 benchmark/tests/chip_control.py --workload <cell> \
        --seconds 5 --seeds 11 12 13 [--controls wrong_filter ...]
"""

import argparse
import json
import os
import subprocess
import sys

_HERE = os.path.dirname(os.path.abspath(__file__))
_BENCH = os.path.dirname(_HERE)
sys.path[:0] = [_HERE, _BENCH, os.path.dirname(_BENCH)]

import sabotage  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--controls", nargs="+", default=sorted(sabotage.ALL),
                    choices=sorted(sabotage.ALL))
    ap.add_argument("--one", default=None, help="(internal) run this "
                    "control in this process, on the first seed")
    args = ap.parse_args()
    if args.one is not None:
        import run

        return run.main(
            ["--workload", args.workload, "--seed", str(args.seeds[0]),
             "--seconds", str(args.seconds), "--trace", "0"],
            sabotage=sabotage.ALL[args.one])
    names = args.controls
    bad = 0
    for i, seed in enumerate(args.seeds):
        name = names[i % len(names)]
        p = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--workload",
             args.workload, "--seconds", str(args.seconds), "--seeds",
             str(seed), "--one", name], capture_output=True, text=True)
        lines = p.stdout.splitlines()
        try:
            out = json.loads(lines[-1])
        except (IndexError, ValueError):
            print(f"control {name} seed {seed}: no result, rc="
                  f"{p.returncode}\n" + "\n".join(lines[-8:]))
            bad += 1
            continue
        bad += out["correct"] is not False
        moved = [ln for ln in lines if ln.startswith("check:")
                 and "(limit 0)" in ln and ": 0 (limit" not in ln]
        print(f"control {name} seed {seed}: correct={out['correct']} "
              f"(must be false) failed={out['failed']} of "
              f"{out['attempted']} {moved}", flush=True)
    print(f"chip_control: {bad} wrong outcomes")
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
