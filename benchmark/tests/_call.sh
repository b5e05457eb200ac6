#!/bin/bash
# _call.sh <tag> <seconds> <trace> <cell> <seed>...  — runs of one cell
# in one call, each a new process, logs under chiprun_out/.
tag=$1; secs=$2; trace=$3; cell=$4; shift 4
mkdir -p chiprun_out
for seed in "$@"; do
  log=chiprun_out/${tag}_${seed}.log
  python3 benchmark/run.py --workload "$cell" --seed "$seed" --seconds "$secs" --trace "$trace" > "$log" 2>&1
  echo "rc=$? $cell seed=$seed | $(grep -a '^seed:' "$log" | cut -c1-70) | $(grep -a '^router:' "$log" | cut -c1-60) | $(grep -a '^warmer [a-z_]*: [0-9]' "$log" | cut -c1-80)"
  grep -a "^check: \|^window: first\|^window: socket\|^window: [0-9]* garbage\|FAILED\|Traceback" "$log" | grep -av ": 0 (limit 0)" | cut -c1-400
  tail -n 1 "$log" | cut -c1-1500
done
