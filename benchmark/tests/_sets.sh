#!/bin/bash
# _sets.sh <cell> <tag> <seconds> <seed>...   — the runs of one cell in
# one call: two sets over the same seeds, each run a new process
# (_spread.py reads the logs).
cell=$1; tag=$2; secs=$3; shift 3
here=$(dirname "$0")
for set in 1 2; do
  "$here/_call.sh" "${tag}_s${set}" "$secs" 0 "$cell" "$@"
done
