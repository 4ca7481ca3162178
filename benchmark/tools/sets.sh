#!/usr/bin/env bash
# Runs of one cell, one new process each, logs under chiprun_out/<tag>/.
#
#   benchmark/tools/sets.sh <tag> <workload> <seconds> <trace> <seed> [<seed> ...]
#
# Prints, per run, the notes worth reading (segments, setup, failed checks)
# and the result line. The two full sets of a cell are two calls of this
# with the same seeds, in one chiprun call so that they share the cache.
set -u
tag=$1; workload=$2; seconds=$3; trace=$4; shift 4
out=chiprun_out/$tag
mkdir -p "$out"
for seed in "$@"; do
  log=$out/${workload}_s${seed}_t${trace}_$(date +%s).log
  start=$(date +%s%N)
  python3 benchmark/run.py --workload "$workload" --seed "$seed" \
      --seconds "$seconds" --trace "$trace" >"$log" 2>"$log.err"
  rc=$?
  end=$(date +%s%N)
  echo "== $tag $workload seed=$seed seconds=$seconds trace=$trace rc=$rc wall_ms=$(( (end - start) / 1000000 ))"
  grep -E '^# (device|segments|setup)' "$log"
  grep -E '^# check' "$log" | grep -v '"ok": true' || true
  if [ "$rc" -ne 0 ]; then tail -n 30 "$log.err"; fi
  tail -n 1 "$log"
done
