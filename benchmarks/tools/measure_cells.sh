#!/usr/bin/env bash
# measure_cells.sh — run cells as the driver does, on the machine with the chip:
#
#   chiprun [--chips 4] -- bash benchmarks/tools/measure_cells.sh <runs per set> <seconds> <cell> [<cell> ...]
#
# For each cell: two sets of <runs per set> runs with --trace 0, every run
# with another --seed, then one --trace 1 run.  Result lines are appended to
# chiprun_out/measure/<cell>.jsonl (with "set" and "seed" added);
# benchmarks/tools/spread.py reads them.  SETS="1" runs one set only,
# TRACE=0 leaves the traced run out, OFFSET=3 numbers the seeds from 4 (more
# runs for sets that have three).
set -u
runs=$1; seconds=$2; shift 2
mkdir -p chiprun_out/measure
for cell in "$@"; do
  for set in ${SETS:-1 2}; do
    for i in $(seq 1 "$runs"); do
      seed=$((set * 100 + ${OFFSET:-0} + i))
      out=$(python3 benchmarks/run.py --workload "$cell" --seed "$seed" --seconds "$seconds" --trace 0 2>&1)
      rc=$?
      echo "$out" | grep '^\[window\]\|^\[check\]\|^\[not' | cut -c1-400
      line=$(echo "$out" | tail -n 1)
      echo "rc=$rc set=$set seed=$seed $line" | cut -c1-600
      if [ $rc -ne 0 ]; then echo "$out" | tail -n 40 | cut -c1-400; echo "run failed: stopping"; exit 1; fi
      echo "$line" | sed "s/^{/{\"set\": $set, \"seed\": $seed, /" >> "chiprun_out/measure/$cell.jsonl"
    done
  done
  [ "${TRACE:-1}" = 0 ] && continue
  out=$(python3 benchmarks/run.py --workload "$cell" --seed 300 --seconds "$seconds" --trace 1 2>&1)
  echo "$out" | grep '^\[window\]\|^\[trace\]\|^\[flash\|^\[not' | cut -c1-400
  echo "$out" | tail -n 1 | sed 's/^{/{"set": 0, "seed": 300, /' >> "chiprun_out/measure/$cell.jsonl"
  echo "$out" | tail -n 1 | cut -c1-3000
done
